"""The seven readers of what PR 36 put on ``RoundRecord`` (``stage``,
``device_memory``, ``proc``, beside ``host_s``, ``metrics`` and
``wall_clock_s``) on hand-made records, and silent on a program without
the field."""

import types

import numpy as np
import pytest

from test_host_metrics import reader

from fedcrack_tpu.parallel.driver import RoundRecord


def record(wall, feed, stage_s, put_s, nbytes, peak, reserved, cpu_s, overflows):
    return RoundRecord(
        round_idx=0, metrics={"budget_overflows": np.asarray(overflows, np.int32)}, wall_clock_s=wall,
        data_fn_s=9.0, staging_s=0.0, staged_bytes=0, overlapped=True,
        host_s={"dispatch": 0.01, "feed": feed, "stage": stage_s, "barrier": 1.0, "handoff": 0.0},
        stage={"put_s": put_s, "land_s": [stage_s * 0.5, stage_s], "bytes": nbytes},
        device_memory={"peak_bytes_in_use": peak, "bytes_in_use": 1, "bytes_reserved": reserved, "bytes_limit": 16 * 10**9},
        proc={"cpu_s": cpu_s, "nivcsw": 3, "majflt": 0},
    )


ROUNDS = [
    record(4.0, 0.15, 0.25, 0.010, [8 * 10**8, 8 * 10**8], 3 * 10**9, 10 * 10**9, 1.0, [1, 0]),
    record(5.0, 0.05, 0.15, 0.030, [8 * 10**8, 8 * 10**8], 3 * 10**9, 11 * 10**9, 7.5, [2, 3]),
]

WANT = {
    "feed_ms": 100.0,
    "stage_put_ms": 20.0,
    "stage_gbps": 3.2 / 0.4,
    "hbm_peak_gb": 14.0,
    "budget_overflow_calls": 3.0,
    "proc_cpu_pct": 100.0 * (0.25 + 1.5) / 2,
    "round_wall_max_over_min": 1.25,
}

# What each reads; a program from before PR 36 has no such attribute.
FIELD = {
    "feed_ms": "host_s", "stage_put_ms": "stage", "stage_gbps": "stage", "hbm_peak_gb": "device_memory",
    "budget_overflow_calls": "metrics", "proc_cpu_pct": "proc", "round_wall_max_over_min": "wall_clock_s",
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reads_the_windows_rounds(name):
    assert reader(name)({"records": ROUNDS}) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_silent_without_records_or_without_the_field(name):
    assert reader(name)({"records": []}) is None
    if FIELD[name] == "wall_clock_s":
        return  # every program's record has a wall clock
    old = types.SimpleNamespace(
        wall_clock_s=4.0, staging_s=0.0, data_fn_s=0.8, metrics={"loss": np.zeros(1)},
        host_s={"dispatch": 0.01, "feed": 0.1, "stage": 0.2, "barrier": 1.0, "handoff": 0.0},
    )
    if FIELD[name] == "host_s":
        del old.host_s
    assert reader(name)({"records": [old]}) is None
    if FIELD[name] != "metrics":
        # A segmented round, or a backend that reports no memory: the field is {}.
        empty = RoundRecord(
            round_idx=0, metrics={}, wall_clock_s=4.0, data_fn_s=0.0, staging_s=0.0, staged_bytes=0, overlapped=True,
        )
        assert reader(name)({"records": [empty]}) is None


def test_stage_gbps_times_stage_hidden_ms_is_the_bytes_a_round_stages():
    run = {"records": ROUNDS}
    assert reader("stage_gbps")(run) * reader("stage_hidden_ms")(run) * 1e6 == pytest.approx(1.6e9)


def test_feed_ms_is_not_data_fn_ms():
    """``data_fn_s`` is the feed's time for THIS round's data, spent under
    the round before: the window's first record carries what ran before the
    window, the harness's ``tracer.collect()`` in a traced run.
    ``host_s["feed"]`` is what ran under the round itself."""
    run = {"records": ROUNDS}
    assert reader("data_fn_ms")(run) == pytest.approx(9000.0)
    assert reader("feed_ms")(run) == pytest.approx(100.0)
