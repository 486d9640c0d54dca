"""The three readers of ``RoundRecord.host_s`` on hand-made records."""

import importlib.util
import os
import types

import pytest

from conftest import BENCH_DIR

from fedcrack_tpu.parallel.driver import RoundRecord


def reader(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH_DIR, "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def record(wall, **host_s):
    return RoundRecord(
        round_idx=0, metrics={}, wall_clock_s=wall, data_fn_s=0.0, staging_s=0.0,
        staged_bytes=0, overlapped=True, host_s=host_s,
    )


ROUNDS = [
    record(4.0, dispatch=0.01, feed=0.8, stage=0.4, barrier=2.79, handoff=0.0),
    record(4.0, dispatch=0.01, feed=0.6, stage=0.6, barrier=2.79, handoff=0.012),
]


@pytest.mark.parametrize(
    "name, want",
    [("stage_hidden_ms", 500.0), ("handoff_ms", 6.0), ("host_busy_pct", 100.0 * 1.21 / 4.0)],
)
def test_reads_the_mean_over_the_windows_rounds(name, want):
    assert reader(name)({"records": ROUNDS}) == pytest.approx(want)


@pytest.mark.parametrize("name", ["stage_hidden_ms", "handoff_ms", "host_busy_pct"])
def test_silent_without_records_or_without_the_counters(name):
    assert reader(name)({"records": []}) is None
    # A program from before the counters: its records have no ``host_s``.
    old = types.SimpleNamespace(wall_clock_s=4.0, staging_s=0.0, data_fn_s=0.8)
    assert reader(name)({"records": [old]}) is None
    # A segmented round keeps its own timeline and leaves ``host_s`` empty.
    assert reader(name)({"records": [record(4.0)]}) is None


def test_host_busy_is_100_where_the_barrier_is_empty():
    paced_by_host = record(3.0, dispatch=0.5, feed=1.5, stage=1.0, barrier=0.0, handoff=0.0)
    assert reader("host_busy_pct")({"records": [paced_by_host]}) == pytest.approx(100.0)
