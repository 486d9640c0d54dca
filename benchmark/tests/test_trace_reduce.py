"""The reduction from a trace to device metrics, on a trace recorded on one
v5e chip (PR 25): a 64 px, 6-steps-of-4 federation, the end of one round,
the boundary and the start of the next."""

import os

import pytest

from conftest import BENCH_DIR
from lib.federated_rounds import _load_module


@pytest.fixture(scope="module")
def reduce():
    return _load_module(os.path.join(BENCH_DIR, "trace", "reduce.py"), "bench_trace_reduce_test")


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    return ProfileData.from_file(os.path.join(BENCH_DIR, "trace", "recorded", "tiny_round_1chip.xplane.pb"))


def test_recorded_trace_reduces_to_known_numbers(reduce, recorded):
    out = reduce.reduce_profile(recorded, 1)
    assert out["busy_s"] == pytest.approx(0.003382393, rel=1e-6)
    assert out["window_s"] == pytest.approx(0.027967696, rel=1e-6)
    assert out["step_period_s"] == pytest.approx(0.000394847, rel=1e-5)
    assert out["collective_s"] == 0.0
    device = out["per_device"][0]
    # The boundary between the two rounds is one long stretch, nearly all idle.
    assert device["boundary_s"] == pytest.approx(0.024395398, rel=1e-5)
    assert 0.98 < device["boundary_idle_s"] / device["boundary_s"] <= 1.0
    assert device["busy_s"] + device["idle_s"] == pytest.approx(device["extent_s"], rel=1e-9)
    ops = out["breakdown"]["device_ops"]
    assert len(ops) == 10 and ops[0][0] == "%fusion.1869" and all(" = " not in name for name, _ in ops)
    # Enclosing events (the scan's while) are in no sum.
    assert not any(name.startswith("%while") for name, _ in ops)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert max(gaps, key=gaps.get) == "XlaLinearize"
    assert out["window_s"] > out["busy_s"] > 0


def test_idle_share_of_a_round(reduce, recorded):
    out = reduce.reduce_profile(recorded, 1, window_s=0.03)
    assert out["window_s"] == 0.03
    device = out["per_device"][0]
    share = reduce.idle_share_of_round(out, 1.0)
    expected = device["boundary_idle_s"] + device["steady_idle_rate"] * (1.0 - device["boundary_s"])
    assert share == pytest.approx(expected)


def test_a_cell_with_more_chips_than_the_trace_has_planes_is_an_error(reduce, recorded):
    with pytest.raises(ValueError):
        reduce.reduce_profile(recorded, 4)


def test_enclosing_and_collective_names(reduce):
    assert reduce.ENCLOSING.match("%while.608") and reduce.ENCLOSING.match("%conditional.3")
    assert not reduce.ENCLOSING.match("%fusion.12") and not reduce.ENCLOSING.match("%while_fusion")
    assert reduce.COLLECTIVE.search("%all-gather.41") and reduce.COLLECTIVE.search("%all-reduce-start.2")
    assert not reduce.COLLECTIVE.search("%fusion.99")
