"""A whole run on the CPU with the look for a chip skipped: a sound program
comes out ``correct``; the timed path broken underneath does not."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import federated_rounds as fr


def _unchanged(round_fn):
    """A round that hands its input state back, with the sound round's metrics."""
    def broken(variables, images, masks, active, n_samples):
        _, metrics = round_fn(variables, images, masks, active, n_samples)
        return variables, metrics
    return broken


def _half_batch(round_fn):
    """A round that leaves the second half of every batch out."""
    def broken(variables, images, masks, active, n_samples):
        half = images.shape[2] // 2
        return round_fn(variables, images[:, :, :half], masks[:, :, :half], active, n_samples)
    return broken


def _lost_carry(round_fn):
    """A round that, from the second on, starts from the first round's start
    again: the carry from round to round is lost."""
    first = {}

    def broken(variables, images, masks, active, n_samples):
        if not first:
            first["start"] = jax.tree_util.tree_map(jnp.copy, variables)
        return round_fn(jax.tree_util.tree_map(jnp.copy, first["start"]), images, masks, active, n_samples)
    return broken


def _no_exchange(round_fn):
    """A round whose result is its first client's model: no other client's
    update crosses the chips."""
    def broken(variables, images, masks, active, n_samples):
        alone = np.zeros_like(np.asarray(active))
        alone[0] = 1.0
        new, _ = round_fn(variables, images, masks, alone, n_samples)
        _, metrics = round_fn(variables, images, masks, active, n_samples)
        return new, metrics
    return broken


def _run(spec, monkeypatch, fault=None):
    if fault is not None:
        real = fr.build_federated_round

        def builder(*args, **kwargs):
            broken = fault(real(*args, **kwargs))
            broken.data_placement = "streamed"
            return broken

        monkeypatch.setattr(fr, "build_federated_round", builder)
    return fr.run(spec, 2**31 + 77, 0.5, False, time.perf_counter(), require_chip=False)


CASES = [
    ("round256_b32_1chip", None, True),
    ("round256_b32_1chip", _unchanged, False),
    ("round256_b32_1chip", _half_batch, False),
    ("round256_b32_1chip", _lost_carry, False),
    ("round512_b16_1chip", None, True),
    ("round512_b16_1chip", _half_batch, False),
    ("round512_b16_1chip", _lost_carry, False),
    ("four_clients", None, True),
    ("four_clients", _no_exchange, False),
]


@pytest.mark.parametrize("workload,fault,expected", CASES, ids=lambda v: getattr(v, "__name__", str(v)))
def test_correct_follows_the_timed_path(tiny_spec, monkeypatch, workload, fault, expected):
    # The harness's path for several clients, on four virtual devices: cell 1
    # with its traffic's mesh set to (4,1). No committed cell has it yet.
    spec = tiny_spec("round256_b32_1chip", mesh=[4, 1]) if workload == "four_clients" else tiny_spec(workload)
    result = _run(spec, monkeypatch, fault)
    assert result["correct"] is expected, result["compared"]
    assert list(result)[-1] == "compared"
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"round_s", "setup_s"}
    assert all(set(pair) == {"value", "limit"} for pair in result["compared"].values())


def test_a_compile_inside_the_window_fails_the_run(tiny_spec, monkeypatch):
    spec = tiny_spec("round256_b32_1chip")
    real = fr.build_federated_round
    calls = {"n": 0}

    def builder(*args, **kwargs):
        round_fn = real(*args, **kwargs)

        def compiling(variables, images, masks, active, n_samples):
            calls["n"] += 1
            if calls["n"] > 1:
                jax.jit(lambda x: x * calls["n"] + 1.5)(jnp.ones(calls["n"] + 3)).block_until_ready()
            return round_fn(variables, images, masks, active, n_samples)

        compiling.data_placement = "streamed"
        return compiling

    monkeypatch.setattr(fr, "build_federated_round", builder)
    result = fr.run(spec, 5, 0.5, False, time.perf_counter(), require_chip=False)
    assert result["correct"] is False
    assert result["compared"]["window_compiles"]["value"] >= 1


def test_no_chip_means_no_result(tiny_spec):
    with pytest.raises(SystemExit) as info:
        fr.run(tiny_spec("round256_b32_1chip"), 5, 0.5, False, time.perf_counter())
    assert info.value.code != 0
