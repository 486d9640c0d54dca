"""The block-diffusion cell on the CPU at a tiny size, the look for a chip
skipped: a sound program comes out ``correct``, the timed path broken
underneath does not; the feed, the operation counts, the scope reader and
the new metric readers on made-up inputs."""

import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from lib import federated_textdiff_rounds as ft, flops_sdar, textgen
from lib.federated_rounds import _load_module

from conftest import BENCH_DIR


@pytest.fixture
def tiny_text_spec():
    spec = run.load_spec("sdar_round_l4096_b2_1chip")
    config = spec["config"]
    config.update(
        hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        moe_intermediate_size=32, num_experts=2, num_experts_per_tok=2, vocab_size=64,
        compute_dtype="float32", batch_size=2, train_samples=8,
    )
    config["share"] = dict(config["share"], router_outputs=8, first_expert=2)
    config["training"] = dict(config["training"], seq_len=32)
    config["optimizer"] = dict(config["optimizer"], learning_rate=1e-3)
    return spec


def _stale_slab(round_fn):
    """A round that trains on its first round's data ever after."""
    first = {}

    def broken(variables, ids, weight, active, n_samples):
        if not first:  # copies: the driver releases a round's slab
            first["data"] = (jnp.copy(ids), jnp.copy(weight))
        return round_fn(variables, *first["data"], active, n_samples)
    return broken


def _unchanged(round_fn):
    def broken(variables, ids, weight, active, n_samples):
        kept = jax.tree_util.tree_map(jnp.copy, variables)  # the round consumes its input
        _, metrics = round_fn(variables, ids, weight, active, n_samples)
        return kept, metrics
    return broken


@pytest.mark.parametrize("fault,expected", [(None, True), (_stale_slab, False), (_unchanged, False)],
                         ids=lambda v: getattr(v, "__name__", str(v)))
def test_correct_follows_the_timed_path(tiny_text_spec, monkeypatch, fault, expected):
    if fault is not None:
        real = ft.build_federated_round

        def builder(*args, **kwargs):
            broken = fault(real(*args, **kwargs))
            broken.data_placement = "streamed"
            return broken

        monkeypatch.setattr(ft, "build_federated_round", builder)
    result = ft.run(tiny_text_spec, 2**31 + 77, 0.3, False, time.perf_counter(), require_chip=False)
    assert result["correct"] is expected, result["compared"]
    assert list(result)[-1] == "compared"
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"round_s", "setup_s"}
    numbers = result["info"]["numbers"]
    if fault is None:
        assert numbers["direction_r0"] < 1e-3 and numbers["step_loss_r1"] < 1e-4 and numbers["expert_rows_r0"] < 0.01


@pytest.mark.parametrize("fault", ["all_experts", "no_renorm", "causal_clean", "stale_slab"])
def test_planted_faults_read_far_from_the_reference(tiny_text_spec, fault):
    """Each fault, planted into the reference put in the program's place,
    parts from the sound reference in at least one compared number."""
    import jax

    cell = ft.Cell(tiny_text_spec, 11, jax.devices()[:1])
    starts = [cell.start, cell.start]
    sound = cell.reference(starts)
    faulty = cell.reference(starts, fault=fault)
    numbers = ft.compare(starts, [dict(r, variables=r["variables"], step_loss=r["step_loss"]) for r in faulty], sound)
    k = 1 if fault == "stale_slab" else 0
    assert max(numbers[f"direction_r{k}"], numbers[f"step_loss_r{k}"]) > 0.01, numbers


def test_feed_is_a_function_of_seed_and_round():
    seqs = textgen.client_sequences(7, 2, 8, 32, 64)
    assert seqs.shape == (2, 8, 32) and seqs.max() <= 62 and seqs.min() >= 0
    a, b = textgen.TextFeed(seqs, 7, 4, 2, 4, (0.1, 1.0)), textgen.TextFeed(seqs, 7, 4, 2, 4, (0.1, 1.0))
    ids0, w0 = (x.copy() for x in a(0))
    ids1, w1 = (x.copy() for x in a(1))
    assert ids0.shape == (2, 4, 2, 32) and w0.dtype == np.float32 and ids0.dtype == np.int32
    assert np.array_equal(b(1)[0], ids1) and np.array_equal(b(1)[1], w1)
    assert not np.array_equal(ids0, ids1) and not np.array_equal(w0, w1)
    # A block shares its t: its masked tokens share one weight, 1/t in [1, 10].
    blocks = w0.reshape(-1, 4)
    for row in blocks[:64]:
        nz = row[row > 0]
        assert nz.size == 0 or (np.all(nz == nz[0]) and 1.0 <= nz[0] <= 10.0 + 1e-5)
    # Every sequence of a round is one of the client's, each at most once.
    flat = ids0[0].reshape(-1, 32)
    assert len({r.tobytes() for r in flat}) == 8 and {r.tobytes() for r in flat} <= {r.tobytes() for r in seqs[0]}


def test_operation_counts_at_the_cell_sizes():
    model = ft.reference_config(run.load_spec("sdar_round_l4096_b2_1chip")["config"])
    assert flops_sdar.allowed_pairs(model) == 4096 * 4100
    assert abs(flops_sdar.allowed_pairs(model) / (2 * 4096) ** 2 - 0.25) < 1e-3
    assert flops_sdar.expected_held_pairs(model, 2) == 16384
    ops, _ = flops_sdar.attention_forward(model, 2)
    assert abs(ops / 1e12 - 0.55) < 0.01
    ops, moved = flops_sdar.experts_forward(model, 16384)
    assert abs(ops / 1e12 - 0.155) < 0.001 and moved > 2 * 3 * 16 * 2048 * 768
    assert abs(flops_sdar.train_step_flops(model, 2) / 1e12 - 17.9) < 0.1
    # Operations follow the counter; absent experts never count.
    assert flops_sdar.train_step_flops(model, 2, 0.0) < flops_sdar.train_step_flops(model, 2)
    seconds, bound = flops_sdar.roofline_seconds(197e12, 1.0, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert seconds == 1.0 and bound == "compute"


HLO = """
HloModule jit_client_fit

%body (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(client_fit)/while/body/jvp(layer0)/checkpoint/blockdiff_attn/dot" source_file="x.py"}
  %copy.3 = f32[8]{0} copy(%fusion.1)
  %custom-call.7 = f32[8]{0} custom-call(%copy.3), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block\\": 512}"
}}, metadata={op_name="jit(client_fit)/while/body/transpose(jvp(layer1))/moe_experts/pallas_call"}
  ROOT %fusion.9 = f32[8]{0} fusion(%custom-call.7), kind=kLoop, calls=%g, metadata={op_name="jit(client_fit)/while/body/optimizer/add"}
}
"""


def _profile(events):
    line = types.SimpleNamespace(name="XLA Ops", events=[
        types.SimpleNamespace(name=f"%{n} = f32[8]", start_ns=s, duration_ns=d) for n, s, d in events
    ])
    return types.SimpleNamespace(planes=[types.SimpleNamespace(name="/device:TPU:0", lines=[line])])


def test_scopes_join_the_trace_to_the_text():
    scopes = _load_module(f"{BENCH_DIR}/trace/scopes.py", "bench_trace_scopes_test")
    names = scopes.scope_map(HLO, ft.KERNEL_SCOPES)
    assert names["fusion.1"] == "blockdiff_attn" and names["custom-call.7"] == "moe_experts"
    assert names["copy.3"] == "moe_experts" and names["fusion.9"] == "optimizer"
    # Two steps and a cut third: means, whatever the number of events.
    events = [("fusion.1", 0, 1000), ("custom-call.7", 1000, 3000), ("fusion.9", 4000, 500),
              ("fusion.1", 5000, 3000), ("custom-call.7", 8000, 3000), ("fusion.9", 11000, 500),
              ("fusion.1", 12000, 2000), ("while.2", 0, 14000)]
    got = scopes.seconds_a_step(_profile(events), HLO, ft.KERNEL_SCOPES)
    assert got["blockdiff_attn"] == pytest.approx(2e-6) and got["moe_experts"] == pytest.approx(3e-6)
    assert "lm_head" not in got


def test_new_metric_readers():
    def reader(name):
        return _load_module(f"{BENCH_DIR}/metrics/{name}.py", "m_" + name).read

    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    run_ctx = {
        "scope_seconds": {"blockdiff_attn": 2.0, "moe_experts": 4.0}, "peaks": peaks,
        "kernel_work": {"blockdiff_attn": (100.0, 1.0), "moe_experts": (10.0, 20.0)},
        "records": [types.SimpleNamespace(metrics={"expert_rows": np.array([[[2.0, 6.0], [4.0, 4.0]]])})],
        "trace": {"collective_s": 0.003},
    }
    assert reader("blockdiff_attn_ms")(run_ctx) == 2000.0 and reader("moe_experts_ms")(run_ctx) == 4000.0
    assert reader("blockdiff_attn_roofline")(run_ctx) == pytest.approx(50.0)  # compute bound: 1 s of 2
    assert reader("moe_experts_roofline")(run_ctx) == pytest.approx(50.0)  # memory bound: 2 s of 4
    assert reader("expert_rows_max_over_mean")(run_ctx) == pytest.approx(1.5)
    assert reader("fold_collective_ms")(run_ctx) == pytest.approx(3.0)
    # A program without the spans and counters: silent, never an error.
    old = {"records": [types.SimpleNamespace(metrics={"loss": np.zeros(1)})], "trace": {"collective_s": 0.0}, "peaks": peaks}
    for name in ("blockdiff_attn_ms", "moe_experts_ms", "blockdiff_attn_roofline", "moe_experts_roofline",
                 "expert_rows_max_over_mean", "fold_collective_ms"):
        assert reader(name)(old) is None
