"""The hybrid language model's cell on the CPU at a tiny size, the look for a
chip skipped: a sound program comes out ``correct``, the timed path broken
underneath does not; every planted fault parts from the sound reference; the
configuration's file against the catalog's keys and itself; the operation
counts by hand and at the published widths; the new metric readers on made-up
inputs; the accepted causal driver is left as it was."""

import json
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from lib import federated_causal_lm_rounds as accepted, federated_hybrid_lm_rounds as fh, flops_qwen3next
from lib.federated_rounds import _load_module, load_reference

from conftest import BENCH_DIR, read_json

CELL = "qwen3next_round_l8192_b2_1chip"
# The catalog's ``config`` of Qwen3-Next-80B-A3B-Instruct (model-configs guide), key for key.
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5120, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next", "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 10000000, "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
FAULTS = ("no_decay", "no_beta", "no_l2norm", "no_conv", "no_out_gate", "no_attn_gate", "rope_on_all",
          "no_shared_gate", "no_renorm", "noncausal", "stale_slab")


@pytest.fixture
def tiny_spec():
    spec = run.load_spec(CELL)
    config = spec["config"]
    config.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=16, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, num_experts=2, num_experts_per_tok=2, vocab_size=64,
        compute_dtype="float32", batch_size=2, train_samples=8,
    )
    config["share"] = dict(config["share"], router_outputs=8, first_expert=2)
    config["training"] = dict(config["training"], seq_len=128)
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
def test_correct_follows_the_timed_path(tiny_spec, monkeypatch, fault, expected):
    if fault is not None:
        real = fh._driver.build_federated_round

        def builder(*args, **kwargs):
            broken = fault(real(*args, **kwargs))
            broken.data_placement = "streamed"
            return broken

        monkeypatch.setattr(fh._driver, "build_federated_round", builder)
    result = fh.run(tiny_spec, 2**31 + 77, 0.3, False, time.perf_counter(), require_chip=False)
    assert result["correct"] is expected, result["compared"]
    assert list(result)[-1] == "compared"
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"round_s", "setup_s"}
    numbers = result["info"]["numbers"]
    if fault is None:
        assert numbers["direction_r0"] < 1e-3 and numbers["step_loss_r1"] < 1e-4 and numbers["expert_rows_r0"] < 0.01
        assert numbers["decay_r0"] < 1e-4 and numbers["decay_r1"] < 1e-4


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_faults_read_far_from_the_reference(tiny_spec, fault):
    """Each fault, planted into the reference put in the program's place,
    parts from the sound reference in at least one compared number."""
    cell = fh.Cell(tiny_spec, 11, jax.devices()[:1])
    starts = [cell.start, cell.start]
    sound = cell.reference(starts)
    faulty = cell.reference(starts, fault=fault)
    numbers = fh.compare(starts, faulty, sound)
    k = 1 if fault == "stale_slab" else 0
    assert max(numbers[f"direction_r{k}"], numbers[f"step_loss_r{k}"], numbers[f"total_change_r{k}"]) > 0.01, numbers
    # The attention layer's own faults show in its own leaves; the others' need not.
    assert fh.compare(starts, sound, sound)[f"gattn_direction_r{k}"] < 1e-9
    if fault in ("rope_on_all", "noncausal"):
        assert numbers[f"gattn_direction_r{k}"] > max(0.05, numbers[f"direction_r{k}"]), numbers


def test_the_accepted_causal_driver_is_left_as_it_was():
    """This kind binds names in an instance of the accepted driver that it
    loaded for itself: the accepted cell's own module still reads its own."""
    assert fh._driver is not accepted and fh._driver.Cell is fh.Cell
    assert accepted.Cell is not fh.Cell and "mtp_loss" in accepted.PROGRAM_METRICS
    assert accepted.flops_joyai is not flops_qwen3next and accepted.MODULE_SCOPES == ("mtp",)
    assert "mtp_loss" not in fh.PROGRAM_METRICS and "gdn_decay_mean" in fh.PROGRAM_METRICS


def test_the_feed_has_no_noise_and_covers_the_slice(tiny_spec):
    cell = fh.Cell(tiny_spec, 5, jax.devices()[:1])
    ids0, w0 = (x.copy() for x in cell.feed(0))
    ids1, _ = cell.feed(1)
    assert ids0.shape == (1, 4, 2, 128) and ids0.dtype == np.int32 and w0.dtype == np.float32
    assert np.all(w0 == 1.0) and 0 <= ids0.min() and ids0.max() == 63  # the last row of the slice is a token like any
    assert not np.array_equal(ids0, ids1)
    assert {r.tobytes() for r in ids0.reshape(-1, 128)} == {r.tobytes() for r in ids1.reshape(-1, 128)}


def test_the_configuration_agrees_with_the_catalog_and_itself():
    config = read_json("benchmark", "configs", "qwen3next80b_a3b_ep32_bf16.json")
    entry = next(c for c in read_json("BENCHMARK.json")["configs"] if c["name"] == "qwen3next80b_a3b_ep32_bf16")
    differing = {k for k, v in PUBLISHED.items() if config.get(k, "missing") != v}
    assert differing == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert set(entry["reduced"]) == differing | {"local_epochs", "mesh_clients"} == set(config["published"])
    for key in differing:
        assert config["published"][key] == PUBLISHED[key]
    # No width is cut: what ``reduced`` names is depth, experts held, rows held.
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size" for k in entry["reduced"])
    share = config["share"]
    assert share["router_outputs"] == PUBLISHED["num_experts"] == share["chips_per_layer"] * config["num_experts"]
    assert share["first_expert"] == share["rank"] * config["num_experts"]
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # One whole period of the layer pattern, at least 8 routed experts a layer.
    assert config["num_hidden_layers"] % config["full_attention_interval"] == 0 and config["num_hidden_layers"] >= 4
    assert config["num_experts"] >= 8
    for key in ("deployment", "assumed", "sources"):
        assert config[key]
    model = fh.reference_config(config)
    program = fh.program_config(config)
    assert program.experts_held == 16 and program.num_experts == 512 and program.vocab_held == 18992
    assert program.seq_len == model["seq_len"] == 8192 and program.rotary_dim == 64 and program.linear_layers == 3
    assert [program.is_linear(i) for i in range(4)] == [True, True, True, False]
    # The parameters the issue counts: 424.3 M, by the reference's shapes and by the arithmetic.
    n = sum(int(np.prod(shape)) for _, shape, _ in load_reference(config)._shapes(model))
    assert abs(n / 1e6 - 424.3) < 0.1 and flops_qwen3next.parameters(model) == n


def test_every_line_of_the_declaration_keeps_its_form():
    """The driver refuses a ``why``, ``layer`` or ``source`` over 200 characters before any run; the accepted test
    holds only the cells' ``why`` to that, and this PR's first configuration line was 205."""
    declared = read_json("BENCHMARK.json")
    lines = [(c["name"], c[k]) for c in declared["configs"] for k in ("why", "source")]
    lines += [(w["name"], w["why"]) for w in declared["workloads"]] + [(m["name"], m["layer"]) for m in declared["per_layer"]]
    bad = [(name, len(text)) for name, text in lines if not (1 <= len(text) <= 200 and text.isascii() and text.isprintable())]
    assert not bad, bad


def test_operation_counts_by_hand_and_at_the_cell_sizes():
    small = dict(
        hidden_size=8, num_hidden_layers=4, full_attention_interval=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=4, linear_num_key_heads=1, linear_num_value_heads=2, linear_key_head_dim=4, linear_value_head_dim=2,
        linear_conv_kernel_dim=4, moe_intermediate_size=4, shared_expert_intermediate_size=6, num_experts_per_tok=2,
        router_outputs=8, experts_held=2, vocab_held=16, seq_len=4,
    )
    assert flops_qwen3next.linear_layers(small) == 3 and flops_qwen3next.attention_layers(small) == 1
    assert flops_qwen3next.causal_pairs(small) == 10 and flops_qwen3next.expected_held_pairs(small, 1) == 2.0
    gdn = 2 * 4 * (8 * (4 + 4 + 4 + 4 + 4) + 4 * 8 + 4 * (4 + 4 + 4))          # products and taps: 1,920
    rule = 2 * 4 * 2 * 3 * 4 * 2                                                  # 384
    gattn = 2 * 4 * 8 * (3 * 16 + 2 * 8)                                          # 4,096
    scores = 2 * 10 * 4 * 2 * 4                                                   # 640
    router, shared, held = 2 * 4 * 8 * 8, 2 * 4 * 8 * (3 * 6 + 1), 2 * 2.0 * 3 * 8 * 4
    head = 2 * 4 * 8 * 16
    by_hand = 3 * (gdn + rule) + gattn + scores + 4 * (router + shared + held) + head
    assert flops_qwen3next.forward_flops(small, 1) == by_hand
    assert flops_qwen3next.rule_step(small, 1) == (3 * 3 * rule, 3 * 3 * 4 * (2 * (2 * 4 + 2 * 4) + 4 * 2 * 2))
    assert flops_qwen3next.attention_step(small, 1)[0] == 3 * scores

    config = run.load_spec(CELL)["config"]
    model = fh.reference_config(config)
    parts = flops_qwen3next.forward_parts(model, 2)
    assert flops_qwen3next.expected_held_pairs(model, 2) == 5120
    # The issue's numbers, TFLOP forward a step of two sequences.
    for name, tflop in (("gdn_products", 1.105), ("gdn_rule", 0.052), ("gattn_products", 0.893), ("gattn_scores", 1.100),
                        ("router", 0.034), ("shared_expert", 0.103), ("held_experts", 0.032), ("head", 1.275)):
        assert abs(parts[name] / 1e12 - tflop) < 0.001, name
    assert abs(flops_qwen3next.forward_flops(model, 2) / 1e12 - 7.41) < 0.01
    assert abs(flops_qwen3next.train_step_flops(model, 2) / 1e12 - 22.2) < 0.05
    # The program's own arithmetic counts the same.
    from fedcrack_tpu.tasks import task_for

    assert abs(task_for(fh.program_config(config)).step_flops(2) / flops_qwen3next.train_step_flops(model, 2) - 1) < 1e-9
    # Operations follow the counter; absent experts never count.
    assert flops_qwen3next.train_step_flops(model, 2, 0.0) < flops_qwen3next.train_step_flops(model, 2)
    # The rule is bound by its bytes on a v5e: 6 x 128 x 128 operations a token a value head against 16.6 kB.
    ops, moved = flops_qwen3next.rule_step(model, 2)
    peaks = read_json("benchmark", "peaks.json")
    assert ops == 3 * 3 * parts["gdn_rule"] and moved == 3 * 3 * 16384 * (2 * (2 * 2048 + 2 * 4096) + 4 * 64)
    assert moved / peaks["TPU v5 lite"]["hbm_bytes_per_s"] > ops / peaks["TPU v5 lite"]["bf16_flops_per_s"]


def test_new_metric_readers():
    def reader(name):
        return _load_module(f"{BENCH_DIR}/metrics/{name}.py", "m_" + name).read

    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    run_ctx = {
        "scope_seconds": {"gdn_rule": 2.0, "gdn_proj": 1.0, "gdn_conv": 0.5, "gattn": 4.0, "gattn_proj": 0.25, "moe_experts": 4.0},
        "peaks": peaks,
        "kernel_work": {"gdn_rule": (10.0, 10.0), "gattn": (100.0, 1.0), "moe_experts": (10.0, 20.0)},
        "records": [types.SimpleNamespace(metrics={"expert_rows": np.array([[[2.0, 6.0], [4.0, 4.0]]])})],
    }
    assert reader("gdn_rule_ms")(run_ctx) == 2000.0 and reader("gdn_proj_ms")(run_ctx) == 1500.0
    assert reader("gattn_ms")(run_ctx) == 4000.0 and reader("gattn_proj_ms")(run_ctx) == 250.0
    assert reader("gdn_rule_roofline")(run_ctx) == pytest.approx(50.0)  # memory bound: 1 s of 2
    assert reader("gattn_roofline")(run_ctx) == pytest.approx(25.0)  # compute bound: 1 s of 4
    assert reader("q3n_moe_experts_ms")(run_ctx) == 4000.0
    assert reader("q3n_moe_experts_roofline")(run_ctx) == pytest.approx(50.0)  # memory bound: 2 s of 4
    assert reader("q3n_expert_rows_max_over_mean")(run_ctx) == pytest.approx(1.5)
    # A program without the spans and counters: silent, never an error.
    old = {"records": [types.SimpleNamespace(metrics={"loss": np.zeros(1)})], "trace": {}, "peaks": peaks}
    for name in ("gdn_rule_ms", "gdn_rule_roofline", "gdn_proj_ms", "gattn_ms", "gattn_roofline", "gattn_proj_ms",
                 "q3n_moe_experts_ms", "q3n_moe_experts_roofline", "q3n_expert_rows_max_over_mean"):
        assert reader(name)(old) is None


def test_steps_of_a_slice_are_the_marking_operations_events():
    """Most instruction names of this model run two or four times a step;
    ``trace/scopes.py``'s median of the counts then reads twice the steps and
    halves every loop. This kind counts the steps by the operation that
    recurs and takes the most time."""
    scopes = fh._driver._load_module(f"{BENCH_DIR}/trace/scopes.py", "bench_trace_scopes_test")
    hlo = "\n".join(
        f'  %{name} = f32[8]{{0}} fusion(%p), kind=kLoop, metadata={{op_name="jit(client_fit)/while/body/{scope}/mul"}}'
        for name, scope in [("mark.1", "gattn"), ("once.1", "optimizer"), ("deep.1", "gdn_rule")]
        + [(f"twice.{i}", "gdn_rule") for i in range(6)]
    )

    def events(name, n, ns):
        return [types.SimpleNamespace(name=f"%{name} = f32[8] fusion(...)", start_ns=0, duration_ns=ns) for _ in range(n)]

    line = types.SimpleNamespace(name="XLA Ops", events=(
        events("mark.1", 5, 10_000_000) + events("once.1", 5, 1_000_000) + events("deep.1", 640, 10_000)
        + [e for i in range(6) for e in events(f"twice.{i}", 10, 500_000)] + events("while.3", 5, 99_000_000)
    ))
    profile = types.SimpleNamespace(planes=[types.SimpleNamespace(name="/device:TPU:0", lines=[line])])
    ours = scopes.seconds_a_step(profile, hlo, ("gattn", "optimizer", "gdn_rule"), 1)
    assert ours["gattn"] == pytest.approx(0.010) and ours["optimizer"] == pytest.approx(0.001)
    # Six instructions twice a step and one 128 times: 6 x 2 x 0.5 ms + 128 x 10 us.
    assert ours["gdn_rule"] == pytest.approx(0.006 + 0.00128)
    theirs = scopes.accepted_seconds_a_step(profile, hlo, ("gattn", "optimizer", "gdn_rule"), 1)
    assert theirs["gdn_rule"] == pytest.approx(0.003 + 0.00064)  # what the median makes of it
    # The accepted cells' own loader still hands out the accepted function.
    plain = _load_module(f"{BENCH_DIR}/trace/scopes.py", "bench_trace_scopes_plain")
    assert not hasattr(plain, "accepted_seconds_a_step")


def test_the_study_names_every_fault_the_reference_plants():
    import re

    study = _load_module(f"{BENCH_DIR}/study/hybrid_lm_study.py", "bench_study_hybrid_test")
    planted = set(re.findall(r'``"(\w+)"``', load_reference({"reference": "qwen3next_gdn_moe"}).__doc__.split("``fault`` plants")[1]))
    assert planted | {"stale_slab"} == set(study.FAULTS) == set(FAULTS)
    assert set(study.VARIANTS) == {"control_fp8", "witness_bf16"} | {f"fault_{name}" for name in FAULTS}
    assert study._study.fc is fh and study._study.study_seed is study.study_seed


def test_the_limits_name_what_the_comparison_gives():
    limits = read_json("benchmark", "limits", CELL + ".json")
    assert {"window_compiles", "failed_rounds"} <= set(limits)
    for k in (0, 1):
        assert {f"direction_r{k}", f"gattn_direction_r{k}", f"total_change_r{k}", f"step_loss_r{k}", f"expert_rows_r{k}",
                f"decay_r{k}"} <= set(limits)
    assert json.dumps(limits)
