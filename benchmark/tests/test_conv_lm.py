"""The convolution language model's cell on the CPU at a tiny size, the look
for a chip skipped: a sound program comes out ``correct``, the timed path
broken underneath does not; every planted fault parts from the sound
reference past a limit; the configuration's file against the catalog's keys
and itself; the operation counts by hand and at the published widths; the
new metric readers on made-up inputs; the study's run of a seed; the accepted
causal driver is left as it was."""

import json
import re
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from lib import federated_causal_lm_rounds as accepted, federated_conv_lm_rounds as fv, flops_lfm2
from lib import federated_looped_lm_rounds as looped
from lib.federated_rounds import _load_module, load_reference

from conftest import BENCH_DIR, read_json

CELL = "lfm2moe_round_l8192_b2_1chip"
# The catalog's ``config`` of LFM2-8B-A1B (model-configs guide), key for key.
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention",
                    "conv", "conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}
FAULTS = ("taps_shifted", "no_b_gate", "bias_in_weights", "no_qk_norm", "stale_slab")


@pytest.fixture
def tiny_spec():
    spec = run.load_spec(CELL)
    config = spec["config"]
    config.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2, intermediate_size=96, moe_intermediate_size=32,
        num_experts=2, num_experts_per_tok=2, vocab_size=64, compute_dtype="float32", batch_size=2, train_samples=8,
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
        real = fv._driver.build_federated_round

        def builder(*args, **kwargs):
            broken = fault(real(*args, **kwargs))
            broken.data_placement = "streamed"
            return broken

        monkeypatch.setattr(fv._driver, "build_federated_round", builder)
    result = fv.run(tiny_spec, 2**31 + 77, 0.3, False, time.perf_counter(), require_chip=False)
    assert result["correct"] is expected, result["compared"]
    assert list(result)[-1] == "compared"
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"round_s", "setup_s"}
    numbers = result["info"]["numbers"]
    if fault is None:
        for k in (0, 1):
            assert numbers[f"direction_r{k}"] < 1e-3 and numbers[f"step_loss_r{k}"] < 1e-4, numbers
            assert numbers[f"conv_direction_r{k}"] < 1e-3 and numbers[f"attn_direction_r{k}"] < 1e-3
            assert numbers[f"expert_rows_r{k}"] < 0.01 and numbers[f"expert_bias_moved_r{k}"] == 0.0


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_faults_read_past_a_limit(tiny_spec, fault):
    """Each fault, planted into the reference put in the program's place,
    parts from the sound reference past at least one of the cell's limits."""
    cell = fv.Cell(tiny_spec, 11, jax.devices()[:1])
    starts = [cell.start, cell.start]
    sound = cell.reference(starts)
    faulty = cell.reference(starts, fault=fault)
    numbers = fv.compare(starts, faulty, sound)
    limits = tiny_spec["limits"]
    k = 1 if fault == "stale_slab" else 0
    held = {name: numbers[name] / limits[name] for name in limits if name.endswith(f"_r{k}") and name in numbers}
    assert max(held.values()) > 1.0, numbers
    assert max(fv.compare(starts, sound, sound).values()) < 1e-9
    if fault in ("taps_shifted", "no_b_gate"):
        assert numbers[f"conv_direction_r{k}"] > numbers[f"direction_r{k}"], numbers
    if fault == "no_qk_norm":
        assert numbers[f"attn_direction_r{k}"] > numbers[f"direction_r{k}"], numbers
    if fault == "bias_in_weights":
        assert numbers[f"expert_bias_moved_r{k}"] > 1e-5, numbers


def test_the_study_runs_a_seed_and_names_every_fault_the_reference_plants(tiny_spec):
    study = _load_module(f"{BENCH_DIR}/study/conv_lm_study.py", "bench_study_conv_test")
    planted = set(re.findall(r'``"(\w+)"``', load_reference({"reference": "lfm2_conv_moe"}).__doc__.split("``fault`` plants")[1]))
    assert planted | {"stale_slab"} == set(study.FAULTS) == set(FAULTS)
    assert set(study.VARIANTS) == {"control_fp8", "witness_bf16"} | {f"fault_{name}" for name in FAULTS}
    assert study._study.fc is fv and study._study.study_seed is study.study_seed
    rows = []
    study.study_seed(tiny_spec, 3, jax.devices()[:1], [("fault_no_b_gate", {0})], lambda *a, **kw: rows.append((a, kw)), 1e9)
    (program, said), (fault, fault_said) = rows
    assert program[0] == "program" and said["verdict"] == "correct" and "decay" not in said
    assert fault[0] == "fault_no_b_gate" and fault_said["verdict"] == "not correct"
    assert all(name.endswith("_r0") for name in fault[2])  # round 1 not followed


def test_the_accepted_causal_driver_is_left_as_it_was():
    """This kind binds names in an instance of the accepted driver that it
    loaded for itself: the accepted cell's own module still reads its own."""
    assert fv._driver is not accepted and fv._driver.Cell is fv.Cell
    assert accepted.Cell is not fv.Cell and "mtp_loss" in accepted.PROGRAM_METRICS
    assert accepted.flops_joyai is not flops_lfm2 and accepted.MODULE_SCOPES == ("mtp",)
    assert accepted._load_module is not looped._load_trace_module and fv._driver._load_module is looped._load_trace_module
    assert fv._driver.MODULE_SCOPES == tuple(f"layer{i}" for i in range(5))


def test_the_feed_has_no_noise_and_covers_the_slice(tiny_spec):
    cell = fv.Cell(tiny_spec, 5, jax.devices()[:1])
    ids0, w0 = (x.copy() for x in cell.feed(0))
    ids1, _ = cell.feed(1)
    assert ids0.shape == (1, 4, 2, 128) and ids0.dtype == np.int32 and w0.dtype == np.float32
    assert np.all(w0 == 1.0) and 0 <= ids0.min() and ids0.max() == 63  # the last row of the slice is a token like any
    assert not np.array_equal(ids0, ids1)
    assert {r.tobytes() for r in ids0.reshape(-1, 128)} == {r.tobytes() for r in ids1.reshape(-1, 128)}


def test_the_configuration_agrees_with_the_catalog_and_itself():
    config = read_json("benchmark", "configs", "lfm2_8b_a1b_ep4_bf16.json")
    entry = next(c for c in read_json("BENCHMARK.json")["configs"] if c["name"] == "lfm2_8b_a1b_ep4_bf16")
    differing = {k for k, v in PUBLISHED.items() if config.get(k, "missing") != v}
    assert differing == {"num_hidden_layers", "layer_types", "num_dense_layers", "num_experts", "vocab_size"}
    assert set(entry["reduced"]) == differing | {"local_epochs", "mesh_clients"} == set(config["published"])
    for key in differing - {"layer_types"}:
        assert config["published"][key] == PUBLISHED[key]
    # No width is cut: what ``reduced`` names is depth, the pattern's length, experts held, rows held.
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size" for k in entry["reduced"])
    share = config["share"]
    assert share["router_outputs"] == PUBLISHED["num_experts"] == share["chips_per_layer"] * config["num_experts"]
    assert share["first_expert"] == share["rank"] * config["num_experts"]
    assert config["vocab_size"] * share["chips_per_layer"] == PUBLISHED["vocab_size"]
    # The published layers 1-5: the leading dense layers count once, then one whole period of the pattern.
    kept = config["layer_types"]
    assert kept == PUBLISHED["layer_types"][1:6] and config["num_dense_layers"] == 1
    assert sorted(kept[1:]) == sorted(PUBLISHED["layer_types"][2:6]) == ["conv", "conv", "conv", "full_attention"]
    assert config["num_hidden_layers"] - config["num_dense_layers"] >= 4 and config["num_experts"] >= 8
    for key in ("deployment", "assumed", "sources"):
        assert config[key]
    assert config["tie_word_embeddings"] is True and any("tie_word_embeddings" in a for a in config["assumed"])
    model = fv.reference_config(config)
    program = fv.program_config(config)
    assert program.experts_held == 8 and program.num_experts == 32 and program.vocab_held == 16384
    assert program.seq_len == model["seq_len"] == 8192 and program.head_dim == 64
    assert [program.is_conv(i) for i in range(5)] == [True, False, True, True, True]
    assert [program.is_sparse(i) for i in range(5)] == [False, True, True, True, True]
    # The parameters the issue counts: 474.3 M in the five layers and 33.6 M
    # of tied embedding, by the reference's shapes and by the arithmetic.
    n = sum(int(np.prod(shape)) for _, shape, _ in load_reference(config)._shapes(model))
    assert abs(n / 1e6 - 507.8) < 0.1 and flops_lfm2.parameters(model) == n


def test_every_line_of_the_declaration_keeps_its_form():
    declared = read_json("BENCHMARK.json")
    lines = [(c["name"], c[k]) for c in declared["configs"] for k in ("why", "source")]
    lines += [(w["name"], w["why"]) for w in declared["workloads"]] + [(m["name"], m["layer"]) for m in declared["per_layer"]]
    bad = [(name, len(text)) for name, text in lines if not (1 <= len(text) <= 200 and text.isascii() and text.isprintable())]
    assert not bad, bad
    ours = [m for m in declared["per_layer"] if m["name"].startswith("lfm_")]
    assert len(ours) == 7 and all(m["workloads"] == [CELL] and m["moves"] == "round_s" for m in ours)
    assert CELL in next(m for m in declared["per_layer"] if m["name"] == "budget_overflow_calls")["workloads"]


def test_operation_counts_by_hand_and_at_the_cell_sizes():
    small = dict(
        hidden_size=8, num_hidden_layers=3, layer_types=["conv", "full_attention", "conv"], num_dense_layers=1,
        num_attention_heads=2, num_key_value_heads=1, conv_L_cache=3, intermediate_size=6, moe_intermediate_size=4,
        num_experts_per_tok=2, router_outputs=8, experts_held=2, vocab_held=16, seq_len=4,
    )
    assert flops_lfm2.conv_layers(small) == 2 and flops_lfm2.attention_layers(small) == 1
    assert flops_lfm2.causal_pairs(small) == 10 and flops_lfm2.expected_held_pairs(small, 1) == 2.0
    conv = 2 * 4 * (8 * 24 + 8 * 8) + 4 * 8 * (2 + 6)                       # products, gates and taps: 2,304
    attn = 2 * 4 * 8 * (2 * 8 + 2 * 4) + 2 * 10 * 2 * 2 * 4                  # products and scores: 1,856
    dense, router, held = 2 * 4 * 3 * 8 * 6, 2 * 4 * 8 * 8, 2 * 2.0 * 3 * 8 * 4
    head = 2 * 4 * 8 * 16
    assert flops_lfm2.forward_flops(small, 1) == 2 * conv + attn + dense + 2 * (router + held) + head
    elements = 4 * 8
    assert flops_lfm2.conv_step(small, 1) == (2 * 3 * elements * 8, 2 * elements * 2 * (4 + 7))
    assert flops_lfm2.attention_step(small, 1) == (3 * 2 * 10 * 2 * 2 * 4, 3 * 2 * 4 * 4 * (2 * 2 + 2 * 1))

    config = run.load_spec(CELL)["config"]
    model = fv.reference_config(config)
    parts = flops_lfm2.forward_parts(model, 2)
    assert flops_lfm2.expected_held_pairs(model, 2) == 16384  # 2,048 rows a held expert a step
    # MFLOP a token forward: convolutions 134.3, dense 88.1, experts 88.6, head 67.1, attention 54.5.
    per_token = {name: value / 16384 / 1e6 for name, value in parts.items()}
    assert abs(4 * (per_token["conv_products"] + per_token["conv_taps"]) - 134.3) < 0.1
    assert abs(per_token["dense_mlp"] - 88.1) < 0.1 and abs(per_token["head"] - 67.1) < 0.1
    assert abs(4 * (per_token["router"] + per_token["held_experts"]) - 88.6) < 0.1
    assert abs(per_token["attn_products"] + per_token["attn_scores"] - 54.5) < 0.1
    assert abs(flops_lfm2.train_step_flops(model, 2) / 1e12 - 21.26) < 0.01
    from fedcrack_tpu.tasks import task_for

    assert abs(task_for(fv.program_config(config)).step_flops(2) / flops_lfm2.train_step_flops(model, 2) - 1) < 1e-9
    assert flops_lfm2.train_step_flops(model, 2, 0.0) < flops_lfm2.train_step_flops(model, 2)
    # On a v5e the gates and taps are bound by their bytes, the 64-lane scores by their operations.
    peaks = read_json("benchmark", "peaks.json")["TPU v5 lite"]
    for name, bound in (("conv", "hbm_bytes_per_s"), ("attention", "bf16_flops_per_s")):
        ops, moved = getattr(flops_lfm2, f"{name}_step")(model, 2)
        by = {"bf16_flops_per_s": ops / peaks["bf16_flops_per_s"], "hbm_bytes_per_s": moved / peaks["hbm_bytes_per_s"]}
        assert max(by, key=by.get) == bound, name


def test_new_metric_readers():
    def reader(name):
        return _load_module(f"{BENCH_DIR}/metrics/{name}.py", "m_" + name).read

    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    run_ctx = {
        "scope_seconds": {"lfm_conv": 2.0, "lfm_conv_proj": 1.0, "lfm_attn": 4.0, "moe_experts": 0.5},
        "peaks": peaks,
        "kernel_work": {"lfm_conv": (10.0, 10.0), "lfm_attn": (100.0, 1.0)},
        "records": [types.SimpleNamespace(metrics={"expert_rows": np.array([[[2.0, 6.0], [4.0, 4.0]]])})],
    }
    assert reader("lfm_conv_ms")(run_ctx) == 2000.0 and reader("lfm_conv_proj_ms")(run_ctx) == 1000.0
    assert reader("lfm_attn_ms")(run_ctx) == 4000.0 and reader("lfm_moe_experts_ms")(run_ctx) == 500.0
    assert reader("lfm_conv_roofline")(run_ctx) == pytest.approx(50.0)  # memory bound: 1 s of 2
    assert reader("lfm_attn_roofline")(run_ctx) == pytest.approx(25.0)  # compute bound: 1 s of 4
    assert reader("lfm_expert_rows_max_over_mean")(run_ctx) == pytest.approx(1.5)
    # A program without the spans and counters: silent, never an error.
    old = {"records": [types.SimpleNamespace(metrics={"loss": np.zeros(1)})], "trace": {}, "peaks": peaks}
    for name in ("lfm_conv_ms", "lfm_conv_roofline", "lfm_conv_proj_ms", "lfm_attn_ms", "lfm_attn_roofline",
                 "lfm_moe_experts_ms", "lfm_expert_rows_max_over_mean"):
        assert reader(name)(old) is None


def test_the_limits_name_what_the_comparison_gives():
    limits = read_json("benchmark", "limits", CELL + ".json")
    assert {"window_compiles", "failed_rounds"} <= set(limits)
    numbers = set(fv.compare(
        [{"params": {"a": {"conv": np.zeros(2)}}}], [{"variables": {"params": {"a": {"conv": np.ones(2)}}},
                                                     "step_loss": [[1.0]], "next_acc": [0.0], "expert_rows": [[1.0]]}],
        [{"variables": {"params": {"a": {"conv": np.ones(2)}}}, "step_loss": [[1.0]], "next_acc": [0.0],
          "expert_rows": [[1.0]], "grad_norms": {"a": {"conv": 1.0}}}],
    ))
    assert set(limits) - {"window_compiles", "failed_rounds"} <= numbers | {n.replace("_r0", "_r1") for n in numbers}
    assert json.dumps(limits)
