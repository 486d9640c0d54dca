"""The causal language model's cell on the CPU at a tiny size, the look for a
chip skipped: a sound program comes out ``correct``, the timed path broken
underneath does not; the configuration's file against the catalog's keys and
itself; the operation counts by hand and at the published widths; the new
metric readers on made-up inputs."""

import json
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from lib import check, federated_causal_lm_rounds as fc, flops_joyai
from lib.federated_rounds import _load_module

from conftest import BENCH_DIR, read_json

CELL = "joyai_round_l8192_b1_1chip"
# The catalog's ``config`` of JoyAI-LLM-Flash (model-configs guide), key for key.
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 7168, "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "joyai_llm_flash", "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 8, "num_hidden_layers": 40, "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280,
}


@pytest.fixture
def tiny_spec():
    spec = run.load_spec(CELL)
    config = spec["config"]
    config.update(
        hidden_size=64, num_hidden_layers=3, num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
        n_routed_experts=2, num_experts_per_tok=2, vocab_size=64, compute_dtype="float32", batch_size=2, train_samples=8,
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
def test_correct_follows_the_timed_path(tiny_spec, monkeypatch, fault, expected):
    if fault is not None:
        real = fc.build_federated_round

        def builder(*args, **kwargs):
            broken = fault(real(*args, **kwargs))
            broken.data_placement = "streamed"
            return broken

        monkeypatch.setattr(fc, "build_federated_round", builder)
    result = fc.run(tiny_spec, 2**31 + 77, 0.3, False, time.perf_counter(), require_chip=False)
    assert result["correct"] is expected, result["compared"]
    assert list(result)[-1] == "compared"
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"round_s", "setup_s"}
    numbers = result["info"]["numbers"]
    if fault is None:
        assert numbers["direction_r0"] < 1e-3 and numbers["step_loss_r1"] < 1e-4 and numbers["expert_rows_r0"] < 0.01
        assert numbers["router_bias_moved_r0"] == 0.0 == numbers["router_bias_moved_r1"]
        assert numbers["mtp_loss_r0"] < 1e-4 and numbers["next_loss_r1"] < 1e-4


@pytest.mark.parametrize("fault", ["no_bias", "no_scale", "no_shared", "rope_on_all", "latent_norm_off", "noncausal", "no_mtp", "stale_slab"])
def test_planted_faults_read_far_from_the_reference(tiny_spec, fault):
    """Each fault, planted into the reference put in the program's place,
    parts from the sound reference in at least one compared number."""
    cell = fc.Cell(tiny_spec, 11, jax.devices()[:1])
    starts = [cell.start, cell.start]
    sound = cell.reference(starts)
    faulty = cell.reference(starts, fault=fault)
    numbers = fc.compare(starts, faulty, sound)
    k = 1 if fault == "stale_slab" else 0
    assert max(numbers[f"direction_r{k}"], numbers[f"step_loss_r{k}"], numbers[f"total_change_r{k}"]) > 0.01, numbers


def test_a_bias_that_moves_is_read_as_moved(tiny_spec):
    """Weights read from ``s + b`` give the selection bias a gradient: Adam
    moves it by about the learning rate a step, and the number held for that
    says so where the sound reference reads 0."""
    cell = fc.Cell(tiny_spec, 11, jax.devices()[:1])
    starts = [cell.start]
    sound = cell.reference(starts)
    numbers = fc.compare(starts, cell.reference(starts, fault="bias_moves"), sound)
    assert fc.compare(starts, sound, sound)["router_bias_moved_r0"] == 0.0
    assert numbers["router_bias_moved_r0"] > tiny_spec["config"]["optimizer"]["learning_rate"], numbers
    assert not check.judge(dict(numbers, window_compiles=0.0, failed_rounds=0.0), tiny_spec["limits"])[0]


def test_the_feed_has_no_noise_and_covers_the_slice(tiny_spec):
    cell = fc.Cell(tiny_spec, 5, jax.devices()[:1])
    ids0, w0 = (x.copy() for x in cell.feed(0))
    ids1, _ = cell.feed(1)
    assert ids0.shape == (1, 4, 2, 32) and ids0.dtype == np.int32 and w0.dtype == np.float32
    assert np.all(w0 == 1.0) and 0 <= ids0.min() and ids0.max() == 63  # the last row of the slice is a token like any
    assert not np.array_equal(ids0, ids1)
    assert {r.tobytes() for r in ids0.reshape(-1, 32)} == {r.tobytes() for r in ids1.reshape(-1, 32)}


def test_the_configuration_agrees_with_the_catalog_and_itself():
    config = read_json("benchmark", "configs", "joyai_flash_ep32_bf16.json")
    entry = next(c for c in read_json("BENCHMARK.json")["configs"] if c["name"] == "joyai_flash_ep32_bf16")
    differing = {k for k, v in PUBLISHED.items() if config.get(k, "missing") != v}
    assert differing == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert differing <= set(entry["reduced"]) == set(config["published"])
    for key in differing:
        assert config["published"][key] == PUBLISHED[key]
    # No width is cut: what ``reduced`` names is depth, experts held, rows held.
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size" for k in entry["reduced"])
    share = config["share"]
    assert share["router_outputs"] == PUBLISHED["n_routed_experts"] == share["chips_per_layer"] * config["n_routed_experts"]
    assert share["first_expert"] == share["rank"] * config["n_routed_experts"]
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"] and config["vocab_size"] >= PUBLISHED["vocab_size"] // 8
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4 and config["n_routed_experts"] >= 8
    for key in ("deployment", "assumed", "sources"):
        assert config[key]
    model = fc.reference_config(config)
    program = fc.program_config(config)
    assert program.experts_held == 8 and program.n_routed_experts == 256 and program.vocab_held == 16160
    assert program.seq_len == model["seq_len"] == 8192 and program.qk_head_dim == PUBLISHED["qk_head_dim"]
    # The parameters the issue counts: 491.7 M.
    n = sum(int(np.prod(shape)) for _, shape, _ in fc.load_reference(config)._shapes(model))
    assert abs(n / 1e6 - 491.7) < 0.2


def test_operation_counts_by_hand_and_at_the_cell_sizes():
    small = dict(
        hidden_size=8, num_hidden_layers=2, num_attention_heads=2, q_lora_rank=4, kv_lora_rank=4, qk_nope_head_dim=4,
        qk_rope_head_dim=2, v_head_dim=4, first_k_dense_replace=1, intermediate_size=16, moe_intermediate_size=4,
        n_shared_experts=1, num_experts_per_tok=2, num_nextn_predict_layers=1, router_outputs=8, experts_held=2,
        vocab_held=16, seq_len=4,
    )
    assert flops_joyai.causal_pairs(small) == 10 and flops_joyai.expected_held_pairs(small, 1) == 2.0
    proj = 2 * 4 * (8 * 4 + 4 * 2 * 6 + 8 * 6 + 4 * 2 * 8 + 2 * 4 * 8)          # 2,048
    scores = 2 * 10 * 2 * (6 + 4)                                                  # 400
    dense, router, shared, held = 2 * 4 * 3 * 8 * 16, 2 * 4 * 8 * 8, 2 * 4 * 3 * 8 * 4, 2 * 2.0 * 3 * 8 * 4
    merge, head = 2 * 4 * 2 * 8 * 8, 2 * 4 * 8 * 16
    by_hand = 3 * (proj + scores) + dense + 2 * (router + shared + held) + merge + 2 * head
    assert flops_joyai.forward_flops(small, 1) == by_hand
    assert flops_joyai.attention_layers(small) == 3 and flops_joyai.sparse_layers(small) == 2
    assert flops_joyai.attention_step(small, 1)[0] == 3 * 3 * scores

    model = fc.reference_config(run.load_spec(CELL)["config"])
    parts = flops_joyai.forward_parts(model, 1)
    assert flops_joyai.causal_pairs(model) == 8192 * 8193 // 2
    assert flops_joyai.expected_held_pairs(model, 1) == 2048
    for name, tflop in (("projections", 0.432), ("scores", 0.687), ("shared_expert", 0.077), ("router", 0.009),
                        ("held_experts", 0.019), ("dense_mlp", 0.7215), ("head", 0.542)):
        assert abs(parts[name] / 1e12 - tflop) < 0.001, name
    assert abs(flops_joyai.forward_flops(model, 1) / 1e12 - 9.18) < 0.01
    assert abs(flops_joyai.forward_flops(dict(model, num_nextn_predict_layers=0), 1) / 1e12 - 7.28) < 0.01
    assert abs(flops_joyai.train_step_flops(model, 1) / 1e12 - 27.5) < 0.05
    # The program's own arithmetic counts the same.
    assert fc.build_federated_round  # the system under test is importable
    from fedcrack_tpu.tasks import task_for

    assert abs(task_for(fc.program_config(run.load_spec(CELL)["config"])).step_flops(1) / flops_joyai.train_step_flops(model, 1) - 1) < 1e-9
    # Operations follow the counter; absent experts never count.
    assert flops_joyai.train_step_flops(model, 1, 0.0) < flops_joyai.train_step_flops(model, 1)
    ops, moved = flops_joyai.attention_step(model, 1)
    assert ops == 3 * 6 * parts["scores"] and moved == 3 * 6 * 2 * 8192 * 32 * (2 * 192 + 2 * 128)


def test_new_metric_readers():
    def reader(name):
        return _load_module(f"{BENCH_DIR}/metrics/{name}.py", "m_" + name).read

    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    run_ctx = {
        "scope_seconds": {"mla_attn": 2.0, "mla_proj": 1.5, "moe_experts": 4.0}, "module_seconds": {"mtp": 0.5}, "peaks": peaks,
        "kernel_work": {"mla_attn": (100.0, 1.0), "moe_experts": (10.0, 20.0)},
        "records": [types.SimpleNamespace(metrics={"expert_rows": np.array([[[2.0, 6.0], [4.0, 4.0]]])})],
    }
    assert reader("mla_attn_ms")(run_ctx) == 2000.0 and reader("mla_proj_ms")(run_ctx) == 1500.0
    assert reader("lm_moe_experts_ms")(run_ctx) == 4000.0 and reader("mtp_ms")(run_ctx) == 500.0
    assert reader("mla_attn_roofline")(run_ctx) == pytest.approx(50.0)  # compute bound: 1 s of 2
    assert reader("lm_moe_experts_roofline")(run_ctx) == pytest.approx(50.0)  # memory bound: 2 s of 4
    assert reader("lm_expert_rows_max_over_mean")(run_ctx) == pytest.approx(1.5)
    # A program without the spans and counters: silent, never an error.
    old = {"records": [types.SimpleNamespace(metrics={"loss": np.zeros(1)})], "trace": {}, "peaks": peaks}
    for name in ("mla_attn_ms", "mla_proj_ms", "mla_attn_roofline", "lm_moe_experts_ms", "lm_moe_experts_roofline",
                 "lm_expert_rows_max_over_mean", "mtp_ms"):
        assert reader(name)(old) is None


def test_the_limits_name_what_the_comparison_gives():
    limits = read_json("benchmark", "limits", CELL + ".json")
    assert {"window_compiles", "failed_rounds"} <= set(limits)
    for k in (0, 1):
        assert {f"direction_r{k}", f"total_change_r{k}", f"step_loss_r{k}"} <= set(limits)
    assert json.dumps(limits)
