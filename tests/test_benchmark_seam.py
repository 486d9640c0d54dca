"""The program's side of the benchmark seam, for every cell of BENCHMARK.json.

The benchmark (``benchmark/``, which ``pytest benchmark/tests`` checks on its
own side) reads a handful of names off the program: the configuration classes
its ``configs/<name>.json`` files build, the task ``tasks.task_for`` finds for
them, ``RoundRecord.host_s``'s keys, three attributes of the task that locate
the round program and its scopes in a trace, and counters in a round's metrics.
A per-layer metric whose name has moved reads ``null`` on the ledger, and a
ledger line without an accepted metric blocks every later ``benchmark`` PR: so a
rename fails here, on the CPU, first. Nothing runs at a cell's size; BENCHMARK.json
and the files under ``benchmark/`` are only read.
"""

import functools
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedcrack_tpu.configs import GdnMoeConfig, Lfm2MoeConfig, LoopedLmConfig, MlaMoeConfig, ModelConfig, SdarMoeConfig
from fedcrack_tpu.data.synthetic import synth_crack_batch
from fedcrack_tpu.data.textdiff import stage_pair
from fedcrack_tpu.parallel import (
    build_federated_round,
    make_mesh,
    run_mesh_federation,
    stack_client_data,
)
from fedcrack_tpu.tasks import CausalLMTask, SegmentationTask, TextDiffusionTask, task_for

from test_gdn_moe import small_config as small_gdn_config
from test_lfm2_moe import small_config as small_lfm2_config
from test_looped_lm import small_config as small_looped_config
from test_mla_moe import small_config as small_mla_config
from test_sdar_moe import small_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


BENCHMARK = _read("BENCHMARK.json")
CELLS = [w["name"] for w in BENCHMARK["workloads"]]

# What each reference family of the benchmark is to the program.
TASKS = {
    "resunet": SegmentationTask, "sdar_moe": TextDiffusionTask, "joyai_mla_moe": CausalLMTask,
    "qwen3next_gdn_moe": CausalLMTask, "ouro_looped_lm": CausalLMTask, "lfm2_conv_moe": CausalLMTask,
}
# RoundRecord.host_s, as metrics/stage_hidden_ms.py, host_busy_pct.py and
# handoff_ms.py index it; "barrier" is the remainder reduce.py names gaps after.
HOST_KEYS = {"dispatch", "feed", "stage", "barrier", "handoff"}
# The scopes benchmark/trace/scopes.py sums for the second model's metrics,
# and the blocks PERF.md section 5 is built from (tools/profile_step.py).
SCOPES = {
    "resunet": ("stem", "enc0", "enc2", "dec0", "dec3", "head"),
    "sdar_moe": ("blockdiff_attn", "moe_experts", "attn_proj", "moe_dispatch", "moe_combine", "lm_head"),
    "joyai_mla_moe": (
        "mla_attn", "mla_proj", "moe_experts", "moe_dispatch", "moe_combine", "shared_expert", "dense_mlp", "mtp_merge",
        "lm_head",
    ),
    "qwen3next_gdn_moe": (
        "gdn_rule", "gdn_proj", "gdn_conv", "gattn", "gattn_proj", "moe_experts", "moe_dispatch", "moe_combine",
        "shared_expert", "router", "lm_head",
    ),
    "ouro_looped_lm": ("embed", "loop_attn_proj", "loop_attn", "loop_mlp", "loop_exit"),
    "lfm2_conv_moe": (
        "embed", "lfm_conv_proj", "lfm_conv", "lfm_attn_proj", "lfm_attn", "dense_mlp", "router", "moe_dispatch",
        "moe_experts", "moe_combine", "lm_head",
    ),
}
# The enclosing scope ``metrics/mtp_ms.py`` sums whole: no block kind of the
# task's pattern, but a name on the instructions' paths all the same.
MODULE_SCOPES = {
    "joyai_mla_moe": ("mtp",), "ouro_looped_lm": ("loop0", "loop1", "loop2", "loop3"),
    "lfm2_conv_moe": ("layer0", "layer1", "layer2", "layer3", "layer4"),
}
# Those of them the toy round's program holds (one encoder block, two decoder blocks).
TOY_SCOPES = {
    "resunet": ("stem", "enc0", "dec0", "dec1", "head"), "sdar_moe": SCOPES["sdar_moe"],
    "joyai_mla_moe": SCOPES["joyai_mla_moe"], "qwen3next_gdn_moe": SCOPES["qwen3next_gdn_moe"],
    "ouro_looped_lm": SCOPES["ouro_looped_lm"], "lfm2_conv_moe": SCOPES["lfm2_conv_moe"],
}


def _cell(name: str) -> tuple[dict, dict]:
    workload = next(w for w in BENCHMARK["workloads"] if w["name"] == name)
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == workload["config"])
    return workload, _read(entry["file"])


def _program_config(config: dict):
    """The program's configuration class from a benchmark configuration file,
    field for field as ``benchmark/lib/federated_rounds.py:Cell.build_round``,
    ``federated_textdiff_rounds.py:program_config``,
    ``federated_causal_lm_rounds.py:program_config``,
    ``federated_hybrid_lm_rounds.py:program_config``,
    ``federated_looped_lm_rounds.py:program_config`` and
    ``federated_conv_lm_rounds.py:program_config`` build it."""
    if config["reference"] == "resunet":
        return ModelConfig(**{k: tuple(v) if isinstance(v, list) else v for k, v in config["model"].items()})
    share, training = config.get("share"), config["training"]
    if config["reference"] == "ouro_looped_lm":
        published = (
            "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "rms_norm_eps", "vocab_size", "total_ut_steps",
        )
        return LoopedLmConfig(
            **{k: config[k] for k in published}, rope_theta=float(config["rope_theta"]),
            exit_entropy_beta=training["exit_entropy_beta"], seq_len=training["seq_len"],
            compute_dtype=config["compute_dtype"], param_dtype=config["param_dtype"],
        )
    if config["reference"] == "lfm2_conv_moe":
        published = (
            "hidden_size", "num_hidden_layers", "num_dense_layers", "num_attention_heads", "num_key_value_heads",
            "conv_L_cache", "intermediate_size", "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "norm_eps",
        )
        return Lfm2MoeConfig(
            **{k: config[k] for k in published}, layer_types=tuple(config["layer_types"]),
            rope_theta=float(config["rope_theta"]), num_experts=share["router_outputs"],
            first_expert=share["first_expert"], experts_held=config["num_experts"], vocab_held=config["vocab_size"],
            seq_len=training["seq_len"], compute_dtype=config["compute_dtype"], param_dtype=config["param_dtype"],
        )
    if config["reference"] == "joyai_mla_moe":
        published = (
            "hidden_size", "num_hidden_layers", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "first_k_dense_replace", "intermediate_size",
            "moe_intermediate_size", "n_shared_experts", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "num_nextn_predict_layers", "rms_norm_eps",
        )
        return MlaMoeConfig(
            **{k: config[k] for k in published}, rope_theta=float(config["rope_theta"]),
            n_routed_experts=share["router_outputs"], first_expert=share["first_expert"],
            experts_held=config["n_routed_experts"], vocab_held=config["vocab_size"], seq_len=training["seq_len"],
            mtp_loss_weight=training["mtp_loss_weight"],
            compute_dtype=config["compute_dtype"], param_dtype=config["param_dtype"],
        )
    if config["reference"] == "qwen3next_gdn_moe":
        published = (
            "hidden_size", "num_hidden_layers", "full_attention_interval", "num_attention_heads",
            "num_key_value_heads", "head_dim", "partial_rotary_factor", "linear_num_key_heads",
            "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim",
            "moe_intermediate_size", "shared_expert_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
            "rms_norm_eps",
        )
        return GdnMoeConfig(
            **{k: config[k] for k in published}, rope_theta=float(config["rope_theta"]),
            num_experts=share["router_outputs"], first_expert=share["first_expert"],
            experts_held=config["num_experts"], vocab_held=config["vocab_size"], seq_len=training["seq_len"],
            compute_dtype=config["compute_dtype"], param_dtype=config["param_dtype"],
        )
    return SdarMoeConfig(
        hidden_size=config["hidden_size"], num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"], num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=share["router_outputs"], num_experts_per_tok=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"], rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]), first_expert=share["first_expert"],
        experts_held=config["num_experts"], vocab_held=config["vocab_size"],
        block_length=training["block_length"], seq_len=training["seq_len"],
        compute_dtype=config["compute_dtype"], param_dtype=config["param_dtype"],
    )


@pytest.mark.parametrize("cell", CELLS)
def test_cell_configuration_builds_the_programs_class_and_finds_its_task(cell):
    workload, config = _cell(cell)
    assert config["name"] == workload["config"]
    model_config = _program_config(config)
    task = task_for(model_config)
    assert isinstance(task, TASKS[config["reference"]])
    assert task.config == model_config
    # What the cell's round needs of the file beside the model.
    assert config["train_samples"] // config["batch_size"] >= 1
    assert config["optimizer"]["learning_rate"] > 0 and config["local_epochs"] >= 1
    traffic = _read("benchmark", "traffic", workload["traffic"] + ".json")
    assert math.prod(traffic["mesh"]) == workload["chips"]


_LOADED_ROUNDS: list = []


@functools.lru_cache(maxsize=None)
def _toy_round(family: str):
    """Two one-step rounds of the family at toy size through the driver:
    ``(task, records, steps)``. One a family: both U-Net cells read the same names."""
    steps, batch = 1, 2
    mesh = make_mesh(1, 1, jax.devices()[:1])
    if family == "resunet":
        config = ModelConfig(img_size=16, stem_features=4, encoder_features=(8,), decoder_features=(8, 4))
        images, masks = stack_client_data([synth_crack_batch(steps * batch, img_size=16, seed=0)], steps, batch)
        data = (images, masks)
    else:
        config = {
            "sdar_moe": small_config, "joyai_mla_moe": small_mla_config, "qwen3next_gdn_moe": small_gdn_config,
            "ouro_looped_lm": small_looped_config, "lfm2_conv_moe": small_lfm2_config,
        }[family]()
        rng = np.random.default_rng(0)
        rows = config.vocab_size if family == "ouro_looped_lm" else config.vocab_held
        sequences = rng.integers(0, rows - 1, (1, steps * batch, config.seq_len)).astype(np.int32)
        # The causal family's pair has no noise.
        data = stage_pair(sequences, steps, batch, getattr(config, "block_length", None), rng)
    task = task_for(config)
    round_fn = build_federated_round(mesh, config, learning_rate=1e-3)
    variables = task.init(jax.random.key(0))
    feed = (*data, np.ones(1, np.float32), np.full(1, float(steps * batch), np.float32))
    _, records = run_mesh_federation(round_fn, variables, lambda r: feed, 2, mesh)
    # The round stays loaded for every cell of the family: the tests below read
    # its executable's text, and a later cell may come after a collection.
    _LOADED_ROUNDS.append(round_fn)
    return task, records, steps


@pytest.mark.parametrize("cell", CELLS)
def test_round_record_and_task_carry_the_names_the_per_layer_metrics_read(cell):
    _, config = _cell(cell)
    family = config["reference"]
    task, records, steps = _toy_round(family)
    assert type(task) is type(task_for(_program_config(config)))

    for record in records:
        assert set(record.host_s) == HOST_KEYS
        assert all(isinstance(v, float) for v in record.host_s.values())
        assert record.wall_clock_s > 0 and record.staging_s >= 0 and record.data_fn_s >= 0
        step_loss = np.asarray(record.metrics["step_loss"])
        assert step_loss.shape == (1, 1, steps) and np.all(np.isfinite(step_loss))
        assert np.isfinite(np.asarray(record.metrics["loss"])).all()
    assert records[1].host_s["handoff"] > 0.0

    # The trace readers find the round program by the task's module name and
    # its blocks by the task's pattern (benchmark/trace/scopes.py looks for
    # "client_fit" among the loaded executables; obs/devtrace.py takes all three).
    assert task.program_name == "client_fit"
    for scope in sorted({*SCOPES[family], *TOY_SCOPES[family]}):
        assert re.fullmatch(task.block_scope, scope), scope
    loaded = [
        m.to_string()
        for e in jax.devices()[0].client.live_executables()
        for m in e.hlo_modules()[:1]
        if task.program_name in m.name
    ]
    assert loaded, "no loaded executable is named after task.program_name"
    # (Other rounds of this worker may be loaded under the same name.)
    for scope in TOY_SCOPES[family] + MODULE_SCOPES.get(family, ()) + ((task.model_scope,) if task.model_scope else ()):
        assert any(re.search(rf'op_name="[^"]*\b{scope}\b', text) for text in loaded), scope

    if family == "sdar_moe":
        toy = task.config
        for record in records:
            rows = np.asarray(record.metrics["expert_rows"])
            assert rows.shape == (1, toy.num_hidden_layers, toy.experts_held)
            held = np.asarray(record.metrics["held_pairs"])
            assert held.shape == (1,) and held[0] > 0 and rows.sum() > 0
            assert np.asarray(record.metrics["budget_overflows"]).shape == (1,)
            assert np.asarray(record.metrics["expert_tiles"]).shape == (1,) and record.metrics["expert_tiles"][0] > 0
            # Off the chip the XLA form moves its row arrays' whole length,
            # never fewer rows than the kept pairs.
            moved = np.asarray(record.metrics["moved_rows"])
            assert moved.shape == (1,) and moved[0] >= held[0] > 0
            assert {"masked_tokens", "masked_acc"} <= set(record.metrics)
    if family == "joyai_mla_moe":
        toy = task.config
        for record in records:
            rows = np.asarray(record.metrics["expert_rows"])
            assert rows.shape == (1, toy.sparse_layers, toy.experts_held)
            held = np.asarray(record.metrics["held_pairs"])
            assert held.shape == (1,) and held[0] > 0 and rows.sum() == held[0]
            assert np.asarray(record.metrics["budget_overflows"]).shape == (1,)
            assert np.asarray(record.metrics["expert_tiles"]).shape == (1,) and record.metrics["expert_tiles"][0] > 0
            # Off the chip the XLA form moves its row arrays' whole length,
            # never fewer rows than the kept pairs.
            moved = np.asarray(record.metrics["moved_rows"])
            assert moved.shape == (1,) and moved[0] >= held[0] > 0
            assert {"next_loss", "mtp_loss", "tokens", "next_acc"} <= set(record.metrics)
    if family == "qwen3next_gdn_moe":
        toy = task.config
        for record in records:
            rows = np.asarray(record.metrics["expert_rows"])
            assert rows.shape == (1, toy.num_hidden_layers, toy.experts_held)
            held = np.asarray(record.metrics["held_pairs"])
            assert held.shape == (1,) and held[0] > 0 and rows.sum() == held[0]
            assert np.asarray(record.metrics["budget_overflows"]).shape == (1,)
            assert np.asarray(record.metrics["expert_tiles"]).shape == (1,) and record.metrics["expert_tiles"][0] > 0
            # Off the chip the XLA form moves its row arrays' whole length,
            # never fewer rows than the kept pairs.
            moved = np.asarray(record.metrics["moved_rows"])
            assert moved.shape == (1,) and moved[0] >= held[0] > 0
            assert np.asarray(record.metrics["gdn_decay_mean"]).shape == (1, toy.linear_layers)
            assert {"next_loss", "tokens", "next_acc"} <= set(record.metrics) and "mtp_loss" not in record.metrics
    if family == "lfm2_conv_moe":
        toy = task.config
        for record in records:
            rows = np.asarray(record.metrics["expert_rows"])
            assert rows.shape == (1, toy.sparse_layers, toy.experts_held)
            held = np.asarray(record.metrics["held_pairs"])
            assert held.shape == (1,) and held[0] > 0 and rows.sum() == held[0]
            assert np.asarray(record.metrics["budget_overflows"]).shape == (1,)
            assert np.asarray(record.metrics["expert_tiles"]).shape == (1,) and record.metrics["expert_tiles"][0] > 0
            # Off the chip the XLA form moves its row arrays' whole length,
            # never fewer rows than the kept pairs.
            moved = np.asarray(record.metrics["moved_rows"])
            assert moved.shape == (1,) and moved[0] >= held[0] > 0
            assert {"next_loss", "tokens", "next_acc"} <= set(record.metrics) and "mtp_loss" not in record.metrics
    if family == "ouro_looped_lm":
        toy = task.config
        for record in records:
            for name in ("exit_mass", "loop_nll"):
                assert np.asarray(record.metrics[name]).shape == (1, toy.total_ut_steps), name
            assert abs(float(np.asarray(record.metrics["exit_mass"]).sum()) - 1.0) < 1e-5
            assert np.asarray(record.metrics["exit_entropy"]).shape == (1,)
            assert {"next_loss", "tokens", "next_acc"} <= set(record.metrics)
            assert not {"mtp_loss", "expert_rows", "held_pairs", "budget_overflows", "expert_tiles", "moved_rows"} & set(
                record.metrics
            )
    # The per-layer metrics this cell lists each have their reader.
    for metric in BENCHMARK["per_layer"]:
        if cell in metric.get("workloads", [cell]):
            assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics", metric["name"] + ".py")), metric["name"]


@pytest.mark.parametrize("cell", CELLS)
def test_step_flops_of_the_task_at_the_cells_own_shape(cell):
    """``round_mfu``'s numerator is the benchmark's own arithmetic
    (tests/test_flops.py pins it to the task's for the U-Net); the task's must
    at least be a number at the shape the cell runs."""
    _, config = _cell(cell)
    task = task_for(_program_config(config))
    flops = task.step_flops(config["batch_size"])
    assert isinstance(flops, float) and math.isfinite(flops) and flops > 0
    # Linear in the batch: a shape the arithmetic does not see would not be.
    assert task.step_flops(2 * config["batch_size"]) == pytest.approx(2 * flops, rel=1e-6)
