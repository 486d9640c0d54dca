"""One run of a cell whose traffic is ``federated_causal_lm_rounds``: federated
rounds of next-token training of a latent-attention mixture-of-experts
language model's share.

The same shape as ``federated_textdiff_rounds.py``: set-up and window are ONE
call of the program's entry, ``run_mesh_federation`` over one
``build_federated_round`` program (the task follows from the class of the
model configuration); the first ``checked_rounds`` rounds compile and warm it
and are the rounds the reference follows (round 0 from the seed's weights,
each later one from the state the program handed on); the window opens when
the last of them has been read back and closes at the end of the first round
that ends ``seconds`` or more later. The feed is ``textgen.TextFeed`` without
its noise (one document a sequence, every token weighs 1; ids uniform over
the whole held slice); the reference (``reference/joyai_mla_moe.py``) runs
once the window has closed, the peak memory has been read and the program's
state is dropped.

``correct`` compares, for each checked round, what the block-diffusion cell
compares: ``direction_r<k>`` (1 minus the cosine between the program's and
the reference's change of all moving parameters over the round, as one
vector), ``total_change_r<k>`` (the gap of that change's norms, as a share of
the reference's), ``step_loss_r<k>`` (the worst step's gap of the per-step
loss, as a share of the reference's), and the exact ``window_compiles`` and
``failed_rounds``. The router's selection bias takes no gradient on either
side and is left out by ``check.moving_leaves``'s rule, as are rows of the
embedding that no token touched.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np

import jax

from . import check, flops_joyai, textgen
from .compile_log import CompileLog
from .federated_rounds import BENCH_DIR, GcWatch, SliceTrace, _load_module, _WindowClosed, device_report, load_reference
from .federated_textdiff_rounds import _say

# The system under test.
from fedcrack_tpu.configs import MlaMoeConfig
from fedcrack_tpu.parallel import build_federated_round, make_mesh, run_mesh_federation

# The kinds of block, summed over the layers (and the module) that hold them.
KERNEL_SCOPES = (
    "embed", "mla_proj", "mla_attn", "dense_mlp", "router", "moe_dispatch", "moe_experts", "moe_combine",
    "shared_expert", "mtp_merge", "lm_head",
    "unpack", "loss", "grad_scale", "optimizer", "step_metrics", "round_init", "fold", "round_metrics",
)
# Whole modules, whatever kinds of block they hold.
MODULE_SCOPES = ("mtp",)
# What a checked round keeps of the program's own report.
PROGRAM_METRICS = ("loss", "step_loss", "next_loss", "mtp_loss", "tokens", "next_acc", "expert_rows", "held_pairs")
PUBLISHED_KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "first_k_dense_replace", "intermediate_size", "moe_intermediate_size",
    "n_shared_experts", "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor", "num_nextn_predict_layers",
    "rms_norm_eps",
)


def reference_config(config: dict) -> dict:
    """The reference's plain ``cfg`` from the configuration file: the
    published keys, with the share's and the training's beside them."""
    share, training = config["share"], config["training"]
    if (config["scoring_func"], config["n_group"], config["topk_group"]) != ("sigmoid", 1, 1) or not config["rope_interleave"]:
        raise ValueError("the reference and the program score by a sigmoid over one group and rotate adjacent pairs")
    return dict(
        {k: config[k] for k in PUBLISHED_KEYS}, rope_theta=float(config["rope_theta"]),
        router_outputs=share["router_outputs"], first_expert=share["first_expert"],
        experts_held=config["n_routed_experts"], vocab_held=config["vocab_size"],
        seq_len=training["seq_len"], mtp_loss_weight=training["mtp_loss_weight"],
    )


def program_config(config: dict) -> MlaMoeConfig:
    """The program's model configuration for the same file."""
    cfg = reference_config(config)
    return MlaMoeConfig(
        **{k: cfg[k] for k in PUBLISHED_KEYS}, rope_theta=cfg["rope_theta"], n_routed_experts=cfg["router_outputs"],
        first_expert=cfg["first_expert"], experts_held=cfg["experts_held"], vocab_held=cfg["vocab_held"],
        seq_len=cfg["seq_len"], mtp_loss_weight=cfg["mtp_loss_weight"],
        compute_dtype=config["compute_dtype"], param_dtype=config["param_dtype"],
    )


class Cell:
    """One seed's weights, data, mesh and round program for a cell."""

    def __init__(self, spec: dict, seed: int, used):
        self.spec, self.used = spec, used
        config, traffic = spec["config"], spec["traffic"]
        self.model = reference_config(config)
        self.batch = config["batch_size"]
        self.steps = config["train_samples"] // self.batch
        self.lr = config["optimizer"]["learning_rate"]
        self.clients, inner = traffic["mesh"]
        self.checked = int(traffic["checked_rounds"])
        self.ref = load_reference(config)
        self.mesh = make_mesh(self.clients, inner, used)
        self.round_fn = build_federated_round(
            self.mesh, program_config(config), learning_rate=self.lr, local_epochs=config["local_epochs"],
        )
        self.start = jax.device_get(self.ref.make_variables(seed, self.model))
        # Ids over every row of the held slice: no row is a mask token here
        # (the generator leaves out the last of the rows it is given).
        sequences = textgen.client_sequences(
            seed, self.clients, self.steps * self.batch, self.model["seq_len"], self.model["vocab_held"] + 1
        )
        # No block length: the program's staging function draws no noise.
        self.feed = textgen.TextFeed(sequences, seed, self.steps, self.batch, None, (1.0, 1.0))
        self.n_samples = np.full(self.clients, float(self.steps * self.batch), np.float32)

    def drive(self, seconds: float, tracer: SliceTrace | None, t_start: float, compiles: CompileLog) -> dict:
        """The one call of the program's entry: checked rounds, then the
        window; traced as ``federated_rounds.Cell.drive`` traces."""
        traffic = self.spec["traffic"]
        active = np.ones(self.clients, np.float32)
        state = {"boundary": False, "traced": tracer is None}
        program_rounds: list = []
        records: list = []

        def data_fn(r):
            if state["boundary"]:
                state["boundary"] = False
                state["collect_s"] = tracer.collect()
                state["restart_after"] = r - 1
            with jax.profiler.TraceAnnotation("bench.data_fn"):
                ids, weight = self.feed(r)
            return ids, weight, active, self.n_samples

        def on_round(record, variables):
            with jax.profiler.TraceAnnotation("bench.on_round"):
                now = time.perf_counter()
                if record.round_idx < self.checked:
                    program_rounds.append({
                        "variables": jax.device_get(variables),
                        **{k: np.asarray(record.metrics[k]).tolist() for k in PROGRAM_METRICS},
                    })
                    if record.round_idx == self.checked - 1:
                        state["window_mark"] = compiles.mark()
                        state["t0"] = time.perf_counter()
                        state["setup_s"] = state["t0"] - t_start
                    return
                records.append(record)
                if not state["traced"]:
                    if tracer.round_ended(record.wall_clock_s):
                        state["boundary"] = state["traced"] = True
                    else:
                        tracer.arm(record.wall_clock_s)
                    return
                if state.get("restart_after") == record.round_idx:
                    state.pop("restart_after")
                    records.clear()
                    state["t0"] = time.perf_counter()
                    return
                if now - state["t0"] >= seconds:
                    state["elapsed_s"] = now - state["t0"]
                    raise _WindowClosed

        try:
            run_mesh_federation(
                self.round_fn, self.start, data_fn, 10**9, self.mesh,
                overlap_staging=bool(traffic["overlap_staging"]), on_round=on_round,
            )
        except _WindowClosed:
            pass
        return {
            "program_rounds": program_rounds, "records": records, "elapsed_s": state["elapsed_s"],
            "setup_s": state["setup_s"], "collect_s": state.get("collect_s"), "window_t0": state["t0"],
            "window_compiles": compiles.summary(state["window_mark"]),
            "setup_compiles": compiles.summary(0, state["window_mark"]),
        }

    def starts(self, program_rounds: list) -> list:
        """Round 0 starts from the seed's weights, a later round from what
        the program handed on to it."""
        return [self.start] + [r["variables"] for r in program_rounds[: self.checked - 1]]

    def reference(self, starts: list, *, operands=None, fault=None) -> list:
        """The reference over the rounds whose start is given, one client
        after another on the first device. ``fault``: the reference's own
        (``no_bias``, ``no_scale``, ``no_shared``, ``rope_on_all``,
        ``latent_norm_off``, ``noncausal``, ``no_mtp``, ``bias_moves``), or ``stale_slab``
        (round 0's data again in every later round)."""
        out = []
        for k, variables in enumerate(starts):
            if variables is None:
                out.append(None)
                continue
            ids, weight = self.feed(0 if fault == "stale_slab" else k)
            results = [
                jax.device_get(self.ref.client_round(
                    variables, ids[c], weight[c], self.model, self.lr, operands=operands,
                    fault=None if fault == "stale_slab" else fault, device=self.used[0],
                ))
                for c in range(self.clients)
            ]
            out.append({
                "variables": self.ref.weighted_average([r[0] for r in results], list(self.n_samples)),
                "loss": [float(r[1]["loss"]) for r in results],
                "step_loss": [np.asarray(r[1]["step_loss"]).tolist() for r in results],
                "next_loss": [float(r[1]["next_loss"]) for r in results],
                "mtp_loss": [float(r[1]["mtp_loss"]) for r in results],
                "next_acc": [float(r[1]["next_hits"]) / max(float(r[1]["tokens"]), 1.0) for r in results],
                "expert_rows": [np.asarray(r[1]["expert_rows"]).tolist() for r in results],
                "grad_norms": jax.tree_util.tree_map(lambda *g: float(np.mean(g)), *[r[1]["grad_norms"] for r in results]),
            })
        return out


def compare(starts: list, program_rounds: list, reference_rounds: list) -> dict:
    """Every number, by name; 0 where program and reference agree."""
    out = {}
    for k, (start, prog, ref) in enumerate(zip(starts, program_rounds, reference_rounds)):
        if ref is None:
            continue
        moving = check.moving_leaves(ref["grad_norms"])
        s, p, r = (check._flatten(t["params"], "params") for t in (start, prog["variables"], ref["variables"]))
        if set(p) != set(r):
            raise ValueError("program and reference hold different leaves")
        dot = pp = rr = 0.0
        for name in sorted(moving & set(r)):
            dp = (p[name] - s[name]).ravel().astype(np.float64)
            dr = (r[name] - s[name]).ravel().astype(np.float64)
            dot, pp, rr = dot + float(dp @ dr), pp + float(dp @ dp), rr + float(dr @ dr)
        out[f"direction_r{k}"] = float(1.0 - dot / np.sqrt(pp * rr)) if pp > 0 and rr > 0 else 1.0
        out[f"total_change_r{k}"] = float(abs(np.sqrt(pp) - np.sqrt(rr)) / np.sqrt(rr)) if rr > 0 else 1.0
        lp = np.asarray(prog["step_loss"], np.float64).reshape(len(ref["step_loss"]), -1)
        lr = np.asarray(ref["step_loss"], np.float64).reshape(lp.shape)
        out[f"step_loss_r{k}"] = float(np.max(np.abs(lp - lr) / np.abs(lr)))
        out[f"loss_r{k}"] = float(np.max(np.abs(lp.mean(axis=1) - lr.mean(axis=1)) / np.abs(lr.mean(axis=1))))
        for name in ("next_loss", "mtp_loss"):
            a, b = np.asarray(prog[name], np.float64).ravel(), np.asarray(ref[name], np.float64).ravel()
            out[f"{name}_r{k}"] = float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))
        out[f"next_acc_r{k}"] = float(np.max(np.abs(np.asarray(prog["next_acc"]).ravel() - np.asarray(ref["next_acc"]))))
        ep, er = np.asarray(prog["expert_rows"], np.float64), np.asarray(ref["expert_rows"], np.float64)
        out[f"expert_rows_r{k}"] = float(np.sum(np.abs(ep - er)) / max(np.sum(er), 1.0))
        # The selection bias, whatever the gradient rule says: it may not move.
        out[f"router_bias_moved_r{k}"] = float(max(
            (np.max(np.abs(p[name] - s[name])) for name in p if name.endswith("/router_bias")), default=0.0
        ))
    for name, v in out.items():
        if not np.isfinite(v):
            out[name] = 1e30
    return out


def kernel_work(model: dict, batch: int, records: list, steps: int) -> dict:
    """(operations, bytes) a step of each kernel whose roofline is reported,
    the experts' from the window's ``held_pairs`` counter."""
    # ``held_pairs`` is a client's pairs over a round's steps and sparse layers.
    layers = flops_joyai.sparse_layers(model)
    pairs = [float(np.mean(rec.metrics["held_pairs"])) / (steps * layers) for rec in records]
    held = sum(pairs) / len(pairs) if pairs else flops_joyai.expected_held_pairs(model, batch)
    return {
        "held_pairs_a_layer": held,
        "mla_attn": flops_joyai.attention_step(model, batch),
        "moe_experts": flops_joyai.experts_step(model, held),
    }


def run(spec: dict, seed: int, seconds: float, trace: bool, t_start: float, *, require_chip: bool = True) -> dict:
    """Run the cell once; returns the result object that ``run.py`` prints."""
    workload, traffic = spec["workload"], spec["traffic"]
    compiles = CompileLog()
    chips = traffic["mesh"][0] * traffic["mesh"][1]
    if chips != workload["chips"]:
        raise ValueError(f"traffic mesh {traffic['mesh']} does not fill {workload['chips']} chip(s)")
    devices = jax.devices()
    device, peaks = device_report(devices, chips, require_chip)
    used = devices[:chips]

    t_build = time.perf_counter()
    cell = Cell(spec, seed, used)
    build_s = time.perf_counter() - t_build
    _say(t_start, "weights, data and round program built")
    tracer = SliceTrace(float(traffic["trace_lead_s"])) if trace else None
    gc_watch = GcWatch()
    gc.callbacks.append(gc_watch)
    driven = cell.drive(seconds, tracer, t_start, compiles)
    gc.callbacks.remove(gc_watch)
    records, elapsed = driven["records"], driven["elapsed_s"]
    rounds = len(records)
    _say(t_start, f"window closed: set-up {driven['setup_s']:.1f} s, {rounds} rounds in {elapsed:.2f} s")

    def held(d):
        stats = d.memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0)), int(stats.get("bytes_reserved", 0))

    allocator_peak, reserved = max((held(d) for d in used), key=sum)
    memory_peak = allocator_peak + reserved
    scratch = max(
        (int(e.get_compiled_memory_stats().temp_size_in_bytes) for e in used[0].client.live_executables()),
        default=0,
    )
    failed = sum(
        1 for rec in records
        if not all(np.all(np.isfinite(np.asarray(v))) for v in rec.metrics.values())
    )
    scope_seconds = module_seconds = None
    if trace:
        scopes = _load_module(os.path.join(BENCH_DIR, "trace", "scopes.py"), "bench_trace_scopes")
        text = scopes.loaded_hlo_text()
        if text is not None:
            scope_seconds = scopes.seconds_a_step(tracer.profile, text, KERNEL_SCOPES, chips)
            module_seconds = scopes.seconds_a_step(tracer.profile, text, MODULE_SCOPES, chips)
    # The program's state goes before the reference comes.
    cell.round_fn = None
    gc.collect()

    t_ref = time.perf_counter()
    starts = cell.starts(driven["program_rounds"])
    numbers = compare(starts, driven["program_rounds"], cell.reference(starts))
    numbers["window_compiles"] = float(driven["window_compiles"]["compiles"])
    numbers["failed_rounds"] = float(failed)
    correct, compared = check.judge(numbers, spec["limits"])
    reference_s = time.perf_counter() - t_ref
    _say(t_start, f"reference followed {len(starts)} rounds in {reference_s:.1f} s")

    result = {"correct": bool(correct), "attempted": rounds, "failed": failed}
    breakdown = None
    work = kernel_work(cell.model, cell.batch, records, cell.steps)
    if trace:
        reducer = _load_module(os.path.join(BENCH_DIR, "trace", "reduce.py"), "bench_trace_reduce")
        reduced = reducer.reduce_profile(tracer.profile, chips, tracer.span_s)
        reduced["idle_share_of_round"] = reducer.idle_share_of_round(reduced, tracer.round_s)
        tracer.profile = None
        context = {
            "records": records, "rounds": rounds, "elapsed_s": elapsed, "steps": cell.steps,
            "clients": cell.clients, "chips": chips, "peaks": peaks, "trace": reduced,
            "step_flops": flops_joyai.train_step_flops(cell.model, cell.batch, work["held_pairs_a_layer"]),
            "scope_seconds": scope_seconds, "module_seconds": module_seconds, "kernel_work": work,
        }
        metrics = {}
        for m in spec["per_layer"]:
            reader = _load_module(os.path.join(BENCH_DIR, "metrics", m["name"] + ".py"), "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(context)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        by_ms = lambda table: {k: 1e3 * v for k, v in sorted((table or {}).items(), key=lambda kv: -kv[1])}
        breakdown = dict(reduced["breakdown"], scope_ms_a_step=by_ms(scope_seconds), module_ms_a_step=by_ms(module_seconds))
    else:
        values = {"round_s": elapsed / rounds, "setup_s": driven["setup_s"]}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in spec["end_to_end"]}
    device["memory_peak_bytes"] = memory_peak
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["info"] = {
        "rounds": rounds, "window_s": elapsed, "round_wall_s": [r.wall_clock_s for r in records],
        "window_gc": gc_watch.within(driven["window_t0"], driven["window_t0"] + elapsed), "reference_s": reference_s, "trace_collect_s": driven["collect_s"],
        "setup_parts_s": {"before_build": t_build - t_start, "build": build_s,
                          "checked_rounds": driven["setup_s"] - (t_build - t_start) - build_s},
        "allocator_peak_bytes": allocator_peak, "program_reserved_bytes": reserved, "compiled_scratch_bytes": scratch,
        "setup_compiles": driven["setup_compiles"], "window_compiles": driven["window_compiles"],
        "held_pairs_a_layer": work["held_pairs_a_layer"], "numbers": numbers,
    }
    result["compared"] = compared
    return result
