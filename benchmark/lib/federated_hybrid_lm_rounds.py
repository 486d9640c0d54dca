"""One run of a cell whose traffic is ``federated_hybrid_lm_rounds``: federated
rounds of next-token training of a hybrid linear-attention mixture-of-experts
language model's share (three Gated DeltaNet layers to one gated
full-attention layer).

The run is ``federated_causal_lm_rounds.py``'s, called and not copied: the one
call of ``run_mesh_federation`` for set-up and window, the checked rounds the
reference follows, the slice trace, the memory readings, ``check.judge`` and
the result object are that file's ``run``, ``Cell.drive`` and ``Cell.starts``.
That file reaches its model through names of its own module
(``reference_config``, ``program_config``, ``Cell``, ``compare``,
``kernel_work``, ``KERNEL_SCOPES``, ``MODULE_SCOPES``, ``PROGRAM_METRICS``,
``flops_joyai``), and it is not this cell's to edit, so
this module loads an instance of it for itself and binds those names to what
differs here: the mapping of the configuration file, the scopes, the
operation counts (``flops_qwen3next.py``), the reference's report and
``compare``'s numbers. The accepted cell's own instance is untouched.

One thing more differs, and is bound the same way: how many steps the traced
slice holds. ``trace/scopes.py`` takes the median of its instructions' event
counts, which is the steps only while most instruction NAMES run once a
step. This model's Gated DeltaNet blocks run the batch's sequences in turn and
its expert blocks 4,096 tokens in turn (they do not fit the chip side by
side), so most of its names run two or four times a step and the median
reads twice the steps, halving every loop's seconds (first traced run, PR 34:
the scopes summed to 400 ms of a 932 ms step). ``seconds_a_step`` here is
that file's own but for the count: the events of the operation that recurs
and takes the most time, the rule ``trace/reduce.py`` finds the steps by
(here the one whole-batch attention kernel a step).

``correct`` compares, for each checked round, ``direction_r<k>``,
``total_change_r<k>``, ``step_loss_r<k>`` and ``expert_rows_r<k>`` as the
accepted causal cell does, ``gattn_direction_r<k>`` (``direction`` over the
attention mixer's own leaves alone: one layer of four, whose faults the whole
vector's direction shows at four times the program's and not at ten),
``decay_r<k>`` (the worst Gated DeltaNet layer's gap of the round's mean
``alpha``, the program's ``gdn_decay_mean`` counter, as a share of the
reference's), and the exact ``window_compiles`` and ``failed_rounds``.
"""

from __future__ import annotations

import os
import re

import numpy as np

import jax

from . import check, flops_qwen3next
from .federated_rounds import _load_module

# The system under test. (A program without this configuration class cannot
# run the cell, and says so here, at once.)
from fedcrack_tpu.configs import GdnMoeConfig

# The accepted causal driver, an instance of our own (see above).
_driver = _load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "federated_causal_lm_rounds.py"),
    __package__ + "._causal_driver_of_hybrid",
)

# The kinds of block, summed over the layers that hold them.
KERNEL_SCOPES = (
    "embed", "gdn_proj", "gdn_conv", "gdn_rule", "gattn_proj", "gattn", "router", "moe_dispatch", "moe_experts",
    "moe_combine", "shared_expert", "lm_head",
    "unpack", "loss", "grad_scale", "optimizer", "step_metrics", "round_init", "fold", "round_metrics",
)
# What a checked round keeps of the program's own report.
PROGRAM_METRICS = ("loss", "step_loss", "next_loss", "tokens", "next_acc", "expert_rows", "held_pairs", "gdn_decay_mean")
# The gated-attention mixer's own leaves, by name (a Gated DeltaNet layer has none of them).
ATTENTION_LEAVES = re.compile(r"/(wq|wk|wv|wo|q_norm|k_norm)$")
# ``trace/reduce.py``'s: an operation that waits for other chips marks no step.
COLLECTIVE = re.compile(r"all-gather|all-reduce|all-to-all|collective-permute|reduce-scatter|collective-broadcast")
PUBLISHED_KEYS = (
    "hidden_size", "num_hidden_layers", "full_attention_interval", "num_attention_heads", "num_key_value_heads",
    "head_dim", "partial_rotary_factor", "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim", "moe_intermediate_size", "shared_expert_intermediate_size",
    "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
)


def reference_config(config: dict) -> dict:
    """The reference's plain ``cfg`` from the configuration file: the
    published keys, with the share's and the training's beside them."""
    share, training = config["share"], config["training"]
    if config["mlp_only_layers"] or config["decoder_sparse_step"] != 1 or config["rope_scaling"] is not None:
        raise ValueError("the reference and the program have an expert layer in every layer and plain rotary angles")
    return dict(
        {k: config[k] for k in PUBLISHED_KEYS}, rope_theta=float(config["rope_theta"]),
        router_outputs=share["router_outputs"], first_expert=share["first_expert"],
        experts_held=config["num_experts"], vocab_held=config["vocab_size"], seq_len=training["seq_len"],
    )


def program_config(config: dict) -> GdnMoeConfig:
    """The program's model configuration for the same file."""
    cfg = reference_config(config)
    return GdnMoeConfig(
        **{k: cfg[k] for k in PUBLISHED_KEYS}, rope_theta=cfg["rope_theta"], num_experts=cfg["router_outputs"],
        first_expert=cfg["first_expert"], experts_held=cfg["experts_held"], vocab_held=cfg["vocab_held"],
        seq_len=cfg["seq_len"], compute_dtype=config["compute_dtype"], param_dtype=config["param_dtype"],
    )


class Cell(_driver.Cell):
    """One seed's weights, data, mesh and round program for a cell: the
    accepted causal cell's (its ``__init__``, ``drive`` and ``starts``) but
    for what the reference reports."""

    def reference(self, starts: list, *, operands=None, fault=None) -> list:
        """The reference over the rounds whose start is given, one client
        after another on the first device. ``fault``: the reference's own
        (``reference/qwen3next_gdn_moe.py``), or ``stale_slab`` (round 0's
        data again in every later round)."""
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
                "next_acc": [float(r[1]["next_hits"]) / max(float(r[1]["tokens"]), 1.0) for r in results],
                "expert_rows": [np.asarray(r[1]["expert_rows"]).tolist() for r in results],
                "gdn_decay_mean": [np.asarray(r[1]["gdn_decay_mean"]).tolist() for r in results],
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
        # [all moving leaves, the attention mixer's]: dot, |program|^2, |reference|^2
        sums = np.zeros((2, 3))
        for name in sorted(moving & set(r)):
            dp = (p[name] - s[name]).ravel().astype(np.float64)
            dr = (r[name] - s[name]).ravel().astype(np.float64)
            sums[: 2 if ATTENTION_LEAVES.search(name) else 1] += float(dp @ dr), float(dp @ dp), float(dr @ dr)
        for label, (dot, pp, rr) in zip(("direction", "gattn_direction"), sums):
            out[f"{label}_r{k}"] = float(1.0 - dot / np.sqrt(pp * rr)) if pp > 0 and rr > 0 else 1.0
        _, pp, rr = sums[0]
        out[f"total_change_r{k}"] = float(abs(np.sqrt(pp) - np.sqrt(rr)) / np.sqrt(rr)) if rr > 0 else 1.0
        lp = np.asarray(prog["step_loss"], np.float64).reshape(len(ref["step_loss"]), -1)
        lr = np.asarray(ref["step_loss"], np.float64).reshape(lp.shape)
        out[f"step_loss_r{k}"] = float(np.max(np.abs(lp - lr) / np.abs(lr)))
        out[f"loss_r{k}"] = float(np.max(np.abs(lp.mean(axis=1) - lr.mean(axis=1)) / np.abs(lr.mean(axis=1))))
        out[f"next_acc_r{k}"] = float(np.max(np.abs(np.asarray(prog["next_acc"]).ravel() - np.asarray(ref["next_acc"]))))
        ep, er = np.asarray(prog["expert_rows"], np.float64), np.asarray(ref["expert_rows"], np.float64)
        out[f"expert_rows_r{k}"] = float(np.sum(np.abs(ep - er)) / max(np.sum(er), 1.0))
        ap, ar = np.asarray(prog["gdn_decay_mean"], np.float64), np.asarray(ref["gdn_decay_mean"], np.float64)
        out[f"decay_r{k}"] = float(np.max(np.abs(ap - ar) / np.maximum(np.abs(ar), 1e-30)))
    for name, v in out.items():
        if not np.isfinite(v):
            out[name] = 1e30
    return out


def kernel_work(model: dict, batch: int, records: list, steps: int) -> dict:
    """(operations, bytes) a step of each kernel whose roofline is reported,
    the experts' from the window's ``held_pairs`` counter."""
    # ``held_pairs`` is a client's pairs over a round's steps and layers.
    layers = model["num_hidden_layers"]
    pairs = [float(np.mean(rec.metrics["held_pairs"])) / (steps * layers) for rec in records]
    held = sum(pairs) / len(pairs) if pairs else flops_qwen3next.expected_held_pairs(model, batch)
    return {
        "held_pairs_a_layer": held,
        "gdn_rule": flops_qwen3next.rule_step(model, batch),
        "gattn": flops_qwen3next.attention_step(model, batch),
        "moe_experts": flops_qwen3next.experts_step(model, held),
    }


def seconds_a_step(accepted, profile, hlo_text: str, scopes, chips: int = 1) -> dict[str, float]:
    """``trace/scopes.py:seconds_a_step`` (``accepted`` is that module) with
    the slice's steps counted by the marking operation's events."""
    names = accepted.scope_map(hlo_text, scopes)
    planes = sorted((p for p in profile.planes if p.name.startswith("/device:TPU:")), key=lambda p: p.name)[:chips]
    out: dict[str, float] = {}
    for plane in planes:
        total: dict[str, float] = {}
        count: dict[str, int] = {}
        for line in plane.lines:
            if line.name != accepted.OPS_LINE:
                continue
            for e in line.events:
                name = e.name.split(" = ", 1)[0].lstrip("%")
                if accepted.ENCLOSING.match(name):
                    continue
                total[name] = total.get(name, 0.0) + e.duration_ns * 1e-9
                count[name] = count.get(name, 0) + 1
        scoped = [n for n in total if names.get(n) is not None]
        recurring = [n for n in total if count[n] >= 4 and not COLLECTIVE.search(n)]
        if not scoped or not recurring:
            continue
        steps = count[max(recurring, key=total.get)]
        for n in scoped:
            # A cut step adds one event to some instructions: 7 of 6 is once.
            times = max(1, int(count[n] / steps + 0.25))
            out[names[n]] = out.get(names[n], 0.0) + total[n] / count[n] * times / len(planes)
    return out


def _load_trace_module(path: str, name: str):
    """The accepted driver's loader, with this kind's step count in ``trace/scopes.py``."""
    module = _load_module(path, name)
    if os.path.basename(path) == "scopes.py":
        accepted = module.seconds_a_step
        module.seconds_a_step = lambda *args, **kwargs: seconds_a_step(module, *args, **kwargs)
        module.accepted_seconds_a_step = accepted
    return module


# What the accepted driver's ``run``, ``Cell.__init__`` and ``Cell.drive`` read by name.
_driver._load_module = _load_trace_module
_driver.reference_config, _driver.program_config = reference_config, program_config
_driver.Cell, _driver.compare, _driver.kernel_work = Cell, compare, kernel_work
_driver.KERNEL_SCOPES, _driver.MODULE_SCOPES, _driver.PROGRAM_METRICS = KERNEL_SCOPES, (), PROGRAM_METRICS
_driver.flops_joyai = flops_qwen3next  # its ``train_step_flops(model, batch, held pairs a layer)``
run = _driver.run
