"""One run of a cell whose traffic is ``federated_conv_lm_rounds``: federated
rounds of next-token training of a hybrid language model's share whose
operators are gated short convolutions and attention, over a leading dense
layer and expert layers.

The run is ``federated_causal_lm_rounds.py``'s, called and not copied, as
``federated_hybrid_lm_rounds.py`` and ``federated_looped_lm_rounds.py`` call
it: this module loads an instance of that file for itself and binds in it the
names that differ here (``reference_config``, ``program_config``, ``Cell``,
``compare``, ``kernel_work``, ``KERNEL_SCOPES``, ``MODULE_SCOPES``,
``PROGRAM_METRICS``, ``flops_joyai``: the operation count, here
``flops_lfm2.py``). The accepted cell's own instance is untouched.

How the trace readers find a step is the looped kind's (its
``_load_trace_module``, bound as the driver's loader): the instruction with
the most time among those with the fewest events, four or more, in the slice.
Every instruction of this program runs once a step but the head's chunks,
which run sixteen times forward and backward: the rule finds a once-a-step
instruction whichever of them weighs most (on the chip the attention's
``dkv`` kernel, about 20 ms a call).

``correct`` compares, for each checked round, ``direction_r<k>``,
``total_change_r<k>``, ``step_loss_r<k>`` and ``expert_rows_r<k>`` as the
accepted causal cell does, ``conv_direction_r<k>`` (``direction`` over the
convolution operators' own leaves, ``in_proj``, the taps and ``out_proj``)
and ``attn_direction_r<k>`` (over the attention operator's, ``wq``, ``wk``,
``wv``, ``wo``, ``q_norm``, ``k_norm``: one layer of five),
``expert_bias_moved_r<k>`` (the largest move of any expert-bias entry over
the round: it selects and may not move), and the exact ``window_compiles``
and ``failed_rounds``.
"""

from __future__ import annotations

import os
import re

import numpy as np

import jax

from . import check, flops_lfm2
from .federated_looped_lm_rounds import _load_trace_module
from .federated_rounds import _load_module

# The system under test. (A program without this configuration class cannot
# run the cell, and says so here, at once.)
from fedcrack_tpu.configs import Lfm2MoeConfig

# The accepted causal driver, an instance of our own (see above).
_driver = _load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "federated_causal_lm_rounds.py"),
    __package__ + "._causal_driver_of_conv",
)

# The kinds of block, summed over the layers that hold them.
KERNEL_SCOPES = (
    "embed", "lfm_conv_proj", "lfm_conv", "lfm_attn_proj", "lfm_attn", "dense_mlp", "router", "moe_dispatch",
    "moe_experts", "moe_combine", "lm_head",
    "unpack", "loss", "grad_scale", "optimizer", "step_metrics", "round_init", "fold", "round_metrics",
)
# Each layer whole: its operator and its feed-forward.
MODULE_SCOPES = ("layer0", "layer1", "layer2", "layer3", "layer4")
# What a checked round keeps of the program's own report.
PROGRAM_METRICS = ("loss", "step_loss", "next_loss", "tokens", "next_acc", "expert_rows", "held_pairs")
# The leaves ``direction`` is also taken over apart, by name.
LEAF_GROUPS = {
    "conv_direction": re.compile(r"/(in_proj|conv|out_proj)$"),
    "attn_direction": re.compile(r"/(wq|wk|wv|wo|q_norm|k_norm)$"),
}
PUBLISHED_KEYS = (
    "hidden_size", "num_hidden_layers", "num_dense_layers", "num_attention_heads", "num_key_value_heads",
    "conv_L_cache", "intermediate_size", "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
    "routed_scaling_factor", "norm_eps",
)


def reference_config(config: dict) -> dict:
    """The reference's plain ``cfg`` from the configuration file: the
    published keys, with the share's and the training's beside them."""
    share, training = config["share"], config["training"]
    if config["conv_bias"] or not config["use_expert_bias"] or not config["tie_word_embeddings"]:
        raise ValueError("the reference and the program have convolutions without a bias, an expert bias and a tied head")
    return dict(
        {k: config[k] for k in PUBLISHED_KEYS}, layer_types=list(config["layer_types"]),
        rope_theta=float(config["rope_theta"]), router_outputs=share["router_outputs"],
        first_expert=share["first_expert"], experts_held=config["num_experts"], vocab_held=config["vocab_size"],
        seq_len=training["seq_len"],
    )


def program_config(config: dict) -> Lfm2MoeConfig:
    """The program's model configuration for the same file."""
    cfg = reference_config(config)
    return Lfm2MoeConfig(
        **{k: cfg[k] for k in PUBLISHED_KEYS}, layer_types=tuple(cfg["layer_types"]), rope_theta=cfg["rope_theta"],
        num_experts=cfg["router_outputs"], first_expert=cfg["first_expert"], experts_held=cfg["experts_held"],
        vocab_held=cfg["vocab_held"], seq_len=cfg["seq_len"],
        compute_dtype=config["compute_dtype"], param_dtype=config["param_dtype"],
    )


class Cell(_driver.Cell):
    """One seed's weights, data, mesh and round program for a cell: the
    accepted causal cell's (its ``__init__``, ``drive`` and ``starts``) but
    for what the reference reports."""

    def reference(self, starts: list, *, operands=None, fault=None) -> list:
        """The reference over the rounds whose start is given, one client
        after another on the first device. ``fault``: the reference's own
        (``reference/lfm2_conv_moe.py``), or ``stale_slab`` (round 0's data
        again in every later round)."""
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
        # [all moving leaves, then each group of LEAF_GROUPS]: dot, |program|^2, |reference|^2
        sums = np.zeros((1 + len(LEAF_GROUPS), 3))
        for name in sorted(moving & set(r)):
            dp = (p[name] - s[name]).ravel().astype(np.float64)
            dr = (r[name] - s[name]).ravel().astype(np.float64)
            terms = float(dp @ dr), float(dp @ dp), float(dr @ dr)
            sums[0] += terms
            for row, pattern in enumerate(LEAF_GROUPS.values(), start=1):
                if pattern.search(name):
                    sums[row] += terms
        for label, (dot, pp, rr) in zip(("direction", *LEAF_GROUPS), sums):
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
        # The expert bias, whatever the gradient rule says: it may not move.
        out[f"expert_bias_moved_r{k}"] = float(max(
            (np.max(np.abs(p[name] - s[name])) for name in p if name.endswith("/expert_bias")), default=0.0
        ))
    for name, v in out.items():
        if not np.isfinite(v):
            out[name] = 1e30
    return out


def kernel_work(model: dict, batch: int, records: list, steps: int) -> dict:
    """(operations, bytes) a step of each kernel whose roofline is reported,
    and the pairs a sparse layer kept a step by the window's ``held_pairs``
    counter (for ``round_mfu``)."""
    # ``held_pairs`` is a client's pairs over a round's steps and sparse layers.
    layers = flops_lfm2.sparse_layers(model)
    pairs = [float(np.mean(rec.metrics["held_pairs"])) / (steps * layers) for rec in records]
    held = sum(pairs) / len(pairs) if pairs else flops_lfm2.expected_held_pairs(model, batch)
    return {
        "held_pairs_a_layer": held,
        "lfm_conv": flops_lfm2.conv_step(model, batch),
        "lfm_attn": flops_lfm2.attention_step(model, batch),
    }


# What the accepted driver's ``run``, ``Cell.__init__`` and ``Cell.drive`` read by name.
_driver._load_module = _load_trace_module
_driver.reference_config, _driver.program_config = reference_config, program_config
_driver.Cell, _driver.compare, _driver.kernel_work = Cell, compare, kernel_work
_driver.KERNEL_SCOPES, _driver.MODULE_SCOPES, _driver.PROGRAM_METRICS = KERNEL_SCOPES, MODULE_SCOPES, PROGRAM_METRICS
_driver.flops_joyai = flops_lfm2  # its ``train_step_flops(model, batch, held pairs a layer)``
run = _driver.run
