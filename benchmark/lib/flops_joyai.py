"""Operations and HBM bytes one training step of the ``joyai_llm_flash`` share
needs, as a whole and for each kernel, from shapes and from the program's
counters. The same work whatever implements it: scores the causal mask
forbids, absent experts, padding rows of a grouped product, lanes a kernel
pads a 192-wide head to and recomputed operations never count; 2 operations
a multiply-add; a training step is three forwards (the backward pass is two
products of the forward's shape for each of its products).

``model`` is the reference's configuration (``reference/joyai_mla_moe.py``'s
``cfg``); ``batch`` the sequences a step.
"""

from __future__ import annotations

BF16, F32 = 2, 4


def causal_pairs(model: dict) -> float:
    """Query-key pairs the causal mask allows in one sequence: ``L (L + 1) / 2``."""
    seq = model["seq_len"]
    return seq * (seq + 1) / 2.0


def attention_layers(model: dict) -> int:
    """Layers with an attention block: every layer, and the module's."""
    return model["num_hidden_layers"] + model["num_nextn_predict_layers"]


def sparse_layers(model: dict) -> int:
    """Layers with an expert layer: all but the leading dense ones, and the module's."""
    return model["num_hidden_layers"] - model["first_k_dense_replace"] + model["num_nextn_predict_layers"]


def attention_forward(model: dict, batch: int) -> tuple[float, float]:
    """(operations, bytes) of one layer's attention forward: ``q k^T`` over
    192 lanes and ``p v`` over 128, over the allowed pairs; ``q``, ``k`` (its
    shared rotary part already broadcast to the heads), ``v`` read and the
    result written once, bf16."""
    heads = model["num_attention_heads"]
    qk, dv = model["qk_nope_head_dim"] + model["qk_rope_head_dim"], model["v_head_dim"]
    ops = 2.0 * batch * causal_pairs(model) * heads * (qk + dv)
    positions = float(model["seq_len"] * batch)
    return ops, BF16 * positions * heads * (2 * qk + 2 * dv)


def attention_step(model: dict, batch: int) -> tuple[float, float]:
    """(operations, bytes) of all layers' attention in one training step:
    the forward once and the backward's two passes."""
    ops, moved = attention_forward(model, batch)
    layers = attention_layers(model)
    return 3.0 * layers * ops, 3.0 * layers * moved


def projections_forward(model: dict, batch: int) -> float:
    """Operations of one layer's latent projections: the two low-rank query
    products, the two key-value ones and the output's."""
    h, heads = model["hidden_size"], model["num_attention_heads"]
    nope, rope, dv = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    q_rank, kv_rank = model["q_lora_rank"], model["kv_lora_rank"]
    weights = h * q_rank + q_rank * heads * (nope + rope) + h * (kv_rank + rope) + kv_rank * heads * (nope + dv) + heads * dv * h
    return 2.0 * model["seq_len"] * batch * weights


def experts_forward(model: dict, held_pairs: float) -> tuple[float, float]:
    """(operations, bytes) of one layer's held routed experts forward for
    ``held_pairs`` (token, slot) pairs: gate, up and down products; every
    held expert's three matrices read once, every pair's row read and
    written at each product, bf16. (``flops_sdar.experts_forward``'s count.)"""
    h, w, held = model["hidden_size"], model["moe_intermediate_size"], model["experts_held"]
    ops = 2.0 * held_pairs * 3 * h * w
    moved = BF16 * (3.0 * held * h * w + held_pairs * (2 * h + 2 * w + w + h))
    return ops, moved


def experts_step(model: dict, held_pairs_a_layer: float) -> tuple[float, float]:
    """(operations, bytes) of all sparse layers' held experts in one training
    step, ``held_pairs_a_layer`` being the mean over those layers of the
    pairs a step kept (the program's ``held_pairs`` counter over layers and
    steps)."""
    ops, moved = experts_forward(model, held_pairs_a_layer)
    layers = sparse_layers(model)
    return 3.0 * layers * ops, 3.0 * layers * moved


def expected_held_pairs(model: dict, batch: int) -> float:
    """Pairs a layer keeps in a step under a uniform router."""
    positions = float(model["seq_len"] * batch)
    return positions * model["num_experts_per_tok"] * model["experts_held"] / model["router_outputs"]


def forward_parts(model: dict, batch: int, held_pairs_a_layer: float | None = None) -> dict:
    """Operations of one forward pass by part (each for ONE layer or one pass
    of the head): what ``forward_flops`` adds up."""
    h, w = model["hidden_size"], model["moe_intermediate_size"]
    positions = float(model["seq_len"] * batch)
    pairs = expected_held_pairs(model, batch) if held_pairs_a_layer is None else held_pairs_a_layer
    scores, _ = attention_forward(model, batch)
    experts, _ = experts_forward(model, pairs)
    return {
        "projections": projections_forward(model, batch), "scores": scores,
        "dense_mlp": 2.0 * positions * 3 * h * model["intermediate_size"],
        "router": 2.0 * positions * h * model["router_outputs"],
        "shared_expert": 2.0 * positions * 3 * h * w * model["n_shared_experts"],
        "held_experts": experts,
        "mtp_merge": 2.0 * positions * 2 * h * h,
        "head": 2.0 * positions * h * model["vocab_held"],
    }


def forward_flops(model: dict, batch: int, held_pairs_a_layer: float | None = None) -> float:
    """Operations of one forward pass: every layer's projections and allowed
    scores, the dense layers' feed-forward, the sparse layers' router, shared
    expert and held experts (at the counter's pairs, or the uniform router's
    expectation), the module's merge and the head once for the model and
    once more for the module."""
    p = forward_parts(model, batch, held_pairs_a_layer)
    mtp = model["num_nextn_predict_layers"]
    return (
        attention_layers(model) * (p["projections"] + p["scores"])
        + model["first_k_dense_replace"] * p["dense_mlp"]
        + sparse_layers(model) * (p["router"] + p["shared_expert"] + p["held_experts"])
        + mtp * p["mtp_merge"] + (1 + mtp) * p["head"]
    )


def train_step_flops(model: dict, batch: int, held_pairs_a_layer: float | None = None) -> float:
    """Operations of one training step: three forwards."""
    return 3.0 * forward_flops(model, batch, held_pairs_a_layer)


def roofline_seconds(ops: float, moved: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound gives it."""
    by_ops, by_bytes = ops / peaks["bf16_flops_per_s"], moved / peaks["hbm_bytes_per_s"]
    return (by_ops, "compute") if by_ops >= by_bytes else (by_bytes, "memory")
