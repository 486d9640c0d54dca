"""Operations and HBM bytes one training step of the ``sdar_moe`` share
needs, as a whole and for each kernel, from shapes and from the program's
counters. The same work whatever implements it: masked-out score tiles,
absent experts, padding rows of a grouped product and recomputed operations
never count; 2 operations a multiply-add; a training step is three forwards
(the backward pass is two products of the forward's shape for each of its
products).

``model`` is the reference's configuration (``reference/sdar_moe.py``'s
``cfg``); ``batch`` the sequences a step.
"""

from __future__ import annotations

BF16, F32 = 2, 4


def allowed_pairs(model: dict) -> float:
    """Query-key pairs the block-diffusion mask allows in one sequence:
    noisy rows see their own block and the clean keys of earlier blocks,
    clean rows the clean keys of their own and earlier blocks: ``L (L + B)``."""
    seq, block = model["seq_len"], model["block_length"]
    return float(seq) * (seq + block)


def attention_forward(model: dict, batch: int) -> tuple[float, float]:
    """(operations, bytes) of one layer's attention forward: ``q k^T`` and
    ``p v`` over the allowed pairs; ``q``, ``k``, ``v`` read and the result
    written once, bf16."""
    heads, kv, d = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    ops = 2.0 * 2.0 * batch * allowed_pairs(model) * heads * d
    positions = 2.0 * model["seq_len"] * batch
    return ops, BF16 * positions * d * (2 * heads + 2 * kv)


def attention_step(model: dict, batch: int) -> tuple[float, float]:
    """(operations, bytes) of all layers' attention in one training step:
    the forward once and the backward's two passes (each rereads ``q``,
    ``k``, ``v``, the result's gradient, and writes gradients of the same
    sizes)."""
    ops, moved = attention_forward(model, batch)
    layers = model["num_hidden_layers"]
    return 3.0 * layers * ops, 3.0 * layers * moved


def experts_forward(model: dict, held_pairs: float) -> tuple[float, float]:
    """(operations, bytes) of one layer's held experts forward for
    ``held_pairs`` (token, slot) pairs: gate, up and down products; every
    held expert's three matrices read once, every pair's row read and
    written at each product, bf16."""
    h, w, held = model["hidden_size"], model["moe_intermediate_size"], model["experts_held"]
    ops = 2.0 * held_pairs * 3 * h * w
    moved = BF16 * (3.0 * held * h * w + held_pairs * (2 * h + 2 * w + w + h))
    return ops, moved


def experts_step(model: dict, held_pairs_a_layer: float) -> tuple[float, float]:
    """(operations, bytes) of all layers' held experts in one training step,
    ``held_pairs_a_layer`` being the mean over the layers of the pairs a
    step kept (the program's ``held_pairs`` counter over layers and steps)."""
    ops, moved = experts_forward(model, held_pairs_a_layer)
    layers = model["num_hidden_layers"]
    return 3.0 * layers * ops, 3.0 * layers * moved


def expected_held_pairs(model: dict, batch: int) -> float:
    """Pairs a layer keeps in a step under a uniform router."""
    positions = 2.0 * model["seq_len"] * batch
    return positions * model["num_experts_per_tok"] * model["experts_held"] / model["router_outputs"]


def train_step_flops(model: dict, batch: int, held_pairs_a_layer: float | None = None) -> float:
    """Operations of one training step: projections, allowed scores, the
    router, the held experts (at the counter's pairs, or the uniform
    router's expectation) and the head."""
    h = model["hidden_size"]
    q_out = model["num_attention_heads"] * model["head_dim"]
    kv_out = model["num_key_value_heads"] * model["head_dim"]
    positions = 2.0 * model["seq_len"] * batch
    proj = 2.0 * positions * h * (2 * q_out + 2 * kv_out)
    scores, _ = attention_forward(model, batch)
    router = 2.0 * positions * h * model["router_outputs"]
    pairs = expected_held_pairs(model, batch) if held_pairs_a_layer is None else held_pairs_a_layer
    experts, _ = experts_forward(model, pairs)
    head = 2.0 * model["seq_len"] * batch * h * model["vocab_held"]
    return 3.0 * (model["num_hidden_layers"] * (proj + scores + router + experts) + head)


def roofline_seconds(ops: float, moved: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound gives it."""
    by_ops, by_bytes = ops / peaks["bf16_flops_per_s"], moved / peaks["hbm_bytes_per_s"]
    return (by_ops, "compute") if by_ops >= by_bytes else (by_bytes, "memory")
