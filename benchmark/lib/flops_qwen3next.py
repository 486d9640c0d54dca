"""Operations and HBM bytes one training step of the ``qwen3_next`` share
needs, as a whole and for each kernel, from shapes and from the program's
counters. The same work whatever implements it: the delta rule counts as its
own three ``d_k x d_v`` products a token a value head (not the chunked form's
extra products, inverse or masks), scores the causal mask forbids, absent
experts, padding rows of a grouped product and recomputed operations never
count; 2 operations a multiply-add; a training step is three forwards (the
backward pass is two products of the forward's shape for each of its
products).

``model`` is the reference's configuration (``reference/qwen3next_gdn_moe.py``'s
``cfg``); ``batch`` the sequences a step.
"""

from __future__ import annotations

BF16, F32 = 2, 4


def linear_layers(model: dict) -> int:
    """Gated DeltaNet layers: all but every ``full_attention_interval``-th."""
    return sum((i + 1) % model["full_attention_interval"] != 0 for i in range(model["num_hidden_layers"]))


def attention_layers(model: dict) -> int:
    return model["num_hidden_layers"] - linear_layers(model)


def causal_pairs(model: dict) -> float:
    """Query-key pairs the causal mask allows in one sequence: ``L (L + 1) / 2``."""
    seq = model["seq_len"]
    return seq * (seq + 1) / 2.0


def rule_forward(model: dict, batch: int) -> tuple[float, float]:
    """(operations, bytes) of one layer's gated delta rule forward: a token a
    value head, ``S^T k``, the rank-one update and ``S^T q``, each ``d_k x
    d_v`` multiply-adds; ``q``, ``k`` (a key head's, read by its value
    heads), ``v`` read and ``o`` written once in bf16, the log-decay and
    ``beta`` read once in float32. The state never leaves the chip."""
    heads, k_heads = model["linear_num_value_heads"], model["linear_num_key_heads"]
    d_k, d_v = model["linear_key_head_dim"], model["linear_value_head_dim"]
    positions = float(model["seq_len"] * batch)
    ops = 2.0 * positions * heads * 3 * d_k * d_v
    moved = positions * (BF16 * (2 * k_heads * d_k + 2 * heads * d_v) + F32 * 2 * heads)
    return ops, moved


def rule_step(model: dict, batch: int) -> tuple[float, float]:
    """(operations, bytes) of all Gated DeltaNet layers' rule in one training
    step: the forward once and the backward's two passes."""
    ops, moved = rule_forward(model, batch)
    layers = linear_layers(model)
    return 3.0 * layers * ops, 3.0 * layers * moved


def gdn_products_forward(model: dict, batch: int) -> float:
    """Operations of one Gated DeltaNet layer's products and convolution:
    ``W_qkvz``, ``W_ba``, ``W_out`` and the taps."""
    h = model["hidden_size"]
    keys = model["linear_num_key_heads"] * model["linear_key_head_dim"]
    values = model["linear_num_value_heads"] * model["linear_value_head_dim"]
    weights = h * (2 * keys + 2 * values + 2 * model["linear_num_value_heads"]) + values * h
    return 2.0 * model["seq_len"] * batch * (weights + model["linear_conv_kernel_dim"] * (2 * keys + values))


def attention_forward(model: dict, batch: int) -> tuple[float, float]:
    """(operations, bytes) of the attention layer's causal scores forward:
    ``q k^T`` and ``p v`` over ``head_dim`` lanes each, over the allowed
    pairs; ``q`` and the result a query head, ``k`` and ``v`` a key/value
    head, read or written once, bf16."""
    heads, kv, d = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    ops = 2.0 * batch * causal_pairs(model) * heads * 2 * d
    positions = float(model["seq_len"] * batch)
    return ops, BF16 * positions * d * (2 * heads + 2 * kv)


def attention_step(model: dict, batch: int) -> tuple[float, float]:
    """(operations, bytes) of all attention layers' scores in one training
    step: the forward once and the backward's two passes."""
    ops, moved = attention_forward(model, batch)
    layers = attention_layers(model)
    return 3.0 * layers * ops, 3.0 * layers * moved


def attention_products_forward(model: dict, batch: int) -> float:
    """Operations of the attention layer's products: ``W_q`` (query and gate),
    ``W_k``, ``W_v``, ``W_o``."""
    h, d = model["hidden_size"], model["head_dim"]
    q_out, kv_out = model["num_attention_heads"] * d, model["num_key_value_heads"] * d
    return 2.0 * model["seq_len"] * batch * h * (3 * q_out + 2 * kv_out)


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
    """(operations, bytes) of all layers' held experts in one training step,
    ``held_pairs_a_layer`` being the mean over the layers of the pairs a step
    kept (the program's ``held_pairs`` counter over layers and steps)."""
    ops, moved = experts_forward(model, held_pairs_a_layer)
    layers = model["num_hidden_layers"]
    return 3.0 * layers * ops, 3.0 * layers * moved


def expected_held_pairs(model: dict, batch: int) -> float:
    """Pairs a layer keeps in a step under a uniform router."""
    positions = float(model["seq_len"] * batch)
    return positions * model["num_experts_per_tok"] * model["experts_held"] / model["router_outputs"]


def forward_parts(model: dict, batch: int, held_pairs_a_layer: float | None = None) -> dict:
    """Operations of one forward pass by part (each for ONE layer or one pass
    of the head): what ``forward_flops`` adds up."""
    h = model["hidden_size"]
    positions = float(model["seq_len"] * batch)
    pairs = expected_held_pairs(model, batch) if held_pairs_a_layer is None else held_pairs_a_layer
    return {
        "gdn_products": gdn_products_forward(model, batch), "gdn_rule": rule_forward(model, batch)[0],
        "gattn_products": attention_products_forward(model, batch), "gattn_scores": attention_forward(model, batch)[0],
        "router": 2.0 * positions * h * model["router_outputs"],
        "shared_expert": 2.0 * positions * h * (3 * model["shared_expert_intermediate_size"] + 1),
        "held_experts": experts_forward(model, pairs)[0],
        "head": 2.0 * positions * h * model["vocab_held"],
    }


def forward_flops(model: dict, batch: int, held_pairs_a_layer: float | None = None) -> float:
    """Operations of one forward pass: every Gated DeltaNet layer's products,
    convolution and rule, every attention layer's products and allowed
    scores, every layer's router, gated shared expert and held experts (at
    the counter's pairs, or the uniform router's expectation), the head."""
    p = forward_parts(model, batch, held_pairs_a_layer)
    return (
        linear_layers(model) * (p["gdn_products"] + p["gdn_rule"])
        + attention_layers(model) * (p["gattn_products"] + p["gattn_scores"])
        + model["num_hidden_layers"] * (p["router"] + p["shared_expert"] + p["held_experts"])
        + p["head"]
    )


def train_step_flops(model: dict, batch: int, held_pairs_a_layer: float | None = None) -> float:
    """Operations of one training step: three forwards."""
    return 3.0 * forward_flops(model, batch, held_pairs_a_layer)


def parameters(model: dict) -> int:
    """Parameters this chip holds, from the shapes alone."""
    h = model["hidden_size"]
    keys = model["linear_num_key_heads"] * model["linear_key_head_dim"]
    values = model["linear_num_value_heads"] * model["linear_value_head_dim"]
    heads = model["linear_num_value_heads"]
    gdn = (
        h * (2 * keys + 2 * values) + h * 2 * heads + (2 * keys + values) * model["linear_conv_kernel_dim"]
        + values * h + 2 * heads + model["linear_value_head_dim"]
    )
    d = model["head_dim"]
    q_out, kv_out = model["num_attention_heads"] * d, model["num_key_value_heads"] * d
    attention = h * 2 * q_out + 2 * h * kv_out + q_out * h + 2 * d
    width, shared = model["moe_intermediate_size"], model["shared_expert_intermediate_size"]
    experts = h * model["router_outputs"] + model["experts_held"] * 3 * h * width + 3 * h * shared + h + 2 * h
    return (
        linear_layers(model) * gdn + attention_layers(model) * attention + model["num_hidden_layers"] * experts
        + 2 * model["vocab_held"] * h + h
    )
