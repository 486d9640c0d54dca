"""Operations and HBM bytes one training step of the ``lfm2_moe`` share needs,
as a whole and for each kernel whose roofline is reported, from shapes and
from the program's counters. The same work whatever implements it: scores the
causal mask forbids, absent experts, padding rows of a grouped product and
recomputed operations never count; 2 operations a multiply-add; a training
step is three forwards (the backward pass is two products of the forward's
shape for each of its products).

``model`` is the reference's configuration (``reference/lfm2_conv_moe.py``'s
``cfg``); ``batch`` the sequences a step.
"""

from __future__ import annotations

BF16 = 2


def conv_layers(model: dict) -> int:
    return sum(kind == "conv" for kind in model["layer_types"])


def attention_layers(model: dict) -> int:
    return model["num_hidden_layers"] - conv_layers(model)


def sparse_layers(model: dict) -> int:
    return model["num_hidden_layers"] - model["num_dense_layers"]


def head_dim(model: dict) -> int:
    return model["hidden_size"] // model["num_attention_heads"]


def causal_pairs(model: dict) -> float:
    """Query-key pairs the causal mask allows in one sequence: ``L (L + 1) / 2``."""
    seq = model["seq_len"]
    return seq * (seq + 1) / 2.0


def conv_forward(model: dict, batch: int) -> tuple[float, float]:
    """(operations, bytes) of one layer's gates and taps forward, a channel a
    position: ``B * x~`` (1 operation), the taps (``conv_L_cache``
    multiply-adds), ``C * v`` (1); ``B``, ``C`` and ``x~`` read and ``z``
    written once in bf16 (the projections' own input and output, the least
    the gates and taps can move)."""
    elements = float(model["seq_len"] * batch * model["hidden_size"])
    return elements * (2 + 2 * model["conv_L_cache"]), elements * BF16 * 4


def conv_step(model: dict, batch: int) -> tuple[float, float]:
    """(operations, bytes) of every convolution layer's gates and taps in one
    training step: the forward once and a backward of twice its operations
    that reads ``z``'s cotangent with ``B``, ``C``, ``x~`` and writes their
    three cotangents, bf16 (the rematerialised forward is recomputation and
    never counts)."""
    ops, moved = conv_forward(model, batch)
    layers = conv_layers(model)
    backward_moved = moved * 7 / 4  # seven arrays where the forward moves four
    return 3.0 * layers * ops, layers * (moved + backward_moved)


def conv_products_forward(model: dict, batch: int) -> float:
    """Operations of one convolution layer's products: ``W_in`` and ``W_out``."""
    h = model["hidden_size"]
    return 2.0 * model["seq_len"] * batch * (h * 3 * h + h * h)


def attention_forward(model: dict, batch: int) -> tuple[float, float]:
    """(operations, bytes) of one attention layer's causal scores forward:
    ``q k^T`` and ``p v`` over ``head_dim`` lanes each, over the allowed
    pairs; ``q`` and the result a query head, ``k`` and ``v`` a key/value
    head, read or written once, bf16."""
    heads, kv, d = model["num_attention_heads"], model["num_key_value_heads"], head_dim(model)
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
    """Operations of one attention layer's products: ``W_q``, ``W_k``,
    ``W_v``, ``W_o``."""
    h, d = model["hidden_size"], head_dim(model)
    q_out, kv_out = model["num_attention_heads"] * d, model["num_key_value_heads"] * d
    return 2.0 * model["seq_len"] * batch * h * (2 * q_out + 2 * kv_out)


def experts_forward(model: dict, held_pairs: float) -> tuple[float, float]:
    """(operations, bytes) of one layer's held routed experts forward for
    ``held_pairs`` (token, slot) pairs: gate, up and down products; every
    held expert's three matrices read once, every pair's row read and
    written at each product, bf16. (``flops_sdar.experts_forward``'s count.)"""
    h, w, held = model["hidden_size"], model["moe_intermediate_size"], model["experts_held"]
    ops = 2.0 * held_pairs * 3 * h * w
    moved = BF16 * (3.0 * held * h * w + held_pairs * (2 * h + 2 * w + w + h))
    return ops, moved


def expected_held_pairs(model: dict, batch: int) -> float:
    """Pairs a sparse layer keeps in a step under a uniform router."""
    positions = float(model["seq_len"] * batch)
    return positions * model["num_experts_per_tok"] * model["experts_held"] / model["router_outputs"]


def forward_parts(model: dict, batch: int, held_pairs_a_layer: float | None = None) -> dict:
    """Operations of one forward pass by part (each for ONE layer or one pass
    of the head): what ``forward_flops`` adds up."""
    h = model["hidden_size"]
    positions = float(model["seq_len"] * batch)
    pairs = expected_held_pairs(model, batch) if held_pairs_a_layer is None else held_pairs_a_layer
    return {
        "conv_products": conv_products_forward(model, batch), "conv_taps": conv_forward(model, batch)[0],
        "attn_products": attention_products_forward(model, batch), "attn_scores": attention_forward(model, batch)[0],
        "dense_mlp": 2.0 * positions * 3 * h * model["intermediate_size"],
        "router": 2.0 * positions * h * model["router_outputs"],
        "held_experts": experts_forward(model, pairs)[0],
        "head": 2.0 * positions * h * model["vocab_held"],
    }


def forward_flops(model: dict, batch: int, held_pairs_a_layer: float | None = None) -> float:
    """Operations of one forward pass: every convolution layer's products,
    gates and taps, every attention layer's products and allowed scores,
    every dense layer's SwiGLU, every sparse layer's router and held experts
    (at the counter's pairs, or the uniform router's expectation), the head."""
    p = forward_parts(model, batch, held_pairs_a_layer)
    return (
        conv_layers(model) * (p["conv_products"] + p["conv_taps"])
        + attention_layers(model) * (p["attn_products"] + p["attn_scores"])
        + model["num_dense_layers"] * p["dense_mlp"]
        + sparse_layers(model) * (p["router"] + p["held_experts"])
        + p["head"]
    )


def train_step_flops(model: dict, batch: int, held_pairs_a_layer: float | None = None) -> float:
    """Operations of one training step: three forwards."""
    return 3.0 * forward_flops(model, batch, held_pairs_a_layer)


def parameters(model: dict) -> int:
    """Parameters this chip holds, from the shapes alone (the head is the
    embedding)."""
    h, d = model["hidden_size"], head_dim(model)
    conv = h * 3 * h + h * model["conv_L_cache"] + h * h
    q_out, kv_out = model["num_attention_heads"] * d, model["num_key_value_heads"] * d
    attention = 2 * h * q_out + 2 * h * kv_out + 2 * d
    dense = 3 * h * model["intermediate_size"]
    sparse = h * model["router_outputs"] + model["router_outputs"] + model["experts_held"] * 3 * h * model["moe_intermediate_size"]
    return (
        conv_layers(model) * conv + attention_layers(model) * attention + model["num_hidden_layers"] * 2 * h
        + model["num_dense_layers"] * dense + sparse_layers(model) * sparse + model["vocab_held"] * h + h
    )
