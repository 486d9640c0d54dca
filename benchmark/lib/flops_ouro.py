"""Operations and HBM bytes one training step of the ``ouro`` share needs, as a
whole and for each kernel, from shapes. The same work whatever implements it:
scores the causal mask forbids, lanes a kernel pads to and recomputed
operations never count; 2 operations a multiply-add; a training step is three
forwards (the backward pass is two products of the forward's shape for each
of its products). Every layer counts once for each pass that applies it
(``total_ut_steps``), every exit once for each pass.

``model`` is the reference's configuration (``reference/ouro_looped_lm.py``'s
``cfg``); ``batch`` the sequences a step.
"""

from __future__ import annotations

BF16, F32 = 2, 4


def applications(model: dict) -> int:
    """Layer applications a forward pass makes: every layer once a pass."""
    return model["total_ut_steps"] * model["num_hidden_layers"]


def causal_pairs(model: dict) -> float:
    """Query-key pairs the causal mask allows in one sequence: ``L (L + 1) / 2``."""
    seq = model["seq_len"]
    return seq * (seq + 1) / 2.0


def attention_forward(model: dict, batch: int) -> tuple[float, float]:
    """(operations, bytes) of one layer application's causal scores forward:
    ``q k^T`` and ``p v`` over ``head_dim`` lanes each, over the allowed
    pairs; ``q``, ``k``, ``v`` read and the result written once, bf16."""
    heads, d = model["num_attention_heads"], model["head_dim"]
    ops = 2.0 * batch * causal_pairs(model) * heads * 2 * d
    positions = float(model["seq_len"] * batch)
    return ops, BF16 * positions * d * (2 * heads + 2 * model["num_key_value_heads"])


def attention_step(model: dict, batch: int) -> tuple[float, float]:
    """(operations, bytes) of every application's scores in one training
    step: the forward once and the backward's two passes."""
    ops, moved = attention_forward(model, batch)
    return 3.0 * applications(model) * ops, 3.0 * applications(model) * moved


def products_forward(model: dict, batch: int) -> float:
    """Operations of one layer application's products: ``W_q``, ``W_k``,
    ``W_v``, ``W_o`` and the SwiGLU's three."""
    h, d = model["hidden_size"], model["head_dim"]
    q_out, kv_out = model["num_attention_heads"] * d, model["num_key_value_heads"] * d
    return 2.0 * model["seq_len"] * batch * (h * (2 * q_out + 2 * kv_out) + 3 * h * model["intermediate_size"])


def exit_forward(model: dict, batch: int) -> tuple[float, float]:
    """(operations, bytes) of one exit forward: the head's product over the
    whole vocabulary and the gate's; the head read once in bf16 and every
    position's ``h_t`` once in float32 (the logits are made and used a chunk at
    a time and never leave the chip whole)."""
    h, vocab = model["hidden_size"], model["vocab_size"]
    positions = float(model["seq_len"] * batch)
    return 2.0 * positions * h * (vocab + 1), BF16 * h * vocab + F32 * positions * h


def exit_step(model: dict, batch: int) -> tuple[float, float]:
    """(operations, bytes) of every exit in one training step."""
    ops, moved = exit_forward(model, batch)
    return 3.0 * model["total_ut_steps"] * ops, 3.0 * model["total_ut_steps"] * moved


def forward_parts(model: dict, batch: int) -> dict:
    """Operations of one forward pass by part, each for ONE layer application
    or one exit: what ``forward_flops`` adds up."""
    return {
        "products": products_forward(model, batch), "scores": attention_forward(model, batch)[0],
        "exit": exit_forward(model, batch)[0],
    }


def forward_flops(model: dict, batch: int) -> float:
    """Operations of one forward pass: every layer application's products and
    allowed scores, every exit's gate and head."""
    p = forward_parts(model, batch)
    return applications(model) * (p["products"] + p["scores"]) + model["total_ut_steps"] * p["exit"]


def train_step_flops(model: dict, batch: int, held_pairs_a_layer: float | None = None) -> float:
    """Operations of one training step: three forwards. (``held_pairs_a_layer``
    is the causal driver's argument for models with experts; this one has
    none.)"""
    del held_pairs_a_layer
    return 3.0 * forward_flops(model, batch)


def parameters(model: dict) -> int:
    """Parameters this chip holds, from the shapes alone: the stage's layers
    (each counted once, however many passes read it), the embedding, the
    head, the final norm and the exit gate."""
    h, d, width = model["hidden_size"], model["head_dim"], model["intermediate_size"]
    q_out, kv_out = model["num_attention_heads"] * d, model["num_key_value_heads"] * d
    layer = h * (2 * q_out + 2 * kv_out) + 3 * h * width + 4 * h
    return model["num_hidden_layers"] * layer + 2 * model["vocab_size"] * h + 2 * h + 1
