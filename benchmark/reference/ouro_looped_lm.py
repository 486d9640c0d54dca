"""Plain float32 reference of one federated round of a looped causal language
model, one chip's share of it.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
the forward, the loss over every exit, its gradients, Adam and the
sample-weighted client average. It imports nothing of ``fedcrack_tpu`` and
takes nothing that the program has made; weights come from the benchmark's
seed (``init_variables`` here), data from ``lib/textgen.py``.

**Equations** (the ``ouro`` family, ByteDance/Ouro-2.6B's ``config.json``;
arXiv:2510.25741 "Scaling Latent Reasoning via Looped Language Models" and the
released ``modeling_ouro.py``), every ``Norm`` an RMSNorm with a plain weight,
eps ``rms_norm_eps``::

    x = E[ids]
    for t = 1..T  (T = total_ut_steps; every pass reads the same layers):
        every layer:  x = x + Norm_a2(W_o Attn(Norm_a1(x)))
                      x = x + Norm_m2(W_down (silu(W_gate n) * W_up n)),  n = Norm_m1(x)
        h_t = Norm_f(x);  x = h_t
        lambda_t = sigmoid(w_g . h_t + b_g);  CE_t = CE(h_t W_head, t_{i+1})
    p_t = lambda_t prod_{j<t} (1 - lambda_j)  (t < T);  p_T = prod_{j<T} (1 - lambda_j)
    objective_i = sum_t p_t CE_t - beta H(p),  H(p) = -sum_t p_t log p_t

``Attn``: causal multi-head attention, ``num_attention_heads`` query heads,
each reading its own key/value head, ``head_dim`` wide, rotary by halves over
the whole head (lane ``i`` with lane ``i + head_dim / 2``), ``rope_theta``; no
biases, no QK-norm. The head is untied.
``loss = sum over the positions that have a next token of weight x objective
/ (B (L - 1))``, weighted by the data's ``weight`` of the target token;
``next_loss`` and the hits are the last exit's.

**The share.** One pipeline stage's ``num_hidden_layers`` layers; the
embedding, the final norm, the exit gate and the head whole.

**Departures, each for memory or time and none in value.** (1) Within a pass
the layers are a ``lax.scan`` over their stacked weights, every layer
rematerialised in the backward pass; the passes are a Python loop, each
handed the same stacked weights (``passes`` hands each its own, to untie them
in a test). (2) Attention is computed ``QUERY_BLOCK`` queries at a time, the
mask written out for that block, against all keys. (3) Each exit's logits are
computed ``HEAD_BLOCK`` positions at a time, rematerialised. (4) The
sequences of a batch are run one after another (``lax.map``). Weights start
normal with standard deviation 0.02 (the embedding, every matrix, the gate's
weight), norm weights 1, the gate's bias 0.

``operands`` selects the precision the operands of every matrix product
(projections, scores, values, feed-forward, head; not the gate's, which stays
float32) are rounded to, forward and backward, before an exact float32
accumulation: ``None`` (the reference proper), ``"bfloat16"`` (what the
configuration states), ``"float8_e4m3fn"`` (the control: e4m3 operands, e5m2
gradients, a scale a tensor).

``fault`` plants into the reference, put in the program's place, the faults
the check has to catch: ``"one_loop"`` (the stack runs once, one exit),
``"last_exit_only"`` (the loss is the last exit's cross-entropy alone),
``"no_entropy"`` (``beta`` 0), ``"no_post_norm"`` (no ``Norm_a2`` or
``Norm_m2``: each block's output added as it is), ``"norm_not_carried"``
(the next pass starts from the stream before the final norm).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-7
INIT_STD = 0.02
QUERY_BLOCK = 1024
HEAD_BLOCK = 1024

# ---- weights from a seed -------------------------------------------------


def _layer_shapes(cfg: dict, prefix: str) -> list[tuple[str, tuple, str]]:
    h, width = cfg["hidden_size"], cfg["intermediate_size"]
    q_out, kv_out = cfg["num_attention_heads"] * cfg["head_dim"], cfg["num_key_value_heads"] * cfg["head_dim"]
    return [
        (prefix + "attn_norm", (h,), "1"), (prefix + "wq", (h, q_out), "w"), (prefix + "wk", (h, kv_out), "w"),
        (prefix + "wv", (h, kv_out), "w"), (prefix + "wo", (q_out, h), "w"), (prefix + "attn_out_norm", (h,), "1"),
        (prefix + "mlp_norm", (h,), "1"), (prefix + "w_gate", (h, width), "w"), (prefix + "w_up", (h, width), "w"),
        (prefix + "w_down", (width, h), "w"), (prefix + "mlp_out_norm", (h,), "1"),
    ]


def _shapes(cfg: dict) -> list[tuple[str, tuple, str]]:
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    out = [
        ("embed", (vocab, h), "w"), ("final_norm", (h,), "1"), ("exit_gate", (h,), "w"), ("exit_gate_bias", (), "0"),
        ("lm_head", (h, vocab), "w"),
    ]
    for i in range(cfg["num_hidden_layers"]):
        out += _layer_shapes(cfg, f"layer{i}/")
    return out


def init_variables(seed_words, cfg: dict) -> dict:
    """``{"params", "batch_stats": {}}`` from a seed given as two uint32
    words (low, high); traceable, so one jitted call makes the model on the
    device."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), seed_words[0]), seed_words[1])
    params: dict = {}
    for n, (path, shape, kind) in enumerate(_shapes(cfg)):
        if kind == "w":
            leaf = INIT_STD * jax.random.normal(jax.random.fold_in(key, n), shape, jnp.float32)
        else:
            leaf = jnp.full(shape, float(kind), jnp.float32)
        node = params
        *parents, last = path.split("/")
        for part in parents:
            node = node.setdefault(part, {})
        node[last] = leaf
    return {"params": params, "batch_stats": {}}


def make_variables(seed: int, cfg: dict) -> dict:
    """:func:`init_variables` in one jitted call, for any non-negative seed."""
    words = np.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], np.uint32)
    return jax.jit(lambda w: init_variables(w, cfg))(words)


# ---- the pieces --------------------------------------------------------------


def _round_to(x, dtype):
    if dtype == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    fmt = {"float8_e4m3fn": jnp.float8_e4m3fn, "float8_e5m2": jnp.float8_e5m2}[dtype]
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(fmt).max)
    return (x / scale).astype(fmt).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rounded_einsum(a, b, spec, operands):
    return jnp.einsum(spec, _round_to(a, operands), _round_to(b, operands))


def _rounded_einsum_fwd(a, b, spec, operands):
    ar, br = _round_to(a, operands), _round_to(b, operands)
    return jnp.einsum(spec, ar, br), (ar, br)


def _rounded_einsum_bwd(spec, operands, residuals, g):
    ar, br = residuals
    grad_type = "float8_e5m2" if operands == "float8_e4m3fn" else operands
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y), ar, br)
    return vjp(_round_to(g, grad_type))


_rounded_einsum.defvjp(_rounded_einsum_fwd, _rounded_einsum_bwd)


def _product(spec: str, a, b, operands):
    if operands is None:
        return jnp.einsum(spec, a, b)
    return _rounded_einsum(a, b, spec, operands)


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotary_halves(x, theta: float):
    """``x`` ``[S, heads, d]``, positions ``0..S-1``: lane ``i`` and lane
    ``i + d / 2`` rotate by ``position x theta^(-2i / d)``."""
    seq_len, _, d = x.shape
    half = d // 2
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    angles = np.arange(seq_len, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos, sin = jnp.asarray(np.cos(angles), jnp.float32)[:, None, :], jnp.asarray(np.sin(angles), jnp.float32)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(q, k, v, operands=None):
    """``q``, ``k``, ``v`` ``[S, heads, d]`` -> ``[S, heads, d]``, query head
    ``j`` reading key/value head ``j``: ``QUERY_BLOCK`` queries at a time
    against all keys, the causal mask written out for the block."""
    seq_len, _, d = q.shape
    step = min(QUERY_BLOCK, seq_len)
    blocks = seq_len // step
    allowed = jnp.asarray((np.arange(seq_len)[None, :] <= np.arange(seq_len)[:, None]).reshape(blocks, step, seq_len))

    @jax.checkpoint
    def rows(qb, mask):
        scores = _product("qnd,knd->nqk", qb, k, operands) * d**-0.5
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return _product("nqk,knd->qnd", jax.nn.softmax(scores, axis=-1), v, operands)

    out = jax.lax.map(lambda a: rows(*a), (q.reshape(blocks, step, *q.shape[1:]), allowed))
    return out.reshape(q.shape)


def swiglu(n, w_gate, w_up, w_down, operands=None):
    gate = _product("th,hw->tw", n, w_gate, operands)
    up = _product("th,hw->tw", n, w_up, operands)
    return _product("tw,wh->th", jax.nn.silu(gate) * up, w_down, operands)


def layer(p: dict, x, cfg: dict, operands=None, fault=None):
    """One layer on one sequence's ``[S, H]``: the attention block, then the
    feed-forward block, each output normed (``Norm_a2``, ``Norm_m2``) before
    it joins the stream."""
    heads, kv_heads, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    post = (lambda y, w: y) if fault == "no_post_norm" else (lambda y, w: norm(y, w, eps))
    n = norm(x, p["attn_norm"], eps)
    q = rotary_halves(_product("sh,ho->so", n, p["wq"], operands).reshape(-1, heads, d), cfg["rope_theta"])
    k = rotary_halves(_product("sh,ho->so", n, p["wk"], operands).reshape(-1, kv_heads, d), cfg["rope_theta"])
    v = _product("sh,ho->so", n, p["wv"], operands).reshape(-1, kv_heads, d)
    attended = attention(q, k, v, operands).reshape(-1, heads * d)
    x = x + post(_product("so,oh->sh", attended, p["wo"], operands), p["attn_out_norm"])
    n = norm(x, p["mlp_norm"], eps)
    return x + post(swiglu(n, p["w_gate"], p["w_up"], p["w_down"], operands), p["mlp_out_norm"])


def _stacked_layers(params: dict, cfg: dict) -> dict:
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *[params[f"layer{i}"] for i in range(cfg["num_hidden_layers"])]
    )


def sequence_exits(params: dict, ids, cfg: dict, operands=None, fault=None, passes=None):
    """One sequence's exits: the gate's logits ``[T, S]`` and the states
    ``h_t`` ``[T, S, H]`` (``T`` is 1 under ``one_loop``). ``passes``, where
    given, is a list of ``T`` parameter trees, pass ``t`` reading the layers
    of the ``t``-th; by default every pass reads ``params``' own."""
    eps = cfg["rms_norm_eps"]
    x = jnp.take(jnp.asarray(params["embed"]), ids, axis=0)
    loops = 1 if fault == "one_loop" else cfg["total_ut_steps"]
    tied = _stacked_layers(params, cfg)
    run = jax.checkpoint(lambda p, x: layer(p, x, cfg, operands, fault))
    gates, hs = [], []
    for t in range(loops):
        stacked = tied if passes is None else _stacked_layers(passes[t], cfg)
        x, _ = jax.lax.scan(lambda x, p: (run(p, x), None), x, stacked)
        h = norm(x, params["final_norm"], eps)
        gates.append(jnp.einsum("sh,h->s", h, params["exit_gate"]) + params["exit_gate_bias"])
        hs.append(h)
        x = x if fault == "norm_not_carried" else h
    return jnp.stack(gates), jnp.stack(hs)


def exit_losses(hs, targets, head, operands=None):
    """Cross-entropy of every exit's logits ``h_t W_head`` against
    ``targets`` ``[S]``, and whether the largest logit is the target:
    ``[T, S]`` each, ``HEAD_BLOCK`` positions at a time."""
    exits, seq_len, h = hs.shape
    step = HEAD_BLOCK if seq_len % HEAD_BLOCK == 0 else seq_len

    @jax.checkpoint
    def block(args):
        hb, tb = args
        logits = _product("sh,hv->sv", hb, head, operands)
        ce = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return ce, (jnp.argmax(logits, axis=-1) == tb).astype(jnp.float32)

    blocks = seq_len // step
    tiled = jnp.broadcast_to(targets.reshape(1, blocks, step), (exits, blocks, step)).reshape(-1, step)
    ce, hit = jax.lax.map(block, (hs.reshape(-1, step, h), tiled))
    return ce.reshape(exits, seq_len), hit.reshape(exits, seq_len)


def exit_distribution(gates):
    """``p_t`` ``[T, ...]`` from the gate's logits, as the equations write it."""
    lam = jax.nn.sigmoid(gates)
    p, remain = [], jnp.ones_like(lam[0])
    for t in range(gates.shape[0] - 1):
        p.append(lam[t] * remain)
        remain = remain * (1.0 - lam[t])
    return jnp.stack(p + [remain])


def batch_loss(params: dict, ids, weight, cfg: dict, operands=None, fault=None, passes=None):
    """The loss over a batch ``[B, L]`` and its statistics: ``next_loss``,
    the weighted targets (``tokens``), those whose largest logit (last exit)
    is the target, and over the weighted positions the mean ``exit_mass``
    and ``loop_nll`` ``[T]`` and ``exit_entropy``."""
    seq_len = ids.shape[-1]
    beta = 0.0 if fault == "no_entropy" else cfg["exit_entropy_beta"]

    def one(args):
        ids_b, weight_b = args
        gates, hs = sequence_exits(params, ids_b, cfg, operands, fault, passes)
        ce, hit = exit_losses(hs, jnp.roll(ids_b, -1), params["lm_head"], operands)
        # Position i is scored against token i + 1: positions 0..L-2.
        ce, hit, w = ce[:, :-1], hit[:, :-1], weight_b[1:]
        p = exit_distribution(gates[:, :-1])
        entropy = -jnp.sum(p * jnp.log(p), axis=0)
        objective = ce[-1] if fault == "last_exit_only" else jnp.sum(p * ce, axis=0) - beta * entropy
        return (
            jnp.sum(w * objective), jnp.sum(w * ce[-1]), jnp.sum(w), jnp.sum(w * hit[-1]),
            jnp.sum(w * p, axis=1), jnp.sum(w * ce, axis=1), jnp.sum(w * entropy),
        )

    # The sequences one after another (one sequence's code, compiled once).
    obj, next_sum, tokens, hits, mass, nll, entropy = (jnp.sum(t, axis=0) for t in jax.lax.map(one, (ids, weight)))
    positions = ids.shape[0] * (seq_len - 1)
    loss = obj / positions
    return loss, {
        "next_loss": next_sum / positions, "tokens": tokens, "next_hits": hits,
        "exit_mass": mass / tokens, "loop_nll": nll / tokens, "exit_entropy": entropy / tokens,
    }


# ---- one client's local fit and the average --------------------------------


def _adam(params, grads, m, v, t, lr):
    m = jax.tree_util.tree_map(lambda a, g: ADAM_B1 * a + (1 - ADAM_B1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda a, g: ADAM_B2 * a + (1 - ADAM_B2) * g * g, v, grads)
    c1, c2 = 1 - ADAM_B1**t, 1 - ADAM_B2**t
    params = jax.tree_util.tree_map(
        lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + ADAM_EPS), params, m, v
    )
    return params, m, v


@functools.partial(jax.jit, static_argnames=("cfg_key", "lr", "operands", "fault"), donate_argnums=(0,))
def _step(carry, ids, weight, *, cfg_key, lr, operands, fault):
    cfg = dict(cfg_key)
    params, m, v, t, grad_norms = carry
    (loss, stats), grads = jax.value_and_grad(
        lambda p: batch_loss(p, ids, weight, cfg, operands, fault), has_aux=True
    )(params)
    t = t + 1.0
    params, m, v = _adam(params, grads, m, v, t, lr)
    grad_norms = jax.tree_util.tree_map(lambda a, g: a + jnp.sqrt(jnp.sum(g * g)), grad_norms, grads)
    return (params, m, v, t, grad_norms), dict(stats, loss=loss)


def client_round(variables, ids, weight, cfg: dict, lr: float, *, operands=None, fault=None, device=None):
    """One client's local epoch over ``ids``/``weight`` ``[steps, B, L]``, Adam
    starting fresh, a batch at a time. Returns the client's variables and
    ``step_loss`` ``[steps]``, its mean ``loss``, the round's mean
    ``next_loss``, ``exit_mass``, ``loop_nll`` and ``exit_entropy``, its
    ``tokens`` and ``next_hits`` and every parameter leaf's mean gradient norm
    (``grad_norms``)."""
    cfg_key = tuple(sorted((k, v) for k, v in cfg.items() if not isinstance(v, (list, dict))))
    scalar = lambda: jax.device_put(jnp.float32(0.0), device)
    grad_norms = jax.tree_util.tree_map(lambda p: scalar(), variables["params"])
    # A fresh copy: the carry is donated step by step, the caller's variables are not.
    params = jax.tree_util.tree_map(jnp.copy, jax.device_put(variables["params"], device))
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
    carry = (params, zeros(), zeros(), scalar(), grad_norms)
    steps = ids.shape[0]
    per_step = []
    with jax.default_matmul_precision("highest"):
        for s in range(steps):
            batch = jax.device_put((np.asarray(ids[s], np.int32), np.asarray(weight[s], np.float32)), device)
            carry, stats = _step(carry, *batch, cfg_key=cfg_key, lr=float(lr), operands=operands, fault=fault)
            per_step.append(stats)
    step_loss = jnp.stack([s["loss"] for s in per_step])
    mean = lambda name: sum(s[name] for s in per_step) / steps
    means = {
        "loss": jnp.mean(step_loss), "step_loss": step_loss,
        **{name: mean(name) for name in ("next_loss", "exit_mass", "loop_nll", "exit_entropy")},
        "tokens": sum(s["tokens"] for s in per_step), "next_hits": sum(s["next_hits"] for s in per_step),
        "grad_norms": jax.tree_util.tree_map(lambda x: x / steps, carry[4]),
    }
    return {"params": carry[0], "batch_stats": {}}, means


def weighted_average(client_variables: list, weights: list) -> dict:
    """FedAvg: the sample-weighted mean of the clients' parameters, in
    float32 on the host."""
    total = float(sum(weights))
    return jax.tree_util.tree_map(
        lambda *leaves: sum(np.float32(w / total) * np.asarray(x, np.float32) for w, x in zip(weights, leaves)),
        *client_variables,
    )
