"""Plain float32 reference of one federated round of a hybrid causal language
model of gated short convolutions and attention with sparse experts, one
chip's share of it.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
the forward, the next-token loss, its gradients, Adam and the sample-weighted
client average. It imports nothing of ``fedcrack_tpu`` and takes nothing that
the program has made; weights come from the benchmark's seed
(``init_variables`` here), data from ``lib/textgen.py``.

**Layer equations** (the ``lfm2_moe`` family, LiquidAI/LFM2-8B-A1B's
``config.json``; the equations are its released modelling code's, Hugging Face
``transformers`` ``modeling_lfm2_moe.py``, as read from the config's keys), on
the residual stream ``x``, every ``Norm`` ``x rsqrt(mean x^2 + 1e-5) w`` with
``w`` starting at 1. Layer ``i``'s operator follows ``layer_types[i]``, its
feed-forward its depth::

    layer:      h = x + Op(Norm_op(x));  y = h + FF(Norm_ff(h))
    conv:       [B | C | x~] = W_in n  (3 x 2048, in that lane order);  u = B * x~
                v_t = sum_{j=0..2} w_j * u_{t-2+j}   (zeros before the sequence; no bias, no activation)
                Op = W_out (C * v)
    attention:  q = W_q n (32 heads of 64);  k = W_k n, v = W_v n (8 heads of 64)
                q, k <- Norm a head over 64 lanes (a weight each);  rotary by halves over all 64 lanes, theta 1e6
                Op = W_o softmax(q k^T / 8, key j <= query i) v   (query head j reads key/value head j // 4)
    layers < num_dense_layers:  FF = W_down (silu(W_gate n) * W_up n)   (width 7168)
    later layers:  s = sigmoid(W_r n) over all 32 experts;  T = the 4 largest of s + b   (b: selection only)
                   w_e = routed_scaling_factor s_e / (sum_{e' in T} s_e' + 1e-6)          (norm_topk_prob)
                   FF = sum_{e in T} w_e E_e(n)   (every E a SwiGLU of width 1792; no shared expert)

then a final norm and the head, which is the embedding's transpose;
``loss = mean over the positions that have a next token of CE(logits_i, t_{i+1})``,
weighted by the data's ``weight`` of the target token.

**The share.** ``experts_held`` routed experts from ``first_expert`` on and
``vocab_held`` rows of the embedding (so of the head) are here; the operators,
the norms and the router are whole on every chip. The router scores all
``router_outputs`` experts and chooses ``num_experts_per_tok`` of them; what
the absent ones would add is left out and the partial result goes on to the
next layer. With ``first_expert`` 0 and every expert held this is the uncut
layer.

**Departures, each for memory or time and none in value.** (1) The
convolution is three shifted multiply-adds. (2) Attention is computed
``QUERY_BLOCK`` queries at a time, the mask written out for that block,
against all keys. (3) The routed experts are a loop over the held experts,
each computed for every token and weighted by a dense ``[tokens, held]``
matrix that is 0 where the expert was not chosen. (4) The sequences of a
batch are run one after another (``lax.map``), every layer rematerialised in
the backward pass. (5) The expert bias ``b`` is a parameter leaf that takes no
gradient (the released code moves it by a balancing rule outside the
gradient; here it is fixed through a round). (6) Whether the head is tied is
no key of the catalogued config: it is tied here, as the family's configs
declare. Weights start normal with standard deviation 0.02 (the taps too),
norm scales 1, the bias normal with standard deviation ``EXPERT_BIAS_STD``.

``operands`` selects the precision the operands of every matrix product
(projections, scores, values, experts, head; not the router's, which the
program too computes in float32) are rounded to, forward and backward, before
an exact float32 accumulation: ``None`` (the reference proper),
``"bfloat16"`` (what the configuration states), ``"float8_e4m3fn"`` (the
control: e4m3 operands, e5m2 gradients, a scale a tensor).

``fault`` plants into the reference, put in the program's place, the faults
the check has to catch: ``"taps_shifted"`` (the taps one position later, so
the last meets the next token: not causal), ``"no_b_gate"`` (``u = x~``),
``"bias_in_weights"`` (the chosen experts' weights read from ``s + b``, so
that the bias weighs and takes a gradient), ``"no_qk_norm"`` (queries and keys
as the projections left them).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-7
INIT_STD = 0.02
EXPERT_BIAS_STD = 0.01
ROUTER_EPS = 1e-6
QUERY_BLOCK = 1024

# ---- weights from a seed -------------------------------------------------


def is_conv(cfg: dict, layer: int) -> bool:
    return cfg["layer_types"][layer] == "conv"


def _layer_shapes(cfg: dict, prefix: str, layer: int) -> list[tuple[str, tuple, str]]:
    h = cfg["hidden_size"]
    if is_conv(cfg, layer):
        out = [
            (prefix + "operator_norm", (h,), "1"), (prefix + "in_proj", (h, 3 * h), "w"),
            (prefix + "conv", (h, cfg["conv_L_cache"]), "w"), (prefix + "out_proj", (h, h), "w"),
        ]
    else:
        d = h // cfg["num_attention_heads"]
        q_out, kv_out = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
        out = [
            (prefix + "operator_norm", (h,), "1"), (prefix + "wq", (h, q_out), "w"),
            (prefix + "wk", (h, kv_out), "w"), (prefix + "wv", (h, kv_out), "w"),
            (prefix + "q_norm", (d,), "1"), (prefix + "k_norm", (d,), "1"), (prefix + "wo", (q_out, h), "w"),
        ]
    out.append((prefix + "ffn_norm", (h,), "1"))
    if layer < cfg["num_dense_layers"]:
        width = cfg["intermediate_size"]
        return out + [(prefix + "w_gate", (h, width), "w"), (prefix + "w_up", (h, width), "w"), (prefix + "w_down", (width, h), "w")]
    held, width = cfg["experts_held"], cfg["moe_intermediate_size"]
    return out + [
        (prefix + "router", (h, cfg["router_outputs"]), "w"), (prefix + "expert_bias", (cfg["router_outputs"],), "b"),
        (prefix + "w_gate", (held, h, width), "w"), (prefix + "w_up", (held, h, width), "w"),
        (prefix + "w_down", (held, width, h), "w"),
    ]


def _shapes(cfg: dict) -> list[tuple[str, tuple, str]]:
    h = cfg["hidden_size"]
    out = [("embed", (cfg["vocab_held"], h), "w"), ("final_norm", (h,), "1")]
    for i in range(cfg["num_hidden_layers"]):
        out += _layer_shapes(cfg, f"layer{i}/", i)
    return out


def init_variables(seed_words, cfg: dict) -> dict:
    """``{"params", "batch_stats": {}}`` from a seed given as two uint32
    words (low, high); traceable, so one jitted call makes the model on the
    device."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), seed_words[0]), seed_words[1])
    params: dict = {}
    for n, (path, shape, kind) in enumerate(_shapes(cfg)):
        if kind == "1":
            leaf = jnp.ones(shape, jnp.float32)
        else:
            std = INIT_STD if kind == "w" else EXPERT_BIAS_STD
            leaf = std * jax.random.normal(jax.random.fold_in(key, n), shape, jnp.float32)
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
    """RMSNorm with a plain weight."""
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


def causal_conv(x, taps, fault=None):
    """``v_t = sum_j taps[:, j] x_{t - (K - 1) + j}`` for ``x`` ``[S, ch]`` and
    ``taps`` ``[ch, K]``: a shifted multiply-add a tap, zeros outside the
    sequence. ``"taps_shifted"``: every tap one position later."""
    taps_n = taps.shape[-1]
    out = jnp.zeros_like(x)
    for j in range(taps_n):
        back = taps_n - 1 - j - (1 if fault == "taps_shifted" else 0)  # tap j meets the token ``back`` positions earlier
        if back > 0:
            shifted = jnp.concatenate([jnp.zeros_like(x[:back]), x[:-back]], axis=0)
        elif back < 0:
            shifted = jnp.concatenate([x[-back:], jnp.zeros_like(x[:-back])], axis=0)
        else:
            shifted = x
        out = out + shifted * taps[:, j]
    return out


def conv_operator(p: dict, x, cfg: dict, operands=None, fault=None):
    """``W_out (C * conv(B * x~))`` of one sequence's ``[S, H]``."""
    h = cfg["hidden_size"]
    n = norm(x, p["operator_norm"], cfg["norm_eps"])
    bcx = _product("sh,ho->so", n, p["in_proj"], operands)
    b, c, xt = bcx[:, :h], bcx[:, h : 2 * h], bcx[:, 2 * h :]
    u = xt if fault == "no_b_gate" else b * xt
    return _product("sh,ho->so", c * causal_conv(u, p["conv"], fault), p["out_proj"], operands)


def attention(q, k, v, operands=None):
    """``q`` ``[S, heads, d]``, ``k``, ``v`` ``[S, kv_heads, d]`` ->
    ``[S, heads, d]``; query head ``j`` reads key/value head ``j // (heads /
    kv_heads)``. ``QUERY_BLOCK`` queries at a time against all keys, the
    causal mask written out for the block."""
    seq_len, heads, d = q.shape
    kv_heads = k.shape[1]
    step = min(QUERY_BLOCK, seq_len)
    blocks = seq_len // step
    allowed = np.arange(seq_len)[None, :] <= np.arange(seq_len)[:, None]  # key j <= query i
    allowed = jnp.asarray(allowed.reshape(blocks, step, seq_len))
    scale = d**-0.5
    grouped = q.reshape(seq_len, kv_heads, heads // kv_heads, d)

    @jax.checkpoint
    def rows(qb, mask):
        scores = _product("qngd,knd->ngqk", qb, k, operands) * scale
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        return _product("ngqk,knd->qngd", jax.nn.softmax(scores, axis=-1), v, operands)

    out = jax.lax.map(lambda a: rows(*a), (grouped.reshape(blocks, step, *grouped.shape[1:]), allowed))
    return out.reshape(seq_len, heads, d)


def attention_operator(p: dict, x, cfg: dict, operands=None, fault=None):
    """``W_o Attn`` of one sequence's ``[S, H]``."""
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // heads
    eps = cfg["norm_eps"]
    n = norm(x, p["operator_norm"], eps)
    q = _product("sh,ho->so", n, p["wq"], operands).reshape(-1, heads, d)
    k = _product("sh,ho->so", n, p["wk"], operands).reshape(-1, kv_heads, d)
    v = _product("sh,ho->so", n, p["wv"], operands).reshape(-1, kv_heads, d)
    if fault != "no_qk_norm":
        q, k = norm(q, p["q_norm"], eps), norm(k, p["k_norm"], eps)
    q, k = rotary_halves(q, cfg["rope_theta"]), rotary_halves(k, cfg["rope_theta"])
    return _product("so,oh->sh", attention(q, k, v, operands).reshape(-1, heads * d), p["wo"], operands)


def swiglu(n, w_gate, w_up, w_down, operands=None):
    gate = _product("th,hw->tw", n, w_gate, operands)
    up = _product("th,hw->tw", n, w_up, operands)
    return _product("tw,wh->th", jax.nn.silu(gate) * up, w_down, operands)


def route(n, router, bias, cfg: dict, fault=None):
    """Dense ``[tokens, router_outputs]`` weights: ``w_e`` where expert ``e``
    is among the token's chosen, else 0."""
    scores = jax.nn.sigmoid(jnp.einsum("th,he->te", n, router))
    _, top_e = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), cfg["num_experts_per_tok"])
    top_w = jnp.take_along_axis(scores + bias if fault == "bias_in_weights" else scores, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + ROUTER_EPS)
    top_w = top_w * cfg["routed_scaling_factor"]
    chosen = jax.nn.one_hot(top_e, cfg["router_outputs"], dtype=jnp.float32)  # [t, k, E]
    return jnp.einsum("tk,tke->te", top_w, chosen)


def expert_layer(n, p: dict, cfg: dict, operands=None, fault=None):
    """The held routed experts' part of the expert layer for ``n``
    ``[tokens, H]`` (normed), and the rows each held expert was chosen for."""
    first, held = cfg["first_expert"], p["w_gate"].shape[0]
    dense = route(n, p["router"], p["expert_bias"], cfg, fault)[:, first : first + held]

    @jax.checkpoint
    def one(n, w_gate, w_up, w_down, weight):
        return weight[:, None] * swiglu(n, w_gate, w_up, w_down, operands)

    def add(acc, e):
        return acc + one(n, p["w_gate"][e], p["w_up"][e], p["w_down"][e], dense[:, e]), None

    out, _ = jax.lax.scan(add, jnp.zeros_like(n), jnp.arange(held))
    return out, jnp.sum(dense > 0, axis=0).astype(jnp.float32)


def layer(p: dict, x, cfg: dict, i: int, operands=None, fault=None):
    """Layer ``i`` on one sequence's ``[S, H]``: the operator, then the
    feed-forward. Returns the rows each held expert computed too (``None`` in
    a dense layer)."""
    operator = conv_operator if is_conv(cfg, i) else attention_operator
    h = x + operator(p, x, cfg, operands, fault)
    n = norm(h, p["ffn_norm"], cfg["norm_eps"])
    if i < cfg["num_dense_layers"]:
        return h + swiglu(n, p["w_gate"], p["w_up"], p["w_down"], operands), None
    part, rows = expert_layer(n, p, cfg, operands, fault)
    return h + part, rows


def sequence_logits(params: dict, ids, cfg: dict, operands=None, fault=None):
    """Float32 logits ``[S, vocab_held]`` of one sequence and ``expert_rows``
    ``[sparse layers, held]``."""
    x = jnp.take(jnp.asarray(params["embed"]), ids, axis=0)
    rows = []
    for i in range(cfg["num_hidden_layers"]):
        x, layer_rows = jax.checkpoint(lambda x, p, i=i: layer(p, x, cfg, i, operands, fault))(x, params[f"layer{i}"])
        if layer_rows is not None:
            rows.append(layer_rows)
    head = jax.checkpoint(
        lambda h, w, e: _product("sh,vh->sv", norm(h, w, cfg["norm_eps"]), e, operands)
    )
    rows = jnp.stack(rows) if rows else jnp.zeros((0, cfg["experts_held"]), jnp.float32)
    return head(x, params["final_norm"], params["embed"]), rows


def batch_loss(params: dict, ids, weight, cfg: dict, operands=None, fault=None):
    """The next-token loss over a batch ``[B, L]``, with the weighted
    targets, those whose largest logit is the target and the summed
    ``expert_rows``."""
    seq_len = ids.shape[-1]

    def one(args):
        ids_b, weight_b = args
        logits, rows = sequence_logits(params, ids_b, cfg, operands, fault)
        # Position i is scored against token i + 1: positions 0..L-2.
        ce = jax.nn.logsumexp(logits[:-1], axis=-1) - jnp.take_along_axis(logits[:-1], ids_b[1:, None], axis=-1)[:, 0]
        hits = jnp.sum(weight_b[1:] * (jnp.argmax(logits[:-1], axis=-1) == ids_b[1:]))
        return jnp.sum(weight_b[1:] * ce), jnp.sum(weight_b[1:]), hits, rows

    # The sequences one after another (one sequence's code, compiled once).
    next_sum, tokens, hits, rows = jax.lax.map(one, (ids, weight))
    next_loss = jnp.sum(next_sum) / (ids.shape[0] * (seq_len - 1))
    return next_loss, {
        "next_loss": next_loss, "tokens": jnp.sum(tokens), "next_hits": jnp.sum(hits),
        "expert_rows": jnp.sum(rows, axis=0),
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


def _cfg_key(cfg: dict) -> tuple:
    """``cfg`` as a hashable static argument: lists as tuples."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in cfg.items()))


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
    ``next_loss``, its ``tokens``, ``next_hits`` and ``expert_rows``, and
    every parameter leaf's mean gradient norm (``grad_norms``)."""
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
            carry, stats = _step(carry, *batch, cfg_key=_cfg_key(cfg), lr=float(lr), operands=operands, fault=fault)
            per_step.append(stats)
    step_loss = jnp.stack([s["loss"] for s in per_step])
    means = {
        "loss": jnp.mean(step_loss), "step_loss": step_loss,
        "next_loss": sum(s["next_loss"] for s in per_step) / steps,
        "tokens": sum(s["tokens"] for s in per_step),
        "next_hits": sum(s["next_hits"] for s in per_step),
        "expert_rows": sum(s["expert_rows"] for s in per_step),
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
