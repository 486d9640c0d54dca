"""Plain float32 reference of one federated round of a hybrid linear-attention
mixture-of-experts causal language model, one chip's share of it.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
the forward, the next-token loss, its gradients, Adam and the sample-weighted
client average. It imports nothing of ``fedcrack_tpu`` and takes nothing that
the program has made; weights come from the benchmark's seed
(``init_variables`` here), data from ``lib/textgen.py``.

**Layer equations** (the ``qwen3_next`` family, Qwen/Qwen3-Next-80B-A3B-Instruct's
``config.json``; the equations are its released modelling code's, Hugging Face
``transformers`` ``modeling_qwen3_next.py``, and those of Gated Delta Networks,
arXiv:2412.06464), on the residual stream ``x``, ``n = Norm(x)``, every
``Norm`` of the stack zero-centred: ``x rsqrt(mean x^2 + eps) (1 + w)``. Layer
``i`` is gated full attention where ``(i + 1) % full_attention_interval == 0``
and a Gated DeltaNet layer otherwise::

    Gated DeltaNet:  [q | k | v | z] = W_qkvz n  (16x128 | 16x128 | 32x128 | 32x128);  [b | a] = W_ba n  (32 | 32)
                     [q | k | v] <- silu(depthwise causal convolution of 4 taps along the sequence, zero history)
                     q, k <- x / sqrt(sum x^2 + 1e-6) over 128 lanes;  q <- q / sqrt(128)
                     value head j reads key head j // 2
                     beta_t = sigmoid(b_t);  alpha_t = exp(-exp(A_log) softplus(a_t + dt_bias))
                     S_0 = 0 in R^{128 x 128};  S'_t = alpha_t S_{t-1}
                     S_t = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T;  o_t = S_t^T q_t
                     h = x + W_out (RMSNorm_w(o_t) * silu(z_t))     (norm a head, plain weight)
    gated attention: [q | gate] = W_q n a head (16 x (256 | 256));  k = W_k n, v = W_v n  (2 x 256)
                     q, k <- Norm a head;  rotary on the first 64 of 256 lanes, lane i with i + 32, theta 1e7
                     h = x + W_o (softmax(q k^T / sqrt(256), key j <= query i) v * sigmoid(gate))
                     (query head j reads key/value head j // 8)
    expert layer:    n = Norm(h);  p = softmax(W_r n) over all 512;  T = the 10 largest
                     w_e = p_e / sum_{e' in T} p_e'                                     (norm_topk_prob)
                     y = h + sum_{e in T} w_e E_e(n) + sigmoid(w_sg . n) E_shared(n)   (every E a SwiGLU of width 512)

then a final zero-centred norm and an untied head;
``loss = mean over the positions that have a next token of CE(logits_i, t_{i+1})``,
weighted by the data's ``weight`` of the target token.

**The share.** ``experts_held`` routed experts from ``first_expert`` on and
``vocab_held`` rows of the embedding and the head are here; both mixers, the
router, the shared expert and its gate are whole on every chip. The router
scores all ``router_outputs`` experts and chooses ``num_experts_per_tok`` of
them; what the absent ones would add is left out and the partial result goes
on to the next layer. With ``first_expert`` 0 and every expert held this is
the uncut layer.

**Departures, each for memory or time and none in value.** (1) The delta rule
runs token by token, exactly as written above (a ``lax.scan`` over
positions), in blocks of ``RULE_BLOCK`` tokens under ``jax.checkpoint``, so
that the backward pass keeps a state a block and not a state a token. (2) The
convolution is four shifted multiply-adds. (3) Attention is computed
``QUERY_BLOCK`` queries at a time, the mask written out for that block,
against all keys. (4) The routed experts are a loop over the held experts,
each computed for every token and weighted by a dense ``[tokens, held]``
matrix that is 0 where the expert was not chosen. (5) The sequences of a
batch are run one after another (``lax.map``), every layer rematerialised in
the backward pass. (6) The fused projections' lanes are laid out ``[q | k | v
| z]`` and ``[b | a]`` and a head's ``[q | gate]``; the released code
interleaves them by key head, which matters only to published weights.
Weights start normal with standard deviation 0.02, zero-centred norm scales
0, the gated norm's 1, ``A_log = log U(0, 16)``, ``dt_bias`` 1 (the released
code's), the convolution's taps normal 0.02.

``operands`` selects the precision the operands of every matrix product
(projections, scores, values, experts, head; not the router's and not the
recurrence's own, which stay float32) are rounded to, forward and backward,
before an exact float32 accumulation: ``None`` (the reference proper),
``"bfloat16"`` (what the configuration states), ``"float8_e4m3fn"`` (the
control: e4m3 operands, e5m2 gradients, a scale a tensor).

``fault`` plants into the reference, put in the program's place, the faults
the check has to catch: ``"no_decay"`` (``alpha`` 1), ``"no_beta"``
(``beta`` 1), ``"no_l2norm"`` (``q``, ``k`` as the convolution left them),
``"no_conv"`` (SiLU of the projection itself), ``"no_out_gate"``
(``silu(z)`` dropped), ``"no_attn_gate"`` (``sigmoid(gate)`` dropped),
``"rope_on_all"`` (rotary over all 256 lanes), ``"no_shared_gate"`` (the
shared expert added plain), ``"no_renorm"`` (the chosen experts' weights not
divided by their sum), ``"noncausal"`` (every query of the attention layer
sees every key).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-7
INIT_STD = 0.02
QUERY_BLOCK = 1024
RULE_BLOCK = 64
L2_EPS = 1e-6

# ---- weights from a seed -------------------------------------------------


def is_linear(cfg: dict, layer: int) -> bool:
    return (layer + 1) % cfg["full_attention_interval"] != 0


def _layer_shapes(cfg: dict, prefix: str, linear: bool) -> list[tuple[str, tuple, str]]:
    h = cfg["hidden_size"]
    if linear:
        keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
        values = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
        heads = cfg["linear_num_value_heads"]
        out = [
            (prefix + "mixer_norm", (h,), "0"), (prefix + "w_qkvz", (h, 2 * keys + 2 * values), "w"),
            (prefix + "w_ba", (h, 2 * heads), "w"),
            (prefix + "conv", (2 * keys + values, cfg["linear_conv_kernel_dim"]), "w"),
            (prefix + "A_log", (heads,), "A"), (prefix + "dt_bias", (heads,), "1"),
            (prefix + "gdn_norm", (cfg["linear_value_head_dim"],), "1"), (prefix + "w_out", (values, h), "w"),
        ]
    else:
        d = cfg["head_dim"]
        q_out, kv_out = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
        out = [
            (prefix + "mixer_norm", (h,), "0"), (prefix + "wq", (h, 2 * q_out), "w"),
            (prefix + "wk", (h, kv_out), "w"), (prefix + "wv", (h, kv_out), "w"),
            (prefix + "q_norm", (d,), "0"), (prefix + "k_norm", (d,), "0"), (prefix + "wo", (q_out, h), "w"),
        ]
    held, width, shared = cfg["experts_held"], cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    return out + [
        (prefix + "moe_norm", (h,), "0"), (prefix + "router", (h, cfg["router_outputs"]), "w"),
        (prefix + "w_gate", (held, h, width), "w"), (prefix + "w_up", (held, h, width), "w"),
        (prefix + "w_down", (held, width, h), "w"),
        (prefix + "shared_gate", (h, shared), "w"), (prefix + "shared_up", (h, shared), "w"),
        (prefix + "shared_down", (shared, h), "w"), (prefix + "shared_expert_gate", (h,), "w"),
    ]


def _shapes(cfg: dict) -> list[tuple[str, tuple, str]]:
    h = cfg["hidden_size"]
    out = [("embed", (cfg["vocab_held"], h), "w"), ("final_norm", (h,), "0"), ("lm_head", (h, cfg["vocab_held"]), "w")]
    for i in range(cfg["num_hidden_layers"]):
        out += _layer_shapes(cfg, f"layer{i}/", is_linear(cfg, i))
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
        elif kind == "A":
            leaf = jnp.log(jax.random.uniform(jax.random.fold_in(key, n), shape, jnp.float32, 1e-6, 16.0))
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
    """The stack's zero-centred RMSNorm: the weight is ``1 + w``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def plain_norm(x, w, eps):
    """The Gated DeltaNet's output norm: the weight is ``w`` itself."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotary_halves(x, rotary_dim: int, theta: float):
    """``x`` ``[S, heads, d]``, positions ``0..S-1``: of the first
    ``rotary_dim`` lanes, lane ``i`` and lane ``i + rotary_dim / 2`` rotate by
    ``position x theta^(-2i / rotary_dim)``; the other lanes pass."""
    seq_len = x.shape[0]
    half = rotary_dim // 2
    inv_freq = 1.0 / (theta ** (np.arange(0, rotary_dim, 2, dtype=np.float64) / rotary_dim))
    angles = np.arange(seq_len, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos, sin = jnp.asarray(np.cos(angles), jnp.float32)[:, None, :], jnp.asarray(np.sin(angles), jnp.float32)[:, None, :]
    a, b = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., rotary_dim:]], axis=-1)


def causal_conv(x, taps):
    """``y_t = sum_j taps[:, j] x_{t - 3 + j}`` for ``x`` ``[S, ch]`` and
    ``taps`` ``[ch, K]``: a shifted multiply-add a tap, zeros before the
    sequence."""
    taps_n = taps.shape[-1]
    out = jnp.zeros_like(x)
    for j in range(taps_n):
        back = taps_n - 1 - j  # tap j meets the token ``back`` positions earlier
        shifted = x if back == 0 else jnp.concatenate([jnp.zeros_like(x[:back]), x[:-back]], axis=0)
        out = out + shifted * taps[:, j]
    return out


def delta_rule(q, k, v, alpha, beta):
    """The gated delta rule token by token. ``q``, ``k`` ``[S, heads, d_k]``,
    ``v`` ``[S, heads, d_v]``, ``alpha``, ``beta`` ``[S, heads]``; returns
    ``o`` ``[S, heads, d_v]``. ``S_0 = 0``."""
    seq_len, heads, d_k = q.shape
    d_v = v.shape[-1]
    step = min(RULE_BLOCK, seq_len)

    def token(state, x):
        q_t, k_t, v_t, a_t, b_t = x
        state = a_t[:, None, None] * state
        delta = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * delta[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = jax.tree_util.tree_map(lambda t: t.reshape(seq_len // step, step, *t.shape[1:]), (q, k, v, alpha, beta))
    _, o = jax.lax.scan(block, jnp.zeros((heads, d_k, d_v), jnp.float32), xs)
    return o.reshape(seq_len, heads, d_v)


def gdn_block(p: dict, x, cfg: dict, operands=None, fault=None):
    """``x + W_out (RMSNorm(o) * silu(z))`` on one sequence's ``[S, H]``."""
    k_heads, v_heads = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    d_k, d_v = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    keys, values = k_heads * d_k, v_heads * d_v
    eps = cfg["rms_norm_eps"]
    n = norm(x, p["mixer_norm"], eps)
    qkvz = _product("sh,ho->so", n, p["w_qkvz"], operands)
    ba = _product("sh,ho->so", n, p["w_ba"], operands)
    qkv, z = qkvz[:, : 2 * keys + values], qkvz[:, 2 * keys + values :]
    qkv = jax.nn.silu(qkv if fault == "no_conv" else causal_conv(qkv, p["conv"]))
    q = qkv[:, :keys].reshape(-1, k_heads, d_k)
    k = qkv[:, keys : 2 * keys].reshape(-1, k_heads, d_k)
    v = qkv[:, 2 * keys :].reshape(-1, v_heads, d_v)
    if fault != "no_l2norm":
        q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    q = q * d_k**-0.5
    # Value head j reads key head j // (v_heads / k_heads).
    q, k = jnp.repeat(q, v_heads // k_heads, axis=1), jnp.repeat(k, v_heads // k_heads, axis=1)
    beta = jnp.ones_like(ba[:, :v_heads]) if fault == "no_beta" else jax.nn.sigmoid(ba[:, :v_heads])
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, v_heads:] + p["dt_bias"]))
    if fault == "no_decay":
        alpha = jnp.ones_like(alpha)
    o = plain_norm(delta_rule(q, k, v, alpha, beta), p["gdn_norm"], eps)
    if fault != "no_out_gate":
        o = o * jax.nn.silu(z.reshape(-1, v_heads, d_v))
    return x + _product("so,oh->sh", o.reshape(-1, values), p["w_out"], operands), jnp.mean(alpha)


def attention(q, k, v, operands=None, fault=None):
    """``q`` ``[S, heads, d]``, ``k``, ``v`` ``[S, kv_heads, d]`` ->
    ``[S, heads, d]``; query head ``j`` reads key/value head ``j // (heads /
    kv_heads)``. ``QUERY_BLOCK`` queries at a time against all keys, the
    causal mask written out for the block."""
    seq_len, heads, d = q.shape
    kv_heads = k.shape[1]
    step = min(QUERY_BLOCK, seq_len)
    blocks = seq_len // step
    allowed = np.arange(seq_len)[None, :] <= np.arange(seq_len)[:, None]  # key j <= query i
    if fault == "noncausal":
        allowed = np.ones_like(allowed)
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


def attention_block(p: dict, x, cfg: dict, operands=None, fault=None):
    """``x + W_o (Attn * sigmoid(gate))`` on one sequence's ``[S, H]``."""
    heads, kv_heads, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    n = norm(x, p["mixer_norm"], eps)
    q_gate = _product("sh,ho->so", n, p["wq"], operands).reshape(-1, heads, 2 * d)
    q, gate = q_gate[..., :d], q_gate[..., d:]
    k = _product("sh,ho->so", n, p["wk"], operands).reshape(-1, kv_heads, d)
    v = _product("sh,ho->so", n, p["wv"], operands).reshape(-1, kv_heads, d)
    rotary_dim = d if fault == "rope_on_all" else int(d * cfg["partial_rotary_factor"])
    q = rotary_halves(norm(q, p["q_norm"], eps), rotary_dim, cfg["rope_theta"])
    k = rotary_halves(norm(k, p["k_norm"], eps), rotary_dim, cfg["rope_theta"])
    attended = attention(q, k, v, operands, fault)
    if fault != "no_attn_gate":
        attended = attended * jax.nn.sigmoid(gate)
    return x + _product("so,oh->sh", attended.reshape(-1, heads * d), p["wo"], operands)


def swiglu(n, w_gate, w_up, w_down, operands=None):
    gate = _product("th,hw->tw", n, w_gate, operands)
    up = _product("th,hw->tw", n, w_up, operands)
    return _product("tw,wh->th", jax.nn.silu(gate) * up, w_down, operands)


def route(n, router, cfg: dict, fault=None):
    """Dense ``[tokens, router_outputs]`` weights: ``w_e`` where expert ``e``
    is among the token's chosen, else 0."""
    gates = jax.nn.softmax(jnp.einsum("th,he->te", n, router), axis=-1)
    top_w, top_e = jax.lax.top_k(gates, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"] and fault != "no_renorm":
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    chosen = jax.nn.one_hot(top_e, cfg["router_outputs"], dtype=jnp.float32)  # [t, k, E]
    return jnp.einsum("tk,tke->te", top_w, chosen)


def expert_layer(n, p: dict, cfg: dict, operands=None, fault=None):
    """The held routed experts' part of the expert layer for ``n``
    ``[tokens, H]`` (normed), WITHOUT the shared expert, and the rows each
    held expert was chosen for."""
    first, held = cfg["first_expert"], p["w_gate"].shape[0]
    dense = route(n, p["router"], cfg, fault)[:, first : first + held]

    @jax.checkpoint
    def one(n, w_gate, w_up, w_down, weight):
        return weight[:, None] * swiglu(n, w_gate, w_up, w_down, operands)

    def add(acc, e):
        return acc + one(n, p["w_gate"][e], p["w_up"][e], p["w_down"][e], dense[:, e]), None

    out, _ = jax.lax.scan(add, jnp.zeros_like(n), jnp.arange(held))
    return out, jnp.sum(dense > 0, axis=0).astype(jnp.float32)


def shared_expert(n, p: dict, operands=None, fault=None):
    """``sigmoid(w_sg . n) E_shared(n)``: every chip computes it whole."""
    out = swiglu(n, p["shared_gate"], p["shared_up"], p["shared_down"], operands)
    if fault == "no_shared_gate":
        return out
    return jax.nn.sigmoid(jnp.einsum("th,h->t", n, p["shared_expert_gate"]))[:, None] * out


def layer(p: dict, x, cfg: dict, linear: bool, operands=None, fault=None):
    """One layer on one sequence's ``[S, H]``: the mixer, then the expert
    layer. Returns the rows each held expert computed and the layer's mean
    ``alpha`` (0 for an attention layer) too."""
    if linear:
        h, decay = gdn_block(p, x, cfg, operands, fault)
    else:
        h, decay = attention_block(p, x, cfg, operands, fault), jnp.float32(0.0)
    n = norm(h, p["moe_norm"], cfg["rms_norm_eps"])
    part, rows = expert_layer(n, p, cfg, operands, fault)
    return h + part + shared_expert(n, p, operands, fault), rows, decay


def sequence_logits(params: dict, ids, cfg: dict, operands=None, fault=None):
    """Float32 logits ``[S, vocab_held]`` of one sequence, ``expert_rows``
    ``[layers, held]`` and each Gated DeltaNet layer's mean ``alpha``."""
    x = jnp.take(jnp.asarray(params["embed"]), ids, axis=0)
    rows, decays = [], []
    for i in range(cfg["num_hidden_layers"]):
        linear = is_linear(cfg, i)
        x, layer_rows, decay = jax.checkpoint(
            lambda x, p, linear=linear: layer(p, x, cfg, linear, operands, fault)
        )(x, params[f"layer{i}"])
        rows.append(layer_rows)
        if linear:
            decays.append(decay)
    head = jax.checkpoint(
        lambda h, w: _product("sh,hv->sv", norm(h, w, cfg["rms_norm_eps"]), params["lm_head"], operands)
    )
    return head(x, params["final_norm"]), jnp.stack(rows), jnp.stack(decays) if decays else jnp.zeros((0,), jnp.float32)


def batch_loss(params: dict, ids, weight, cfg: dict, operands=None, fault=None):
    """The next-token loss over a batch ``[B, L]``, with the weighted
    targets, those whose largest logit is the target, the summed
    ``expert_rows`` and the mean ``alpha`` a Gated DeltaNet layer."""
    seq_len = ids.shape[-1]

    def one(args):
        ids_b, weight_b = args
        logits, rows, decays = sequence_logits(params, ids_b, cfg, operands, fault)
        # Position i is scored against token i + 1: positions 0..L-2.
        ce = jax.nn.logsumexp(logits[:-1], axis=-1) - jnp.take_along_axis(logits[:-1], ids_b[1:, None], axis=-1)[:, 0]
        hits = jnp.sum(weight_b[1:] * (jnp.argmax(logits[:-1], axis=-1) == ids_b[1:]))
        return jnp.sum(weight_b[1:] * ce), jnp.sum(weight_b[1:]), hits, rows, decays

    # The sequences one after another (one sequence's code, compiled once).
    next_sum, tokens, hits, rows, decays = jax.lax.map(one, (ids, weight))
    next_loss = jnp.sum(next_sum) / (ids.shape[0] * (seq_len - 1))
    return next_loss, {
        "next_loss": next_loss, "tokens": jnp.sum(tokens), "next_hits": jnp.sum(hits),
        "expert_rows": jnp.sum(rows, axis=0), "gdn_decay_mean": jnp.mean(decays, axis=0),
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
    ``next_loss``, its ``tokens``, ``next_hits`` and ``expert_rows``, the
    round's mean ``gdn_decay_mean`` and every parameter leaf's mean gradient
    norm (``grad_norms``)."""
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
    means = {
        "loss": jnp.mean(step_loss), "step_loss": step_loss,
        "next_loss": sum(s["next_loss"] for s in per_step) / steps,
        "tokens": sum(s["tokens"] for s in per_step),
        "next_hits": sum(s["next_hits"] for s in per_step),
        "expert_rows": sum(s["expert_rows"] for s in per_step),
        "gdn_decay_mean": sum(s["gdn_decay_mean"] for s in per_step) / steps,
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
