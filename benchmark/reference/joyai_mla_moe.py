"""Plain float32 reference of one federated round of a latent-attention
mixture-of-experts causal language model, one chip's share of it.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
the forward, the next-token and multi-token-prediction losses, their
gradients, Adam and the sample-weighted client average. It imports nothing of
``fedcrack_tpu`` and takes nothing that the program has made; weights come
from the benchmark's seed (``init_variables`` here), data from
``lib/textgen.py``.

**Layer equations** (the ``joyai_llm_flash`` family, jdopensource/JoyAI-LLM-Flash's
``config.json``, which is DeepSeek-V3's shape key for key; the equations are
that family's, arXiv:2412.19437 sections 2.1-2.2 and its released modelling
code), on the residual stream ``x``, ``n = RMSNorm(x)`` (eps 1e-6)::

    c_q = RMSNorm(W_qa n) (1536);  [q_nope | q_rope] = W_qb c_q   (32 heads x 128 | 64)
    [c_kv | k_rope] = W_kva n  (512 | 64; k_rope is ONE head shared by all 32)
    c_kv = RMSNorm(c_kv);  [k_nope | v] = W_kvb c_kv              (32 heads x 128 | 128)
    q_rope, k_rope <- rotary embedding over adjacent pairs (rope_interleave), theta 32e6
    q = [q_nope | q_rope], k = [k_nope | k_rope]  (192 wide), v 128 wide
    h = x + W_o . softmax(q k^T / sqrt(192), key j <= query i) v
    n = RMSNorm(h)
    layer 0 (first_k_dense_replace 1):  y = h + W_down (silu(W_gate n) * W_up n)   (width 7168)
    layers 1..:  s = sigmoid(W_r n) over all 256 experts
                 T = the 8 largest of s + b   (e_score_correction_bias: selection only)
                 w_e = routed_scaling_factor s_e / sum_{e' in T} s_e'               (norm_topk_prob)
                 y = h + sum_{e in T} w_e E_e(n) + E_shared(n)     (every E a SwiGLU of width 768)

then a final RMSNorm and an untied head. **Multi-token prediction**, depth 1
(``num_nextn_predict_layers``): with ``h_i`` the last layer's output before
the final norm, ``h'_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]``
goes through one more sparse layer with its own weights and its own final
norm, then the model's own head, and predicts ``t_{i+2}``.
``loss = CE_next + lambda CE_mtp``: each the mean over the positions that have
such a target (``L - 1`` and ``L - 2`` a sequence), weighted by the data's
``weight`` of the target token.

**The share.** ``experts_held`` routed experts from ``first_expert`` on and
``vocab_held`` rows of the embedding and the head are here; the shared expert
is whole on every chip. The router scores all ``router_outputs`` experts and
chooses ``num_experts_per_tok`` of them; what the absent ones would add is
left out and the partial result goes on to the next layer. With
``first_expert`` 0 and every expert held this is the uncut layer.

**Departures, each for memory or time and none in value.** (1) The routed
experts are a loop over the held experts, each computed for every token and
weighted by a dense ``[tokens, held]`` matrix that is 0 where the expert was
not chosen. (2) Attention is computed ``QUERY_BLOCK`` queries at a time, the
mask written out for that block, against all keys (the block's scores are
rematerialised in the backward pass). (3) The sequences of a batch are run
one after another (``lax.map``), the sparse layers as a scan over their
stacked weights, every layer rematerialised in the backward pass. (4)
``rope_interleave`` is computed as the rotation of lanes ``(2i, 2i+1)`` in
place; the released code moves the pairs apart first (to ``i`` and
``i + 32``) in queries and keys alike, which leaves every score as it is. (5)
The bias ``b`` is a parameter leaf that takes no gradient (the released code
moves it by a balancing rule outside the gradient; here it is fixed). (6)
``1e-20`` is added to the sum of the chosen scores, as the released code does.
Weights start normal with standard deviation 0.02, norm scales 1, the bias
normal with standard deviation ``ROUTER_BIAS_STD``.

``operands`` selects the precision the operands of every matrix product
(projections, scores, values, experts, head; not the router's, which the
program too computes in float32) are rounded to, forward and backward, before
an exact float32 accumulation: ``None`` (the reference proper),
``"bfloat16"`` (what the configuration states), ``"float8_e4m3fn"`` (the
control: e4m3 operands, e5m2 gradients, a scale a tensor).

``fault`` plants into the reference, put in the program's place, the faults
the check has to catch: ``"no_bias"`` (selection by ``s`` alone),
``"no_scale"`` (``routed_scaling_factor`` dropped), ``"no_shared"`` (no
shared expert), ``"rope_on_all"`` (rotary over all 192 lanes of queries and
keys), ``"latent_norm_off"`` (neither latent is normed), ``"noncausal"``
(every query sees every key), ``"no_mtp"`` (``lambda`` 0), ``"bias_moves"``
(the chosen experts' weights read from ``s + b``, so that the bias takes a
gradient and Adam moves it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-7
INIT_STD = 0.02
ROUTER_BIAS_STD = 0.01
QUERY_BLOCK = 1024

# ---- weights from a seed -------------------------------------------------


def _layer_shapes(cfg: dict, prefix: str, sparse: bool) -> list[tuple[str, tuple, str]]:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    out = [
        (prefix + "attn_norm", (h,), "1"), (prefix + "wq_a", (h, cfg["q_lora_rank"]), "w"),
        (prefix + "q_a_norm", (cfg["q_lora_rank"],), "1"),
        (prefix + "wq_b", (cfg["q_lora_rank"], heads * (nope + rope)), "w"),
        (prefix + "wkv_a", (h, cfg["kv_lora_rank"] + rope), "w"), (prefix + "kv_a_norm", (cfg["kv_lora_rank"],), "1"),
        (prefix + "wkv_b", (cfg["kv_lora_rank"], heads * (nope + dv)), "w"), (prefix + "wo", (heads * dv, h), "w"),
        (prefix + "mlp_norm", (h,), "1"),
    ]
    if not sparse:
        width = cfg["intermediate_size"]
        return out + [(prefix + "w_gate", (h, width), "w"), (prefix + "w_up", (h, width), "w"), (prefix + "w_down", (width, h), "w")]
    held, width = cfg["experts_held"], cfg["moe_intermediate_size"]
    shared = width * cfg["n_shared_experts"]
    return out + [
        (prefix + "router", (h, cfg["router_outputs"]), "w"), (prefix + "router_bias", (cfg["router_outputs"],), "b"),
        (prefix + "w_gate", (held, h, width), "w"), (prefix + "w_up", (held, h, width), "w"),
        (prefix + "w_down", (held, width, h), "w"),
        (prefix + "shared_gate", (h, shared), "w"), (prefix + "shared_up", (h, shared), "w"),
        (prefix + "shared_down", (shared, h), "w"),
    ]


def _shapes(cfg: dict) -> list[tuple[str, tuple, str]]:
    h = cfg["hidden_size"]
    out = [("embed", (cfg["vocab_held"], h), "w"), ("final_norm", (h,), "1"), ("lm_head", (h, cfg["vocab_held"]), "w")]
    for i in range(cfg["num_hidden_layers"]):
        out += _layer_shapes(cfg, f"layer{i}/", i >= cfg["first_k_dense_replace"])
    if cfg["num_nextn_predict_layers"]:
        out += _layer_shapes(cfg, "mtp/", True)
        out += [("mtp/enorm", (h,), "1"), ("mtp/hnorm", (h,), "1"), ("mtp/eh_proj", (2 * h, h), "w"), ("mtp/final_norm", (h,), "1")]
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
            std = INIT_STD if kind == "w" else ROUTER_BIAS_STD
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


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary_pairs(x, theta: float):
    """``x`` ``[S, heads, d]``, positions ``0..S-1``: lanes ``(2i, 2i+1)``
    rotate by ``position x theta^(-2i/d)``."""
    seq_len, _, d = x.shape
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    angles = np.arange(seq_len, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos, sin = jnp.asarray(np.cos(angles), jnp.float32)[:, None, :], jnp.asarray(np.sin(angles), jnp.float32)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def attention(q, k, v, operands=None, fault=None):
    """``q``, ``k`` ``[S, heads, 192]``, ``v`` ``[S, heads, 128]`` ->
    ``[S, heads, 128]``. ``QUERY_BLOCK`` queries at a time against all keys,
    the causal mask written out for the block."""
    seq_len, _, d = q.shape
    step = min(QUERY_BLOCK, seq_len)
    blocks = seq_len // step
    allowed = np.arange(seq_len)[None, :] <= np.arange(seq_len)[:, None]  # key j <= query i
    if fault == "noncausal":
        allowed = np.ones_like(allowed)
    allowed = jnp.asarray(allowed.reshape(blocks, step, seq_len))
    scale = d ** -0.5

    @jax.checkpoint
    def rows(qb, mask):
        scores = _product("qhd,khd->hqk", qb, k, operands) * scale
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return _product("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v, operands)

    out = jax.lax.map(lambda a: rows(*a), (q.reshape(blocks, step, *q.shape[1:]), allowed))
    return out.reshape(seq_len, *v.shape[1:])


def attention_block(p: dict, x, cfg: dict, operands=None, fault=None):
    """``x + W_o . Attn`` on one sequence's ``[S, H]``."""
    heads, nope, rope, dv = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps, rank = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    latent_norm = (lambda c, scale: c) if fault == "latent_norm_off" else (lambda c, scale: rms_norm(c, scale, eps))
    n = rms_norm(x, p["attn_norm"], eps)
    c_q = latent_norm(_product("sh,hr->sr", n, p["wq_a"], operands), p["q_a_norm"])
    q = _product("sr,ro->so", c_q, p["wq_b"], operands).reshape(-1, heads, nope + rope)
    kv_a = _product("sh,hr->sr", n, p["wkv_a"], operands)
    c_kv = latent_norm(kv_a[:, :rank], p["kv_a_norm"])
    kv = _product("sr,ro->so", c_kv, p["wkv_b"], operands).reshape(-1, heads, nope + dv)
    k_rope = jnp.broadcast_to(kv_a[:, None, rank:], (x.shape[0], heads, rope))  # one head for all
    k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
    if fault == "rope_on_all":
        q, k = rotary_pairs(q, cfg["rope_theta"]), rotary_pairs(k, cfg["rope_theta"])
    else:
        q = jnp.concatenate([q[..., :nope], rotary_pairs(q[..., nope:], cfg["rope_theta"])], axis=-1)
        k = jnp.concatenate([k[..., :nope], rotary_pairs(k[..., nope:], cfg["rope_theta"])], axis=-1)
    attended = attention(q, k, kv[..., nope:], operands, fault).reshape(-1, heads * dv)
    return x + _product("so,oh->sh", attended, p["wo"], operands)


def swiglu(n, w_gate, w_up, w_down, operands=None):
    gate = _product("th,hw->tw", n, w_gate, operands)
    up = _product("th,hw->tw", n, w_up, operands)
    return _product("tw,wh->th", jax.nn.silu(gate) * up, w_down, operands)


def route(n, router, bias, cfg: dict, fault=None):
    """Dense ``[tokens, router_outputs]`` weights: ``w_e`` where expert ``e``
    is among the token's chosen, else 0."""
    scores = jax.nn.sigmoid(jnp.einsum("th,he->te", n, router))
    _, top_e = jax.lax.top_k(scores if fault == "no_bias" else scores + jax.lax.stop_gradient(bias), cfg["num_experts_per_tok"])
    top_w = jnp.take_along_axis(scores + bias if fault == "bias_moves" else scores, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    if fault != "no_scale":
        top_w = top_w * cfg["routed_scaling_factor"]
    chosen = jax.nn.one_hot(top_e, cfg["router_outputs"], dtype=jnp.float32)  # [t, k, E]
    return jnp.einsum("tk,tke->te", top_w, chosen)


def expert_layer(n, p: dict, cfg: dict, operands=None, fault=None):
    """The held routed experts' part of the expert layer for ``n``
    ``[tokens, H]`` (normed), WITHOUT the shared expert, and the rows each
    held expert was chosen for."""
    first, held = cfg["first_expert"], p["w_gate"].shape[0]
    dense = route(n, p["router"], p["router_bias"], cfg, fault)[:, first : first + held]

    @jax.checkpoint
    def one(n, w_gate, w_up, w_down, weight):
        return weight[:, None] * swiglu(n, w_gate, w_up, w_down, operands)

    def add(acc, e):
        return acc + one(n, p["w_gate"][e], p["w_up"][e], p["w_down"][e], dense[:, e]), None

    out, _ = jax.lax.scan(add, jnp.zeros_like(n), jnp.arange(held))
    return out, jnp.sum(dense > 0, axis=0).astype(jnp.float32)


def shared_expert(n, p: dict, operands=None):
    """``E_shared(n)``: every chip computes it whole."""
    return swiglu(n, p["shared_gate"], p["shared_up"], p["shared_down"], operands)


def sparse_layer(p: dict, x, cfg: dict, operands=None, fault=None):
    """One layer of the sparse kind on one sequence's ``[S, H]``."""
    h = attention_block(p, x, cfg, operands, fault)
    n = rms_norm(h, p["mlp_norm"], cfg["rms_norm_eps"])
    part, rows = expert_layer(n, p, cfg, operands, fault)
    if fault != "no_shared":
        part = part + shared_expert(n, p, operands)
    return h + part, rows


def dense_layer(p: dict, x, cfg: dict, operands=None, fault=None):
    """One leading layer: a dense SwiGLU in the expert layer's place."""
    h = attention_block(p, x, cfg, operands, fault)
    n = rms_norm(h, p["mlp_norm"], cfg["rms_norm_eps"])
    return h + swiglu(n, p["w_gate"], p["w_up"], p["w_down"], operands)


def _layer_names(params: dict, cfg: dict) -> tuple[list, list]:
    names = sorted((k for k in params if k.startswith("layer")), key=lambda k: int(k[5:]))
    return names[: cfg["first_k_dense_replace"]], names[cfg["first_k_dense_replace"] :]


def stack_layers(params: dict, cfg: dict) -> dict:
    """The parameter tree with its sparse ``layer<i>`` entries stacked along
    a new leading axis under ``sparse``: what the scan over them reads. A
    client's round holds its weights and Adam's moments in this form."""
    _, sparse = _layer_names(params, cfg)
    out = {k: v for k, v in params.items() if k not in sparse}
    if sparse:
        out["sparse"] = jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *[params[k] for k in sparse])
    return out


def unstack_layers(stacked: dict, cfg: dict) -> dict:
    """The inverse of :func:`stack_layers`."""
    out = {k: v for k, v in stacked.items() if k != "sparse"}
    if "sparse" in stacked:
        n = jax.tree_util.tree_leaves(stacked["sparse"])[0].shape[0]
        for i in range(n):
            out[f"layer{cfg['first_k_dense_replace'] + i}"] = jax.tree_util.tree_map(lambda leaf: leaf[i], stacked["sparse"])
    return out


def sequence_logits(params: dict, ids, cfg: dict, operands=None, fault=None):
    """Float32 logits ``[S, vocab_held]`` of one sequence, the
    multi-token-prediction module's (``None`` without one) and
    ``expert_rows`` ``[layers with experts, held]`` (the module's last)."""
    eps = cfg["rms_norm_eps"]
    embed = jnp.asarray(params["embed"])
    x = jnp.take(embed, ids, axis=0)
    stacked = params if "sparse" in params or not _layer_names(params, cfg)[1] else stack_layers(params, cfg)
    for name in _layer_names(params, cfg)[0]:
        x = jax.checkpoint(lambda x, p: dense_layer(p, x, cfg, operands, fault))(x, params[name])
    rows = jnp.zeros((0, cfg["experts_held"]), jnp.float32)
    if "sparse" in stacked:
        x, rows = jax.lax.scan(jax.checkpoint(lambda x, p: sparse_layer(p, x, cfg, operands, fault)), x, stacked["sparse"])
    head = jax.checkpoint(lambda h, norm: _product("sh,hv->sv", rms_norm(h, norm, eps), params["lm_head"], operands))
    logits = head(x, params["final_norm"])
    if not cfg["num_nextn_predict_layers"]:
        return logits, None, rows
    p = params["mtp"]
    # Position i reads the embedding of token i + 1 (the last position's
    # wraps round: it has no target and the causal mask keeps it to itself).
    both = jnp.concatenate([rms_norm(jnp.take(embed, jnp.roll(ids, -1), axis=0), p["enorm"], eps), rms_norm(x, p["hnorm"], eps)], axis=-1)
    merged = _product("sh,ho->so", both, p["eh_proj"], operands)
    x_mtp, mtp_rows = jax.checkpoint(lambda x, p: sparse_layer(p, x, cfg, operands, fault))(merged, p)
    return logits, head(x_mtp, p["final_norm"]), jnp.concatenate([rows, mtp_rows[None]])


def batch_loss(params: dict, ids, weight, cfg: dict, operands=None, fault=None):
    """``CE_next + lambda CE_mtp`` over a batch ``[B, L]``, with both terms,
    the weighted targets, those whose largest logit is the target, and the
    summed ``expert_rows``."""
    seq_len = ids.shape[-1]

    def ce(logits, targets):
        return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]

    def one(args):
        ids_b, weight_b = args
        logits, mtp_logits, rows = sequence_logits(params, ids_b, cfg, operands, fault)
        # Position i is scored against token i + 1: positions 0..L-2.
        next_sum = jnp.sum(weight_b[1:] * ce(logits[:-1], ids_b[1:]))
        hits = jnp.sum(weight_b[1:] * (jnp.argmax(logits[:-1], axis=-1) == ids_b[1:]))
        # The module's position i against token i + 2: positions 0..L-3.
        mtp_sum = jnp.float32(0.0) if mtp_logits is None else jnp.sum(weight_b[2:] * ce(mtp_logits[:-2], ids_b[2:]))
        return next_sum, mtp_sum, jnp.sum(weight_b[1:]), hits, rows

    # The sequences one after another (one sequence's code, compiled once).
    next_sum, mtp_sum, tokens, hits, rows = jax.lax.map(one, (ids, weight))
    next_loss = jnp.sum(next_sum) / (ids.shape[0] * (seq_len - 1))
    mtp_loss = jnp.sum(mtp_sum) / (ids.shape[0] * (seq_len - 2))
    lam = 0.0 if fault == "no_mtp" else cfg["mtp_loss_weight"]
    return next_loss + lam * mtp_loss, {
        "next_loss": next_loss, "mtp_loss": mtp_loss, "tokens": jnp.sum(tokens), "next_hits": jnp.sum(hits),
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


@functools.partial(jax.jit, static_argnames=("cfg_key", "lr", "operands", "fault"), donate_argnums=(0,))
def _step(carry, ids, weight, *, cfg_key, lr, operands, fault):
    cfg = dict(cfg_key)
    params, m, v, t, grad_norms = carry
    (loss, stats), grads = jax.value_and_grad(
        lambda p: batch_loss(p, ids, weight, cfg, operands, fault), has_aux=True
    )(params)
    t = t + 1.0
    params, m, v = _adam(params, grads, m, v, t, lr)
    grad_norms = jax.tree_util.tree_map(lambda a, g: a + jnp.sqrt(jnp.sum(g * g)), grad_norms, unstack_layers(grads, cfg))
    return (params, m, v, t, grad_norms), dict(stats, loss=loss)


def client_round(variables, ids, weight, cfg: dict, lr: float, *, operands=None, fault=None, device=None):
    """One client's local epoch over ``ids``/``weight`` ``[steps, B, L]``, Adam
    starting fresh, a batch at a time. Returns the client's variables and
    ``step_loss`` ``[steps]``, its mean ``loss``, the round's means of
    ``next_loss`` and ``mtp_loss``, its ``tokens``, ``next_hits`` and
    ``expert_rows``, and every parameter leaf's mean gradient norm
    (``grad_norms``)."""
    cfg_key = tuple(sorted((k, v) for k, v in cfg.items() if not isinstance(v, (list, dict))))
    scalar = lambda: jax.device_put(jnp.float32(0.0), device)
    grad_norms = jax.tree_util.tree_map(lambda p: scalar(), variables["params"])
    # Weights and moments with the sparse layers stacked (a fresh copy: the
    # carry is donated step by step, the caller's variables are not).
    params = jax.tree_util.tree_map(jnp.copy, stack_layers(jax.device_put(variables["params"], device), cfg))
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
        "mtp_loss": sum(s["mtp_loss"] for s in per_step) / steps,
        "tokens": sum(s["tokens"] for s in per_step),
        "next_hits": sum(s["next_hits"] for s in per_step),
        "expert_rows": sum(s["expert_rows"] for s in per_step),
        "grad_norms": jax.tree_util.tree_map(lambda x: x / steps, carry[4]),
    }
    return {"params": unstack_layers(carry[0], cfg), "batch_stats": {}}, means


def weighted_average(client_variables: list, weights: list) -> dict:
    """FedAvg: the sample-weighted mean of the clients' parameters, in
    float32 on the host."""
    total = float(sum(weights))
    return jax.tree_util.tree_map(
        lambda *leaves: sum(np.float32(w / total) * np.asarray(x, np.float32) for w, x in zip(weights, leaves)),
        *client_variables,
    )
