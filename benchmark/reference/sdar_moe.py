"""Plain float32 reference of one federated round of a block-diffusion
mixture-of-experts language model, one chip's share of it.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
the forward, the block-diffusion loss, its gradients, Adam and the
sample-weighted client average. It imports nothing of ``fedcrack_tpu`` and
takes nothing that the program has made; weights come from the benchmark's
seed (``init_variables`` here), data from ``lib/textgen.py``.

**Layer equations** (the ``sdar_moe`` family, JetLM/SDAR-30B-A3B-Chat's
``config.json``; the Qwen3-MoE decoder it derives from), on the residual
stream ``x``, ``n = RMSNorm(x)`` (eps 1e-6)::

    q = W_q n (32 heads x 128);  k = W_k n, v = W_v n (4 KV heads x 128; no bias)
    q, k <- RMSNorm over head_dim on each head, then rotary embedding (theta 1e6)
    h = x + W_o . softmax(q k^T / sqrt(128), under the mask below) v
    n = RMSNorm(h);  g = softmax(W_r n) over all 128 experts
    T = the 8 largest;  w_e = g_e / sum_{e' in T} g_e'      (norm_topk_prob)
    y = h + sum_{e in T} w_e W_down,e (silu(W_gate,e n) * W_up,e n)   (width 768)

every layer sparse; a final RMSNorm and an untied head.

**Training by block diffusion** (BD3-LM, arXiv:2503.09573, which SDAR
follows). A sequence ``x`` of ``L`` tokens in blocks of ``B``; each block
draws ``t``, each of its tokens is replaced by the mask token with
probability ``t`` (``m_i``). The model reads ``[x~ ; x]``: the noisy copy,
then the clean copy, ``2L`` positions, both halves at positions ``0..L-1``.
With ``b(i)`` the block of position ``i``, a noisy query attends the noisy
keys of its own block and the clean keys of earlier blocks; a clean query
attends the clean keys of its own and earlier blocks. Logits are taken on the
noisy half only; loss = ``sum_i m_i (1/t_b(i)) CE(logits_i, x_i) / (sequences x L)``.
The data carries ``weight_i = m_i / t_b(i)``, so the noise is data.

**The share.** ``experts_held`` experts from ``first_expert`` on and
``vocab_held`` rows of the embedding and the head are here. The router scores
all ``router_outputs`` experts and chooses ``num_experts_per_tok`` of them;
what the absent ones would add is left out and the partial result goes on to
the next layer. With ``first_expert`` 0 and every expert held this is the
uncut layer.

**Departures, each for memory or time and none in value.** (1) The experts
are a loop over the held experts, each computed for every token and weighted
by a dense ``[tokens, held]`` matrix that is 0 where the expert was not
chosen. (2) Attention is computed a block of queries at a time, the mask
written out for that block (the block's scores are rematerialised in the
backward pass); the noisy keys of other blocks, which the mask forbids to
every query of the block, are sliced off before the product, which leaves
out only exact zeros of the softmax. (3) The
sequences of a batch are run one after another (``lax.map``), the layers as
a scan over their stacked weights, each layer rematerialised in the backward
pass. (4) QK-norm and the rotary half-rotation
follow Qwen3-MoE (the config names neither); weights start normal with
standard deviation 0.02, norm scales 1.

``operands`` selects the precision the operands of every matrix product
(projections, scores, values, experts, head; not the router's, which the
program too computes in float32) are rounded to, forward and backward, before
an exact float32 accumulation: ``None`` (the reference proper),
``"bfloat16"`` (what the configuration states), ``"float8_e4m3fn"`` (the
control: e4m3 operands, e5m2 gradients, a scale a tensor).

``fault`` plants into the reference, put in the program's place, the faults
the check has to catch: ``"all_experts"`` (no pair is left out: an absent
expert's pair is computed by held expert ``e mod held``), ``"no_renorm"``
(``w_e = g_e``), ``"causal_clean"`` (the clean half attends token by token,
not block by block).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-7
INIT_STD = 0.02
QUERY_BLOCK = 1024

# ---- weights from a seed -------------------------------------------------


def _shapes(cfg: dict) -> list[tuple[str, tuple, str]]:
    h = cfg["hidden_size"]
    q_out = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_out = cfg["num_key_value_heads"] * cfg["head_dim"]
    held, width = cfg["experts_held"], cfg["moe_intermediate_size"]
    out = [("embed", (cfg["vocab_held"], h), "w"), ("final_norm", (h,), "1"), ("lm_head", (h, cfg["vocab_held"]), "w")]
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}/"
        out += [
            (p + "attn_norm", (h,), "1"), (p + "wq", (h, q_out), "w"), (p + "wk", (h, kv_out), "w"),
            (p + "wv", (h, kv_out), "w"), (p + "wo", (q_out, h), "w"),
            (p + "q_norm", (cfg["head_dim"],), "1"), (p + "k_norm", (cfg["head_dim"],), "1"),
            (p + "moe_norm", (h,), "1"), (p + "router", (h, cfg["router_outputs"]), "w"),
            (p + "w_gate", (held, h, width), "w"), (p + "w_up", (held, h, width), "w"),
            (p + "w_down", (held, width, h), "w"),
        ]
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
            leaf = jnp.ones(shape, jnp.float32)
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


@functools.lru_cache(maxsize=4)
def attention_mask(seq_len: int, block_length: int, fault: str | None = None) -> np.ndarray:
    """``[2L, 2L]`` bool, query by key, written out from the equations."""
    mask = np.zeros((2 * seq_len, 2 * seq_len), bool)
    block = np.arange(seq_len) // block_length
    for i in range(seq_len):
        mask[i, :seq_len] = block == block[i]                # noisy -> noisy, own block
        mask[i, seq_len:] = block < block[i]                 # noisy -> clean, earlier blocks
        if fault == "causal_clean":
            mask[seq_len + i, seq_len:] = np.arange(seq_len) <= i
        else:
            mask[seq_len + i, seq_len:] = block <= block[i]  # clean -> clean, own and earlier
    return mask


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


def _rotary(x, seq_len: int, theta: float):
    """``x`` ``[2L, heads, d]``; positions ``0..L-1`` in both halves."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    pos = np.concatenate([np.arange(seq_len), np.arange(seq_len)]).astype(np.float64)
    angles = np.concatenate([pos[:, None] * inv_freq[None, :]] * 2, axis=-1)
    cos, sin = jnp.asarray(np.cos(angles), jnp.float32), jnp.asarray(np.sin(angles), jnp.float32)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * cos[:, None, :] + rotated * sin[:, None, :]


def attention(q, k, v, cfg: dict, operands=None, fault=None):
    """``q`` ``[2L, heads, d]``, ``k``/``v`` ``[2L, kv_heads, d]`` -> ``[2L, heads, d]``.
    A block of ``QUERY_BLOCK`` queries at a time, the mask written out for
    the block. A noisy block is set against its own noisy keys and all clean
    keys, a clean block against all clean keys: the noisy keys of other
    blocks, which the mask forbids to every query of the block, are sliced
    off, and that leaves out only exact zeros of the softmax."""
    seq_len = cfg["seq_len"]
    mask = attention_mask(seq_len, cfg["block_length"], fault)
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scale = cfg["head_dim"] ** -0.5
    step = min(QUERY_BLOCK, seq_len)
    blocks = seq_len // step
    own = [slice(j * step, (j + 1) * step) for j in range(blocks)]
    mask_noisy = jnp.asarray(np.stack([np.concatenate([mask[r, r], mask[r, seq_len:]], axis=1) for r in own]))
    mask_clean = jnp.asarray(np.stack([mask[seq_len + j * step : seq_len + (j + 1) * step, seq_len:] for j in range(blocks)]))
    split = lambda x: x.reshape(blocks, step, *x.shape[1:])
    k_clean, v_clean = k[seq_len:], v[seq_len:]

    @jax.checkpoint
    def rows(qb, kb, vb, allowed):
        scores = _product("qhd,khd->hqk", qb, kb, operands) * scale
        scores = jnp.where(allowed[None], scores, -jnp.inf)
        return _product("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), vb, operands)

    noisy = jax.lax.map(
        lambda a: rows(a[0], jnp.concatenate([a[1], k_clean]), jnp.concatenate([a[2], v_clean]), a[3]),
        (split(q[:seq_len]), split(k[:seq_len]), split(v[:seq_len]), mask_noisy),
    )
    clean = jax.lax.map(lambda a: rows(a[0], k_clean, v_clean, a[1]), (split(q[seq_len:]), mask_clean))
    return jnp.concatenate([noisy.reshape(seq_len, *q.shape[1:]), clean.reshape(seq_len, *q.shape[1:])])


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
    """The held experts' part of the expert layer for ``n`` ``[tokens, H]``
    (normed), and the rows each held expert was chosen for."""
    dense = route(n, p["router"], cfg, fault)
    first, held = cfg["first_expert"], p["w_gate"].shape[0]
    if fault == "all_experts":
        dense = dense.reshape(n.shape[0], -1, held).sum(axis=1)
    else:
        dense = dense[:, first : first + held]

    @jax.checkpoint
    def one(n, w_gate, w_up, w_down, weight):
        gate = _product("th,hw->tw", n, w_gate, operands)
        up = _product("th,hw->tw", n, w_up, operands)
        return weight[:, None] * _product("tw,wh->th", jax.nn.silu(gate) * up, w_down, operands)

    def add(acc, e):
        return acc + one(n, p["w_gate"][e], p["w_up"][e], p["w_down"][e], dense[:, e]), None

    out, _ = jax.lax.scan(add, jnp.zeros_like(n), jnp.arange(held))
    return out, jnp.sum(dense > 0, axis=0).astype(jnp.float32)


def layer(p: dict, x, cfg: dict, operands=None, fault=None):
    """One decoder layer on one sequence's ``[2L, H]``."""
    heads, kv_heads, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    n = rms_norm(x, p["attn_norm"], eps)
    q = _product("sh,ho->so", n, p["wq"], operands).reshape(-1, heads, d)
    k = _product("sh,ho->so", n, p["wk"], operands).reshape(-1, kv_heads, d)
    v = _product("sh,ho->so", n, p["wv"], operands).reshape(-1, kv_heads, d)
    q = _rotary(rms_norm(q, p["q_norm"], eps), cfg["seq_len"], cfg["rope_theta"])
    k = _rotary(rms_norm(k, p["k_norm"], eps), cfg["seq_len"], cfg["rope_theta"])
    attended = attention(q, k, v, cfg, operands, fault).reshape(-1, heads * d)
    h = x + _product("so,oh->sh", attended, p["wo"], operands)
    part, rows = expert_layer(rms_norm(h, p["moe_norm"], eps), p, cfg, operands, fault)
    return h + part, rows


def stack_layers(params: dict) -> dict:
    """The parameter tree with its ``layer<i>`` entries stacked along a new
    leading axis under ``layers``: what the scan over the layers reads. A
    client's round holds its weights and Adam's moments in this form, so no
    step copies them."""
    names = sorted((k for k in params if k.startswith("layer")), key=lambda k: int(k[5:]))
    out = {k: v for k, v in params.items() if k not in names}
    out["layers"] = jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *[params[k] for k in names])
    return out


def unstack_layers(stacked: dict) -> dict:
    """The inverse of :func:`stack_layers`."""
    out = {k: v for k, v in stacked.items() if k != "layers"}
    n = jax.tree_util.tree_leaves(stacked["layers"])[0].shape[0]
    for i in range(n):
        out[f"layer{i}"] = jax.tree_util.tree_map(lambda leaf: leaf[i], stacked["layers"])
    return out


def sequence_logits(params: dict, ids, masked, cfg: dict, operands=None, fault=None):
    """Float32 logits ``[L, vocab_held]`` of the noisy half of one sequence
    and ``expert_rows`` ``[layers, held]``."""
    noisy = jnp.where(masked, cfg["vocab_held"] - 1, ids)
    x = jnp.take(jnp.asarray(params["embed"]), jnp.concatenate([noisy, ids]), axis=0)
    # The layers one after another as a scan over their stacked weights (one
    # layer's code, compiled once), each rematerialised in the backward pass.
    stacked = params["layers"] if "layers" in params else stack_layers(params)["layers"]
    x, rows = jax.lax.scan(jax.checkpoint(lambda x, p: layer(p, x, cfg, operands, fault)), x, stacked)
    n = rms_norm(x[: cfg["seq_len"]], params["final_norm"], cfg["rms_norm_eps"])
    return _product("sh,hv->sv", n, params["lm_head"], operands), rows


def batch_loss(params: dict, ids, weight, cfg: dict, operands=None, fault=None):
    """``sum_i weight_i CE(logits_i, ids_i) / (sequences x L)`` over a batch
    ``[B, L]``, with the masked tokens, those of them whose largest logit is
    the clean token, and the summed ``expert_rows``."""
    def one(args):
        ids_b, weight_b = args
        masked_b = weight_b > 0
        logits, rows = sequence_logits(params, ids_b, masked_b, cfg, operands, fault)
        ce = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, ids_b[:, None], axis=-1)[:, 0]
        hit = jnp.sum(masked_b & (jnp.argmax(logits, axis=-1) == ids_b))
        return jnp.sum(weight_b * ce), jnp.sum(masked_b).astype(jnp.float32), hit.astype(jnp.float32), rows

    # The sequences one after another (one sequence's code, compiled once).
    total, masked_n, hits, rows = jax.lax.map(one, (ids, weight))
    return jnp.sum(total) / ids.size, {
        "masked_tokens": jnp.sum(masked_n), "masked_hits": jnp.sum(hits), "expert_rows": jnp.sum(rows, axis=0),
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
    grad_norms = jax.tree_util.tree_map(lambda a, g: a + jnp.sqrt(jnp.sum(g * g)), grad_norms, unstack_layers(grads))
    return (params, m, v, t, grad_norms), dict(stats, loss=loss)


def client_round(variables, ids, weight, cfg: dict, lr: float, *, operands=None, fault=None, device=None):
    """One client's local epoch over ``ids``/``weight`` ``[steps, B, L]``, Adam
    starting fresh, a batch at a time. Returns the client's variables and
    ``step_loss`` ``[steps]``, its mean ``loss``, the round's
    ``masked_tokens``, ``masked_hits`` and ``expert_rows``, and every
    parameter leaf's mean gradient norm (``grad_norms``)."""
    cfg_key = tuple(sorted((k, v) for k, v in cfg.items() if not isinstance(v, (list, dict))))
    scalar = lambda: jax.device_put(jnp.float32(0.0), device)
    grad_norms = jax.tree_util.tree_map(lambda p: scalar(), variables["params"])
    # Weights and moments with the layers stacked (a fresh copy: the carry is
    # donated step by step, the caller's variables are not).
    params = jax.tree_util.tree_map(jnp.copy, stack_layers(jax.device_put(variables["params"], device)))
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
        "masked_tokens": sum(s["masked_tokens"] for s in per_step),
        "masked_hits": sum(s["masked_hits"] for s in per_step),
        "expert_rows": sum(s["expert_rows"] for s in per_step),
        "grad_norms": jax.tree_util.tree_map(lambda x: x / steps, carry[4]),
    }
    return {"params": unstack_layers(carry[0]), "batch_stats": {}}, means


def weighted_average(client_variables: list, weights: list) -> dict:
    """FedAvg: the sample-weighted mean of the clients' parameters, in
    float32 on the host."""
    total = float(sum(weights))
    return jax.tree_util.tree_map(
        lambda *leaves: sum(np.float32(w / total) * np.asarray(x, np.float32) for w, x in zip(weights, leaves)),
        *client_variables,
    )
