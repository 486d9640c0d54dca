"""One chip's share of a block-diffusion mixture-of-experts language model.

The ``sdar_moe`` family (JetLM/SDAR-30B-A3B-Chat: the Qwen3-MoE decoder
trained by block diffusion, BD3-LM, arXiv:2503.09573). Per layer, on the
residual stream ``x``::

    n = RMSNorm(x);  q = W_q n (32 heads x 128);  k = W_k n, v = W_v n (4 x 128)
    q, k <- RMSNorm over head_dim on every head, then rotary embedding
    h = x + W_o . softmax(q k^T / sqrt(128), under the block-diffusion mask) v
    n = RMSNorm(h);  g = softmax(W_r n) over all 128 experts
    T = the 8 largest;  w_e = g_e / sum_{e' in T} g_e'
    y = h + sum_{e in T, e held here} w_e W_down,e (silu(W_gate,e n) * W_up,e n)

**The share.** The expert layer is told which experts it holds
(``first_expert``, ``experts_held``). It routes over all of the router's
outputs, keeps the (token, slot) pairs whose expert is held, orders them by
expert, runs the three matrix products as one grouped product over the held
experts and adds the weighted rows back. What absent experts would add is
left out, and that partial result goes on to the next layer: no code stands
in for the absent chips or their exchange. No pair is ever dropped: the
grouped product has room for every pair, whatever the routing. The embedding
and the head hold ``vocab_held`` rows; ids, logits and loss are over those.

**Block diffusion.** A sequence ``x`` of ``L`` tokens in blocks of ``B``; the
model reads ``[x~ ; x]``: the noisy copy (masked tokens replaced by the mask
token), then the clean copy, ``2L`` positions, both halves at positions
``0..L-1``. With ``b(i)`` the block of position ``i``: a noisy query attends
the noisy keys of its own block and the clean keys of earlier blocks; a clean
query attends the clean keys of its own and earlier blocks. A quarter of the
``[2L, 2L]`` scores is allowed, and only that quarter is computed (tiles that
the mask empties are never visited; the scores never exist as a whole).
Logits are taken on the noisy half only, in chunks of positions, and leave
the model as each position's cross-entropy against the clean token.

Float32 parameters, bf16 matrix products with float32 accumulation; norms,
softmaxes, the router's product and the loss in float32. Every layer's
attention block and every sequence's expert block is rematerialised in the
backward pass (``jax.checkpoint``): at 8,192 positions a sequence the
activations of one block are all that fits beside the weights, their
gradient and Adam's moments.

Kernels: the attention is JAX's splash-attention Pallas kernel under the
block-diffusion mask and the grouped product is JAX's megablox ``gmm``, on a
TPU at sizes their tiles divide; elsewhere the attention is a masked dense
softmax and the grouped product ``jax.lax.ragged_dot``. ``kernels`` steers
that for tests (``"pallas"``, ``"interpret"``, ``"xla"``). Neither library
kernel declares over which mesh axes its result varies, so a ``shard_map``
that holds this model runs with ``check_vma=False`` (``tasks.py``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from fedcrack_tpu.configs import SdarMoeConfig

# Positions a chunk of the head: [chunk, vocab_held] float32 logits are all
# of the logits that ever exist (1024 x 18,992 x 4 B = 78 MB).
HEAD_CHUNK = 1024
# Tiles of the kernels: (m, k, n) of the grouped product and the attention's
# square tile of queries and keys.
GMM_TILE_M = 512
ATTN_TILE = 512
# The grouped product always runs over at least this multiple of the rows a
# uniform router would send to the held experts (rows beyond the kept pairs
# ride in the last group and are thrown away), so that a step's time does not
# move with the routing until the load is three times the uniform one. With
# fresh weights the attention's output, an average over thousands of keys,
# outweighs a token's own embedding, so most positions of a sequence route
# alike: a layer's held load is about 0, 1, 2 or 3 times the uniform one as
# 0, 1, 2 or 3 of those eight shared choices are held here (measured 1.6
# times in the mean of four layers; at a budget of 2 one round in five held a
# layer beyond it and read 0.3-0.6% slower).
ROW_BUDGET = 3.0


def block_diffusion_mask(seq_len: int, block_length: int) -> np.ndarray:
    """``[2L, 2L]`` bool: may query ``i`` (rows) attend key ``j`` (columns).
    Rows and columns ``0..L-1`` are the noisy copy, ``L..2L-1`` the clean."""
    b = np.arange(seq_len) // block_length
    same = b[:, None] == b[None, :]
    earlier = b[None, :] < b[:, None]
    noisy_rows = np.concatenate([same, earlier], axis=1)
    clean_rows = np.concatenate([np.zeros_like(same), same | earlier], axis=1)
    return np.concatenate([noisy_rows, clean_rows], axis=0)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over the last axis in float32; returns float32."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return x32 * lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rotary_tables(seq_len: int, head_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """``cos``, ``sin`` ``[2L, head_dim]`` for positions ``0..L-1`` twice (the
    noisy and the clean copy share their positions)."""
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
    pos = np.concatenate([np.arange(seq_len), np.arange(seq_len)]).astype(np.float64)
    angles = pos[:, None] * inv_freq[None, :]
    angles = np.concatenate([angles, angles], axis=-1)
    return jnp.asarray(np.cos(angles), jnp.float32), jnp.asarray(np.sin(angles), jnp.float32)


def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """``x`` ``[B, S, heads, head_dim]`` float32, the half-rotation form."""
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + rotated * sin[None, :, None, :]


def _resolve_kernels(kernels: str | None) -> str:
    if kernels is None:
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if kernels not in ("pallas", "interpret", "xla"):
        raise ValueError(f"kernels must be None, 'pallas', 'interpret' or 'xla', got {kernels!r}")
    return kernels


@functools.lru_cache(maxsize=8)
def _splash_kernel(seq_len: int, block_length: int, q_per_kv: int, tile: int, interpret: bool):
    """The splash-attention kernel for one key/value head and its
    ``q_per_kv`` query heads under the block-diffusion mask. The mask's
    tiles are worked out once, on the host, as the program is traced."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    mask = sm.NumpyMask(block_diffusion_mask(seq_len, block_length))
    sizes = sk.BlockSizes(
        block_q=tile, block_kv=tile, block_kv_compute=tile,
        block_q_dkv=tile, block_kv_dkv=tile, block_kv_dkv_compute=tile,
        block_q_dq=tile, block_kv_dq=tile,
    )
    # The kernel's mask tables must be plain constants of whatever program is
    # being traced, not tracers of the first one that asked.
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mqa(
            sm.MultiHeadMask([mask] * q_per_kv), block_sizes=sizes,
            head_shards=1, q_seq_shards=1, interpret=interpret,
        )


def blockdiff_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, block_length: int, kernels: str | None = None
) -> jax.Array:
    """Softmax attention under the block-diffusion mask.

    ``q`` ``[B, 2L, heads, d]`` already scaled by ``1/sqrt(d)``, ``k``/``v``
    ``[B, 2L, kv_heads, d]``; query head ``h`` reads key/value head
    ``h // (heads / kv_heads)``. Returns ``[B, 2L, heads, d]`` in ``q``'s dtype."""
    batch, s2, heads, d = q.shape
    kv_heads = k.shape[2]
    group = heads // kv_heads
    seq_len = s2 // 2
    mode = _resolve_kernels(kernels)
    tile = min(ATTN_TILE, s2)
    if mode != "xla" and s2 % tile == 0 and tile % 128 == 0:
        kernel = _splash_kernel(seq_len, block_length, group, tile, mode == "interpret")
        # [B, kv, group, S, d] queries beside [B, kv, S, d] keys and values.
        qh = q.reshape(batch, s2, kv_heads, group, d).transpose(0, 2, 3, 1, 4)
        kh, vh = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        out = jax.vmap(jax.vmap(kernel))(qh, kh, vh)
        return out.transpose(0, 3, 1, 2, 4).reshape(batch, s2, heads, d).astype(q.dtype)
    mask = jnp.asarray(block_diffusion_mask(seq_len, block_length))
    qh = q.reshape(batch, s2, kv_heads, group, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qh, k, preferred_element_type=jnp.float32)
    scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v, preferred_element_type=jnp.float32)
    return out.reshape(batch, s2, heads, d).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _rows_to_pairs(x, order, inverse, held, top_k):
    """``x[order // top_k]``: row ``p`` of the result is the token of pair
    ``order[p]``. The backward pass is a gather through ``inverse`` and a sum
    over each token's held slots, where autodiff would scatter-add; what
    comes back for a pair that is not ``held`` (``[T, top_k]``) is undefined
    (``grouped_product``) and is left out."""
    del inverse, held
    return x[order // top_k]


def _rows_to_pairs_fwd(x, order, inverse, held, top_k):
    return x[order // top_k], (inverse, held)


def _rows_to_pairs_bwd(top_k, res, g):
    inverse, held = res
    by_pair = g[inverse].reshape(held.shape[0], top_k, g.shape[-1])
    return jnp.sum(jnp.where(held[..., None], by_pair, jnp.zeros((), g.dtype)), axis=1), None, None, None


_rows_to_pairs.defvjp(_rows_to_pairs_fwd, _rows_to_pairs_bwd)


@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    """``x[perm]`` for a permutation ``perm`` with its ``inverse``: the
    backward pass gathers through the inverse."""
    del inverse
    return x[perm]


def _permute_rows_fwd(x, perm, inverse):
    return x[perm], (inverse,)


def _permute_rows_bwd(res, g):
    return g[res[0]], None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def grouped_product(
    rows: jax.Array, weights: jax.Array, group_sizes: jax.Array, *, kernels: str | None = None
) -> jax.Array:
    """``rows[start_g : start_g + size_g] @ weights[g]`` for every group, the
    groups laid end to end from row 0. Rows past the last group are zeros
    from ``ragged_dot`` and UNDEFINED from the kernel, forward and backward:
    the caller masks them (``held_expert_layer`` does, on the way in and out).
    ``rows`` ``[m, k]``, ``weights`` ``[groups, k, n]``; returns ``[m, n]`` in
    ``rows``' dtype, accumulated in float32."""
    mode = _resolve_kernels(kernels)
    m, k = rows.shape
    n = weights.shape[-1]
    if mode != "xla" and m % GMM_TILE_M == 0 and k % 128 == 0 and n % 128 == 0:
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        tiling = (GMM_TILE_M, min(k, 1024), min(n, 1024))
        return megablox.gmm(rows, weights, group_sizes, rows.dtype, tiling, None, None, False, mode == "interpret")
    return lax.ragged_dot(rows, weights, group_sizes, preferred_element_type=jnp.float32).astype(rows.dtype)


def route(n32: jax.Array, router: jax.Array, top_k: int, norm_topk: bool):
    """``g = softmax(W_r n)`` over all the router's experts in float32; the
    ``top_k`` largest and their weights (renormalised over the chosen
    ``top_k`` where ``norm_topk``). ``[T, top_k]`` each."""
    logits = jnp.dot(n32, router.astype(jnp.float32), precision=lax.Precision.HIGHEST)
    gates = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = lax.top_k(gates, top_k)
    if norm_topk:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return top_e, top_w


def held_expert_layer(
    n32: jax.Array,
    router: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    first_expert: int,
    top_k: int,
    norm_topk: bool,
    compute_dtype,
    kernels: str | None = None,
):
    """The held experts' part of the expert layer for tokens ``n32`` ``[T, H]``
    (normed, float32). Returns that part ``[T, H]`` in ``compute_dtype`` and
    the counters ``expert_rows`` ``[experts_held]`` (rows each held expert
    computed) and ``held_pairs`` (pairs kept of ``T x top_k``)."""
    tokens, hidden = n32.shape
    held_n = w_gate.shape[0]
    with jax.named_scope("router"):
        top_e, top_w = route(n32, router, top_k, norm_topk)
    with jax.named_scope("moe_dispatch"):
        local = top_e - first_expert
        held = (local >= 0) & (local < held_n)
        # Pairs of absent experts sort behind every held one.
        key = jnp.where(held, local, held_n).reshape(-1).astype(jnp.int32)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        pairs = order.shape[0]
        inverse = jnp.zeros(pairs, jnp.int32).at[order].set(jnp.arange(pairs, dtype=jnp.int32))
        group_sizes = jnp.sum(key[:, None] == jnp.arange(held_n, dtype=jnp.int32)[None, :], axis=0, dtype=jnp.int32)
        kept = jnp.sum(group_sizes)
        # Rows past the kept pairs hold other tokens; nothing comes back
        # through them (``_rows_to_pairs``), so as many of them as fill the
        # row budget ride in the last group, to be thrown away.
        budget = min(pairs, int(ROW_BUDGET * pairs * held_n / router.shape[-1]))
        run_sizes = group_sizes.at[-1].add(jnp.maximum(budget - kept, 0))
        rows = _rows_to_pairs(n32.astype(compute_dtype), order, inverse, held, top_k)
    with jax.named_scope("moe_experts"):
        gate = grouped_product(rows, w_gate.astype(compute_dtype), run_sizes, kernels=kernels)
        up = grouped_product(rows, w_up.astype(compute_dtype), run_sizes, kernels=kernels)
        mid = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)).astype(compute_dtype)
        down = grouped_product(mid, w_down.astype(compute_dtype), run_sizes, kernels=kernels)
    with jax.named_scope("moe_combine"):
        # A pair that is not held reads a row past the kept pairs, which the
        # kernel leaves undefined: selected away before anything multiplies
        # it (0 x NaN is NaN, in the weights' gradient too).
        by_pair = _permute_rows(down, inverse, order).reshape(tokens, top_k, hidden)
        by_pair = jnp.where(held[..., None], by_pair, jnp.zeros((), compute_dtype))
        part = jnp.sum(by_pair.astype(jnp.float32) * top_w[..., None], axis=1)
    return part.astype(compute_dtype), group_sizes.astype(jnp.float32), kept.astype(jnp.float32)


def _token_losses(hidden32: jax.Array, head: jax.Array, targets: jax.Array, compute_dtype):
    """Cross-entropy of every position against its target and whether the
    largest logit is the target, the logits existing a chunk of positions at
    a time (and again, a chunk at a time, in the backward pass)."""
    positions = hidden32.shape[0]
    chunk = min(HEAD_CHUNK, positions)
    if positions % chunk:
        chunk = positions
    head_c = head.astype(compute_dtype)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def one(args):
        h, t = args
        logits = jnp.dot(h.astype(compute_dtype), head_c, preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        return lse - picked, (jnp.argmax(logits, axis=-1) == t).astype(jnp.float32)

    nll, hit = lax.map(one, (hidden32.reshape(-1, chunk, hidden32.shape[-1]), targets.reshape(-1, chunk)))
    return nll.reshape(positions), hit.reshape(positions)


@dataclasses.dataclass(frozen=True)
class SdarMoe:
    """The model as pure functions of a parameter tree (nested dicts):
    ``embed`` ``[vocab_held, H]``, ``layer<i>`` (``attn_norm``, ``wq``,
    ``wk``, ``wv``, ``wo``, ``q_norm``, ``k_norm``, ``moe_norm``, ``router``
    ``[H, num_experts]``, ``w_gate``/``w_up`` ``[experts_held, H, width]``,
    ``w_down`` ``[experts_held, width, H]``), ``final_norm``, ``lm_head``
    ``[H, vocab_held]`` (untied)."""

    config: SdarMoeConfig = dataclasses.field(default_factory=SdarMoeConfig)
    kernels: str | None = None

    def init(self, rng: jax.Array) -> dict:
        c = self.config
        dtype = jnp.dtype(c.param_dtype)
        q_out = c.num_attention_heads * c.head_dim
        kv_out = c.num_key_value_heads * c.head_dim
        shapes = {
            "wq": (c.hidden_size, q_out), "wk": (c.hidden_size, kv_out), "wv": (c.hidden_size, kv_out),
            "wo": (q_out, c.hidden_size), "router": (c.hidden_size, c.num_experts),
            "w_gate": (c.experts_held, c.hidden_size, c.moe_intermediate_size),
            "w_up": (c.experts_held, c.hidden_size, c.moe_intermediate_size),
            "w_down": (c.experts_held, c.moe_intermediate_size, c.hidden_size),
        }
        norms = {"attn_norm": c.hidden_size, "moe_norm": c.hidden_size, "q_norm": c.head_dim, "k_norm": c.head_dim}

        def normal(key, shape):
            return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

        keys = jax.random.split(rng, c.num_hidden_layers + 2)
        params = {
            "embed": normal(keys[0], (c.vocab_held, c.hidden_size)),
            "final_norm": jnp.ones((c.hidden_size,), dtype),
            "lm_head": normal(keys[1], (c.hidden_size, c.vocab_held)),
        }
        for i in range(c.num_hidden_layers):
            sub = jax.random.split(keys[2 + i], len(shapes))
            layer = {name: normal(k, shape) for k, (name, shape) in zip(sub, sorted(shapes.items()))}
            layer.update({name: jnp.ones((width,), dtype) for name, width in norms.items()})
            params[f"layer{i}"] = layer
        return params

    def _attention_block(self, p: dict, x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
        """``h = x + W_o . Attn(...)`` on one sequence's ``[2L, H]``."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        x = x[None]
        batch, s2, _ = x.shape
        with jax.named_scope("attn_proj"):
            n = rms_norm(x, p["attn_norm"], c.rms_norm_eps).astype(cd)
            q = jnp.dot(n, p["wq"].astype(cd), preferred_element_type=jnp.float32)
            k = jnp.dot(n, p["wk"].astype(cd), preferred_element_type=jnp.float32)
            v = jnp.dot(n, p["wv"].astype(cd), preferred_element_type=jnp.float32)
            q = q.reshape(batch, s2, c.num_attention_heads, c.head_dim)
            k = k.reshape(batch, s2, c.num_key_value_heads, c.head_dim)
            v = v.reshape(batch, s2, c.num_key_value_heads, c.head_dim).astype(cd)
            q = apply_rotary(rms_norm(q, p["q_norm"], c.rms_norm_eps), cos, sin)
            k = apply_rotary(rms_norm(k, p["k_norm"], c.rms_norm_eps), cos, sin).astype(cd)
            q = (q * (c.head_dim ** -0.5)).astype(cd)
        with jax.named_scope("blockdiff_attn"):
            attended = blockdiff_attention(q, k, v, block_length=c.block_length, kernels=self.kernels)
        with jax.named_scope("attn_proj"):
            out = jnp.dot(attended.reshape(batch, s2, -1), p["wo"].astype(cd), preferred_element_type=jnp.float32)
            return (x.astype(jnp.float32) + out).astype(cd)[0]

    def _expert_block(self, p: dict, h: jax.Array):
        """``y = h + MoE(RMSNorm(h))`` on one sequence's ``[2L, H]``, with the
        counters of ``held_expert_layer``."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        with jax.named_scope("router"):
            n32 = rms_norm(h, p["moe_norm"], c.rms_norm_eps)
        part, expert_rows, held_pairs = held_expert_layer(
            n32, p["router"], p["w_gate"], p["w_up"], p["w_down"],
            first_expert=c.first_expert, top_k=c.num_experts_per_tok, norm_topk=c.norm_topk_prob,
            compute_dtype=cd, kernels=self.kernels,
        )
        with jax.named_scope("moe_combine"):
            y = (h.astype(jnp.float32) + part.astype(jnp.float32)).astype(cd)
        return y, expert_rows, held_pairs

    def _layer(self, p: dict, x: jax.Array, cos: jax.Array, sin: jax.Array):
        """One decoder layer on ``[B, 2L, H]``, a sequence at a time, its
        attention block and its expert block rematerialised apart: the
        grouped product's rows (room for every pair: 8 a position) and the
        float32 queries are the largest arrays of a step, and one sequence's
        are all that is ever live."""
        attention_block = jax.checkpoint(self._attention_block, prevent_cse=False)
        expert_block = jax.checkpoint(self._expert_block, prevent_cse=False)
        ys, rows, pairs = zip(*(expert_block(p, attention_block(p, x[b], cos, sin)) for b in range(x.shape[0])))
        return jnp.stack(ys), sum(rows), sum(pairs)

    def hidden(self, params: dict, ids: jax.Array, masked: jax.Array):
        """The residual stream after the last layer, ``[B, 2L, H]``, with the
        counters ``expert_rows`` ``[layers, experts_held]`` and ``held_pairs``."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        if ids.shape[-1] != c.seq_len:
            raise ValueError(f"sequences of {ids.shape[-1]} tokens, the configuration's are {c.seq_len}")
        with jax.named_scope("embed"):
            noisy = jnp.where(masked, jnp.int32(c.mask_token), ids)
            tokens = jnp.concatenate([noisy, ids], axis=1)
            x = jnp.take(params["embed"], tokens, axis=0).astype(cd)
            cos, sin = rotary_tables(c.seq_len, c.head_dim, c.rope_theta)
        rows, pairs = [], []
        for i in range(c.num_hidden_layers):
            with jax.named_scope(f"layer{i}"):
                x, expert_rows, held_pairs = self._layer(params[f"layer{i}"], x, cos, sin)
            rows.append(expert_rows)
            pairs.append(held_pairs)
        return x, jnp.stack(rows), jnp.sum(jnp.stack(pairs))

    def logits(self, params: dict, ids: jax.Array, masked: jax.Array) -> jax.Array:
        """Float32 logits of the noisy half, ``[B, L, vocab_held]``, whole:
        for tests at small sizes."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        x, _, _ = self.hidden(params, ids, masked)
        n = rms_norm(x[:, : c.seq_len], params["final_norm"], c.rms_norm_eps).astype(cd)
        return jnp.dot(n, params["lm_head"].astype(cd), preferred_element_type=jnp.float32)

    def apply(self, params: dict, ids: jax.Array, masked: jax.Array) -> dict:
        """``nll`` and ``hit`` ``[B, L]`` (each noisy position's cross-entropy
        against the clean token, and whether its largest logit is that
        token), ``expert_rows`` ``[layers, experts_held]``, ``held_pairs``."""
        c = self.config
        x, expert_rows, held_pairs = self.hidden(params, ids, masked)
        with jax.named_scope("lm_head"):
            n32 = rms_norm(x[:, : c.seq_len], params["final_norm"], c.rms_norm_eps)
            nll, hit = _token_losses(
                n32.reshape(-1, c.hidden_size), params["lm_head"], ids.reshape(-1), jnp.dtype(c.compute_dtype)
            )
        return {
            "nll": nll.reshape(ids.shape), "hit": hit.reshape(ids.shape),
            "expert_rows": expert_rows, "held_pairs": held_pairs,
        }
