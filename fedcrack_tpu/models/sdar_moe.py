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
(``first_expert``, ``experts_held``): ``moe_layers.held_expert_layer``, which
the other two families run too, handed this family's router
(``moe_layers.softmax_route``: softmax over all the router's outputs, the
chosen renormalised). What absent experts would add is left out, and that
partial result goes on to the next layer. The embedding and the head hold
``vocab_held`` rows; ids, logits and loss are over those.

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
block-diffusion mask (``moe_layers.splash_kernel``) on a TPU at sizes its
tiles divide, elsewhere a masked dense softmax; ``kernels`` steers that for
tests (``moe_layers``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from fedcrack_tpu.configs import SdarMoeConfig
from fedcrack_tpu.models.moe_layers import (
    ATTN_TILE,
    EXPERT_COUNTERS,
    held_expert_layer,
    layer_totals,
    resolve_kernels,
    rms_norm,
    softmax_route,
    splash_kernel,
    token_losses,
)


def block_diffusion_mask(seq_len: int, block_length: int) -> np.ndarray:
    """``[2L, 2L]`` bool: may query ``i`` (rows) attend key ``j`` (columns).
    Rows and columns ``0..L-1`` are the noisy copy, ``L..2L-1`` the clean."""
    b = np.arange(seq_len) // block_length
    same = b[:, None] == b[None, :]
    earlier = b[None, :] < b[:, None]
    noisy_rows = np.concatenate([same, earlier], axis=1)
    clean_rows = np.concatenate([np.zeros_like(same), same | earlier], axis=1)
    return np.concatenate([noisy_rows, clean_rows], axis=0)


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


def _blockdiff_splash_mask(seq_len: int, block_length: int):
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as sm

    return sm.NumpyMask(block_diffusion_mask(seq_len, block_length))


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
    mode = resolve_kernels(kernels)
    tile = min(ATTN_TILE, s2)
    if mode != "xla" and s2 % tile == 0 and tile % 128 == 0:
        # One key/value head and its ``group`` query heads a kernel call.
        kernel = splash_kernel(_blockdiff_splash_mask, (seq_len, block_length), group, True, tile, mode == "interpret")
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

    # What ``tasks.TextDiffusionTask`` reports beside the loss and the masked
    # tokens, with how they reduce: the held-expert layer's statistics.
    counters = EXPERT_COUNTERS

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
        record of ``held_expert_layer``."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        with jax.named_scope("router"):
            n32 = rms_norm(h, p["moe_norm"], c.rms_norm_eps)
        part, counters = held_expert_layer(
            n32, p["router"], p["w_gate"], p["w_up"], p["w_down"],
            first_expert=c.first_expert,
            route=functools.partial(softmax_route, top_k=c.num_experts_per_tok, norm_topk=c.norm_topk_prob),
            compute_dtype=cd, kernels=self.kernels,
        )
        with jax.named_scope("moe_combine"):
            y = (h.astype(jnp.float32) + part.astype(jnp.float32)).astype(cd)
        return y, counters

    def _layer(self, p: dict, x: jax.Array, cos: jax.Array, sin: jax.Array):
        """One decoder layer on ``[B, 2L, H]``, a sequence at a time, its
        attention block and its expert block rematerialised apart: the
        grouped product's rows and the float32 queries are the largest arrays
        of a step, and one sequence's are all that is ever live. Nothing of the
        attention block is kept (the causal family keeps its kernel's output
        and logsumexp, ``mla_moe.MlaMoe._layer``): this program fills its chip
        to 1.1 GB as it is, so its forward kernel runs twice a step. Returns
        the record of ``held_expert_layer`` too, summed over the sequences."""
        attention_block = jax.checkpoint(self._attention_block, prevent_cse=False)
        expert_block = jax.checkpoint(self._expert_block, prevent_cse=False)
        ys, counted = zip(*(expert_block(p, attention_block(p, x[b], cos, sin)) for b in range(x.shape[0])))
        return jnp.stack(ys), jax.tree.map(lambda *calls: sum(calls), *counted)

    def hidden(self, params: dict, ids: jax.Array, masked: jax.Array):
        """The residual stream after the last layer, ``[B, 2L, H]``, with the
        record of the expert layers (``moe_layers.layer_totals``)."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        if ids.shape[-1] != c.seq_len:
            raise ValueError(f"sequences of {ids.shape[-1]} tokens, the configuration's are {c.seq_len}")
        with jax.named_scope("embed"):
            noisy = jnp.where(masked, jnp.int32(c.mask_token), ids)
            tokens = jnp.concatenate([noisy, ids], axis=1)
            x = jnp.take(params["embed"], tokens, axis=0).astype(cd)
            cos, sin = rotary_tables(c.seq_len, c.head_dim, c.rope_theta)
        counted = []
        for i in range(c.num_hidden_layers):
            with jax.named_scope(f"layer{i}"):
                x, counters = self._layer(params[f"layer{i}"], x, cos, sin)
            counted.append(counters)
        return x, layer_totals(counted, c.experts_held)

    def logits(self, params: dict, ids: jax.Array, masked: jax.Array) -> jax.Array:
        """Float32 logits of the noisy half, ``[B, L, vocab_held]``, whole:
        for tests at small sizes."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        x, *_ = self.hidden(params, ids, masked)
        n = rms_norm(x[:, : c.seq_len], params["final_norm"], c.rms_norm_eps).astype(cd)
        return jnp.dot(n, params["lm_head"].astype(cd), preferred_element_type=jnp.float32)

    def apply(self, params: dict, ids: jax.Array, masked: jax.Array) -> dict:
        """``nll`` and ``hit`` ``[B, L]`` (each noisy position's cross-entropy
        against the clean token, and whether its largest logit is that
        token) and the expert layers' record."""
        c = self.config
        x, counters = self.hidden(params, ids, masked)
        with jax.named_scope("lm_head"):
            n32 = rms_norm(x[:, : c.seq_len], params["final_norm"], c.rms_norm_eps)
            nll, hit = token_losses(
                n32.reshape(-1, c.hidden_size), params["lm_head"], ids.reshape(-1), jnp.dtype(c.compute_dtype)
            )
        return {"nll": nll.reshape(ids.shape), "hit": hit.reshape(ids.shape), **counters}
