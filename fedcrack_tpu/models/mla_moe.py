"""One chip's share of a latent-attention mixture-of-experts causal language
model.

The ``joyai_llm_flash`` family (jdopensource/JoyAI-LLM-Flash, 48B-A2.7B; its
``config.json`` is DeepSeek-V3's shape key for key, and the equations here are
that family's: arXiv:2412.19437 sections 2.1-2.2). Per layer, on the residual
stream ``x``, ``n = RMSNorm(x)``::

    c_q = RMSNorm(W_qa n) (1536);  [q_nope | q_rope] = W_qb c_q  (32 heads x 128 | 64)
    [c_kv | k_rope] = W_kva n (512 | 64; k_rope ONE head, shared by all 32)
    c_kv = RMSNorm(c_kv);  [k_nope | v] = W_kvb c_kv  (32 heads x 128 | 128)
    q_rope, k_rope <- rotary embedding over adjacent pairs (rope_interleave)
    q = [q_nope | q_rope], k = [k_nope | k_rope]  (192 wide; v 128 wide)
    h = x + W_o . softmax(q k^T / sqrt(192), under the causal mask) v
    n = RMSNorm(h)
    layer 0:     y = h + W_down (silu(W_gate n) * W_up n)            (width 7168)
    layers 1..:  s = sigmoid(W_r n) over all 256 experts, in float32
                 T = the 8 largest of s + b   (b: selection only, no gradient)
                 w_e = 2.5 s_e / sum_{e' in T} s_e'
                 y = h + sum_{e in T, e held here} w_e E_e(n) + E_shared(n)

every ``E`` a SwiGLU of width 768. **Multi-token prediction**, depth 1: with
``h_i`` the last layer's output before the final norm,
``h'_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]`` goes through one
more layer of the sparse kind with its own weights and its own final norm,
then the model's own head, and predicts ``t_{i+2}``.

**The share** is ``moe_layers.held_expert_layer``'s, the layer the
block-diffusion family (``models/sdar_moe.py``) runs, handed this family's
router (``sigmoid_route``); the shared expert is computed here, once, beside
it. The embedding and the head hold ``vocab_held`` rows.

A sequence is one document of ``L`` tokens: position ``i`` is scored against
``t_{i+1}`` (and ``t_{i+2}`` by the module); the last position (the last two)
has no target and weighs nothing. Logits exist a chunk of positions at a
time. Float32 parameters, bf16 matrix products with float32 accumulation;
norms, softmaxes, the router and the loss in float32. Every layer's attention
block and feed-forward block is rematerialised in the backward pass; of the
attention block the forward kernel's output and logsumexp are kept
(``ATTN_RESIDUALS``: 68 MB a block at the published widths), which is all
the kernel's backward needs from its forward, so the projections are computed
again and the kernel is not.

Kernels: JAX's splash-attention Pallas kernel under a ``CausalMask``, queries
and keys 192 wide beside values 128 wide, ``k_rope`` broadcast to the heads
before it; off the chip a masked dense softmax (``moe_layers``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from fedcrack_tpu.configs import MlaMoeConfig
from fedcrack_tpu.models.moe_layers import (
    ATTN_TILE,
    EXPERT_COUNTERS,
    causal_splash_mask,
    held_expert_layer,
    layer_totals,
    resolve_kernels,
    rms_norm,
    rotary_tables,
    splash_kernel,
    swiglu,
    token_losses,
)

# The name the splash kernel gives its output and logsumexp
# (``checkpoint_name``), and the one thing ``_layer``'s rematerialisation of
# the attention block keeps.
ATTN_RESIDUALS = "mla_attn_residuals"

# Standard deviation of the selection bias's draw: large enough that the
# selection differs from the weights' order, small beside a sigmoid's 0.5.
ROUTER_BIAS_STD = 0.01


def apply_rotary_pairs(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """``x`` ``[S, heads, d]`` float32: lanes ``(2i, 2i+1)`` rotate together by
    the position's ``i``-th angle (``rope_interleave``)."""
    pairs = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([a * c - b * s, b * c + a * s], axis=-1).reshape(x.shape)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *, kernels: str | None = None) -> jax.Array:
    """Softmax attention under the causal mask, head-major: ``q`` (already
    scaled) and ``k`` ``[heads, S, d_qk]``, ``v`` ``[heads, S, d_v]``;
    returns ``[heads, S, d_v]`` in ``q``'s dtype. The kernel names its output
    and logsumexp ``ATTN_RESIDUALS``; the dense path names nothing (its
    backward reads the probabilities, not the output), so under ``_layer``'s
    policy it stays a plain rematerialisation."""
    heads, seq_len, _ = q.shape
    mode = resolve_kernels(kernels)
    tile = min(ATTN_TILE, seq_len)
    if mode != "xla" and seq_len % tile == 0 and tile % 128 == 0:
        kernel = splash_kernel(
            causal_splash_mask, (seq_len,), heads, False, tile, mode == "interpret", ATTN_RESIDUALS
        )
        return kernel(q, k, v).astype(q.dtype)
    scores = jnp.einsum("hqd,hkd->hqk", q, k, preferred_element_type=jnp.float32)
    allowed = jnp.asarray(np.tril(np.ones((seq_len, seq_len), bool)))
    probs = jax.nn.softmax(jnp.where(allowed[None], scores, -jnp.inf), axis=-1).astype(v.dtype)
    return jnp.einsum("hqk,hkd->hqd", probs, v, preferred_element_type=jnp.float32).astype(q.dtype)


def sigmoid_route(
    n32: jax.Array, router: jax.Array, *, bias: jax.Array, top_k: int, norm_topk: bool, scale: float,
    eps: float = 1e-20,
):
    """``s = sigmoid(W_r n)`` over all the router's experts in float32; the
    ``top_k`` largest of ``s + bias`` and their weights ``s`` (without the
    bias; divided by their sum plus ``eps`` where ``norm_topk``, the family's
    own epsilon; times ``scale``). ``[T, top_k]`` each. One group
    (``n_group`` 1), so no group is limited."""
    logits = jnp.dot(n32, router.astype(jnp.float32), precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, top_e = lax.top_k(scores + lax.stop_gradient(bias.astype(jnp.float32)), top_k)
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    if norm_topk:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + eps)
    return top_e, top_w * scale


@dataclasses.dataclass(frozen=True)
class MlaMoe:
    """The model as pure functions of a parameter tree (nested dicts):
    ``embed`` ``[vocab_held, H]``; ``layer<i>``: ``attn_norm``, ``wq_a``,
    ``q_a_norm``, ``wq_b``, ``wkv_a``, ``kv_a_norm``, ``wkv_b``, ``wo``,
    ``mlp_norm`` and either the dense ``w_gate``/``w_up`` ``[H, I]``,
    ``w_down`` ``[I, H]`` or ``router`` ``[H, n_routed_experts]``,
    ``router_bias`` ``[n_routed_experts]``, ``w_gate``/``w_up``
    ``[experts_held, H, width]``, ``w_down`` ``[experts_held, width, H]``,
    ``shared_gate``/``shared_up`` ``[H, shared width]``, ``shared_down``;
    ``final_norm``, ``lm_head`` ``[H, vocab_held]`` (untied); ``mtp``: a
    sparse layer's entries with ``enorm``, ``hnorm``, ``eh_proj``
    ``[2H, H]`` and its own ``final_norm``."""

    config: MlaMoeConfig = dataclasses.field(default_factory=MlaMoeConfig)
    kernels: str | None = None

    # What ``tasks.CausalLMTask`` reads off its model: the kinds of block,
    # summed over the layers (and the module) that hold them; that ``apply``
    # returns ``nll_mtp`` (zeros without a module); the statistics beside the
    # causal models' common ones, with how they reduce: the held-expert
    # layer's.
    block_scope = (
        r"^(embed|mla_proj|mla_attn|dense_mlp|router|moe_dispatch|moe_experts|moe_combine|shared_expert"
        r"|mtp_merge|lm_head)$"
    )
    has_mtp_loss = True
    counters = EXPERT_COUNTERS

    # ---- weights -------------------------------------------------------------

    def layer_shapes(self, sparse: bool) -> tuple[dict, dict]:
        """(matrices, norm scales) of one layer, by name."""
        c = self.config
        h, heads = c.hidden_size, c.num_attention_heads
        matrices = {
            "wq_a": (h, c.q_lora_rank), "wq_b": (c.q_lora_rank, heads * c.qk_head_dim),
            "wkv_a": (h, c.kv_lora_rank + c.qk_rope_head_dim),
            "wkv_b": (c.kv_lora_rank, heads * (c.qk_nope_head_dim + c.v_head_dim)),
            "wo": (heads * c.v_head_dim, h),
        }
        if sparse:
            width, shared = c.moe_intermediate_size, c.moe_intermediate_size * c.n_shared_experts
            matrices.update({
                "router": (h, c.n_routed_experts),
                "w_gate": (c.experts_held, h, width), "w_up": (c.experts_held, h, width),
                "w_down": (c.experts_held, width, h),
                "shared_gate": (h, shared), "shared_up": (h, shared), "shared_down": (shared, h),
            })
        else:
            matrices.update({
                "w_gate": (h, c.intermediate_size), "w_up": (h, c.intermediate_size),
                "w_down": (c.intermediate_size, h),
            })
        norms = {"attn_norm": h, "q_a_norm": c.q_lora_rank, "kv_a_norm": c.kv_lora_rank, "mlp_norm": h}
        return matrices, norms

    def init(self, rng: jax.Array) -> dict:
        c = self.config
        dtype = jnp.dtype(c.param_dtype)

        def normal(key, shape, std=0.02):
            return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

        def layer(key, sparse, extra=None):
            matrices, norms = self.layer_shapes(sparse)
            matrices = dict(matrices, **(extra or {}))
            sub = jax.random.split(key, len(matrices) + 1)
            out = {name: normal(k, shape) for k, (name, shape) in zip(sub, sorted(matrices.items()))}
            out.update({name: jnp.ones((width,), dtype) for name, width in norms.items()})
            if sparse:
                out["router_bias"] = normal(sub[-1], (c.n_routed_experts,), ROUTER_BIAS_STD)
            return out

        keys = jax.random.split(rng, c.num_hidden_layers + 3)
        params = {
            "embed": normal(keys[0], (c.vocab_held, c.hidden_size)),
            "final_norm": jnp.ones((c.hidden_size,), dtype),
            "lm_head": normal(keys[1], (c.hidden_size, c.vocab_held)),
        }
        for i in range(c.num_hidden_layers):
            params[f"layer{i}"] = layer(keys[3 + i], i >= c.first_k_dense_replace)
        if c.num_nextn_predict_layers:
            mtp = layer(keys[2], True, {"eh_proj": (2 * c.hidden_size, c.hidden_size)})
            mtp.update({name: jnp.ones((c.hidden_size,), dtype) for name in ("enorm", "hnorm", "final_norm")})
            params["mtp"] = mtp
        return params

    # ---- blocks, each on one sequence's [L, H] -------------------------------

    def _attention_block(self, p: dict, x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
        """``h = x + W_o . Attn(...)``."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        heads, nope, rope = c.num_attention_heads, c.qk_nope_head_dim, c.qk_rope_head_dim
        seq_len = x.shape[0]
        with jax.named_scope("mla_proj"):
            n = rms_norm(x, p["attn_norm"], c.rms_norm_eps).astype(cd)
            c_q = jnp.dot(n, p["wq_a"].astype(cd), preferred_element_type=jnp.float32)
            c_q = rms_norm(c_q, p["q_a_norm"], c.rms_norm_eps).astype(cd)
            q = jnp.dot(c_q, p["wq_b"].astype(cd), preferred_element_type=jnp.float32)
            q = q.reshape(seq_len, heads, nope + rope)
            kv_a = jnp.dot(n, p["wkv_a"].astype(cd), preferred_element_type=jnp.float32)
            c_kv = rms_norm(kv_a[:, : c.kv_lora_rank], p["kv_a_norm"], c.rms_norm_eps).astype(cd)
            kv = jnp.dot(c_kv, p["wkv_b"].astype(cd), preferred_element_type=jnp.float32)
            kv = kv.reshape(seq_len, heads, nope + c.v_head_dim)
            q_rope = apply_rotary_pairs(q[..., nope:], cos, sin)
            k_rope = apply_rotary_pairs(kv_a[:, None, c.kv_lora_rank :], cos, sin)
            scale = (nope + rope) ** -0.5
            q = (jnp.concatenate([q[..., :nope], q_rope], axis=-1) * scale).astype(cd)
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (seq_len, heads, rope))], axis=-1).astype(cd)
            v = kv[..., nope:].astype(cd)
            # The kernels' head-major layout.
            q, k, v = (t.transpose(1, 0, 2) for t in (q, k, v))
        with jax.named_scope("mla_attn"):
            attended = causal_attention(q, k, v, kernels=self.kernels)
        with jax.named_scope("mla_proj"):
            attended = attended.transpose(1, 0, 2).reshape(seq_len, heads * c.v_head_dim)
            out = jnp.dot(attended, p["wo"].astype(cd), preferred_element_type=jnp.float32)
            return (x.astype(jnp.float32) + out).astype(cd)

    def _dense_block(self, p: dict, h: jax.Array) -> jax.Array:
        """``y = h + SwiGLU(RMSNorm(h))``, the leading layers' feed-forward."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        with jax.named_scope("dense_mlp"):
            n = rms_norm(h, p["mlp_norm"], c.rms_norm_eps).astype(cd)
            return (h.astype(jnp.float32) + swiglu(n, p["w_gate"], p["w_up"], p["w_down"], cd)).astype(cd)

    def _expert_block(self, p: dict, h: jax.Array):
        """``y = h + held part of MoE(RMSNorm(h)) + shared expert``, with the
        record of ``held_expert_layer``."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        with jax.named_scope("router"):
            n32 = rms_norm(h, p["mlp_norm"], c.rms_norm_eps)
        part, counters = held_expert_layer(
            n32, p["router"], p["w_gate"], p["w_up"], p["w_down"],
            first_expert=c.first_expert,
            route=functools.partial(
                sigmoid_route, bias=p["router_bias"], top_k=c.num_experts_per_tok,
                norm_topk=c.norm_topk_prob, scale=c.routed_scaling_factor,
            ),
            compute_dtype=cd, kernels=self.kernels,
        )
        with jax.named_scope("shared_expert"):
            shared = swiglu(n32.astype(cd), p["shared_gate"], p["shared_up"], p["shared_down"], cd)
        with jax.named_scope("moe_combine"):
            y = (h.astype(jnp.float32) + part.astype(jnp.float32) + shared).astype(cd)
        return y, counters

    def _layer(self, p: dict, x: jax.Array, cos: jax.Array, sin: jax.Array, sparse: bool):
        """One decoder layer on ``[B, L, H]``, a sequence at a time, its
        attention block and its feed-forward block rematerialised apart; the
        attention block keeps its kernel's output and logsumexp
        (``ATTN_RESIDUALS``), so the backward pass computes the projections
        again and runs the forward kernel once a step. Returns the record of
        ``held_expert_layer`` too, summed over the sequences (``None`` for a
        dense layer)."""
        attention_block = jax.checkpoint(
            self._attention_block, policy=jax.checkpoint_policies.save_only_these_names(ATTN_RESIDUALS)
        )
        if not sparse:
            dense_block = jax.checkpoint(self._dense_block)
            return jnp.stack([dense_block(p, attention_block(p, x[b], cos, sin)) for b in range(x.shape[0])]), None
        expert_block = jax.checkpoint(self._expert_block)
        ys, counted = zip(*(expert_block(p, attention_block(p, x[b], cos, sin)) for b in range(x.shape[0])))
        return jnp.stack(ys), jax.tree.map(lambda *calls: sum(calls), *counted)

    def _mtp_merge(self, p: dict, embed: jax.Array, h: jax.Array, next_ids: jax.Array) -> jax.Array:
        """``W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]`` on ``[B, L, H]``."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        with jax.named_scope("embed"):
            e = jnp.take(embed, next_ids, axis=0).astype(cd)
        with jax.named_scope("mtp_merge"):
            both = jnp.concatenate(
                [rms_norm(e, p["enorm"], c.rms_norm_eps), rms_norm(h, p["hnorm"], c.rms_norm_eps)], axis=-1
            ).astype(cd)
            return jnp.dot(both, p["eh_proj"].astype(cd), preferred_element_type=jnp.float32).astype(cd)

    # ---- the model -----------------------------------------------------------

    def hidden(self, params: dict, ids: jax.Array):
        """The residual stream after the last layer and after the
        multi-token-prediction module's layer (``None`` without one),
        ``[B, L, H]`` each, both before their final norm, with the record of
        the expert layers (``moe_layers.layer_totals``; the module's layer
        last)."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        if ids.shape[-1] != c.seq_len:
            raise ValueError(f"sequences of {ids.shape[-1]} tokens, the configuration's are {c.seq_len}")
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], ids, axis=0).astype(cd)
            cos, sin = rotary_tables(c.seq_len, c.qk_rope_head_dim, c.rope_theta)
        counted = []
        for i in range(c.num_hidden_layers):
            with jax.named_scope(f"layer{i}"):
                x, counters = self._layer(params[f"layer{i}"], x, cos, sin, sparse=i >= c.first_k_dense_replace)
            if counters is not None:
                counted.append(counters)
        x_mtp = None
        if c.num_nextn_predict_layers:
            with jax.named_scope("mtp"):
                p = params["mtp"]
                merged = jax.checkpoint(self._mtp_merge)(
                    p, params["embed"], x, jnp.roll(ids, -1, axis=-1)
                )
                x_mtp, counters = self._layer(p, merged, cos, sin, sparse=True)
            counted.append(counters)
        return x, x_mtp, layer_totals(counted, c.experts_held)

    def logits(self, params: dict, ids: jax.Array) -> tuple[jax.Array, jax.Array | None]:
        """Float32 logits ``[B, L, vocab_held]`` of the model and of the
        module (``None`` without one), whole: for tests at small sizes."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        x, x_mtp, *_ = self.hidden(params, ids)
        head = params["lm_head"].astype(cd)

        def through_head(h, norm):
            return jnp.dot(rms_norm(h, norm, c.rms_norm_eps).astype(cd), head, preferred_element_type=jnp.float32)

        main = through_head(x, params["final_norm"])
        return main, None if x_mtp is None else through_head(x_mtp, params["mtp"]["final_norm"])

    def apply(self, params: dict, ids: jax.Array) -> dict:
        """``nll_next`` and ``hit_next`` ``[B, L]`` (position ``i``'s
        cross-entropy against ``t_{i+1}`` and whether its largest logit is
        that token; the last position's wraps round and weighs nothing with
        the caller), ``nll_mtp`` ``[B, L]`` (the module's against
        ``t_{i+2}``; zeros without one) and the expert layers' record."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        x, x_mtp, counters = self.hidden(params, ids)

        def losses(h, norm, shift):
            n32 = rms_norm(h, norm, c.rms_norm_eps)
            targets = jnp.roll(ids, -shift, axis=-1)
            nll, hit = token_losses(n32.reshape(-1, c.hidden_size), params["lm_head"], targets.reshape(-1), cd)
            return nll.reshape(ids.shape), hit.reshape(ids.shape)

        with jax.named_scope("lm_head"):
            nll_next, hit_next = losses(x, params["final_norm"], 1)
        if x_mtp is None:
            nll_mtp = jnp.zeros_like(nll_next)
        else:
            with jax.named_scope("mtp"), jax.named_scope("lm_head"):
                nll_mtp, _ = losses(x_mtp, params["mtp"]["final_norm"], 2)
        return {"nll_next": nll_next, "hit_next": hit_next, "nll_mtp": nll_mtp, **counters}

    def step_flops(self, batch: int) -> float:
        """Matrix products of one step, 2 operations a multiply-add, forward
        times three; held experts at their expected ``top_k * experts_held /
        n_routed_experts`` pairs a position, causal scores only, the head
        once more for the module."""
        c = self.config
        positions = float(c.seq_len * batch)
        heads, h, width = c.num_attention_heads, c.hidden_size, c.moe_intermediate_size
        proj = 2.0 * positions * (
            h * c.q_lora_rank + c.q_lora_rank * heads * c.qk_head_dim + h * (c.kv_lora_rank + c.qk_rope_head_dim)
            + c.kv_lora_rank * heads * (c.qk_nope_head_dim + c.v_head_dim) + heads * c.v_head_dim * h
        )
        scores = 2.0 * batch * (c.seq_len * (c.seq_len + 1) / 2) * heads * (c.qk_head_dim + c.v_head_dim)
        dense = 2.0 * positions * 3 * h * c.intermediate_size
        pairs = positions * c.num_experts_per_tok * c.experts_held / c.n_routed_experts
        sparse = (
            2.0 * positions * h * c.n_routed_experts + 2.0 * pairs * 3 * h * width
            + 2.0 * positions * 3 * h * width * c.n_shared_experts
        )
        head = 2.0 * positions * h * c.vocab_held
        n_dense = c.first_k_dense_replace
        n_sparse = c.num_hidden_layers - n_dense + c.num_nextn_predict_layers
        merge = 2.0 * positions * 2 * h * h * c.num_nextn_predict_layers
        return 3.0 * (
            (n_dense + n_sparse) * (proj + scores) + n_dense * dense + n_sparse * sparse + merge
            + (1 + c.num_nextn_predict_layers) * head
        )
