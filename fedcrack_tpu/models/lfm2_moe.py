"""One chip's share of a hybrid causal language model of gated short
convolutions and attention, with sparse experts.

The ``lfm2_moe`` family (LiquidAI/LFM2-8B-A1B, 8.3B-A1.5B; the equations are
its released modelling code's, Hugging Face ``transformers``
``modeling_lfm2_moe.py``, as read from its ``config.json``). On the residual
stream ``x``, every ``Norm`` an RMSNorm with a plain weight (starting at 1),
eps 1e-5, ``n = Norm(x)``::

    layer:      h = x + Op(Norm_op(x));  y = h + FF(Norm_ff(h))
    conv:       [B | C | x~] = W_in n  (2048 | 2048 | 2048);  u = B * x~
                v_t = sum_{j=0..2} w_j * u_{t-2+j}   (depthwise, zero before the first token, no bias, no activation)
                Op = W_out (C * v)
    attention:  q = W_q n (32 x 64);  k = W_k n, v = W_v n (8 x 64)
                q, k <- Norm a head (64 lanes, a weight each), then rotary by halves over all 64 lanes, theta 1e6
                Op = W_o softmax(q k^T / sqrt(64), causal; 4 query heads a key/value head) v
    dense FF:   W_down (silu(W_gate n) * W_up n)   (width 7168; the first num_dense_layers layers)
    expert FF:  s = sigmoid(W_r n) over all 32 experts, in float32;  T = the 4 largest of s + b
                (b: the expert bias, selection only, no gradient);  w_e = s_e / (sum_T s + 1e-6) * 1
                sum_{e in T, e held here} w_e E_e(n)   (every E a SwiGLU of width 1792; no shared expert)

then a final norm and the head, which is the embedding's transpose (tied).
Which operator a layer has follows ``layer_types``; which feed-forward, its
depth. The gated convolution is neither attention nor a recurrence: two
elementwise gates around three taps along the sequence.

**The share** is ``moe_layers.held_expert_layer``'s, handed the JoyAI family's
sigmoid router (``mla_moe.sigmoid_route``) with this family's epsilon; the
embedding, and so the head, holds ``vocab_held`` rows.

A sequence is one document of ``L`` tokens: position ``i`` is scored against
``t_{i+1}``; the last position weighs nothing. Float32 parameters, bf16
matrix products with float32 accumulation; the gates and taps, norms, the
router and the loss in float32. The operator block of a layer runs the batch
whole (the attention kernel's two sequences in one instruction) and keeps,
across its rematerialisation, the attention kernel's output and logsumexp
(``gdn_moe.ATTN_RESIDUALS``); the feed-forward block runs a sequence at a
time, each rematerialised apart. Every call is its own instructions, once a
step. Kernels: on the chip JAX's splash-attention Pallas kernel under a
``CausalMask`` at 64 lanes a head (``gdn_moe.gated_causal_attention``) and
megablox ``gmm``; the convolution is ``gdn_moe.causal_conv`` in XLA (the
hybrid model's Pallas form applies a SiLU this family does not have). Off the
chip a masked dense softmax.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from fedcrack_tpu.configs import Lfm2MoeConfig
from fedcrack_tpu.models.gdn_moe import ATTN_RESIDUALS, apply_rotary_halves, causal_conv, gated_causal_attention
from fedcrack_tpu.models.mla_moe import ROUTER_BIAS_STD, sigmoid_route
from fedcrack_tpu.models.moe_layers import (
    EXPERT_COUNTERS,
    held_expert_layer,
    layer_totals,
    rms_norm,
    rotary_tables,
    swiglu,
    token_losses,
)

# What the family's router adds to the chosen scores' sum before dividing.
ROUTER_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Lfm2Moe:
    """The model as pure functions of a parameter tree (nested dicts):
    ``embed`` ``[vocab_held, H]`` (the head too); ``layer<i>``:
    ``operator_norm``, ``ffn_norm`` ``[H]`` and either a convolution operator
    (``in_proj`` ``[H, 3H]``, ``conv`` ``[H, taps]``, ``out_proj`` ``[H, H]``)
    or an attention one (``wq`` ``[H, heads x head_dim]``, ``wk``, ``wv``,
    ``q_norm``, ``k_norm`` ``[head_dim]``, ``wo``); then either the dense
    ``w_gate``/``w_up`` ``[H, I]``, ``w_down`` ``[I, H]`` or ``router`` ``[H,
    num_experts]``, ``expert_bias`` ``[num_experts]``, ``w_gate``/``w_up``
    ``[experts_held, H, width]``, ``w_down`` ``[experts_held, width, H]``;
    ``final_norm``."""

    config: Lfm2MoeConfig = dataclasses.field(default_factory=Lfm2MoeConfig)
    kernels: str | None = None

    # What ``tasks.CausalLMTask`` reads off its model: the kinds of block,
    # summed over the layers that hold them; the statistics ``apply`` returns
    # beside the causal models' common ones, with how they reduce: the
    # held-expert layer's.
    block_scope = (
        r"^(embed|lfm_conv_proj|lfm_conv|lfm_attn_proj|lfm_attn|dense_mlp|router|moe_dispatch|moe_experts"
        r"|moe_combine|lm_head)$"
    )
    has_mtp_loss = False
    counters = EXPERT_COUNTERS

    # ---- weights -------------------------------------------------------------

    def layer_shapes(self, layer: int) -> tuple[dict, dict]:
        """(matrices, norm scales) of layer ``layer``, by name."""
        c = self.config
        h, d = c.hidden_size, c.head_dim
        if c.is_conv(layer):
            matrices = {"in_proj": (h, 3 * h), "conv": (h, c.conv_L_cache), "out_proj": (h, h)}
            norms = {"operator_norm": h, "ffn_norm": h}
        else:
            q_out, kv_out = c.num_attention_heads * d, c.num_key_value_heads * d
            matrices = {"wq": (h, q_out), "wk": (h, kv_out), "wv": (h, kv_out), "wo": (q_out, h)}
            norms = {"operator_norm": h, "ffn_norm": h, "q_norm": d, "k_norm": d}
        if c.is_sparse(layer):
            width = c.moe_intermediate_size
            matrices.update({
                "router": (h, c.num_experts),
                "w_gate": (c.experts_held, h, width), "w_up": (c.experts_held, h, width),
                "w_down": (c.experts_held, width, h),
            })
        else:
            matrices.update({
                "w_gate": (h, c.intermediate_size), "w_up": (h, c.intermediate_size),
                "w_down": (c.intermediate_size, h),
            })
        return matrices, norms

    def init(self, rng: jax.Array) -> dict:
        c = self.config
        dtype = jnp.dtype(c.param_dtype)

        def normal(key, shape, std=0.02):
            return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

        def layer(key, i):
            matrices, norms = self.layer_shapes(i)
            sub = jax.random.split(key, len(matrices) + 1)
            out = {name: normal(k, shape) for k, (name, shape) in zip(sub, sorted(matrices.items()))}
            out.update({name: jnp.ones((width,), dtype) for name, width in norms.items()})
            if c.is_sparse(i):
                out["expert_bias"] = normal(sub[-1], (c.num_experts,), ROUTER_BIAS_STD)
            return out

        keys = jax.random.split(rng, c.num_hidden_layers + 1)
        params = {
            "embed": normal(keys[0], (c.vocab_held, c.hidden_size)),
            "final_norm": jnp.ones((c.hidden_size,), dtype),
        }
        for i in range(c.num_hidden_layers):
            params[f"layer{i}"] = layer(keys[1 + i], i)
        return params

    # ---- blocks --------------------------------------------------------------

    def _conv_block(self, p: dict, x: jax.Array) -> jax.Array:
        """``h = x + W_out (C * conv(B * x~))`` on the batch's ``[B, L, H]``."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        h = c.hidden_size
        with jax.named_scope("lfm_conv_proj"):
            n = rms_norm(x, p["operator_norm"], c.norm_eps).astype(cd)
            bcx = jnp.dot(n, p["in_proj"].astype(cd), preferred_element_type=jnp.float32).astype(cd)
        with jax.named_scope("lfm_conv"):
            u = bcx[..., :h].astype(jnp.float32) * bcx[..., 2 * h :].astype(jnp.float32)
            z = (bcx[..., h : 2 * h].astype(jnp.float32) * causal_conv(u, p["conv"].astype(jnp.float32))).astype(cd)
        with jax.named_scope("lfm_conv_proj"):
            out = jnp.dot(z, p["out_proj"].astype(cd), preferred_element_type=jnp.float32)
            return (x.astype(jnp.float32) + out).astype(cd)

    def _attention_block(self, p: dict, x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
        """``h = x + W_o Attn(...)`` on the batch's ``[B, L, H]``."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        heads, kv_heads, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        batch, seq_len, _ = x.shape
        with jax.named_scope("lfm_attn_proj"):
            n = rms_norm(x, p["operator_norm"], c.norm_eps).astype(cd)
            q = jnp.dot(n, p["wq"].astype(cd), preferred_element_type=jnp.float32).reshape(batch, seq_len, heads, d)
            k = jnp.dot(n, p["wk"].astype(cd), preferred_element_type=jnp.float32).reshape(batch, seq_len, kv_heads, d)
            v = jnp.dot(n, p["wv"].astype(cd), preferred_element_type=jnp.float32).reshape(batch, seq_len, kv_heads, d)
            q = apply_rotary_halves(rms_norm(q, p["q_norm"], c.norm_eps), cos, sin) * d**-0.5
            k = apply_rotary_halves(rms_norm(k, p["k_norm"], c.norm_eps), cos, sin)
            # The kernels' layout: [B, kv_heads, group, L, d] beside [B, kv_heads, L, d].
            q = q.astype(cd).reshape(batch, seq_len, kv_heads, heads // kv_heads, d).transpose(0, 2, 3, 1, 4)
            k, v = (t.astype(cd).transpose(0, 2, 1, 3) for t in (k, v))
        with jax.named_scope("lfm_attn"):
            attended = gated_causal_attention(q, k, v, kernels=self.kernels)
        with jax.named_scope("lfm_attn_proj"):
            attended = attended.transpose(0, 3, 1, 2, 4).reshape(batch, seq_len, heads * d)
            out = jnp.dot(attended, p["wo"].astype(cd), preferred_element_type=jnp.float32)
            return (x.astype(jnp.float32) + out).astype(cd)

    def _dense_block(self, p: dict, h: jax.Array) -> jax.Array:
        """``y = h + SwiGLU(Norm(h))`` on one sequence's ``[L, H]``."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        with jax.named_scope("dense_mlp"):
            n = rms_norm(h, p["ffn_norm"], c.norm_eps).astype(cd)
            return (h.astype(jnp.float32) + swiglu(n, p["w_gate"], p["w_up"], p["w_down"], cd)).astype(cd)

    def _expert_block(self, p: dict, h: jax.Array):
        """``y = h + held part of MoE(Norm(h))`` on one sequence's ``[L, H]``,
        with the record of ``held_expert_layer``."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        with jax.named_scope("router"):
            n32 = rms_norm(h, p["ffn_norm"], c.norm_eps)
        part, counters = held_expert_layer(
            n32, p["router"], p["w_gate"], p["w_up"], p["w_down"],
            first_expert=c.first_expert,
            route=functools.partial(
                sigmoid_route, bias=p["expert_bias"], top_k=c.num_experts_per_tok, norm_topk=c.norm_topk_prob,
                scale=c.routed_scaling_factor, eps=ROUTER_EPS,
            ),
            compute_dtype=cd, kernels=self.kernels,
        )
        with jax.named_scope("moe_combine"):
            y = (h.astype(jnp.float32) + part.astype(jnp.float32)).astype(cd)
        return y, counters

    def _layer(self, p: dict, x: jax.Array, cos: jax.Array, sin: jax.Array, layer: int):
        """Layer ``layer`` on ``[B, L, H]``: its operator block on the batch,
        then its feed-forward block a sequence at a time, each rematerialised
        apart; the attention block keeps its kernel's output and logsumexp
        (``ATTN_RESIDUALS``). Returns the record of ``held_expert_layer`` too,
        summed over the sequences (``None`` for a dense layer)."""
        c = self.config
        if c.is_conv(layer):
            h = jax.checkpoint(self._conv_block)(p, x)
        else:
            attention_block = jax.checkpoint(
                self._attention_block, policy=jax.checkpoint_policies.save_only_these_names(ATTN_RESIDUALS)
            )
            h = attention_block(p, x, cos, sin)
        if not c.is_sparse(layer):
            dense_block = jax.checkpoint(self._dense_block)
            return jnp.stack([dense_block(p, h[b]) for b in range(h.shape[0])]), None
        expert_block = jax.checkpoint(self._expert_block)
        ys, counted = zip(*(expert_block(p, h[b]) for b in range(h.shape[0])))
        return jnp.stack(ys), jax.tree.map(lambda *calls: sum(calls), *counted)

    # ---- the model -----------------------------------------------------------

    def hidden(self, params: dict, ids: jax.Array):
        """The residual stream after the last layer, ``[B, L, H]`` before the
        final norm, with the record of the expert layers
        (``moe_layers.layer_totals``)."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        if ids.shape[-1] != c.seq_len:
            raise ValueError(f"sequences of {ids.shape[-1]} tokens, the configuration's are {c.seq_len}")
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], ids, axis=0).astype(cd)
            cos, sin = rotary_tables(c.seq_len, c.head_dim, c.rope_theta)
        counted = []
        for i in range(c.num_hidden_layers):
            with jax.named_scope(f"layer{i}"):
                x, counters = self._layer(params[f"layer{i}"], x, cos, sin, i)
            if counters is not None:
                counted.append(counters)
        return x, layer_totals(counted, c.experts_held)

    def logits(self, params: dict, ids: jax.Array) -> jax.Array:
        """Float32 logits ``[B, L, vocab_held]``, whole: for tests at small sizes."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        x, *_ = self.hidden(params, ids)
        n = rms_norm(x, params["final_norm"], c.norm_eps).astype(cd)
        return jnp.dot(n, params["embed"].astype(cd).T, preferred_element_type=jnp.float32)

    def apply(self, params: dict, ids: jax.Array) -> dict:
        """``nll_next`` and ``hit_next`` ``[B, L]`` (position ``i``'s
        cross-entropy against ``t_{i+1}`` and whether its largest logit is
        that token; the last position's wraps round and weighs nothing with
        the caller) and the expert layers' record."""
        c = self.config
        x, counters = self.hidden(params, ids)
        with jax.named_scope("lm_head"):
            n32 = rms_norm(x, params["final_norm"], c.norm_eps)
            nll, hit = token_losses(
                n32.reshape(-1, c.hidden_size), params["embed"].T, jnp.roll(ids, -1, axis=-1).reshape(-1),
                jnp.dtype(c.compute_dtype),
            )
        return {"nll_next": nll.reshape(ids.shape), "hit_next": hit.reshape(ids.shape), **counters}

    def step_flops(self, batch: int) -> float:
        """Operations one training step needs, 2 a multiply-add, forward
        times three: every product once, the convolution's taps and its two
        gates (an operation each), causal scores only, held experts at their
        expected ``top_k * experts_held / num_experts`` pairs a position, the
        head once."""
        c = self.config
        positions = float(c.seq_len * batch)
        h, d = c.hidden_size, c.head_dim
        conv = 2.0 * positions * (h * 3 * h + h * h + (c.conv_L_cache + 1) * h)
        q_out, kv_out = c.num_attention_heads * d, c.num_key_value_heads * d
        attention = 2.0 * positions * h * (2 * q_out + 2 * kv_out) + 2.0 * batch * (
            c.seq_len * (c.seq_len + 1) / 2
        ) * c.num_attention_heads * 2 * d
        dense = 2.0 * positions * 3 * h * c.intermediate_size
        pairs = positions * c.num_experts_per_tok * c.experts_held / c.num_experts
        sparse = 2.0 * positions * h * c.num_experts + 2.0 * pairs * 3 * h * c.moe_intermediate_size
        head = 2.0 * positions * h * c.vocab_held
        convs = sum(c.is_conv(i) for i in range(c.num_hidden_layers))
        return 3.0 * (
            convs * conv + (c.num_hidden_layers - convs) * attention
            + c.num_dense_layers * dense + c.sparse_layers * sparse + head
        )
