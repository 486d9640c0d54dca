"""One chip's share of a looped causal language model: a stack of decoder
layers that runs several times a token with the same weights.

The ``ouro`` family (ByteDance/Ouro-2.6B, a LoopLM: arXiv:2510.25741 "Scaling
Latent Reasoning via Looped Language Models", and its released modelling
code). On the residual stream ``x``, every ``Norm`` an RMSNorm with a plain
weight (starting at 1), eps 1e-6::

    x = E[ids]
    for t = 1..T  (T = total_ut_steps; every pass reads the SAME layers):
        every layer:  x = x + Norm_a2(W_o Attn(Norm_a1(x)))
                      x = x + Norm_m2(W_down (silu(W_gate n) * W_up n)),  n = Norm_m1(x)
        h_t = Norm_f(x);  x = h_t                      (the final norm's output is carried)
        lambda_t = sigmoid(w_g . h_t + b_g);  CE_t = CE(h_t W_head, t_{i+1})
    p_t = lambda_t prod_{j<t} (1 - lambda_j)  (t < T);  p_T = prod_{j<T} (1 - lambda_j)
    objective_i = sum_t p_t CE_t - beta H(p),  H(p) = -sum_t p_t log p_t

``Attn`` is causal multi-head attention, 16 query and 16 key/value heads of
128, rotary by halves over the whole head (lane ``i`` with ``i + 64``), theta
1e6, no biases and no QK-norm. The head is untied. Training runs every pass:
the family's ``early_exit_threshold`` is a setting of inference.

**The share** is one pipeline stage's layers (four of the published 48) with
the embedding, the final norm, the exit gate and the head whole.

Every layer application is its own instructions, once a step, under the scope
``loop<t>/layer<i>``: the passes and the layers are unrolled in Python, as the
other models unroll their layers. A layer's weights are read by ``T``
applications, so their gradient is the sum of ``T`` parts, which autodiff adds
up. Each application's attention block and feed-forward block is
rematerialised apart in the backward pass; of the attention block the splash
kernel's output and logsumexp are kept (``mla_moe.ATTN_RESIDUALS``), so the
backward kernels read them and the forward kernel runs once a step. Each exit
is the final norm, the gate in float32 and ``moe_layers.token_losses``, the
head a chunk of positions at a time.

A sequence is one document of ``L`` tokens; position ``i`` is scored against
``t_{i+1}`` and the last position weighs nothing. Float32 parameters; norms,
the gate, the exit distribution and the loss in float32. Kernels
(``resolve_kernels``): on the chip JAX's splash-attention Pallas kernel under a
``CausalMask`` (``mla_moe.causal_attention``), off the chip a masked dense
softmax.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from fedcrack_tpu.configs import LoopedLmConfig
from fedcrack_tpu.models.gdn_moe import apply_rotary_halves
from fedcrack_tpu.models.mla_moe import ATTN_RESIDUALS, causal_attention
from fedcrack_tpu.models.moe_layers import rms_norm, rotary_tables, swiglu, token_losses


def exit_log_distribution(gate_logits: jax.Array) -> jax.Array:
    """``log p_t`` ``[T, ...]`` from the gate's logits ``[T, ...]`` (float32):
    ``log lambda_t + sum_{j<t} log(1 - lambda_j)`` for ``t < T``, and the last
    exit takes what no earlier one took; each term a ``log_sigmoid``. The last
    gate's own logit weighs nothing."""
    zeros = jnp.zeros_like(gate_logits[:1])
    survive = jnp.concatenate([zeros, jnp.cumsum(jax.nn.log_sigmoid(-gate_logits[:-1]), axis=0)], axis=0)
    return survive + jnp.concatenate([jax.nn.log_sigmoid(gate_logits[:-1]), zeros], axis=0)


@dataclasses.dataclass(frozen=True)
class LoopedLm:
    """The model as pure functions of a parameter tree (nested dicts):
    ``embed`` ``[vocab_size, H]``; ``layer<i>``: ``attn_norm``,
    ``attn_out_norm``, ``mlp_norm``, ``mlp_out_norm`` ``[H]``, ``wq``, ``wk``,
    ``wv`` ``[H, heads x head_dim]``, ``wo``, ``w_gate``/``w_up`` ``[H, I]``,
    ``w_down`` ``[I, H]``; ``final_norm``, ``exit_gate`` ``[H]``,
    ``exit_gate_bias`` ``[]``, ``lm_head`` ``[H, vocab_size]`` (untied)."""

    config: LoopedLmConfig = dataclasses.field(default_factory=LoopedLmConfig)
    kernels: str | None = None

    # What ``tasks.CausalLMTask`` reads off its model: the kinds of block,
    # summed over the passes and layers that hold them; the statistics it
    # reports beside the causal models' common ones, with how they reduce over
    # steps (each a weighted mean over the positions that have a target, from
    # ``apply``'s ``per_position``).
    block_scope = r"^(embed|loop_attn_proj|loop_attn|loop_mlp|loop_exit)$"
    has_mtp_loss = False
    counters = (("exit_mass", "mean"), ("loop_nll", "mean"), ("exit_entropy", "mean"))

    # ---- weights -------------------------------------------------------------

    def layer_shapes(self) -> tuple[dict, dict]:
        """(matrices, norm scales) of one layer, by name."""
        c = self.config
        h, width = c.hidden_size, c.intermediate_size
        q_out, kv_out = c.num_attention_heads * c.head_dim, c.num_key_value_heads * c.head_dim
        matrices = {
            "wq": (h, q_out), "wk": (h, kv_out), "wv": (h, kv_out), "wo": (q_out, h),
            "w_gate": (h, width), "w_up": (h, width), "w_down": (width, h),
        }
        norms = {"attn_norm": h, "attn_out_norm": h, "mlp_norm": h, "mlp_out_norm": h}
        return matrices, norms

    def init(self, rng: jax.Array) -> dict:
        c = self.config
        dtype = jnp.dtype(c.param_dtype)

        def normal(key, shape):
            return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

        def layer(key):
            matrices, norms = self.layer_shapes()
            sub = jax.random.split(key, len(matrices))
            out = {name: normal(k, shape) for k, (name, shape) in zip(sub, sorted(matrices.items()))}
            out.update({name: jnp.ones((width,), dtype) for name, width in norms.items()})
            return out

        keys = jax.random.split(rng, c.num_hidden_layers + 3)
        params = {
            "embed": normal(keys[0], (c.vocab_size, c.hidden_size)),
            "final_norm": jnp.ones((c.hidden_size,), dtype),
            "exit_gate": normal(keys[2], (c.hidden_size,)),
            "exit_gate_bias": jnp.zeros((), dtype),
            "lm_head": normal(keys[1], (c.hidden_size, c.vocab_size)),
        }
        for i in range(c.num_hidden_layers):
            params[f"layer{i}"] = layer(keys[3 + i])
        return params

    # ---- blocks, each on one sequence's [L, H] -------------------------------

    def _attention_block(self, p: dict, x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
        """``x + Norm_a2(W_o Attn(Norm_a1(x)))``."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        heads, kv_heads, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        seq_len = x.shape[0]
        with jax.named_scope("loop_attn_proj"):
            n = rms_norm(x, p["attn_norm"], c.rms_norm_eps).astype(cd)
            q = jnp.dot(n, p["wq"].astype(cd), preferred_element_type=jnp.float32).reshape(seq_len, heads, d)
            k = jnp.dot(n, p["wk"].astype(cd), preferred_element_type=jnp.float32).reshape(seq_len, kv_heads, d)
            v = jnp.dot(n, p["wv"].astype(cd), preferred_element_type=jnp.float32).reshape(seq_len, kv_heads, d)
            q = apply_rotary_halves(q, cos, sin) * d**-0.5
            k = apply_rotary_halves(k, cos, sin)
            # The kernel's head-major layout.
            q, k, v = (t.astype(cd).transpose(1, 0, 2) for t in (q, k, v))
        with jax.named_scope("loop_attn"):
            attended = causal_attention(q, k, v, kernels=self.kernels)
        with jax.named_scope("loop_attn_proj"):
            attended = attended.transpose(1, 0, 2).reshape(seq_len, heads * d)
            out = jnp.dot(attended, p["wo"].astype(cd), preferred_element_type=jnp.float32)
            return (x.astype(jnp.float32) + rms_norm(out, p["attn_out_norm"], c.rms_norm_eps)).astype(cd)

    def _mlp_block(self, p: dict, h: jax.Array) -> jax.Array:
        """``h + Norm_m2(SwiGLU(Norm_m1(h)))``."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        with jax.named_scope("loop_mlp"):
            n = rms_norm(h, p["mlp_norm"], c.rms_norm_eps).astype(cd)
            out = swiglu(n, p["w_gate"], p["w_up"], p["w_down"], cd)
            return (h.astype(jnp.float32) + rms_norm(out, p["mlp_out_norm"], c.rms_norm_eps)).astype(cd)

    def _layer(self, p: dict, x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
        """One application of a layer on ``[B, L, H]``, a sequence at a time,
        its attention block (keeping the kernel's output and logsumexp) and
        its feed-forward block rematerialised apart."""
        attention_block = jax.checkpoint(
            self._attention_block, policy=jax.checkpoint_policies.save_only_these_names(ATTN_RESIDUALS)
        )
        mlp_block = jax.checkpoint(self._mlp_block)
        return jnp.stack([mlp_block(p, attention_block(p, x[b], cos, sin)) for b in range(x.shape[0])])

    def _exit(self, params: dict, x: jax.Array, targets: jax.Array):
        """The exit after a pass on ``[B, L, H]``: ``h_t`` (what the next pass
        starts from, in the compute dtype), the gate's logit ``w_g . h_t +
        b_g`` ``[B, L]`` in float32, and each position's cross-entropy
        against its target and whether its largest logit is that target."""
        c = self.config
        with jax.named_scope("loop_exit"):
            h32 = rms_norm(x, params["final_norm"], c.rms_norm_eps)
            gate = jnp.dot(h32, params["exit_gate"].astype(jnp.float32), precision=lax.Precision.HIGHEST)
            gate = gate + params["exit_gate_bias"].astype(jnp.float32)
            nll, hit = token_losses(
                h32.reshape(-1, c.hidden_size), params["lm_head"], targets.reshape(-1), jnp.dtype(c.compute_dtype)
            )
        return h32.astype(c.compute_dtype), gate, nll.reshape(targets.shape), hit.reshape(targets.shape)

    # ---- the model -----------------------------------------------------------

    def passes(self, params: dict, ids: jax.Array):
        """Every pass and its exit on ``ids`` ``[B, L]``: the gate's logits,
        the cross-entropies and the hits, each ``[T, B, L]``, and the states
        ``h_t`` the passes handed on, ``T`` of ``[B, L, H]``."""
        c = self.config
        if ids.shape[-1] != c.seq_len:
            raise ValueError(f"sequences of {ids.shape[-1]} tokens, the configuration's are {c.seq_len}")
        targets = jnp.roll(ids, -1, axis=-1)
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], ids, axis=0).astype(c.compute_dtype)
            cos, sin = rotary_tables(c.seq_len, c.head_dim, c.rope_theta)
        exits, handed_on = [], []
        for t in range(c.total_ut_steps):
            with jax.named_scope(f"loop{t}"):
                for i in range(c.num_hidden_layers):
                    with jax.named_scope(f"layer{i}"):
                        x = self._layer(params[f"layer{i}"], x, cos, sin)
                x, *out = self._exit(params, x, targets)
            exits.append(out)
            handed_on.append(x)
        gates, nll, hit = (jnp.stack(part) for part in zip(*exits))
        return gates, nll, hit, handed_on

    def apply(self, params: dict, ids: jax.Array) -> dict:
        """``nll_next`` and ``hit_next`` ``[B, L]`` (the last exit's, against
        ``t_{i+1}``; the last position's wraps round and weighs nothing with
        the caller), ``objective`` ``[B, L]`` (the exits' expected
        cross-entropy less ``beta`` times the exit distribution's entropy: what
        the loss weighs) and ``per_position``: ``exit_mass`` (``p_t``) and
        ``loop_nll`` (``CE_t``) ``[T, B, L]``, ``exit_entropy`` ``[B, L]``."""
        beta = self.config.exit_entropy_beta
        gates, nll, hit, _ = self.passes(params, ids)
        with jax.named_scope("loop_exit"):
            log_p = exit_log_distribution(gates)
            p = jnp.exp(log_p)
            objective = jnp.sum(p * (nll + beta * log_p), axis=0)
            entropy = -jnp.sum(p * log_p, axis=0)
        return {
            "nll_next": nll[-1], "hit_next": hit[-1], "objective": objective,
            "per_position": {"exit_mass": p, "loop_nll": nll, "exit_entropy": entropy},
        }

    def step_flops(self, batch: int) -> float:
        """Operations one training step needs, 2 a multiply-add, forward
        times three: every layer application's products and causal scores,
        every exit's gate and head."""
        c = self.config
        positions = float(c.seq_len * batch)
        h, heads, d = c.hidden_size, c.num_attention_heads, c.head_dim
        q_out, kv_out = heads * d, c.num_key_value_heads * d
        products = 2.0 * positions * (h * (2 * q_out + 2 * kv_out) + 3 * h * c.intermediate_size)
        scores = 2.0 * batch * (c.seq_len * (c.seq_len + 1) / 2) * heads * 2 * d
        exit_ = 2.0 * positions * h * (c.vocab_size + 1)
        return 3.0 * c.total_ut_steps * (c.num_hidden_layers * (products + scores) + exit_)
