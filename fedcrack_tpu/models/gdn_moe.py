"""One chip's share of a hybrid linear-attention mixture-of-experts causal
language model.

The ``qwen3_next`` family (Qwen/Qwen3-Next-80B-A3B-Instruct; the equations are
its released modelling code's and those of Gated Delta Networks,
arXiv:2412.06464). Three layers of four are Gated DeltaNet layers, the fourth
is gated full attention; every layer has an expert layer. On the residual
stream ``x``, ``n = Norm(x)``, every ``Norm`` of the stack zero-centred
(``x rsqrt(mean x^2 + eps) (1 + w)``, ``w`` starting at 0)::

    Gated DeltaNet:  [q | k | v | z] = W_qkvz n  (16x128 | 16x128 | 32x128 | 32x128)
                     [b | a] = W_ba n  (32 | 32)
                     [q | k | v] <- silu(depthwise causal convolution, 4 taps, zero history)
                     q, k <- L2-normalised over 128 lanes; q / sqrt(128); a key head serves 2 value heads
                     beta_t = sigmoid(b_t);  alpha_t = exp(-exp(A_log) softplus(a_t + dt_bias))
                     a value head, float32, S_0 = 0 in R^{128 x 128}:
                       S'_t = alpha_t S_{t-1};  S_t = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T;  o_t = S_t^T q_t
                     h = x + W_out (RMSNorm_w(o_t) * silu(z_t))   (norm a head, plain weight starting at 1)
    gated attention: [q | gate] = W_q n a head (16 x (256 | 256));  k = W_k n, v = W_v n  (2 x 256)
                     q, k <- Norm a head, then rotary on the first 64 of 256 lanes, lane i with i + 32
                     h = x + W_o (softmax(q k^T / sqrt(256), causal; 8 query heads a key/value head) v * sigmoid(gate))
    expert layer:    n = Norm(h);  p = softmax(W_r n) over all 512; T = the 10 largest; w_e = p_e / sum_T p
                     y = h + sum_{e in T, e held here} w_e E_e(n) + sigmoid(w_sg . n) E_shared(n)

every ``E`` a SwiGLU of width 512. **The delta rule runs in its chunked
form**: within a chunk of ``GDN_CHUNK`` tokens the cumulative log-decays, the
unit lower-triangular system ``I + tril(diag(beta) (K K^T * decay), -1)``
inverted once (by halves: the inverse of ``[[A, 0], [C, B]]`` is
``[[A', 0], [-B' C A', B']]``) and applied to ``beta v`` and ``beta k``, and
the scores ``Q K^T * decay`` under the causal mask; across chunks the state
``S`` ``[32, 128, 128]`` a sequence in float32. Products take
``compute_dtype`` operands and accumulate in float32; decays, ``beta``, the
inverse and the state are float32. Two forms of it: ``chunked_delta_rule``
below (XLA operations: a ``lax.scan`` over the chunks that leaves every
chunk's state behind, all outputs in one batched product after it,
autodiff's backward) and ``kernels/delta_rule.py`` (Pallas: the state in VMEM
from chunk to chunk, forward and backward each a kernel, the inverse by
forward substitution).

**The share** is ``moe_layers.held_expert_layer``'s, the layer the other two
language models run, handed ``moe_layers.softmax_route`` (the block-diffusion
family's router); the shared expert is this module's own SwiGLU times its
gate, added once beside it. The embedding and the head hold ``vocab_held``
rows.

A sequence is one document of ``L`` tokens; position ``i`` is scored against
``t_{i+1}`` and the last position weighs nothing. Float32 parameters; norms,
softmaxes, the router and the loss in float32. Every layer's mixer block and
expert block is rematerialised apart in the backward pass: a Gated DeltaNet
block whole, a sequence at a time (its chunk states live through that
sequence's backward pass in that layer only), the attention block but for its
kernel's output and logsumexp (``ATTN_RESIDUALS``), so the forward kernel
runs once a step; the expert block ``EXPERT_TOKENS`` at a time
(``GdnMoe._layer`` says what fits the chip and what a trace needs).

Kernels (``resolve_kernels``): on the chip, JAX's splash-attention Pallas
kernel under a ``CausalMask``, 8 query heads over each key/value head, 256
wide, the convolution and its SiLU as one kernel each way
(``kernels/causal_conv.py``, where its channels are whole lane tiles) and the
delta rule's kernels (``kernels/delta_rule.py``, where the heads are whole lane
tiles and the sequence whole chunks); off the chip a masked dense softmax,
``causal_conv`` with ``jax.nn.silu``, and ``chunked_delta_rule``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from fedcrack_tpu.configs import GDN_CHUNK, GdnMoeConfig
from fedcrack_tpu.kernels import causal_conv as causal_conv_kernel
from fedcrack_tpu.kernels import delta_rule
from fedcrack_tpu.models.moe_layers import (
    ATTN_TILE,
    EXPERT_COUNTERS,
    causal_splash_mask,
    held_expert_layer,
    layer_totals,
    resolve_kernels,
    rms_norm,
    rotary_tables,
    softmax_route,
    splash_kernel,
    swiglu,
    token_losses,
)

# The name the splash kernel gives its output and logsumexp, and the one thing
# ``_layer``'s rematerialisation of the attention block keeps.
ATTN_RESIDUALS = "gattn_residuals"
# What ``l2_normalise`` adds under its root (the released kernels' 1e-6).
L2_EPS = 1e-6
# Tokens the expert block takes at a time (a sequence's tokens are independent
# there). Where the kept pairs fit the held-expert layer's row budget its
# arrays are 4,096 rows whatever this is; it is kept for the overflow's branch,
# whose row arrays are sized for every (token, slot) pair: with 10 experts a
# token 671 MB in float32 for a whole sequence of 8,192, scratch the compiler
# sets aside whichever branch runs, and the step does not fit its chip beside
# them.
EXPERT_TOKENS = 4096


def apply_rotary_halves(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """``x`` ``[..., S, heads, d]`` float32: of the first ``2 r`` lanes
    (``cos`` and ``sin`` are ``[S, r]``), lane ``i`` and lane ``i + r`` rotate
    together by the position's ``i``-th angle; the other lanes pass."""
    r = cos.shape[-1]
    a, b = x[..., :r], x[..., r : 2 * r]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s, x[..., 2 * r :]], axis=-1)


def gated_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *, kernels: str | None = None) -> jax.Array:
    """Softmax attention under the causal mask, grouped: ``q`` (already
    scaled) ``[B, kv_heads, group, S, d]`` beside ``k``, ``v`` ``[B,
    kv_heads, S, d]``; returns ``q``'s shape and dtype. The kernel names its
    output and logsumexp ``ATTN_RESIDUALS``; the dense path names nothing."""
    group, seq_len = q.shape[2], q.shape[3]
    mode = resolve_kernels(kernels)
    tile = min(ATTN_TILE, seq_len)
    if mode != "xla" and seq_len % tile == 0 and tile % 128 == 0:
        # One key/value head and its ``group`` query heads a kernel call, the
        # batch's sequences and the key/value heads in ONE instruction.
        kernel = splash_kernel(causal_splash_mask, (seq_len,), group, True, tile, mode == "interpret", ATTN_RESIDUALS)
        return jax.vmap(jax.vmap(kernel))(q, k, v).astype(q.dtype)
    scores = jnp.einsum("bngqd,bnkd->bngqk", q, k, preferred_element_type=jnp.float32)
    allowed = jnp.asarray(np.tril(np.ones((seq_len, seq_len), bool)))
    probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1).astype(v.dtype)
    return jnp.einsum("bngqk,bnkd->bngqd", probs, v, preferred_element_type=jnp.float32).astype(q.dtype)


def causal_conv(x: jax.Array, taps: jax.Array) -> jax.Array:
    """Depthwise causal convolution along the sequence with zero history:
    ``y_t = sum_j taps[:, j] x_{t - (K - 1) + j}`` for ``x`` ``[B, L, ch]``
    and ``taps`` ``[ch, K]`` (the last tap meets the token itself)."""
    taps_n = taps.shape[-1]
    seq_len = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps_n - 1, 0), (0, 0)))
    return sum(padded[:, j : j + seq_len] * taps[:, j] for j in range(taps_n))


def l2_normalise(x: jax.Array) -> jax.Array:
    """``x / sqrt(sum x^2 + eps)`` over the last axis, float32."""
    x32 = x.astype(jnp.float32)
    return x32 * lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1, keepdims=True) + L2_EPS)


def _inverse_by_halves(a: jax.Array) -> jax.Array:
    c = a.shape[-1]
    a = a.astype(jnp.float32)
    rows = np.arange(c)
    inv = jnp.broadcast_to(jnp.eye(c, dtype=jnp.float32), a.shape)
    size = 1
    while size < c:
        # The lower-left quarters of the diagonal blocks of ``2 size`` rows:
        # with ``inv`` the inverse of the diagonal blocks of ``size`` rows,
        # ``inv - inv low inv`` is that of the blocks twice as large.
        same_block = rows[:, None] // (2 * size) == rows[None, :] // (2 * size)
        low = jnp.where(same_block & (rows[:, None] % (2 * size) >= size) & (rows[None, :] % (2 * size) < size), a, 0.0)
        inv = inv - jnp.matmul(
            jnp.matmul(inv, low, precision=lax.Precision.HIGHEST), inv, precision=lax.Precision.HIGHEST
        )
        size *= 2
    return inv


@jax.custom_vjp
def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + a)^{-1}`` for strictly lower-triangular ``a`` ``[..., C, C]``
    (``C`` a power of two), float32: block forward substitution by halves,
    from diagonal blocks of one row up, each level two batched products
    (``[[A, 0], [C, B]]^-1 = [[A', 0], [-B' C A', B']]``). Its cotangent is
    the closed form ``-T^T g T^T`` and not the levels' own."""
    return _inverse_by_halves(a)


def _unit_lower_inverse_fwd(a):
    t = _inverse_by_halves(a)
    return t, t


def _unit_lower_inverse_bwd(t, g):
    t_t = jnp.swapaxes(t, -1, -2)
    da = -jnp.matmul(jnp.matmul(t_t, g, precision=lax.Precision.HIGHEST), t_t, precision=lax.Precision.HIGHEST)
    return (jnp.tril(da, -1),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def chunked_delta_rule(
    q: jax.Array, k: jax.Array, v: jax.Array, log_decay: jax.Array, beta: jax.Array, *, compute_dtype
) -> jax.Array:
    """The gated delta rule over whole sequences, chunk by chunk.

    ``q`` (normalised and scaled), ``k`` (normalised) ``[B, L, heads, d_k]``,
    ``v`` ``[B, L, heads, d_v]``; ``log_decay`` (``log alpha_t <= 0``) and
    ``beta`` ``[B, L, heads]`` float32. ``L`` is whole chunks of
    ``GDN_CHUNK``. Returns ``o`` ``[B, L, heads, d_v]`` float32 with
    ``S_0 = 0``. Within a chunk, with ``G_i`` the chunk's cumulative
    log-decay and ``D_ij = exp(G_i - G_j)`` for ``j <= i``:
    ``T = (I + tril(diag(beta) K K^T * D, -1))^{-1}``, ``U = T (beta v)``,
    ``W = T (beta k exp(G))``; then for the state ``S`` the chunk starts from,
    ``V' = U - W S``, ``o = (q exp(G)) S + tril(Q K^T * D) V'`` and the next
    chunk starts from ``exp(G_last) S + (k exp(G_last - G))^T V'``. Only
    ``V'`` and the next state are computed chunk after chunk; every chunk's
    ``o`` follows at once from the states the scan left behind."""
    cd = jnp.dtype(compute_dtype)
    batch, seq_len, heads, _ = q.shape
    chunks = seq_len // GDN_CHUNK

    def by_chunk(x):  # [B, L, heads, ...] -> [chunks, B, heads, C, ...]
        x = x.reshape(batch, chunks, GDN_CHUNK, heads, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)

    qc, kc = by_chunk(q.astype(cd)), by_chunk(k.astype(cd))
    bc = by_chunk(beta.astype(jnp.float32))
    g = jnp.cumsum(by_chunk(log_decay.astype(jnp.float32)), axis=-1)  # [chunks, B, heads, C]
    rows = np.arange(GDN_CHUNK)
    on_or_below, below = rows[:, None] >= rows[None, :], rows[:, None] > rows[None, :]
    # exp of a masked difference: above the diagonal the difference is
    # positive and may overflow, so it is never exponentiated.
    decay = jnp.exp(jnp.where(on_or_below, g[..., :, None] - g[..., None, :], -jnp.inf))
    kk = jnp.einsum("...id,...jd->...ij", kc, kc, preferred_element_type=jnp.float32)
    t = unit_lower_inverse(jnp.where(below, bc[..., :, None] * kk * decay, 0.0))
    k32 = kc.astype(jnp.float32)
    u = jnp.matmul(t, by_chunk(v).astype(jnp.float32) * bc[..., None], precision=lax.Precision.HIGHEST)
    w = jnp.matmul(t, k32 * (bc * jnp.exp(g))[..., None], precision=lax.Precision.HIGHEST).astype(cd)
    k_out = (k32 * jnp.exp(g[..., -1:] - g)[..., None]).astype(cd)
    carried = jnp.exp(g[..., -1])  # [chunks, B, heads]

    def one_chunk(state, xs):
        """The state a chunk starts from (as the products read it) and the
        chunk's ``V'``; carries the state the next one starts from."""
        u_i, w_i, k_i, carried_i = xs
        s_cd = state.astype(cd)
        v_new = (u_i - jnp.einsum("bhck,bhkv->bhcv", w_i, s_cd, preferred_element_type=jnp.float32)).astype(cd)
        state = state * carried_i[..., None, None] + jnp.einsum(
            "bhck,bhcv->bhkv", k_i, v_new, preferred_element_type=jnp.float32
        )
        return state, (s_cd, v_new)

    state0 = jnp.zeros((batch, heads, q.shape[-1], v.shape[-1]), jnp.float32)
    _, (states, v_new) = lax.scan(one_chunk, state0, (u, w, k_out, carried))
    # Every chunk's output at once, from the states the scan left behind.
    scores = (jnp.einsum("...id,...jd->...ij", qc, kc, preferred_element_type=jnp.float32) * decay).astype(cd)
    q_in = (qc.astype(jnp.float32) * jnp.exp(g)[..., None]).astype(cd)
    out = jnp.einsum("nbhck,nbhkv->nbhcv", q_in, states, preferred_element_type=jnp.float32) + jnp.einsum(
        "nbhij,nbhjv->nbhiv", scores, v_new, preferred_element_type=jnp.float32
    )
    # [chunks, B, heads, C, d_v] -> [B, L, heads, d_v]
    return jnp.moveaxis(jnp.moveaxis(out, 2, 3), 0, 1).reshape(batch, seq_len, heads, v.shape[-1])


@dataclasses.dataclass(frozen=True)
class GdnMoe:
    """The model as pure functions of a parameter tree (nested dicts):
    ``embed`` ``[vocab_held, H]``; ``layer<i>``: ``mixer_norm`` and either a
    Gated DeltaNet mixer (``w_qkvz`` ``[H, 2 keys + 2 values]``, ``w_ba``
    ``[H, 2 value heads]``, ``conv`` ``[2 keys + values, taps]``, ``A_log``,
    ``dt_bias`` ``[value heads]``, ``gdn_norm`` ``[d_v]``, ``w_out``) or a
    gated-attention one (``wq`` ``[H, heads x 2 head_dim]``, ``wk``, ``wv``,
    ``q_norm``, ``k_norm`` ``[head_dim]``, ``wo``); then ``moe_norm``,
    ``router`` ``[H, num_experts]``, ``w_gate``/``w_up`` ``[experts_held, H,
    width]``, ``w_down`` ``[experts_held, width, H]``, ``shared_gate``/
    ``shared_up`` ``[H, shared width]``, ``shared_down``,
    ``shared_expert_gate`` ``[H]``; ``final_norm``, ``lm_head`` ``[H,
    vocab_held]`` (untied). The lane order of the fused projections is this
    repo's (it matters only to published weights)."""

    config: GdnMoeConfig = dataclasses.field(default_factory=GdnMoeConfig)
    kernels: str | None = None

    # What ``tasks.CausalLMTask`` reads off its model: the kinds of block,
    # summed over the layers that hold them; the statistics ``apply`` returns
    # beside the causal models' common ones, with how they reduce: the
    # held-expert layer's and the decays.
    block_scope = (
        r"^(embed|gdn_proj|gdn_conv|gdn_rule|gattn_proj|gattn|router|moe_dispatch|moe_experts|moe_combine"
        r"|shared_expert|lm_head)$"
    )
    has_mtp_loss = False
    counters = (*EXPERT_COUNTERS, ("gdn_decay_mean", "mean"))

    # ---- weights -------------------------------------------------------------

    def layer_shapes(self, linear: bool) -> tuple[dict, dict, dict]:
        """(matrices, zero-centred norm scales, plain norm scales) of one
        layer, by name."""
        c = self.config
        h, width, shared = c.hidden_size, c.moe_intermediate_size, c.shared_expert_intermediate_size
        keys = c.linear_num_key_heads * c.linear_key_head_dim
        values = c.linear_num_value_heads * c.linear_value_head_dim
        if linear:
            matrices = {
                "w_qkvz": (h, 2 * keys + 2 * values), "w_ba": (h, 2 * c.linear_num_value_heads),
                "conv": (2 * keys + values, c.linear_conv_kernel_dim), "w_out": (values, h),
            }
            centred, plain = {"mixer_norm": h, "moe_norm": h}, {"gdn_norm": c.linear_value_head_dim}
        else:
            q_out, kv_out = c.num_attention_heads * c.head_dim, c.num_key_value_heads * c.head_dim
            matrices = {"wq": (h, 2 * q_out), "wk": (h, kv_out), "wv": (h, kv_out), "wo": (q_out, h)}
            centred = {"mixer_norm": h, "moe_norm": h, "q_norm": c.head_dim, "k_norm": c.head_dim}
            plain = {}
        matrices.update({
            "router": (h, c.num_experts),
            "w_gate": (c.experts_held, h, width), "w_up": (c.experts_held, h, width),
            "w_down": (c.experts_held, width, h),
            "shared_gate": (h, shared), "shared_up": (h, shared), "shared_down": (shared, h),
            "shared_expert_gate": (h,),
        })
        return matrices, centred, plain

    def init(self, rng: jax.Array) -> dict:
        c = self.config
        dtype = jnp.dtype(c.param_dtype)

        def normal(key, shape):
            return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

        def layer(key, linear):
            matrices, centred, plain = self.layer_shapes(linear)
            sub = jax.random.split(key, len(matrices) + 1)
            out = {name: normal(k, shape) for k, (name, shape) in zip(sub, sorted(matrices.items()))}
            out.update({name: jnp.zeros((width,), dtype) for name, width in centred.items()})
            out.update({name: jnp.ones((width,), dtype) for name, width in plain.items()})
            if linear:
                heads = c.linear_num_value_heads
                out["A_log"] = jnp.log(jax.random.uniform(sub[-1], (heads,), jnp.float32, 1e-6, 16.0)).astype(dtype)
                out["dt_bias"] = jnp.ones((heads,), dtype)
            return out

        keys = jax.random.split(rng, c.num_hidden_layers + 2)
        params = {
            "embed": normal(keys[0], (c.vocab_held, c.hidden_size)),
            "final_norm": jnp.zeros((c.hidden_size,), dtype),
            "lm_head": normal(keys[1], (c.hidden_size, c.vocab_held)),
        }
        for i in range(c.num_hidden_layers):
            params[f"layer{i}"] = layer(keys[2 + i], c.is_linear(i))
        return params

    # ---- blocks --------------------------------------------------------------

    def _norm(self, x: jax.Array, w: jax.Array) -> jax.Array:
        """The stack's zero-centred norm: ``rms_norm`` handed ``1 + w``."""
        return rms_norm(x, 1.0 + w.astype(jnp.float32), self.config.rms_norm_eps)

    def _gdn_inputs(self, p: dict, x: jax.Array):
        """What the rule reads, from the batch's ``[B, L, H]``: ``q``
        (normalised, scaled) and ``k`` (normalised) ``[B, L, key heads,
        d_k]``, ``v`` and the output gate ``z`` ``[B, L, value heads, d_v]``
        in the compute dtype; ``log_decay`` and ``beta`` ``[B, L, value
        heads]`` float32."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        lead = x.shape[:-1]
        k_heads, v_heads = c.linear_num_key_heads, c.linear_num_value_heads
        d_k, d_v = c.linear_key_head_dim, c.linear_value_head_dim
        keys, values = k_heads * d_k, v_heads * d_v
        with jax.named_scope("gdn_proj"):
            n = self._norm(x, p["mixer_norm"]).astype(cd)
            qkvz = jnp.dot(n, p["w_qkvz"].astype(cd), preferred_element_type=jnp.float32).astype(cd)
            ba = jnp.dot(n, p["w_ba"].astype(cd), preferred_element_type=jnp.float32)
            z = qkvz[..., 2 * keys + values :].reshape(*lead, v_heads, d_v)
        with jax.named_scope("gdn_conv"):
            mode = resolve_kernels(self.kernels)
            if mode != "xla" and causal_conv_kernel.fits(qkvz, p["conv"]):
                qkv = causal_conv_kernel.causal_conv_silu(qkvz, p["conv"], interpret=mode == "interpret")
            else:
                qkv = causal_conv(qkvz[..., : 2 * keys + values].astype(jnp.float32), p["conv"].astype(jnp.float32))
                qkv = jax.nn.silu(qkv).astype(cd)
        with jax.named_scope("gdn_rule"):
            q = (l2_normalise(qkv[..., :keys].reshape(*lead, k_heads, d_k)) * d_k**-0.5).astype(cd)
            k = l2_normalise(qkv[..., keys : 2 * keys].reshape(*lead, k_heads, d_k)).astype(cd)
            v = qkv[..., 2 * keys :].reshape(*lead, v_heads, d_v)
            beta = jax.nn.sigmoid(ba[..., :v_heads])
            log_decay = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
                ba[..., v_heads:] + p["dt_bias"].astype(jnp.float32)
            )
        return q, k, v, z, log_decay, beta

    def _gdn_output(self, p: dict, x: jax.Array, o: jax.Array, z: jax.Array) -> jax.Array:
        """``x + W_out (RMSNorm(o) * silu(z))`` on the batch."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        with jax.named_scope("gdn_proj"):
            gated = rms_norm(o, p["gdn_norm"], c.rms_norm_eps) * jax.nn.silu(z.astype(jnp.float32))
            out = jnp.dot(gated.reshape(*x.shape[:-1], -1).astype(cd), p["w_out"].astype(cd), preferred_element_type=jnp.float32)
            return (x.astype(jnp.float32) + out).astype(cd)

    def _gdn_block(self, p: dict, x: jax.Array):
        """``h = x + W_out (RMSNorm(o) * silu(z))`` on the batch's ``[B, L,
        H]``, and the mean ``alpha`` over tokens and heads. The rule runs on
        ``kernels/delta_rule.py`` unless ``kernels`` resolves to ``"xla"``
        or the shapes are not the kernels' (``delta_rule.fits``); there a
        key head's ``q`` and ``k`` are read by its value heads through the
        kernels' blocks, here repeated for ``chunked_delta_rule``. What comes
        before the rule and what comes after it are rematerialised apart
        inside the block's own rematerialisation, so that the block's
        backward pass holds the rule's chunk states beside one of them and
        not both (3.4 GB of scratch a sequence without, 2.0 with, at the
        published widths, in the XLA form)."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        mode = resolve_kernels(self.kernels)
        q, k, v, z, log_decay, beta = jax.checkpoint(self._gdn_inputs)(p, x)
        with jax.named_scope("gdn_rule"):
            if mode != "xla" and delta_rule.fits(q, v):
                o = delta_rule.delta_rule(q, k, v, log_decay, beta, compute_dtype=cd, interpret=mode == "interpret")
            else:
                # A key head's q and k serve its ``value heads / key heads`` value heads.
                per_key = c.linear_num_value_heads // c.linear_num_key_heads
                q, k = (jnp.repeat(t, per_key, axis=2) for t in (q, k))
                o = chunked_delta_rule(q, k, v, log_decay, beta, compute_dtype=cd)
            decay_mean = jnp.mean(jnp.exp(log_decay))
        return jax.checkpoint(self._gdn_output)(p, x, o, z), decay_mean

    def _attention_block(self, p: dict, x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
        """``h = x + W_o (Attn(...) * sigmoid(gate))`` on the batch's ``[B, L, H]``."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        heads, kv_heads, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        batch, seq_len, _ = x.shape
        with jax.named_scope("gattn_proj"):
            n = self._norm(x, p["mixer_norm"]).astype(cd)
            q_gate = jnp.dot(n, p["wq"].astype(cd), preferred_element_type=jnp.float32).reshape(batch, seq_len, heads, 2 * d)
            k = jnp.dot(n, p["wk"].astype(cd), preferred_element_type=jnp.float32).reshape(batch, seq_len, kv_heads, d)
            v = jnp.dot(n, p["wv"].astype(cd), preferred_element_type=jnp.float32).reshape(batch, seq_len, kv_heads, d)
            q = apply_rotary_halves(self._norm(q_gate[..., :d], p["q_norm"]), cos, sin) * d**-0.5
            k = apply_rotary_halves(self._norm(k, p["k_norm"]), cos, sin)
            gate = jax.nn.sigmoid(q_gate[..., d:])
            # The kernels' layout: [B, kv_heads, group, L, d] beside [B, kv_heads, L, d].
            q = q.astype(cd).reshape(batch, seq_len, kv_heads, heads // kv_heads, d).transpose(0, 2, 3, 1, 4)
            k, v = (t.astype(cd).transpose(0, 2, 1, 3) for t in (k, v))
        with jax.named_scope("gattn"):
            attended = gated_causal_attention(q, k, v, kernels=self.kernels)
        with jax.named_scope("gattn_proj"):
            attended = attended.transpose(0, 3, 1, 2, 4).reshape(batch, seq_len, heads, d)
            attended = (attended.astype(jnp.float32) * gate).astype(cd).reshape(batch, seq_len, heads * d)
            out = jnp.dot(attended, p["wo"].astype(cd), preferred_element_type=jnp.float32)
            return (x.astype(jnp.float32) + out).astype(cd)

    def _expert_block(self, p: dict, h: jax.Array):
        """``y = h + held part of MoE(Norm(h)) + gated shared expert`` on
        ``[tokens, H]`` of one sequence, with the record of
        ``held_expert_layer``."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        with jax.named_scope("router"):
            n32 = self._norm(h, p["moe_norm"])
        part, counters = held_expert_layer(
            n32, p["router"], p["w_gate"], p["w_up"], p["w_down"],
            first_expert=c.first_expert,
            route=functools.partial(softmax_route, top_k=c.num_experts_per_tok, norm_topk=c.norm_topk_prob),
            compute_dtype=cd, kernels=self.kernels,
        )
        with jax.named_scope("shared_expert"):
            opened = jax.nn.sigmoid(jnp.dot(n32, p["shared_expert_gate"].astype(jnp.float32), precision=lax.Precision.HIGHEST))
            shared = opened[:, None] * swiglu(n32.astype(cd), p["shared_gate"], p["shared_up"], p["shared_down"], cd)
        with jax.named_scope("moe_combine"):
            y = (h.astype(jnp.float32) + part.astype(jnp.float32) + shared).astype(cd)
        return y, counters

    def _layer(self, p: dict, x: jax.Array, cos: jax.Array, sin: jax.Array, linear: bool):
        """One decoder layer on ``[B, L, H]``, its mixer block and its expert
        block rematerialised apart. A Gated DeltaNet block runs the sequences
        in turn (``lax.map``: side by side, or as two instructions, the
        compiler holds both sequences' scratch at once, 18-20 GB compiled for
        a v5e against 14.3) and keeps nothing, so its chunk states are live
        through its own backward pass only; the attention block takes the
        batch whole (one kernel instruction a step, by whose starts a trace
        finds the steps) and keeps its kernel's output and logsumexp
        (``ATTN_RESIDUALS``); the expert block runs ``EXPERT_TOKENS`` of the
        batch at a time, in turn. Returns the record of ``held_expert_layer``
        too, summed over those calls, and ``decay_mean`` (``None`` for an
        attention layer)."""
        if linear:
            gdn_block = jax.checkpoint(self._gdn_block)
            h, decays = lax.map(lambda x_b: gdn_block(p, x_b[None]), x)
            h, decay_mean = h[:, 0], jnp.mean(decays)
        else:
            attention_block = jax.checkpoint(
                self._attention_block, policy=jax.checkpoint_policies.save_only_these_names(ATTN_RESIDUALS)
            )
            h, decay_mean = attention_block(p, x, cos, sin), None
        expert_block = jax.checkpoint(self._expert_block)
        tokens = min(EXPERT_TOKENS, x.shape[1])
        if x.shape[1] % tokens:
            tokens = x.shape[1]
        ys, counters = lax.map(lambda part: expert_block(p, part), h.reshape(-1, tokens, h.shape[-1]))
        return ys.reshape(h.shape), jax.tree.map(lambda calls: jnp.sum(calls, axis=0), counters), decay_mean

    # ---- the model -----------------------------------------------------------

    def hidden(self, params: dict, ids: jax.Array):
        """The residual stream after the last layer, ``[B, L, H]`` before the
        final norm, with the model's record: the expert layers'
        (``moe_layers.layer_totals``) and ``gdn_decay_mean`` ``[Gated DeltaNet
        layers]``."""
        c = self.config
        cd = jnp.dtype(c.compute_dtype)
        if ids.shape[-1] != c.seq_len:
            raise ValueError(f"sequences of {ids.shape[-1]} tokens, the configuration's are {c.seq_len}")
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], ids, axis=0).astype(cd)
            cos, sin = rotary_tables(c.seq_len, c.rotary_dim, c.rope_theta)
        counted, decays = [], []
        for i in range(c.num_hidden_layers):
            with jax.named_scope(f"layer{i}"):
                x, counters, decay_mean = self._layer(params[f"layer{i}"], x, cos, sin, c.is_linear(i))
            counted.append(counters)
            if decay_mean is not None:
                decays.append(decay_mean)
        decays = jnp.stack(decays) if decays else jnp.zeros((0,), jnp.float32)
        return x, {**layer_totals(counted, c.experts_held), "gdn_decay_mean": decays}

    def logits(self, params: dict, ids: jax.Array) -> jax.Array:
        """Float32 logits ``[B, L, vocab_held]``, whole: for tests at small sizes."""
        cd = jnp.dtype(self.config.compute_dtype)
        x, *_ = self.hidden(params, ids)
        n = self._norm(x, params["final_norm"]).astype(cd)
        return jnp.dot(n, params["lm_head"].astype(cd), preferred_element_type=jnp.float32)

    def apply(self, params: dict, ids: jax.Array) -> dict:
        """``nll_next`` and ``hit_next`` ``[B, L]`` (position ``i``'s
        cross-entropy against ``t_{i+1}`` and whether its largest logit is
        that token; the last position's wraps round and weighs nothing with
        the caller) and the model's record (``hidden``). No ``nll_mtp``: the
        family's configuration has no key for such a module and none is
        built."""
        c = self.config
        x, counters = self.hidden(params, ids)
        with jax.named_scope("lm_head"):
            n32 = self._norm(x, params["final_norm"])
            nll, hit = token_losses(
                n32.reshape(-1, c.hidden_size), params["lm_head"], jnp.roll(ids, -1, axis=-1).reshape(-1),
                jnp.dtype(c.compute_dtype),
            )
        return {"nll_next": nll.reshape(ids.shape), "hit_next": hit.reshape(ids.shape), **counters}

    def step_flops(self, batch: int) -> float:
        """Operations one training step needs, 2 a multiply-add, forward
        times three: every product once, the recurrence as its own three
        ``d_k x d_v`` products a token a value head (whatever form computes
        it), causal scores only, held experts at their expected ``top_k *
        experts_held / num_experts`` pairs a position."""
        c = self.config
        positions = float(c.seq_len * batch)
        h, width = c.hidden_size, c.moe_intermediate_size
        keys = c.linear_num_key_heads * c.linear_key_head_dim
        values = c.linear_num_value_heads * c.linear_value_head_dim
        gdn = 2.0 * positions * (
            h * (2 * keys + 2 * values + 2 * c.linear_num_value_heads) + values * h
            + c.linear_conv_kernel_dim * (2 * keys + values)
            + 3 * c.linear_num_value_heads * c.linear_key_head_dim * c.linear_value_head_dim
        )
        q_out, kv_out = c.num_attention_heads * c.head_dim, c.num_key_value_heads * c.head_dim
        attention = 2.0 * positions * h * (3 * q_out + 2 * kv_out) + 2.0 * batch * (
            c.seq_len * (c.seq_len + 1) / 2
        ) * c.num_attention_heads * 2 * c.head_dim
        pairs = positions * c.num_experts_per_tok * c.experts_held / c.num_experts
        experts = (
            2.0 * positions * h * (c.num_experts + 1) + 2.0 * pairs * 3 * h * width
            + 2.0 * positions * 3 * h * c.shared_expert_intermediate_size
        )
        head = 2.0 * positions * h * c.vocab_held
        linear = c.linear_layers
        return 3.0 * (
            linear * gdn + (c.num_hidden_layers - linear) * attention + c.num_hidden_layers * experts + head
        )
