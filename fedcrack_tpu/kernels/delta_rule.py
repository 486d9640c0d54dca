"""The gated delta rule as a Pallas kernel pair: the chunked rule's forward
and its backward on the chip, the recurrent state kept in VMEM from chunk to
chunk.

The mathematics is ``models/gdn_moe.py:chunked_delta_rule``'s (the XLA form,
kept off the chip and as the tests' second oracle). Within a chunk of ``C``
tokens, with ``G`` the chunk's cumulative log-decay and ``D_ij = exp(G_i -
G_j)`` on and below the diagonal::

    T = (I + tril(diag(beta) K K^T * D, -1))^-1      (forward substitution, float32)
    U = T (beta v);  W = T (beta k exp(G))           (float32 HIGHEST)
    V' = U - W S;  o = (q exp(G)) S + tril(Q K^T * D) V'
    S <- exp(G_last) S + (k exp(G_last - G))^T V'

Four ``pallas_call``s. ``delta_rule_inverse`` solves every chunk's ``T`` at
once, a lane a (chunk, head), by exact float32 forward substitution on the
VPU; the systems are built and ``T`` laid out a (chunk, head) at a time in
XLA around it. The other three run over a grid of (sequence, block of
``HEADS_A_STEP`` value heads, chunk):

- ``delta_rule_fwd``: the chunks in order (``"arbitrary"``), ``S`` in a
  float32 VMEM scratch zeroed at chunk 0; writes ``o`` and, for the
  backward, the float32 state each chunk starts from.
- ``delta_rule_dstate``: the chunks in reverse, the state's cotangent ``dS``
  in a float32 VMEM scratch; writes the ``dS`` each chunk ends with.
- ``delta_rule_grads``: every chunk on its own (``"parallel"``), from its
  start state and end cotangent: recomputes ``U``, ``W`` and ``V'`` and
  returns the cotangents of ``q``, ``k``, ``v``, ``G`` and ``beta``.

The backward reads ``T`` and the states as the forward's residuals and
recomputes the rest. ``q`` and ``k`` are read straight from their ``[L, heads
x d]`` layout: a value head reads its key head through the block it is handed
(``per_key`` value heads a key head), so nothing is repeated or transposed in
HBM. The per-token scalars come in ``[blocks, L, heads a block]`` (a column
a head) and ``G`` also in ``[blocks, chunks, heads a block, C]`` (a row a
head, for ``D``'s ``G_j``). Products take the operand dtype
``chunked_delta_rule`` gives them: ``compute_dtype`` where it casts, float32
``HIGHEST`` for ``U``, ``W`` and the inverse's cotangent (``-T^T g T^T``,
here as ``-(T^T d[U | W]) [U | W]^T``); every product accumulates in float32;
decays, ``beta``, the inverse and the state stay float32. A cotangent that
feeds a ``compute_dtype`` product is cast to it, as XLA's one-pass product of
a float32 cotangent with a bf16 operand rounds it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tokens a chunk of the kernels (equal to any other chunk in exact
# arithmetic), and value heads a grid step: chosen by measurement (PERF.md).
CHUNK = 64
HEADS_A_STEP = 8
HIGHEST = lax.Precision.HIGHEST
# Terms of the forward substitution's sums a loop iteration, and bytes of
# systems a grid step of it.
UNROLL = 4
LANE_BYTES = 8 << 20
F32 = jnp.float32


class _Shape(NamedTuple):
    """What the kernels are built for (hashable: a ``custom_vjp``'s static
    argument)."""

    chunk: int
    heads: int  # value heads a grid step
    per_key: int  # value heads a key head serves
    d_k: int
    d_v: int
    cd: str  # the products' operand dtype
    interpret: bool


def _dot(a, b, precision=None):  # a @ b
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())), precision=precision, preferred_element_type=F32)


def _dot_tn(a, b, precision=None):  # a^T @ b
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())), precision=precision, preferred_element_type=F32)


def _dot_nt(a, b, precision=None):  # a @ b^T
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=precision, preferred_element_type=F32)


def _column(block, i):
    """Lane ``i`` of ``[C, heads]`` as ``[C, 1]``."""
    lanes = lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lanes == i, block, 0.0), axis=1, keepdims=True)


def _inverse_kernel(a_ref, t_ref):
    """``(I + A)^-1`` of ``lanes`` unit lower-triangular systems at once,
    ``A`` ``[C (row), C (column), lanes]`` strictly lower: exact float32
    forward substitution on the VPU, ``T_i = e_i - sum_{j < i} A_ij T_j`` a
    row at a time, every lane its own system. Row ``i`` of ``T`` is zero past
    column ``i``, so a row's sum runs over its first columns only."""
    c, lanes = a_ref.shape[0], a_ref.shape[2]
    for i in range(c):
        width = min(c, -(-(i + 1) // 8) * 8)
        acc = jnp.where(lax.broadcasted_iota(jnp.int32, (width, lanes), 0) == i, 1.0, 0.0).astype(F32)

        def terms(j, acc, n=UNROLL, i=i, width=width):
            for r in range(n):
                acc = acc - a_ref[i, pl.ds(j + r, 1), :] * t_ref[j + r, :width, :]
            return acc

        acc = lax.fori_loop(0, i // UNROLL, lambda jj, acc: terms(jj * UNROLL, acc), acc)
        acc = terms(i - i % UNROLL, acc, i % UNROLL)
        t_ref[i, :width, :] = acc
        if width < c:
            t_ref[i, width:, :] = jnp.zeros((c - width, lanes), F32)


def _systems(shape: _Shape, k, g, beta):
    """The chunks' ``tril(diag(beta) K K^T * D, -1)`` ``[C, C, lanes]``, a
    lane a (sequence, chunk, value head), from ``k`` ``[B, L, key heads x
    d_k]`` and ``g``, ``beta`` ``[B, L, value heads]``: ``K K^T`` once a key
    head, ``compute_dtype`` operands, as ``chunked_delta_rule`` forms it."""
    c, cd = shape.chunk, jnp.dtype(shape.cd)
    batch, seq_len, heads = g.shape
    chunks = seq_len // c
    kc = k.reshape(batch, chunks, c, -1, shape.d_k).astype(cd)
    kk = jnp.einsum("bnihd,bnjhd->ijbnh", kc, kc, preferred_element_type=F32)
    kk = jnp.repeat(kk, shape.per_key, axis=-1)  # [C, C, B, chunks, value heads]
    by_row = lambda x: jnp.moveaxis(x.reshape(batch, chunks, c, heads), 2, 0)  # [C, B, chunks, heads]
    rows = np.arange(c)[:, None, None, None, None]
    cols = np.arange(c)[None, :, None, None, None]
    # exp of a masked difference, as in the kernels below.
    decay = jnp.exp(jnp.where(rows >= cols, by_row(g)[:, None] - by_row(g)[None], -jnp.inf))
    return jnp.where(rows > cols, by_row(beta)[:, None] * kk * decay, 0.0).reshape(c, c, -1)


def _solve(shape: _Shape, a):
    """``_inverse_kernel`` over ``a`` ``[C, C, lanes]``, ``LANE_BYTES`` of
    systems a grid step."""
    c, lanes = a.shape[0], a.shape[-1]
    block = max(128, LANE_BYTES // (4 * c * c))
    block = block if lanes % block == 0 else lanes
    return pl.pallas_call(
        _inverse_kernel,
        grid=(lanes // block,),
        in_specs=[pl.BlockSpec((c, c, block), lambda n: (0, 0, n))],
        out_specs=pl.BlockSpec((c, c, block), lambda n: (0, 0, n)),
        out_shape=jax.ShapeDtypeStruct(a.shape, F32),
        compiler_params=_params("parallel"),
        interpret=shape.interpret,
        name="delta_rule_inverse",
    )(a)


def _inverse(shape: _Shape, k, g, beta):
    """The chunks' ``T = (I + tril(diag(beta) K K^T * D, -1))^-1`` ``[B,
    chunks, value heads, C, C]`` float32: the systems in XLA, solved by
    ``_inverse_kernel`` a lane each, then a (chunk, head) at a time."""
    batch, seq_len, heads = g.shape
    c = shape.chunk
    t = _solve(shape, _systems(shape, k, g, beta))
    return jnp.transpose(t.reshape(c, c, batch, seq_len // c, heads), (2, 3, 4, 0, 1))


class _Chunk(NamedTuple):
    """One value head's chunk, recomputed from the inputs."""

    q: jax.Array  # [C, d_k] compute dtype
    k: jax.Array  # [C, d_k] compute dtype
    g: jax.Array  # [C, 1] cumulative log-decay
    g_last: jax.Array  # [1, 1]
    beta: jax.Array  # [C, 1]
    decay: jax.Array  # [C, C] D, zero above the diagonal
    t: jax.Array  # [C, C] the inverse


def _chunk(shape: _Shape, q_ref, k_ref, gc_ref, gr_ref, b_ref, t_ref, i: int) -> _Chunk:
    c, cd = shape.chunk, jnp.dtype(shape.cd)
    kh = i // shape.per_key
    q = q_ref[:, kh * shape.d_k : (kh + 1) * shape.d_k].astype(cd)
    k = k_ref[:, kh * shape.d_k : (kh + 1) * shape.d_k].astype(cd)
    g = _column(gc_ref[...], i)
    g_row = gr_ref[i : i + 1, :]
    last = lax.broadcasted_iota(jnp.int32, (1, c), 1) == c - 1
    g_last = jnp.sum(jnp.where(last, g_row, 0.0), axis=1, keepdims=True)
    beta = _column(b_ref[...], i)
    rows = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # exp of a masked difference: above the diagonal the difference is
    # positive and may overflow, so it is never exponentiated.
    decay = jnp.exp(jnp.where(rows >= cols, g - g_row, -jnp.inf))
    return _Chunk(q, k, g, g_last, beta, decay, t_ref[i])


def _pieces(shape: _Shape, ch: _Chunk):
    """``q exp(G)`` and ``k exp(G_last - G)`` (float32, and cast), the scores
    ``Q K^T * D`` (float32, and cast) and ``exp(G_last)``."""
    cd = jnp.dtype(shape.cd)
    q_in = ch.q.astype(F32) * jnp.exp(ch.g)
    k_out = ch.k.astype(F32) * jnp.exp(ch.g_last - ch.g)
    qk = _dot_nt(ch.q, ch.k)
    scores = (qk * ch.decay).astype(cd)
    return q_in, q_in.astype(cd), k_out, k_out.astype(cd), qk, scores, jnp.exp(ch.g_last)


def _fwd_kernel(shape: _Shape, q_ref, k_ref, v_ref, gc_ref, gr_ref, b_ref, t_ref, o_ref, s0_ref, s_ref):
    cd, d_v = jnp.dtype(shape.cd), shape.d_v

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros(s_ref.shape, F32)

    for i in range(shape.heads):
        ch = _chunk(shape, q_ref, k_ref, gc_ref, gr_ref, b_ref, t_ref, i)
        v = v_ref[:, i * d_v : (i + 1) * d_v].astype(F32)
        rhs = jnp.concatenate([v * ch.beta, ch.k.astype(F32) * (ch.beta * jnp.exp(ch.g))], axis=1)
        uw = _dot(ch.t, rhs, HIGHEST)
        u, w = uw[:, :d_v], uw[:, d_v:].astype(cd)
        _, q_in, _, k_out, _, scores, carried = _pieces(shape, ch)
        state = s_ref[i]
        s0_ref[i] = state
        s_cd = state.astype(cd)
        v_new = (u - _dot(w, s_cd)).astype(cd)
        o_ref[:, i * d_v : (i + 1) * d_v] = _dot(q_in, s_cd) + _dot(scores, v_new)
        s_ref[i] = state * carried + _dot_tn(k_out, v_new)


def _dstate_kernel(shape: _Shape, q_ref, k_ref, gc_ref, gr_ref, b_ref, t_ref, do_ref, ds_ref, acc_ref):
    cd, d_v = jnp.dtype(shape.cd), shape.d_v

    @pl.when(pl.program_id(2) == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    for i in range(shape.heads):
        ch = _chunk(shape, q_ref, k_ref, gc_ref, gr_ref, b_ref, t_ref, i)
        w = _dot(ch.t, ch.k.astype(F32) * (ch.beta * jnp.exp(ch.g)), HIGHEST).astype(cd)
        _, q_in, _, k_out, _, scores, carried = _pieces(shape, ch)
        d_state = acc_ref[i]
        ds_ref[i] = d_state
        d_out = do_ref[:, i * d_v : (i + 1) * d_v].astype(cd)
        d_v_new = _dot_tn(scores, d_out) + _dot(k_out, d_state.astype(cd))
        acc_ref[i] = d_state * carried + _dot_tn(q_in, d_out) - _dot_tn(w, d_v_new.astype(cd))


def _grads_kernel(
    shape: _Shape, q_ref, k_ref, v_ref, gc_ref, gr_ref, b_ref, t_ref, s0_ref, ds_ref, do_ref,
    dq_ref, dk_ref, dv_ref, dgc_ref, dgr_ref, db_ref,
):
    cd, c, d_v = jnp.dtype(shape.cd), shape.chunk, shape.d_v
    lanes = lax.broadcasted_iota(jnp.int32, (c, shape.heads), 1)
    sublanes = lax.broadcasted_iota(jnp.int32, (shape.heads, c), 0)
    last = lax.broadcasted_iota(jnp.int32, (1, c), 1) == c - 1
    below = lax.broadcasted_iota(jnp.int32, (c, c), 0) > lax.broadcasted_iota(jnp.int32, (c, c), 1)
    dg_cols = jnp.zeros((c, shape.heads), F32)
    dg_rows = jnp.zeros((shape.heads, c), F32)
    d_betas = jnp.zeros((c, shape.heads), F32)
    dq = dk = None
    for i in range(shape.heads):
        ch = _chunk(shape, q_ref, k_ref, gc_ref, gr_ref, b_ref, t_ref, i)
        k32 = ch.k.astype(F32)
        v32 = v_ref[:, i * d_v : (i + 1) * d_v].astype(F32)
        gated = ch.beta * jnp.exp(ch.g)
        rhs = jnp.concatenate([v32 * ch.beta, k32 * gated], axis=1)  # [beta v | beta k exp(G)]
        uw = _dot(ch.t, rhs, HIGHEST)
        u, w = uw[:, :d_v], uw[:, d_v:].astype(cd)
        q_in32, q_in, k_out32, k_out, qk, scores, carried = _pieces(shape, ch)
        state = s0_ref[i]
        s_cd = state.astype(cd)
        v_new = (u - _dot(w, s_cd)).astype(cd)

        d_state = ds_ref[i]
        ds_cd = d_state.astype(cd)
        d_out = do_ref[:, i * d_v : (i + 1) * d_v].astype(cd)
        d_v_new = _dot_tn(scores, d_out) + _dot(k_out, ds_cd)
        d_q_in = _dot_nt(d_out, s_cd)
        d_scores = _dot_nt(d_out, v_new)
        d_k_out = _dot_nt(v_new, ds_cd)
        d_w = -_dot_nt(d_v_new.astype(cd), s_cd)
        d_carried = jnp.sum(state * d_state, keepdims=True)  # [1, 1]
        # Through [U | W] = T [beta v | beta k exp(G)] and the inverse:
        # d rhs = T^T d[U | W], and dA = -T^T (d[U | W] rhs^T) T^T = -(d rhs) [U | W]^T.
        d_rhs = _dot_tn(ch.t, jnp.concatenate([d_v_new, d_w], axis=1), HIGHEST)
        d_a = jnp.where(below, -_dot_nt(d_rhs, uw, HIGHEST), 0.0)
        d_bv, d_bk = d_rhs[:, :d_v], d_rhs[:, d_v:]
        dv_ref[:, i * d_v : (i + 1) * d_v] = (d_bv * ch.beta).astype(dv_ref.dtype)
        bk_row = jnp.sum(d_bk * k32, axis=1, keepdims=True)
        d_beta = jnp.sum(d_bv * v32, axis=1, keepdims=True) + bk_row * jnp.exp(ch.g)
        d_g = bk_row * gated
        d_k = d_bk * gated
        # Through A = tril(beta K K^T * D, -1).
        kk = _dot_nt(ch.k, ch.k)
        d_beta = d_beta + jnp.sum(d_a * kk * ch.decay, axis=1, keepdims=True)
        d_kk = (d_a * ch.beta * ch.decay).astype(cd)
        d_decay = d_a * ch.beta * kk
        d_k = d_k + _dot(d_kk, ch.k) + _dot_tn(d_kk, ch.k)
        # Through the scores Q K^T * D.
        d_qk = d_scores * ch.decay
        d_decay = d_decay + d_scores * qk
        d_qk_cd = d_qk.astype(cd)
        d_q = _dot(d_qk_cd, ch.k) + d_q_in * jnp.exp(ch.g)
        d_k = d_k + _dot_tn(d_qk_cd, ch.q) + d_k_out * jnp.exp(ch.g_last - ch.g)
        d_g = d_g + jnp.sum(d_q_in * q_in32, axis=1, keepdims=True)
        out_row = jnp.sum(d_k_out * k_out32, axis=1, keepdims=True)
        d_g = d_g - out_row
        d_last = jnp.sum(out_row, axis=0, keepdims=True) + d_carried * carried  # [1, 1]
        # Through D_ij = exp(G_i - G_j).
        e = d_decay * ch.decay
        d_g = d_g + jnp.sum(e, axis=1, keepdims=True)
        d_g_row = -jnp.sum(e, axis=0, keepdims=True) + jnp.where(last, d_last, 0.0)
        dg_cols = dg_cols + jnp.where(lanes == i, d_g, 0.0)
        dg_rows = dg_rows + jnp.where(sublanes == i, d_g_row, 0.0)
        d_betas = d_betas + jnp.where(lanes == i, d_beta, 0.0)
        # A key head's cotangent sums over the value heads it serves.
        dq = d_q if i % shape.per_key == 0 else dq + d_q
        dk = d_k if i % shape.per_key == 0 else dk + d_k
        if i % shape.per_key == shape.per_key - 1:
            kh = i // shape.per_key
            dq_ref[:, kh * shape.d_k : (kh + 1) * shape.d_k] = dq.astype(dq_ref.dtype)
            dk_ref[:, kh * shape.d_k : (kh + 1) * shape.d_k] = dk.astype(dk_ref.dtype)
    dgc_ref[...] = dg_cols
    dgr_ref[...] = dg_rows
    db_ref[...] = d_betas


def _specs(shape: _Shape, chunks: int, reverse: bool = False):
    """Block specs over the grid ``(sequence, head block, chunk)``: the
    token-major arrays, the per-head scalars' two layouts, and a chunk's
    states ``[heads, d_k, d_v]`` and inverses ``[heads, C, C]``."""
    h, c = shape.heads, shape.chunk
    at = (lambda j: chunks - 1 - j) if reverse else (lambda j: j)
    keys = pl.BlockSpec((None, c, h // shape.per_key * shape.d_k), lambda b, j, n: (b, at(n), j))
    values = pl.BlockSpec((None, c, h * shape.d_v), lambda b, j, n: (b, at(n), j))
    cols = pl.BlockSpec((None, None, c, h), lambda b, j, n: (b, j, at(n), 0))
    rows = pl.BlockSpec((None, None, None, h, c), lambda b, j, n: (b, j, at(n), 0, 0))
    states = pl.BlockSpec((None, None, h, shape.d_k, shape.d_v), lambda b, j, n: (b, at(n), j, 0, 0))
    inverses = pl.BlockSpec((None, None, h, c, c), lambda b, j, n: (b, at(n), j, 0, 0))
    return keys, values, cols, rows, states, inverses


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics, vmem_limit_bytes=64 * 1024 * 1024)


def _cols(shape: _Shape, x):
    """A per-token scalar ``[B, L, value heads]`` a column a head: ``[B,
    blocks, L, heads a block]``."""
    batch, seq_len, heads = x.shape
    return x.reshape(batch, seq_len, heads // shape.heads, shape.heads).transpose(0, 2, 1, 3)


def _rows(shape: _Shape, x):
    """The same a row a head: ``[B, blocks, chunks, heads a block, C]``."""
    batch, seq_len, heads = x.shape
    h, c = shape.heads, shape.chunk
    return x.reshape(batch, seq_len // c, c, heads // h, h).transpose(0, 3, 1, 4, 2)


def _uncols(cols):
    batch, blocks, seq_len, h = cols.shape
    return cols.transpose(0, 2, 1, 3).reshape(batch, seq_len, blocks * h)


def _unrows(rows):
    batch, blocks, chunks, h, c = rows.shape
    return rows.transpose(0, 2, 4, 1, 3).reshape(batch, chunks * c, blocks * h)


def _forward(shape: _Shape, q, k, v, g, beta, t):
    batch, seq_len, values = v.shape
    chunks, blocks = seq_len // shape.chunk, values // (shape.heads * shape.d_v)
    keys, vals, cols, rows, states, inverses = _specs(shape, chunks)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, shape),
        grid=(batch, blocks, chunks),
        in_specs=[keys, keys, vals, cols, rows, cols, inverses],
        out_specs=[vals, states],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, F32),
            jax.ShapeDtypeStruct((batch, chunks, values // shape.d_v, shape.d_k, shape.d_v), F32),
        ],
        scratch_shapes=[pltpu.VMEM((shape.heads, shape.d_k, shape.d_v), F32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=shape.interpret,
        name="delta_rule_fwd",
    )(q, k, v, _cols(shape, g), _rows(shape, g), _cols(shape, beta), t)


def _dstate(shape: _Shape, q, k, g, beta, t, d_out):
    """The state's cotangent each chunk ends with, ``[B, chunks, heads, d_k, d_v]``."""
    batch, seq_len, values = d_out.shape
    chunks, blocks = seq_len // shape.chunk, values // (shape.heads * shape.d_v)
    keys, vals, cols, rows, states, inverses = _specs(shape, chunks, reverse=True)
    return pl.pallas_call(
        functools.partial(_dstate_kernel, shape),
        grid=(batch, blocks, chunks),
        in_specs=[keys, keys, cols, rows, cols, inverses, vals],
        out_specs=states,
        out_shape=jax.ShapeDtypeStruct((batch, chunks, values // shape.d_v, shape.d_k, shape.d_v), F32),
        scratch_shapes=[pltpu.VMEM((shape.heads, shape.d_k, shape.d_v), F32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=shape.interpret,
        name="delta_rule_dstate",
    )(q, k, _cols(shape, g), _rows(shape, g), _cols(shape, beta), t, d_out)


def _grads(shape: _Shape, q, k, v, g, beta, t, starts, ends, d_out):
    """The cotangents of ``q``, ``k``, ``v``, ``g`` and ``beta``."""
    batch, seq_len, values = v.shape
    chunks, blocks = seq_len // shape.chunk, values // (shape.heads * shape.d_v)
    keys, vals, cols, rows, states, inverses = _specs(shape, chunks)
    g_cols, g_rows, b_cols = _cols(shape, g), _rows(shape, g), _cols(shape, beta)
    dq, dk, dv, dg_cols, dg_rows, db_cols = pl.pallas_call(
        functools.partial(_grads_kernel, shape),
        grid=(batch, blocks, chunks),
        in_specs=[keys, keys, vals, cols, rows, cols, inverses, states, states, vals],
        out_specs=[keys, keys, vals, cols, rows, cols],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype), jax.ShapeDtypeStruct(g_cols.shape, F32),
            jax.ShapeDtypeStruct(g_rows.shape, F32), jax.ShapeDtypeStruct(b_cols.shape, F32),
        ],
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=shape.interpret,
        name="delta_rule_grads",
    )(q, k, v, g_cols, g_rows, b_cols, t, starts, ends, d_out)
    return dq, dk, dv, _uncols(dg_cols) + _unrows(dg_rows), _uncols(db_cols)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rule(shape, q, k, v, g, beta):
    return _forward(shape, q, k, v, g, beta, _inverse(shape, k, g, beta))[0]


def _rule_fwd(shape, q, k, v, g, beta):
    t = _inverse(shape, k, g, beta)
    out, starts = _forward(shape, q, k, v, g, beta, t)
    return out, (q, k, v, g, beta, t, starts)


def _rule_bwd(shape, residuals, d_out):
    q, k, v, g, beta, t, starts = residuals
    ends = _dstate(shape, q, k, g, beta, t, d_out)
    return _grads(shape, q, k, v, g, beta, t, starts, ends, d_out)


_rule.defvjp(_rule_fwd, _rule_bwd)


def fits(q: jax.Array, v: jax.Array) -> bool:
    """Whether the kernels take these shapes: heads a whole number of lane
    tiles wide, whole chunks, value heads whole blocks of key heads."""
    seq_len, key_heads, d_k = q.shape[1:]
    return d_k % 128 == 0 and v.shape[-1] % 128 == 0 and seq_len % CHUNK == 0 and v.shape[2] % key_heads == 0


def delta_rule(
    q: jax.Array, k: jax.Array, v: jax.Array, log_decay: jax.Array, beta: jax.Array, *, compute_dtype,
    interpret: bool = False,
) -> jax.Array:
    """``chunked_delta_rule``'s contract, on the kernels: ``q`` (normalised,
    scaled), ``k`` (normalised) ``[B, L, key heads, d_k]`` (a key head serves
    ``value heads / key heads`` value heads in turn), ``v`` ``[B, L, value
    heads, d_v]``; ``log_decay`` and ``beta`` ``[B, L, value heads]``
    float32. Returns ``o`` ``[B, L, value heads, d_v]`` float32, ``S_0 = 0``.
    ``fits(q, v)`` says whether the shapes are the kernels'."""
    batch, seq_len, key_heads, d_k = q.shape
    heads, d_v = v.shape[2:]
    per_key = heads // key_heads
    block = next(h for h in range(min(HEADS_A_STEP, heads), 0, -1) if heads % h == 0 and h % per_key == 0)
    shape = _Shape(CHUNK, block, per_key, d_k, d_v, jnp.dtype(compute_dtype).name, interpret)
    chunks = seq_len // CHUNK
    g = jnp.cumsum(log_decay.astype(F32).reshape(batch, chunks, CHUNK, heads), axis=2).reshape(batch, seq_len, heads)
    out = _rule(
        shape, q.reshape(batch, seq_len, key_heads * d_k), k.reshape(batch, seq_len, key_heads * d_k),
        v.reshape(batch, seq_len, heads * d_v), g, beta.astype(F32),
    )
    return out.reshape(batch, seq_len, heads, d_v)
