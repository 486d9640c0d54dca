"""The Gated DeltaNet's depthwise causal convolution and its SiLU as a Pallas
kernel pair: ``silu(causal_conv(x, taps))`` forward and backward, each a pass
that reads and writes every full-size array once.

The mathematics is ``models/gdn_moe.py:causal_conv`` followed by SiLU (the XLA
form, kept off the chip and as the tests' oracle): over the first ``C``
channels of ``x`` ``[B, L, width]`` with ``taps`` ``[C, K]`` and zero history,
``a_t = sum_j taps_j x_{t - (K - 1) + j}`` summed in that order in float32,
``y = a sigmoid(a)`` in float32, written in ``x``'s dtype. Two
``pallas_call``s over a grid of (sequence, block of ``LANES`` channels, block
of ``ROWS`` tokens), each walking its block ``STRIP`` tokens at a time so that
a strip's float32 values stay in registers:

- ``causal_conv_fwd``: reads the block and the ``HALO`` rows before it (zero
  before the sequence's first token) and writes ``y``.
- ``causal_conv_bwd``: the blocks, and the strips within a block, in reverse
  (``"arbitrary"``); recomputes ``a`` from ``x``, ``da = dy silu'(a)`` in
  float32, then ``dx_t = sum_j taps_j da_{t + K - 1 - j}`` with the next
  strip's first ``HALO`` rows of ``da`` carried (across blocks in VMEM, zero
  after the last token), written in ``x``'s dtype (as the cast's transpose
  rounds it); the taps' cotangent accumulates in float32 across the strips
  and blocks and is written once a sequence.

``x`` is read straight from the full ``[B, L, width]`` array through the
blocks' index maps: the first ``C`` lanes, no slice and no float32 copy in
HBM. The residuals are ``x`` and ``taps``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tokens and channels a grid step and tokens a strip at most (chosen by
# measurement: PERF.md), and rows of the previous block read beside a block:
# one bf16 tile, so at most ``HALO + 1`` taps.
ROWS = 1024
LANES = 512
STRIP = 32
HALO = 16
F32 = jnp.float32


class _Shape(NamedTuple):
    """What the kernels are built for (hashable: a ``custom_vjp``'s static
    argument)."""

    rows: int
    lanes: int
    strip: int
    channels: int
    interpret: bool


def _block(size: int, limit: int, unit: int) -> int:
    """The largest multiple of ``unit`` up to ``limit`` that divides ``size``."""
    return next(b for b in range(min(limit, size) // unit * unit, 0, -unit) if size % b == 0)


def _first_window(x_ref, halo_ref, strip: int, first):
    """``x``'s rows ``[-HALO, strip)`` of the block in float32: the ``HALO``
    rows before it, zeros before the sequence's first token."""
    head = jnp.where(first, 0.0, halo_ref[...].astype(F32))
    return jnp.concatenate([head, x_ref[:strip, :].astype(F32)], axis=0)


def _window(x_ref, r, strip: int):
    """``x``'s rows ``[r - HALO, r + strip)`` of the block in float32, ``r >= HALO``."""
    return x_ref[pl.ds(r - HALO, strip + HALO), :].astype(F32)


def _shifted(w, taps_n: int, strip: int):
    """The strip's ``x_{t - (K - 1) + j}``, tap by tap, from its window."""
    start = HALO - (taps_n - 1)
    return [w[start + j : start + j + strip] for j in range(taps_n)]


def _taps_sum(terms, taps):
    """``sum_j taps_j terms_j``, the taps in order."""
    total = None
    for term, tap in zip(terms, taps):
        total = term * tap if total is None else total + term * tap
    return total


def _fwd_kernel(shape: _Shape, taps_ref, x_ref, halo_ref, y_ref):
    st, taps_n = shape.strip, taps_ref.shape[0]
    taps = [taps_ref[j : j + 1, :] for j in range(taps_n)]

    def emit(r, w):
        a = _taps_sum(_shifted(w, taps_n, st), taps)
        y_ref[pl.ds(r, st), :] = (a * jax.nn.sigmoid(a)).astype(y_ref.dtype)

    emit(0, _first_window(x_ref, halo_ref, st, pl.program_id(2) == 0))

    def strip(i, carry):
        r = pl.multiple_of(i * st, st)
        emit(r, _window(x_ref, r, st))
        return carry

    if shape.rows > st:
        lax.fori_loop(1, shape.rows // st, strip, 0)


def _bwd_kernel(shape: _Shape, taps_ref, x_ref, halo_ref, dy_ref, dx_ref, dtaps_ref, carry_ref):
    st, taps_n, strips = shape.strip, taps_ref.shape[0], shape.rows // shape.strip
    n = pl.program_id(2)
    taps = [taps_ref[j : j + 1, :] for j in range(taps_n)]

    @pl.when(n == 0)  # the sequence's last block: nothing after it
    def _():
        carry_ref[...] = jnp.zeros(carry_ref.shape, F32)
        dtaps_ref[...] = jnp.zeros(dtaps_ref.shape, F32)

    def step(r, w, da_after, sums):
        """The strip at row ``r`` from its window of ``x`` and the ``HALO``
        rows of ``da`` after it; returns its own first rows of ``da`` and the
        taps' sums, each folded to 8 rows."""
        xs = _shifted(w, taps_n, st)
        a = _taps_sum(xs, taps)
        s = jax.nn.sigmoid(a)
        da = dy_ref[pl.ds(r, st), :].astype(F32) * (s * (1.0 + a * (1.0 - s)))
        da_w = jnp.concatenate([da, da_after], axis=0)
        dx = _taps_sum([da_w[taps_n - 1 - j : taps_n - 1 - j + st] for j in range(taps_n)], taps)
        dx_ref[pl.ds(r, st), :] = dx.astype(dx_ref.dtype)
        folded = []
        for x_j, sum_j in zip(xs, sums):
            p = da * x_j
            while p.shape[0] > 8:
                p = p[: p.shape[0] // 2] + p[p.shape[0] // 2 :]
            folded.append(sum_j + p)
        return da[:HALO], tuple(folded)

    def strip(k, carry):
        r = pl.multiple_of((strips - 1 - k) * st, st)
        return step(r, _window(x_ref, r, st), *carry)

    carry = (carry_ref[...], tuple(jnp.zeros((8, shape.lanes), F32) for _ in range(taps_n)))
    if strips > 1:
        carry = lax.fori_loop(0, strips - 1, strip, carry)
    first = _first_window(x_ref, halo_ref, st, n == pl.num_programs(2) - 1)
    da_after, sums = step(0, first, *carry)
    carry_ref[...] = da_after
    for j in range(taps_n):
        dtaps_ref[j : j + 1, :] += jnp.sum(sums[j], axis=0, keepdims=True)


def _specs(shape: _Shape, taps_n: int, blocks: int, reverse: bool = False):
    """Block specs over the grid ``(sequence, channel block, token block)``:
    the taps ``[K, C]``, a token block of ``x``-shaped arrays, and the
    ``HALO`` rows before it (the first block's own first rows, unread)."""
    rows, lanes = shape.rows, shape.lanes
    at = (lambda n: blocks - 1 - n) if reverse else (lambda n: n)
    taps = pl.BlockSpec((taps_n, lanes), lambda b, j, n: (0, j))
    block = pl.BlockSpec((None, rows, lanes), lambda b, j, n: (b, at(n), j))
    halo = pl.BlockSpec((None, HALO, lanes), lambda b, j, n: (b, jnp.maximum(at(n) * (rows // HALO) - 1, 0), j))
    return taps, block, halo


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics, vmem_limit_bytes=64 * 1024 * 1024)


def _forward(shape: _Shape, x, taps_t):
    batch, seq_len, _ = x.shape
    blocks = seq_len // shape.rows
    taps, block, halo = _specs(shape, taps_t.shape[0], blocks)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, shape),
        grid=(batch, shape.channels // shape.lanes, blocks),
        in_specs=[taps, block, halo],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((batch, seq_len, shape.channels), x.dtype),
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=shape.interpret,
        name="causal_conv_fwd",
    )(taps_t, x, x)


def _backward(shape: _Shape, x, taps_t, dy):
    """``dx`` ``[B, L, C]`` in ``x``'s dtype and the taps' cotangent ``[B, K, C]``
    float32, a sequence each."""
    batch, seq_len, _ = x.shape
    taps_n = taps_t.shape[0]
    blocks = seq_len // shape.rows
    taps, block, halo = _specs(shape, taps_n, blocks, reverse=True)
    per_sequence = pl.BlockSpec((None, taps_n, shape.lanes), lambda b, j, n: (b, 0, j))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, shape),
        grid=(batch, shape.channels // shape.lanes, blocks),
        in_specs=[taps, block, halo, block],
        out_specs=[block, per_sequence],
        out_shape=[
            jax.ShapeDtypeStruct((batch, seq_len, shape.channels), x.dtype),
            jax.ShapeDtypeStruct((batch, taps_n, shape.channels), F32),
        ],
        scratch_shapes=[pltpu.VMEM((HALO, shape.lanes), F32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=shape.interpret,
        name="causal_conv_bwd",
    )(taps_t, x, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _conv_silu(shape, x, taps_t):
    return _forward(shape, x, taps_t)


def _conv_silu_fwd(shape, x, taps_t):
    return _forward(shape, x, taps_t), (x, taps_t)


def _conv_silu_bwd(shape, residuals, dy):
    x, taps_t = residuals
    dx, dtaps = _backward(shape, x, taps_t, dy)
    return jnp.pad(dx, ((0, 0), (0, 0), (0, x.shape[-1] - shape.channels))), jnp.sum(dtaps, axis=0)


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def fits(x: jax.Array, taps: jax.Array) -> bool:
    """Whether the kernels take these shapes: the channels whole lane tiles,
    the sequence whole tiles of rows, at most ``HALO + 1`` taps."""
    channels, taps_n = taps.shape
    return channels % 128 == 0 and x.shape[1] % HALO == 0 and taps_n <= HALO + 1


def causal_conv_silu(x: jax.Array, taps: jax.Array, *, interpret: bool = False) -> jax.Array:
    """``silu(causal_conv(x[..., :C], taps))`` in ``x``'s dtype, float32
    inside, on the kernels: ``x`` ``[B, L, width]``, ``taps`` ``[C, K]``.
    ``fits(x, taps)`` says whether the shapes are the kernels'."""
    channels = taps.shape[0]
    rows = _block(x.shape[1], ROWS, HALO)
    shape = _Shape(rows, _block(channels, LANES, 128), _block(rows, STRIP, HALO), channels, interpret)
    return _conv_silu(shape, x, taps.astype(F32).T)
