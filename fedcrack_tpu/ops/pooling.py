"""3x3 / stride-2 SAME max pooling with a scatter-free backward pass.

The encoder's pool (reference: Keras ``MaxPooling2D(3, strides=2, "same")``,
client_fit_model.py:113) takes its gradient through XLA's SelectAndScatter
by default, which on TPU lowers to a poorly-vectorized windowed scan —
BASELINE.md's round-2 profile put it (with the upsample-gradient reduces)
behind roughly a third of non-conv device time at the flagship shape.

This op keeps the forward EXACTLY as ``flax.linen.max_pool`` computes it
(same ``lax.reduce_window``, so forward parity tests — h5 import, mesh
golden values — pin it bit-for-bit) and swaps the backward for nine
strided-slice comparisons plus interior-dilated dense pads:

- for each window offset (dy, dx) in row-major order, the candidate slice
  ``c = xp[:, dy::2, dx::2, :]`` is compared against the pooled output;
- the FIRST offset (row-major, XLA SelectAndScatter's own visit order) that
  matches claims the output's cotangent (``claimed`` mask), so every output
  routes its gradient to exactly one input — tie-break identical to the
  default lowering;
- each claimed contribution returns to input coordinates via ``lax.pad``
  with interior dilation (a dense op the TPU vectorizes), not a scatter.

Cost: 9 elementwise compares over the output grid + 9 dense adds over the
(padded) input grid — all fusable, no serialized window walk.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import lax

_WINDOW = 3
_STRIDE = 2


def _same_pads(size: int) -> tuple[int, int, int]:
    """(out_size, pad_lo, pad_hi) for window 3 / stride 2 SAME."""
    out = -(-size // _STRIDE)  # ceil
    total = max((out - 1) * _STRIDE + _WINDOW - size, 0)
    lo = total // 2
    return out, lo, total - lo


def _reduce_window_max(x: jax.Array) -> jax.Array:
    return lax.reduce_window(
        x,
        -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min,
        lax.max,
        (1, _WINDOW, _WINDOW, 1),
        (1, _STRIDE, _STRIDE, 1),
        "SAME",
    )


@jax.custom_vjp
def max_pool_3x3_s2(x: jax.Array) -> jax.Array:
    """NHWC max pool, window 3x3, stride 2, SAME — forward identical to
    ``nn.max_pool(x, (3, 3), (2, 2), "SAME")``, backward scatter-free."""
    return _reduce_window_max(x)


def _fwd(x: jax.Array):
    out = _reduce_window_max(x)
    return out, (x, out)


def _bwd(res, g):
    """Accumulate per-offset contributions in OUTPUT-grid space, then
    interleave the four (row, col) parity classes into input coordinates
    with one reshape — input position ``p = 2i + dy - pad`` has row parity
    ``dy % 2``, so offsets partition cleanly by parity. A first draft
    instead dilated each contribution to the padded INPUT grid and summed
    nine full-size arrays; measured on a v5e that was 1.4-1.7x SLOWER than
    XLA's SelectAndScatter — the output-grid accumulation carries ~4x less
    HBM traffic."""
    x, out = res
    n, h, w, c = x.shape
    ho, lo_h, hi_h = _same_pads(h)
    wo, lo_w, hi_w = _same_pads(w)
    neg = jnp.asarray(-jnp.inf, x.dtype)
    xp = jnp.pad(x, ((0, 0), (lo_h, hi_h), (lo_w, hi_w), (0, 0)), constant_values=neg)

    zero = jnp.zeros((), g.dtype)
    u, v = ho + 1, wo + 1  # parity-class grids ([2*u, 2*v] covers the padded input)
    classes = {
        (a, b): jnp.zeros((n, u, v, c), g.dtype) for a in (0, 1) for b in (0, 1)
    }
    claimed = jnp.zeros(out.shape, jnp.bool_)
    for dy in range(_WINDOW):
        lim_y = dy + _STRIDE * (ho - 1) + 1
        for dx in range(_WINDOW):
            lim_x = dx + _STRIDE * (wo - 1) + 1
            cand = lax.slice(
                xp, (0, dy, dx, 0), (n, lim_y, lim_x, c), (1, _STRIDE, _STRIDE, 1)
            )
            # ~(cand < out) instead of (cand == out): identical for finite
            # values (cand <= out always, out being the window max), but a
            # NaN max still claims an offset — an equality mask would match
            # nothing (NaN != NaN) and silently ZERO the gradient where the
            # default lowering propagates it, hiding mid-training divergence.
            # The validity mask bars SAME-pad candidates from claiming: for
            # finite values they are -inf and lose anyway, but under a NaN
            # max every comparison is False and a pad cell at the window's
            # first offset would swallow the cotangent (the slice at the end
            # discards pad positions).
            ys = jnp.arange(ho) * _STRIDE + dy
            xs = jnp.arange(wo) * _STRIDE + dx
            valid = (
                ((ys >= lo_h) & (ys < lo_h + h))[:, None]
                & ((xs >= lo_w) & (xs < lo_w + w))[None, :]
            )
            m = ~(cand < out) & ~claimed & valid[None, :, :, None]
            claimed = claimed | m
            contrib = jnp.where(m, g, zero)
            # Padded-input row hit by window row i at this offset: 2i + dy.
            # Row parity a = dy % 2; class-row index u' = i + (1 if dy == 2).
            a, b = dy % 2, dx % 2
            ro, co = (1 if dy == 2 else 0), (1 if dx == 2 else 0)
            classes[(a, b)] = (
                classes[(a, b)].at[:, ro : ro + ho, co : co + wo, :].add(contrib)
            )
    # Interleave: stack the parity axis right after its grid axis, then
    # flatten — index order (u', a) reads back as padded row 2u' + a.
    cols = {
        a: jnp.stack([classes[(a, 0)], classes[(a, 1)]], axis=3).reshape(n, u, 2 * v, c)
        for a in (0, 1)
    }
    dxp = jnp.stack([cols[0], cols[1]], axis=2).reshape(n, 2 * u, 2 * v, c)
    dx_full = lax.slice(dxp, (0, lo_h, lo_w, 0), (n, lo_h + h, lo_w + w, c))
    return (dx_full.astype(x.dtype),)


max_pool_3x3_s2.defvjp(_fwd, _bwd)

# Grid-size crossover for the automatic choice, measured on a TPU v5e
# (round-level A/B, bf16, batch 16): the scatter-free backward is ~1.6x
# faster per train step when every pool grid is <= 64x64 (the reference's
# 128 px crop), but ~25% SLOWER than SelectAndScatter on a 128x128 grid
# (the first pool of a 256 px crop) — at that size its output-grid
# accumulation and interleave cost more HBM round-trips than XLA's
# windowed scan. Override with FEDCRACK_POOL_CUSTOM_MAX_GRID.
_CUSTOM_MAX_GRID = int(os.environ.get("FEDCRACK_POOL_CUSTOM_MAX_GRID", "64"))


def max_pool_auto(x: jax.Array) -> jax.Array:
    """3x3/s2 SAME max pool choosing the faster backward for this grid
    size (values identical either way; the choice is trace-time static)."""
    if max(x.shape[1], x.shape[2]) <= _CUSTOM_MAX_GRID:
        return max_pool_3x3_s2(x)
    return _reduce_window_max(x)


@jax.custom_vjp
def _max3_first(a: jax.Array, b: jax.Array, c: jax.Array) -> jax.Array:
    """Elementwise max of three whose cotangent goes, whole, to the FIRST
    argument that holds the max (SelectAndScatter's rule along one axis;
    ``jnp.maximum`` would split it between ties)."""
    return jnp.maximum(jnp.maximum(a, b), c)


def _max3_fwd(a, b, c):
    out = _max3_first(a, b, c)
    return out, (a, b, out)


def _max3_bwd(res, g):
    a, b, out = res
    # ~(x < out), not (x == out): a NaN max still claims (see _bwd above).
    first = ~(a < out)
    second = ~first & ~(b < out)
    zero = jnp.zeros((), g.dtype)
    return jnp.where(first, g, zero), jnp.where(second, g, zero), jnp.where(first | second, zero, g)


_max3_first.defvjp(_max3_fwd, _max3_bwd)


def max_pool_width_folded(x: jax.Array) -> jax.Array:
    """``nn.max_pool(3x3, stride 2, SAME)`` of ``[N,H,W,C]`` read from its
    width fold ``[N,H,W/2,2C]`` (channel ``dj*C + c`` is column ``2j+dj``),
    so that a producer that writes the fold never has to undo it: on the TPU
    the unfold is a relayout, two full-size copies each way.

    Output column ``j`` covers columns ``2j, 2j+1, 2j+2``: both phases of
    folded column ``j`` and phase 0 of ``j+1`` (``SAME`` pads one column on
    the right for even ``W``). Columns first, by :func:`_max3_first`, then
    rows, by a 3/2 ``reduce_window`` over ``H`` alone: the first row that
    holds the window's max and the first column within it, which is the
    row-major first match of XLA's SelectAndScatter, so values AND gradient
    routing are those of the unfolded pool."""
    c = x.shape[-1] // 2
    even, odd = x[..., :c], x[..., c:]
    neg = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
    after = jnp.pad(even[:, :, 1:], ((0, 0), (0, 0), (0, 1), (0, 0)), constant_values=neg)
    return lax.reduce_window(
        _max3_first(even, odd, after), neg, lax.max,
        (1, _WINDOW, 1, 1), (1, _STRIDE, 1, 1), "SAME",
    )
