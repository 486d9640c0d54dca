"""Residual U-Net for crack segmentation, as a Flax module.

Capability parity with the reference's Keras builder
(reference: client_fit_model.py:92-150, identical in test/Segmentation.py:102-159):

- stem ``Conv(32, 3x3, stride 2, SAME)`` + BN + ReLU
- encoder blocks, filters (64, 128, 256): two ``ReLU -> SeparableConv -> BN``
  then ``MaxPool(3x3, stride 2, SAME)``, with a strided 1x1-conv residual add.
  Below 128 input channels a separable convolution runs as ONE composed
  convolution (see "The encoder's separable convolutions" below)
- decoder blocks, filters (256, 128, 64, 32): two ``ReLU -> ConvT(3x3) -> BN``
  then nearest x2 upsampling, with an upsampled 1x1-conv residual add. The
  upsampled tensor is never built: each block hands its LOW-resolution
  output on and the next block's two readers take it directly, and the last
  block stays packed four pixels to a channel group up to the head's logits
  (see "The decoder's upsample" below)
- head ``Conv(1, 1x1)`` — this module returns **logits**; the reference bakes
  sigmoid into the head (client_fit_model.py:145) and we apply it in the loss
  (numerically stable) and in ``predict``.

TPU-first choices: NHWC layout, optional bfloat16 compute with float32 params,
static shapes throughout (everything jit/pjit-traceable), BatchNorm hyperparams
matched to Keras defaults (momentum 0.99, eps 1e-3) so an h5 weight import is
tensor-for-tensor (SURVEY.md §7 "hard parts").

Spatial bookkeeping: stem /2 and three pools /2 take 128x128 -> 8x8 at the
bottleneck; four x2 upsampling stages return to 128x128, matching the
full-resolution masks (SURVEY.md §2.3).

Layout transforms (``ModelConfig.stem_layout`` / ``res_layout``): exact
re-expressions of the same math targeting the HBM-bound narrow-channel convs.
Parameter shapes NEVER change — the transformed kernels are derived
in-forward from the reference weights
(``fold_stem_kernel_s2d`` and friends; the derivation is linear, so
gradients flow back to the reference parameterization and training is the
same program family either way), which keeps h5 imports/exports, FedAvg,
the wire format and checkpoints layout-blind.

Why "s2d" is a width fold and not the fully collapsed stride-1 conv: XLA
contracts a conv's reduction dimensions in (kh, kw, c) order, and a layout
transform is bit-exact iff it preserves the relative order of the NONZERO
terms (inserting exact zero taps anywhere is a no-op; reordering real taps
reassociates the float sum). Folding W into channels keeps that order
(per kh: kw-major, zeros appended); folding H too would need tap (0,2) to
land between (0,1) and (1,0), but (0,1)/(1,0) share a 2x2 block while (0,2)
does not — impossible for any channel permutation. The fully folded variant
is still offered as ``stem_layout="s2d_full"`` (ROADMAP D4), with its
~1-ulp reassociation documented rather than hidden (measured in
tests/test_model.py).

The decoder's upsample (``UpsampledConvT``, ``fold_upsample_into_kernel``,
``PhaseBatchNorm``; the last block's ``PackedConvT`` and ``PhaseConv1x1``).
Keras upsamples a block's output and the next block reads the big tensor
twice. Both readers commute with the replication, so neither needs it:

- a 1x1 conv of an upsampled image is the upsample of the 1x1 conv, so
  ``dec{i}_res`` runs on the low-resolution tensor and its ``Cout``-channel
  result is replicated into the add (the head does the same with the last
  upsample);
- a 3x3 conv of an upsampled image reads, for each of the four output
  phases ``(di, dj)`` of a low-resolution pixel, only that pixel's 3x3
  low-resolution neighbours, with taps that are sums of the original ones.
  Per axis, for low-resolution offsets (-1, 0, +1) and taps ``k0,k1,k2``:
  phase 0 takes ``(k0, k1+k2, 0)`` and phase 1 takes ``(0, k0+k1, k2)``;
  zero padding of the upsampled image is zero padding of the small one. So
  ``dec{i}_convT1`` is ONE stride-1 conv of the low-resolution tensor into
  ``4*Cout`` channels (phase-major) followed by ``depth_to_space``: the
  same multiply-adds over a quarter of the input bytes and four times the
  MXU columns. ``relu`` commutes with replication and moves with it.

``dec{i}_bn1`` and its ``relu`` then run on the conv's packed output
(``PhaseBatchNorm``: the moments of the four phase groups together are the
moments of the unpacked tensor): at 32-64 channels the unpacked tensor fills
a quarter or half of the TPU's 128 lanes, and every pass over it pays for
the padding. A middle block unpacks after them (``depth_to_space``), before
``convT2``: the next block's two readers want the unpacked low-resolution
tensor.

The LAST block never unpacks a feature tensor (``PackedConvT``,
``fold_kernel_phases``, ``PhaseConv1x1``). Its packed ``[N,h,w,4C]`` is
``convT1``'s own output, and everything after it can read the pack:

- ``convT2``, a stride-1 ``SAME`` 3x3 correlation of the unpacked image.
  An axis at a time, output phase ``d`` and tap ``a`` read unpacked position
  ``2i + d + a - 1 = 2(i+o) + e``, low-resolution offset ``o`` and input
  phase ``e``: ``(d,a) -> (o,e)`` is ``(0,0)->(-1,1)``, ``(0,1)->(0,0)``,
  ``(0,2)->(0,1)``, ``(1,0)->(0,0)``, ``(1,1)->(0,1)``, ``(1,2)->(+1,0)``.
  So it is ONE stride-1 ``SAME`` conv of the pack with a ``[3,3,4C,4C]``
  kernel. Each ``(o,e,d)`` is reached by at most one tap ``a``, so each of
  the 36 non-zero ``C x C`` blocks (of 144) holds exactly one original tap:
  no tap sums, the same products, only the order of accumulation differs;
  zero padding of the image is zero padding of the pack. Four times the
  multiply-adds on four times the MXU columns (128 instead of 32): the same
  MXU time for a quarter of the bytes. The bias is tiled four times.
- ``bn2`` is ``PhaseBatchNorm`` again, and the residual is added on the
  pack: ``dec{i}_res`` writes it replicated into the four phases, ``4C``
  columns from its kernel and bias tiled four times, so the replication is a
  conv's own output on full lanes (``jnp.tile`` of the ``C``-channel result
  is the same values and 1.7-4.9% of a step slower on the v5e).
- the head, a 1x1 conv, reads one pixel: on the pack it is a block-diagonal
  ``[1,1,4C,4]`` kernel (float32, as before) giving four logits a
  low-resolution pixel. ``depth_to_space`` of THOSE and the deferred
  ``upsample2x`` give the ``[N,4h,4w,1]`` logits.

Which block keeps the pack is read off its shapes: the last one (only the
head can read a pack; a middle block's reader cannot), and only where the
pack fills the lanes exactly or less, ``4*Cout <= 128``. A wider pack pays
twice the MXU time to halve its passes' bytes. The one such block measured
on the v5e is a MIDDLE one, ``dec2`` (256 packed channels), which must also
unpack after its add: 0.4-0.5% of a step slower at both of the benchmark's
shapes (PERF.md section 6, PR 31). A last block wider than 32 channels
never unpacks; no configuration has one, so that side of the rule is
unmeasured (PERF.md section 7).

Each identity is exact in real arithmetic for every shape, so there is one
path, in train and eval mode alike; in floats the tap sums of ``convT1`` and
the moments reassociate (~1e-6 in float32; in bf16 ``k1+k2`` is summed in
float32 and rounded once) and the packed ``convT2`` and head accumulate the
same products in another order. Every packed kernel is derived in-forward
from the float32 parameter under the rules above and cast once; no
parameter, statistic or name differs from the Keras layout's.

The encoder's separable convolutions (``SeparableConv``,
``compose_separable_kernel``, ``fold_kernel_width``,
``ops.pooling.max_pool_width_folded``).
No bias and no nonlinearity lies between Keras's depthwise 3x3 and its
pointwise 1x1, so the pair is ONE dense 3x3 convolution with the kernel

    ``K[kh,kw,c,f] = depthwise[kh,kw,0,c] * pointwise[0,0,c,f]``

(the product in the parameters' float32, cast once to the compute dtype;
the bias as before). The TPU lays ``[N,H,W,C]`` out with ``C`` on its 128
lanes, so a tensor of 32 or 64 channels is padded 4x or 2x in HBM, every
pass over one pays for the padding, and the depthwise output (and in the
backward pass its cotangent) is such a tensor that exists only because the
convolution runs as two; the depthwise itself never reaches the MXU.
Composed, neither is written or read, ``dK`` comes from one dense
convolution and reaches both parameters through the product (linear in
each). One algorithm, three forms, chosen by the shapes the layer sees:

- ``Cin`` >= ``_COMPOSE_BELOW`` (128: ``enc1_sep2``, ``enc2``): the two
  convolutions as stated; the lanes are full there and composing would cost
  9x the arithmetic.
- below it, **composed**: ``[3,3,Cin,Cout]`` on ``[N,H,W,Cin]``
  (``enc1_sep1``, and any layer whose width is odd).
- composed and **lane-dense**, where two columns of ``Cout`` channels fit the
  MXU's 128 and the width is even (``enc0``): the conv writes two adjacent
  columns a pixel, ``[N,H,W/2,2Cout]`` with channel ``dj*Cout + f`` for
  column ``2j+dj``. Logically that is a row-major reshape of
  ``[N,H,W,Cout]``, but on the chip's tiled layout (``{3,0,2,1:T(8,128)}``:
  channels on lanes, then the BATCH on sublanes) it swaps the column phase
  with the batch axis, two full-size copies each way, so the fold is only
  ever a conv's own output and nothing unfolds it: ``enc0_sep1`` reads the
  stem's output as it is, through a ``[3,4,Cin,2Cout]`` kernel at stride
  (1,2); ``enc0_bn1`` + ``relu`` run on the fold (``PhaseBatchNorm`` with 2
  phases); ``enc0_sep2`` reads and writes it through a ``[3,3,2Cin,2Cout]``
  kernel (half its blocks exact zeros: twice the arithmetic, full MXU
  columns, half the bytes); ``enc0_bn2`` runs on it; and the 3x3/2 pool
  reads it (``max_pool_width_folded``: columns, then rows; values and
  gradient routing of the unfolded pool) and writes the plain
  ``[N,H/2,W/2,Cout]`` the residual add wants.

Measured on the v5e (PERF.md section 6, PR 29): at ``enc0`` the composed
form alone takes 8% off the whole step, the lane-dense one 14% (256 px) and
11% (512 px); a form that folds and then unfolds for the pool lands between
them. The threshold is the lane count because that is where the padding
ends. Exact in real arithmetic; in floats the composed conv sums
``dw*pw*x`` in one contraction where the separable one rounds the depthwise
sum first (bf16: the kernel is rounded once where every depthwise output
pixel was). No parameter, statistic or name differs from the Keras layout's.
"""

from __future__ import annotations

import os
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedcrack_tpu.configs import ModelConfig
from fedcrack_tpu.ops.pooling import max_pool_auto, max_pool_width_folded

# A/B escape hatch: FEDCRACK_POOL=default routes the encoder pool through
# flax's nn.max_pool (XLA SelectAndScatter backward) instead of the
# grid-size-aware custom VJP — for benchmarking the two lowerings against
# each other on real hardware. Values are identical either way.
_USE_CUSTOM_POOL = os.environ.get("FEDCRACK_POOL", "custom") != "default"

# Keras BatchNormalization defaults (the reference relies on them).
_BN_MOMENTUM = 0.99
_BN_EPSILON = 1e-3

_glorot = nn.initializers.glorot_uniform()

# Output columns of the MXU on the v5e, the chip both forms of
# `UpsampledConvT` were measured on: where it changes form.
_MXU_COLUMNS = 128
# A `SeparableConv` whose input has fewer channels than this runs as one
# composed conv: the chip's 128 lanes, where the padding ends. Measured on the
# v5e at 32 and 64 channels (PERF.md section 6, PR 29: at `enc1_sep1`, 64 ->
# 128 on the half-resolution grid, a wash: -1.1% of a step at 512 px, +0.8% at
# 256 px); from 128 on composing costs 9x the arithmetic for nothing.
_COMPOSE_BELOW = 128


def space_to_depth(x: jax.Array) -> jax.Array:
    """``[N,H,W,C] -> [N,H/2,W/2,4C]``: 2x2 pixel blocks to channels,
    block-position-major (packed channel = ``(di*2+dj)*C + c`` for the pixel
    at block offset ``(di, dj)``). Pure data movement — the canonical packed
    input layout for ``stem_layout="s2d"``/``"s2d_full"``; the host-side
    twin for staging is ``data.pipeline.space_to_depth_images``."""
    n, h, w, c = x.shape
    if h % 2 or w % 2:
        raise ValueError(f"space_to_depth needs even H,W; got {(h, w)}")
    x = x.reshape(n, h // 2, 2, w // 2, 2, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, h // 2, w // 2, 4 * c)


def depth_to_space(x: jax.Array) -> jax.Array:
    """Inverse of :func:`space_to_depth`."""
    n, h2, w2, c4 = x.shape
    if c4 % 4:
        raise ValueError(f"depth_to_space needs channels % 4 == 0; got {c4}")
    c = c4 // 4
    x = x.reshape(n, h2, w2, 2, 2, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, 2 * h2, 2 * w2, c)


def fold_stem_kernel_s2d(kernel: jax.Array) -> jax.Array:
    """Reference stem kernel ``[3,3,C,F]`` -> width-folded ``[3,2,2C,F]``.

    Tap ``(kh, kw, c)`` lands at ``[kh, kw//2, (kw%2)*C + c]``; the unused
    slot ``(kh, bw=1, dj=1)`` is exact zero. Preserves XLA's (kh, kw, c)
    contraction order, so the folded conv (strides (2,1), padding
    ((0,1),(0,1)) on the width-packed input) is BIT-EXACT vs the reference
    stem. Linear in ``kernel`` — differentiable, gradients flow back to the
    reference parameterization."""
    if kernel.shape[:2] != (3, 3):
        raise ValueError(f"expected a 3x3 stem kernel, got {kernel.shape}")
    k0 = jnp.concatenate([kernel[:, 0], kernel[:, 1]], axis=1)  # [3, 2C, F]
    k1 = jnp.concatenate([kernel[:, 2], jnp.zeros_like(kernel[:, 2])], axis=1)
    return jnp.stack([k0, k1], axis=1)  # [3, 2, 2C, F]


def unfold_stem_kernel_s2d(folded: jax.Array) -> jax.Array:
    """Exact inverse of :func:`fold_stem_kernel_s2d` (weight export for a
    kernel held in the folded layout)."""
    if folded.shape[:2] != (3, 2):
        raise ValueError(f"expected a [3,2,2C,F] folded kernel, got {folded.shape}")
    c = folded.shape[2] // 2
    k0, k1 = folded[:, 0], folded[:, 1]
    return jnp.stack([k0[:, :c], k0[:, c:], k1[:, :c]], axis=1)


def fold_stem_kernel_s2d_full(kernel: jax.Array) -> jax.Array:
    """Reference stem kernel ``[3,3,C,F]`` -> fully folded ``[2,2,4C,F]``
    for the stride-1 conv on the space-to-depth input.

    Tap ``(kh, kw, c)`` lands at ``[kh//2, kw//2, ((kh%2)*2 + kw%2)*C + c]``;
    the 2x2 block structure forces taps of different kh rows into one packed
    block, which REORDERS the contraction — mathematically identical (same
    multiplies plus exact zeros) but reassociated, so agreement with the
    reference stem is ~1 ulp rather than bitwise (module docstring)."""
    if kernel.shape[:2] != (3, 3):
        raise ValueError(f"expected a 3x3 stem kernel, got {kernel.shape}")
    zeros = jnp.zeros_like(kernel[0, 0])  # [C, F]

    def tap(kh: int, kw: int) -> jax.Array:
        return kernel[kh, kw] if kh < 3 and kw < 3 else zeros

    rows = []
    for bh in range(2):
        row = [
            jnp.concatenate(
                [tap(2 * bh + di, 2 * bw + dj) for di in (0, 1) for dj in (0, 1)],
                axis=0,
            )
            for bw in range(2)
        ]
        rows.append(jnp.stack(row, axis=0))
    return jnp.stack(rows, axis=0)  # [2, 2, 4C, F]


def unfold_stem_kernel_s2d_full(folded: jax.Array) -> jax.Array:
    """Exact inverse of :func:`fold_stem_kernel_s2d_full`."""
    if folded.shape[:2] != (2, 2):
        raise ValueError(f"expected a [2,2,4C,F] folded kernel, got {folded.shape}")
    c = folded.shape[2] // 4
    taps = []
    for kh in range(3):
        row = []
        for kw in range(3):
            lo = ((kh % 2) * 2 + kw % 2) * c
            row.append(folded[kh // 2, kw // 2, lo : lo + c])
        taps.append(jnp.stack(row, axis=0))
    return jnp.stack(taps, axis=0)


def pack_res_kernel(kernel: jax.Array) -> jax.Array:
    """Reference 1x1 residual kernel ``[1,1,C,F]`` -> ``[1,1,4C,F]`` for the
    stride-1 conv on the space-to-depth-packed block input: the real taps
    (block offset (0,0) — exactly the pixels a stride-2 1x1 conv reads) stay
    FIRST, zero-extension follows, so the contraction order of the nonzero
    terms is preserved and the packed projection is bit-exact."""
    if kernel.shape[:2] != (1, 1):
        raise ValueError(f"expected a 1x1 residual kernel, got {kernel.shape}")
    zeros = jnp.zeros(
        (1, 1, 3 * kernel.shape[2], kernel.shape[3]), dtype=kernel.dtype
    )
    return jnp.concatenate([kernel, zeros], axis=2)


def unpack_res_kernel(packed: jax.Array) -> jax.Array:
    """Exact inverse of :func:`pack_res_kernel`."""
    if packed.shape[2] % 4:
        raise ValueError(f"expected a [1,1,4C,F] packed kernel, got {packed.shape}")
    return packed[:, :, : packed.shape[2] // 4]


def fold_upsample_into_kernel(kernel: jax.Array) -> jax.Array:
    """Reference 3x3 kernel ``[3,3,C,F]`` -> phase-folded ``[3,3,C,4F]``: the
    stride-1 ``SAME`` conv of a LOW-resolution image with the result, then
    :func:`depth_to_space`, equals the stride-1 ``SAME`` conv of
    ``upsample2x(image)`` with ``kernel`` (module docstring). Output channel
    ``(di*2+dj)*F + f`` is output phase ``(di, dj)``, the order
    ``depth_to_space`` unpacks. Sum the taps in the parameters' dtype and
    cast after. Linear in ``kernel``: gradients flow back to the reference
    parameterization."""
    if kernel.shape[:2] != (3, 3):
        raise ValueError(f"expected a 3x3 kernel, got {kernel.shape}")

    def phases(k: jax.Array, axis: int) -> tuple[jax.Array, jax.Array]:
        k0, k1, k2 = (jax.lax.slice_in_dim(k, t, t + 1, axis=axis) for t in range(3))
        zero = jnp.zeros_like(k0)
        return (
            jnp.concatenate([k0, k1 + k2, zero], axis=axis),
            jnp.concatenate([zero, k0 + k1, k2], axis=axis),
        )

    return jnp.concatenate(
        [cols for rows in phases(kernel, 0) for cols in phases(rows, 1)], axis=3
    )


def upsample2x(x: jax.Array) -> jax.Array:
    """Nearest-neighbor x2 upsampling on NHWC, Keras ``UpSampling2D(2)`` semantics.

    One broadcast materializes both axes at once: two chained ``jnp.repeat``
    calls lower to two full-tensor HBM round-trips, which profiling showed
    were ~30% of forward device time at the flagship shape.
    """
    n, h, w, c = x.shape
    x = jnp.broadcast_to(x[:, :, None, :, None, :], (n, h, 2, w, 2, c))
    return x.reshape(n, 2 * h, 2 * w, c)


def compose_separable_kernel(depthwise: jax.Array, pointwise: jax.Array) -> jax.Array:
    """Depthwise ``[3,3,1,C]`` x pointwise ``[1,1,C,F]`` -> the dense
    ``[3,3,C,F]`` kernel ``K[kh,kw,c,f] = depthwise[kh,kw,0,c] *
    pointwise[0,0,c,f]``, whose conv is ``pointwise(depthwise(x))`` (module
    docstring, "The encoder's separable convolutions"). Multiply in the
    parameters' dtype and cast after. Linear in each factor: gradients flow
    back to both parameters through the product."""
    if depthwise.shape[:3] != (3, 3, 1) or pointwise.shape[:3] != (1, 1, depthwise.shape[3]):
        raise ValueError(f"expected [3,3,1,C] and [1,1,C,F], got {depthwise.shape}, {pointwise.shape}")
    return depthwise[:, :, 0, :, None] * pointwise[0, 0]


def fold_kernel_width(kernel: jax.Array, folded_input: bool) -> jax.Array:
    """Reference 3x3 kernel ``[3,3,C,F]`` -> the kernel that writes the
    stride-1 ``SAME`` conv WIDTH-FOLDED, ``[N,H,W/2,2F]`` with output channel
    ``dj*F + f`` for column ``2j+dj`` (a row-major reshape of ``[N,H,W,F]``).
    Column ``2j+dj`` reads columns ``2j+dj-1 .. 2j+dj+1``.

    ``folded_input``: from the input folded the same way, ``[N,H,W/2,2C]``,
    a ``[3,3,2C,2F]`` kernel at stride 1, ``SAME``: input column ``2(j+b)+di``
    meets output phase ``dj`` at tap ``kw = 2b + di - dj + 1`` (half the
    blocks are exact zeros). Otherwise from ``[N,H,W,C]`` as it is, a
    ``[3,4,C,2F]`` kernel at stride (1,2), padding ((1,1),(1,1)): window
    column ``u`` is input column ``2j-1+u`` and meets phase ``dj`` at tap
    ``kw = u - dj``. Linear in ``kernel``."""
    if kernel.shape[:2] != (3, 3):
        raise ValueError(f"expected a 3x3 kernel, got {kernel.shape}")
    zero = jnp.zeros_like(kernel[:, 0])  # [3, C, F]

    def tap(kw: int) -> jax.Array:
        return kernel[:, kw] if 0 <= kw <= 2 else zero

    if not folded_input:
        return jnp.stack(
            [jnp.concatenate([tap(u - dj) for dj in (0, 1)], axis=-1) for u in range(4)], axis=1
        )
    return jnp.stack(
        [
            jnp.concatenate(
                [
                    jnp.concatenate([tap(2 * b + di - dj + 1) for dj in (0, 1)], axis=-1)
                    for di in (0, 1)
                ],
                axis=-2,
            )
            for b in (-1, 0, 1)
        ],
        axis=1,
    )


def fold_kernel_phases(kernel: jax.Array) -> jax.Array:
    """Reference 3x3 kernel ``[3,3,C,F]`` -> ``[3,3,4C,4F]``: the stride-1
    ``SAME`` conv that reads AND writes the 2x2 pack of :func:`space_to_depth`
    (``[N,h,w,4C] -> [N,h,w,4F]``, channel ``(di*2+dj)*C + c``) and is the
    stride-1 ``SAME`` conv of the unpacked image with ``kernel``. An axis at a
    time, output phase ``d`` and tap ``a`` read low-resolution offset ``o``
    and input phase ``e`` with ``2o + e = d + a - 1``, which is
    :func:`fold_kernel_width`'s rule: columns first, then rows over the
    ``[3,3,2C,2F]`` result, so the row phase lands major. 36 of the 144
    ``C x F`` blocks hold one original tap each, the rest exact zeros.
    Linear in ``kernel``."""
    columns = fold_kernel_width(kernel, folded_input=True)
    return fold_kernel_width(columns.swapaxes(0, 1), folded_input=True).swapaxes(0, 1)


class _ConvParams(nn.Module):
    """The parameters of an ``nn.Conv`` under its names (``kernel`` glorot,
    ``bias`` zeros), for a parent that runs its own convolution with them."""

    kernel_shape: tuple[int, ...]
    use_bias: bool
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self) -> tuple[jax.Array, jax.Array | None]:
        kernel = self.param("kernel", _glorot, self.kernel_shape, self.param_dtype)
        if not self.use_bias:
            return kernel, None
        return kernel, self.param(
            "bias", nn.initializers.zeros_init(), self.kernel_shape[-1:], self.param_dtype
        )


class SeparableConv(nn.Module):
    """Depthwise 3x3 + pointwise 1x1, Keras ``SeparableConv2D`` semantics.

    Keras puts the bias only on the pointwise projection; the depthwise stage
    is bias-free with depth_multiplier=1. Parameters are those of the two
    ``nn.Conv`` the layer is made of (``depthwise/kernel`` ``[3,3,1,C]``,
    ``pointwise/kernel`` ``[1,1,C,F]`` + ``pointwise/bias``; glorot, zeros).

    One algorithm in three forms, chosen by the shapes it sees (module
    docstring, "The encoder's separable convolutions"). ``C`` from
    ``_COMPOSE_BELOW`` on: the two convolutions as stated. Below it: ONE conv
    with the composed ``[3,3,C,F]`` kernel, and where two columns of ``F``
    channels fit the MXU's columns and the width is even, that conv writes
    its output width-folded, ``[N,H,W/2,2F]``. ``in_fold`` = 2 says that ``x``
    arrives folded the same way, ``[N,H,W/2,2C]``, as the layer before wrote
    it."""

    features: int
    in_fold: int = 1
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        c, f = x.shape[-1] // self.in_fold, self.features
        depthwise, _ = _ConvParams((3, 3, 1, c), False, self.param_dtype, name="depthwise")()
        pointwise, bias = _ConvParams((1, 1, c, f), True, self.param_dtype, name="pointwise")()
        x = x.astype(self.dtype)
        bias = bias.astype(self.dtype)

        def conv(x, kernel, strides=(1, 1), padding="SAME", groups=1):
            return jax.lax.conv_general_dilated(
                x, kernel.astype(self.dtype), window_strides=strides, padding=padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=groups,
            )

        if c >= _COMPOSE_BELOW:
            return conv(conv(x, depthwise, groups=c), pointwise) + bias
        kernel = compose_separable_kernel(depthwise, pointwise)
        if self.in_fold == 2:
            return conv(x, fold_kernel_width(kernel, folded_input=True)) + jnp.tile(bias, 2)
        if 2 * f <= _MXU_COLUMNS and x.shape[2] % 2 == 0:
            folded = fold_kernel_width(kernel, folded_input=False)
            return conv(x, folded, (1, 2), [(1, 1), (1, 1)]) + jnp.tile(bias, 2)
        return conv(x, kernel) + bias


class S2DStemConv(nn.Module):
    """The stem conv executed in a space-to-depth layout.

    Declares the SAME parameters as the reference ``nn.Conv`` stem — kernel
    ``[3,3,C,F]`` (glorot) and bias ``[F]`` (zeros) under the same module
    name — so the variables pytree, its initialization values (same RNG
    fold), h5 import/export and FedAvg are all identical to the reference
    layout; only the executed program changes. Accepts the reference input
    ``[N,H,W,C]`` (packed on device: the width fold is a FREE row-major
    reshape) or the pre-packed ``[N,H/2,W/2,4C]`` of :func:`space_to_depth`
    (staged that way by ``parallel.driver``-style loops).

    ``layout="s2d"``: width-folded ``[3,2,2C,F]`` kernel, strides (2,1) —
    bit-exact (contraction-order-preserving, see module docstring).
    ``layout="s2d_full"``: fully folded ``[2,2,4C,F]`` kernel, stride 1 —
    mathematically identical, reassociated (~1 ulp).
    """

    features: int
    in_channels: int
    layout: str  # "s2d" | "s2d_full"
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        c = self.in_channels
        kernel = self.param("kernel", _glorot, (3, 3, c, self.features), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros_init(), (self.features,), self.param_dtype)
        kernel = kernel.astype(self.dtype)
        bias = bias.astype(self.dtype)

        packed = x.shape[-1] == 4 * c
        if not packed and x.shape[-1] != c:
            raise ValueError(
                f"stem input has {x.shape[-1]} channels; expected {c} "
                f"(reference layout) or {4 * c} (space_to_depth-packed)"
            )
        n = x.shape[0]
        if self.layout == "s2d":
            if packed:
                h2, w2 = x.shape[1], x.shape[2]
                # Unpack H only: [N,H/2,W/2,4C] -> [N,H,W/2,2C] (data movement).
                x = x.reshape(n, h2, w2, 2, 2, c)
                x = x.transpose(0, 1, 3, 2, 4, 5).reshape(n, 2 * h2, w2, 2 * c)
            else:
                h, w = x.shape[1], x.shape[2]
                # Width fold is a pure row-major reshape — no copy.
                x = x.reshape(n, h, w // 2, 2 * c)
            folded = fold_stem_kernel_s2d(kernel)
            strides = (2, 1)
        else:  # "s2d_full"
            if not packed:
                x = space_to_depth(x)
            folded = fold_stem_kernel_s2d_full(kernel)
            strides = (1, 1)
        y = jax.lax.conv_general_dilated(
            x,
            folded,
            window_strides=strides,
            padding=[(0, 1), (0, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return y + bias


class PackedResConv(nn.Module):
    """An encoder residual projection — reference ``Conv(F, 1x1, stride 2)``
    — executed as a stride-1 1x1 conv over the space-to-depth-packed block
    input with a zero-extended ``[1,1,4C,F]`` kernel (bit-exact: the packed
    block offset (0,0) channels are exactly the pixels the strided conv
    reads, and they stay first in the contraction). Parameters are identical
    to the reference ``nn.Conv`` (kernel ``[1,1,C,F]`` glorot + bias)."""

    features: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        c = x.shape[-1]
        kernel = self.param("kernel", _glorot, (1, 1, c, self.features), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros_init(), (self.features,), self.param_dtype)
        y = jax.lax.conv_general_dilated(
            space_to_depth(x),
            pack_res_kernel(kernel.astype(self.dtype)),
            window_strides=(1, 1),
            padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return y + bias.astype(self.dtype)


class UpsampledConvT(nn.Module):
    """``ConvTranspose(F, 3x3, SAME)(upsample2x(x))`` without the upsample,
    PACKED: returns ``[N,h,w,4F]`` whose :func:`depth_to_space` is the
    ``[N,2h,2w,F]`` result (module docstring). Parameters are identical to
    the reference ``nn.ConvTranspose`` (kernel ``[3,3,C,F]`` glorot + bias; a
    stride-1 flax ``ConvTranspose`` is a plain correlation with the unflipped
    kernel).

    One algorithm in two forms, chosen by ``F``, which the MXU's width
    decides: below its 128 columns the dense ``[3,3,C,4F]`` conv (5 of a
    phase's 9 taps are zero, but width is what a narrow conv lacks); from 128
    on, where a phase alone fills the columns, four ``[2,2,C,F]`` convs over
    the phase's nonzero taps, 16/36 of the multiply-adds (PERF.md section 6,
    PR 27: on the v5e the dense form is 15-22% slower at ``dec1`` and the
    four-conv form 12-19% slower at ``dec3``)."""

    features: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        c, f = x.shape[-1], self.features
        kernel = self.param("kernel", _glorot, (3, 3, c, f), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros_init(), (f,), self.param_dtype)
        folded = fold_upsample_into_kernel(kernel).astype(self.dtype)
        x = x.astype(self.dtype)

        def conv(k: jax.Array, padding) -> jax.Array:
            return jax.lax.conv_general_dilated(
                x, k, window_strides=(1, 1), padding=padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )

        if f >= _MXU_COLUMNS:
            # Phase (di, dj) reads low-resolution rows di-1..di, cols dj-1..dj.
            phases = [(di, dj) for di in (0, 1) for dj in (0, 1)]
            y = jnp.concatenate(
                [
                    conv(
                        folded[di : di + 2, dj : dj + 2, :, p * f : (p + 1) * f],
                        [(1 - di, di), (1 - dj, dj)],
                    )
                    for p, (di, dj) in enumerate(phases)
                ],
                axis=-1,
            )
        else:
            y = conv(folded, "SAME")
        return y + jnp.tile(bias.astype(self.dtype), 4)


class PackedConvT(nn.Module):
    """``ConvTranspose(F, 3x3, SAME)`` of the tensor that the packed
    ``[N,h,w,4C]`` input is (its :func:`depth_to_space`), written packed the
    same way, ``[N,h,w,4F]``: ONE stride-1 ``SAME`` conv with the
    ``[3,3,4C,4F]`` kernel of :func:`fold_kernel_phases` (module docstring,
    "The decoder's upsample"). Parameters are identical to the reference
    ``nn.ConvTranspose`` (kernel ``[3,3,C,F]`` glorot + bias), the packed
    kernel is derived in-forward from the float32 parameter and cast once."""

    features: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        c, f = x.shape[-1] // 4, self.features
        kernel = self.param("kernel", _glorot, (3, 3, c, f), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros_init(), (f,), self.param_dtype)
        y = jax.lax.conv_general_dilated(
            x.astype(self.dtype), fold_kernel_phases(kernel).astype(self.dtype),
            window_strides=(1, 1), padding="SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return y + jnp.tile(bias.astype(self.dtype), 4)


class PhaseConv1x1(nn.Module):
    """``nn.Conv(F, 1x1)`` of the unpacked tensor, then nearest-neighbour
    upsampling by ``replicate``, on packs: ``x`` holds ``phases`` pixels in
    its channels (a square block, row-major: 1, or the 4 of
    :func:`space_to_depth`) and the result holds the ``phases *
    replicate**2`` pixels they become, ``[..., phases*replicate**2*F]``. A 1x1
    conv reads one pixel and replicated pixels give replicated dot products,
    so the packed kernel holds the ``[C,F]`` parameter at block ``(p, q)``
    wherever output pixel ``q`` lies over input pixel ``p``, and exact zeros
    elsewhere: ``phases`` 4 is the head on the last decoder block's pack
    (block-diagonal), ``phases`` 1 with ``replicate`` 2 that block's residual
    (the parameter tiled 4 times: a conv that writes its own upsample packed,
    on full lanes). Same parameters as ``nn.Conv`` (kernel ``[1,1,C,F]``
    glorot + bias); the packed kernel is built in the parameters' dtype and
    cast once."""

    features: int
    phases: int = 1
    replicate: int = 1
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        c, f = x.shape[-1] // self.phases, self.features
        kernel = self.param("kernel", _glorot, (1, 1, c, f), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros_init(), (f,), self.param_dtype)
        side = round(self.phases**0.5)
        # An axis at a time, output position r lies over input position r // replicate.
        over = jnp.kron(jnp.eye(side, dtype=kernel.dtype), jnp.ones((1, self.replicate), kernel.dtype))
        packed = jnp.kron(jnp.kron(over, over), kernel[0, 0])[None, None]
        y = jax.lax.conv_general_dilated(
            x.astype(self.dtype), packed.astype(self.dtype), window_strides=(1, 1),
            padding="VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return y + jnp.tile(bias.astype(self.dtype), packed.shape[-1] // f)


class PhaseBatchNorm(nn.Module):
    """``nn.BatchNorm`` of the unpacked tensor applied to a packed ``x`` that
    holds ``phases`` pixels in its channels, phase-major: ``[N,h,w,4C]`` whose
    :func:`depth_to_space` is the tensor (the decoder's phase conv), or the
    width fold ``[N,H,W/2,2C]`` of the encoder's lane-dense convs. Moments per
    packed channel, then over the phase groups (equal counts, so the mean of
    means is the mean), and the affine tiled ``phases`` times. Same fields,
    parameters (``scale``, ``bias``) and ``batch_stats`` (``mean``, ``var``,
    float32) as ``nn.BatchNorm``, and its arithmetic: float32 moments,
    ``E[x^2] - E[x]^2`` clamped at 0, one stacked ``pmean`` under
    ``axis_name``. Why: at 32 channels the unpacked tensor fills a quarter of
    the TPU's 128 lanes, so every pass over it moves four times the bytes;
    here the moments fuse into the conv that makes ``x`` and the affine + relu
    run on full lanes (PERF.md section 6, PRs 27 and 29)."""

    use_running_average: bool
    momentum: float
    epsilon: float
    phases: int = 4
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    axis_name: str | None = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        p = self.phases
        c = x.shape[-1] // p
        ra_mean = self.variable("batch_stats", "mean", jnp.zeros, (c,), jnp.float32)
        ra_var = self.variable("batch_stats", "var", jnp.ones, (c,), jnp.float32)
        scale = self.param("scale", nn.initializers.ones_init(), (c,), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros_init(), (c,), self.param_dtype)
        if self.use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            xf = x.astype(jnp.float32)
            moments = jnp.stack([xf.mean((0, 1, 2)), jnp.square(xf).mean((0, 1, 2))])
            moments = moments.reshape(2, p, c).mean(1)
            if self.axis_name is not None and not self.is_initializing():
                moments = jax.lax.pmean(moments, self.axis_name)
            mean = moments[0]
            var = jnp.maximum(0.0, moments[1] - jnp.square(mean))
            if not self.is_initializing():
                ra_mean.value = self.momentum * ra_mean.value + (1 - self.momentum) * mean
                ra_var.value = self.momentum * ra_var.value + (1 - self.momentum) * var
        mul = jax.lax.rsqrt(var + self.epsilon) * scale
        y = (x - jnp.tile(mean, p)) * jnp.tile(mul, p) + jnp.tile(bias, p)
        return y.astype(self.dtype)


class ResUNet(nn.Module):
    """The crack-segmentation residual U-Net. Returns per-pixel logits.

    ``bn_axis_name``: when training under ``shard_map`` with the batch split
    across a mesh axis, set this to that axis so BatchNorm moments
    pmean-synchronize across the data-parallel shards — keeping the sharded
    step numerically identical to the single-device one. Inference is
    unaffected (running stats)."""

    config: ModelConfig = ModelConfig()
    bn_axis_name: str | None = None
    # Keras-parity default; 0.0 turns a train-mode forward into an exact
    # per-batch moment estimator (used by ``train.recalibrate_batch_stats``).
    bn_momentum: float = _BN_MOMENTUM

    @nn.compact
    def __call__(self, x: jax.Array, *, train: bool = False) -> jax.Array:
        cfg = self.config
        dtype = jnp.dtype(cfg.compute_dtype)
        pdtype = jnp.dtype(cfg.param_dtype)
        conv_kw = dict(
            padding="SAME", kernel_init=_glorot, dtype=dtype, param_dtype=pdtype
        )

        def bn(name: str, phases: int = 1):
            """BatchNorm of a tensor that holds `phases` pixels in its channels."""
            cls, kw = (nn.BatchNorm, {}) if phases == 1 else (PhaseBatchNorm, {"phases": phases})
            return cls(
                **kw,
                use_running_average=not train,
                momentum=self.bn_momentum,
                epsilon=_BN_EPSILON,
                dtype=dtype,
                param_dtype=pdtype,
                axis_name=self.bn_axis_name,
                name=name,
            )

        # One ``jax.named_scope`` a block (``stem``, ``enc<i>``, ``dec<i>``,
        # ``head``) around the block's modules AND its inline ops (relu,
        # pool, residual add, upsample), so that every instruction of the
        # compiled forward and backward resolves to a block
        # (``obs/devtrace.py``). A scope is metadata: no parameter name and
        # no value changes.
        with jax.named_scope("stem"):
            x = x.astype(dtype)
            # Entry block (stem): /2. Under a space-to-depth layout the stem
            # consumes either the reference [N,H,W,C] input or the packed
            # [N,H/2,W/2,4C] of `space_to_depth` and runs a folded kernel
            # derived in-forward from the SAME parameters (S2DStemConv);
            # everything from stem_bn on is layout-independent.
            if cfg.stem_layout == "reference":
                x = nn.Conv(cfg.stem_features, (3, 3), strides=(2, 2), name="stem_conv", **conv_kw)(x)
            else:
                x = S2DStemConv(
                    cfg.stem_features,
                    in_channels=cfg.in_channels,
                    layout=cfg.stem_layout,
                    dtype=dtype,
                    param_dtype=pdtype,
                    name="stem_conv",
                )(x)
            x = bn("stem_bn")(x)
            x = nn.relu(x)
        previous = x  # residual carried across blocks

        # Encoder: each block halves H,W.
        for i, features in enumerate(cfg.encoder_features):
            with jax.named_scope(f"enc{i}"):
                x = nn.relu(x)
                x = SeparableConv(features, dtype=dtype, param_dtype=pdtype, name=f"enc{i}_sep1")(x)
                # 2 where `sep1` wrote two columns a pixel (`[N,H,W/2,2F]`):
                # `bn1`, `relu`, `sep2`, `bn2` and the pool then read that.
                fold = x.shape[-1] // features
                x = bn(f"enc{i}_bn1", fold)(x)
                x = nn.relu(x)
                x = SeparableConv(
                    features, in_fold=fold, dtype=dtype, param_dtype=pdtype, name=f"enc{i}_sep2"
                )(x)
                x = bn(f"enc{i}_bn2", fold)(x)
                # Same values as nn.max_pool(3x3, s2, SAME); on grids where it
                # measures faster the backward avoids XLA's SelectAndScatter
                # (ops/pooling.py — measured crossover at 64x64 on v5e).
                if fold == 2:
                    x = max_pool_width_folded(x)
                elif _USE_CUSTOM_POOL:
                    x = max_pool_auto(x)
                else:
                    x = nn.max_pool(x, window_shape=(3, 3), strides=(2, 2), padding="SAME")
                if cfg.res_layout == "packed":
                    # Strided 1x1 conv re-expressed channel-packed (bit-exact).
                    residual = PackedResConv(
                        features, dtype=dtype, param_dtype=pdtype, name=f"enc{i}_res"
                    )(previous)
                else:
                    residual = nn.Conv(
                        features, (1, 1), strides=(2, 2), name=f"enc{i}_res", **conv_kw
                    )(previous)
                x = x + residual
            previous = x

        # Decoder: each block after the first doubles H,W on its way IN. A
        # block hands on its low-resolution output; the next block's two
        # readers of the upsample (`convT1` through `relu`, and `res`) take
        # that directly, so the upsampled tensor is never built, and `bn1` +
        # `relu` run on `convT1`'s packed output (module docstring, "The
        # decoder's upsample"). `dec0` reads the bottleneck as it is. A middle
        # block unpacks there (`depth_to_space`), because the next block reads
        # the unpacked low-resolution tensor. The LAST block keeps the pack to
        # the end where it fills the lanes exactly or less: `convT2`, `bn2`,
        # the residual add and the head all read `[N,h,w,4C]`, and what is
        # unpacked is the head's output. Its upsample is deferred past the
        # head below.
        phases = 1  # pixels the block's output holds in its channels
        for i, features in enumerate(cfg.decoder_features):
            # The last block keeps `convT1`'s pack where it fills the lanes
            # exactly or less; `dec{i}_res` then writes its own upsample packed.
            if i > 0 and i + 1 == len(cfg.decoder_features) and 4 * features <= _MXU_COLUMNS:
                phases = 4
            with jax.named_scope(f"dec{i}"):
                if phases == 4:
                    residual = PhaseConv1x1(
                        features, replicate=2, dtype=dtype, param_dtype=pdtype, name=f"dec{i}_res"
                    )(x)
                else:
                    residual = nn.Conv(features, (1, 1), name=f"dec{i}_res", **conv_kw)(x)
                x = nn.relu(x)
                if i == 0:
                    x = nn.ConvTranspose(features, (3, 3), name="dec0_convT1", **conv_kw)(x)
                    x = nn.relu(bn("dec0_bn1")(x))
                else:
                    x = UpsampledConvT(
                        features, dtype=dtype, param_dtype=pdtype, name=f"dec{i}_convT1"
                    )(x)
                    x = nn.relu(bn(f"dec{i}_bn1", 4)(x))
                    if phases == 1:
                        x = depth_to_space(x)
                        residual = upsample2x(residual)
                if phases == 4:
                    x = PackedConvT(features, dtype=dtype, param_dtype=pdtype, name=f"dec{i}_convT2")(x)
                else:
                    x = nn.ConvTranspose(features, (3, 3), name=f"dec{i}_convT2", **conv_kw)(x)
                x = bn(f"dec{i}_bn2", phases)(x)
                x = x + residual

        # Per-pixel classification head; logits in float32 for a stable loss.
        # The head's 1x1 conv commutes with the final nearest-neighbor
        # upsample (replicated pixels produce replicated dot products) and
        # reads one pixel, so it runs on the last block's output as it is,
        # packed or not, and only its float32 logits (`num_classes` channels a
        # pixel, not `decoder_features[-1]`) are unpacked and replicated. What
        # that costs on the chip is the `head` row of the per-scope table
        # (PERF.md section 5).
        with jax.named_scope("head"):
            logits = PhaseConv1x1(
                cfg.num_classes, phases, dtype=jnp.float32, param_dtype=pdtype, name="head"
            )(x)
            if phases == 4:
                logits = depth_to_space(logits)
            return upsample2x(logits)


def init_variables(rng: jax.Array, config: ModelConfig | None = None) -> dict:
    """Initialize {'params', 'batch_stats'} for the model (host-side helper)."""
    config = config or ModelConfig()
    model = ResUNet(config=config)
    dummy = jnp.zeros((1, *config.input_shape), jnp.float32)
    return model.init(rng, dummy, train=False)


def predict(variables: dict, images: jax.Array, config: ModelConfig | None = None) -> jax.Array:
    """Sigmoid probabilities for a batch of images (inference mode)."""
    model = ResUNet(config=config or ModelConfig())
    logits = model.apply(variables, images, train=False)
    return jax.nn.sigmoid(logits)
