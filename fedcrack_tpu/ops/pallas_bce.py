"""Fused Pallas TPU kernel: BCE loss + segmentation statistics in one pass.

The training hot path computes four reductions over the same logits/mask
tensors every step: BCE sum, correct-pixel count, IoU intersection and IoU
union (ops/losses.py; the reference computed loss and accuracy in separate
Keras graph ops, client_fit_model.py:157). Naively that is four reads of the
batch from HBM; this kernel streams each (block, 128)-tile through VMEM once
and accumulates all four statistics on the VPU — one HBM pass, no
intermediate materialization.

Layout: inputs are flattened and padded to ``(rows, 128)`` lane tiles; the
grid walks row-blocks sequentially (TPU grid order), each step masking the
tail padding by global element index and accumulating partial sums into a
single shared ``(8, 128)`` VMEM output block (lanes 0..3 of row 0 hold the
four statistics).

The backward pass stays in plain XLA: d(BCE)/dlogits = sigmoid(x) - y is a
single fused elementwise op that the compiler already emits optimally — a
hand kernel would add nothing. The win is the fused multi-statistic forward
reduction; ``jax.custom_vjp`` stitches the two together.

Dispatch: ``impl=None`` selects the pure-XLA implementation everywhere (see
``default_impl``) and ``FEDCRACK_BCE_IMPL=pallas`` opts into the kernel;
tests run the kernel body under the Pallas interpreter for numerics parity
on CPU, and ``chip_smoke.py`` compiles it on the chip against the XLA twin.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import optax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
BLOCK_ROWS = 256  # 256x128 f32 tiles: 128 KiB per input block in VMEM


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


# ---- forward kernel ----


def _fwd_kernel(x_ref, y_ref, out_ref, *, n_valid: int, block_rows: int):
    i = pl.program_id(0)
    x = x_ref[:]
    y = y_ref[:]
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    idx = i * block_rows * LANE + row * LANE + col
    valid = idx < n_valid

    # Python-literal constants throughout: concrete jnp scalars created at
    # trace time carry an empty vma and break check_vma under shard_map.
    # Stable log-sigmoid BCE: max(x,0) - x*y + log1p(exp(-|x|)).
    bce = jnp.where(
        valid, jnp.maximum(x, 0.0) - x * y + jnp.log1p(jnp.exp(-jnp.abs(x))), 0.0
    )
    pred = x > 0.0  # sigmoid(x) > 0.5
    tgt = y > 0.5
    correct = jnp.where(valid & (pred == tgt), 1.0, 0.0)
    inter = jnp.where(valid & pred & tgt, 1.0, 0.0)
    union = jnp.where(valid & (pred | tgt), 1.0, 0.0)

    # Positive-pixel BCE sum: lets the host compose a class-weighted loss
    # (w = 1 + (pos_weight-1)*y) for ANY pos_weight from the same kernel —
    # the weight never becomes a kernel constant, so it never recompiles.
    s = (
        jnp.sum(bce),
        jnp.sum(correct),
        jnp.sum(inter),
        jnp.sum(union),
        jnp.sum(y * bce),
    )
    orow = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    ocol = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    vec = sum(
        jnp.where((orow == 0) & (ocol == k), s[k], 0.0) for k in range(5)
    )

    @pl.when(i == 0)
    def _init():
        out_ref[:] = vec

    @pl.when(i > 0)
    def _accumulate():
        out_ref[:] = out_ref[:] + vec


def _sums_pallas(x: jax.Array, y: jax.Array, interpret: bool) -> jax.Array:
    n = x.size
    flat_x = x.reshape(-1).astype(jnp.float32)
    flat_y = y.reshape(-1).astype(jnp.float32)
    rows = _cdiv(n, LANE)
    rows_pad = max(_cdiv(rows, BLOCK_ROWS), 1) * BLOCK_ROWS
    pad = rows_pad * LANE - n
    xp = jnp.pad(flat_x, (0, pad)).reshape(rows_pad, LANE)
    yp = jnp.pad(flat_y, (0, pad)).reshape(rows_pad, LANE)

    spec_kw = {"memory_space": pltpu.VMEM}
    # Under shard_map the output varies over the same mesh axes as the inputs
    # (per-device statistics); propagate the vma so check_vma stays on.
    vma = jax.typeof(xp).vma | jax.typeof(yp).vma
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, n_valid=n, block_rows=BLOCK_ROWS),
        grid=(rows_pad // BLOCK_ROWS,),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0), **spec_kw),
            pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0), **spec_kw),
        ],
        out_specs=pl.BlockSpec((8, LANE), lambda i: (0, 0), **spec_kw),
        out_shape=jax.ShapeDtypeStruct((8, LANE), jnp.float32, vma=vma),
        interpret=interpret,
    )(xp, yp)
    return out[0, :5]


def _sums_jnp(x: jax.Array, y: jax.Array) -> jax.Array:
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    per_pixel = optax.sigmoid_binary_cross_entropy(x, y)
    bce = jnp.sum(per_pixel)
    ybce = jnp.sum(y * per_pixel)
    pred = x > 0
    tgt = y > 0.5
    correct = jnp.sum((pred == tgt).astype(jnp.float32))
    inter = jnp.sum((pred & tgt).astype(jnp.float32))
    union = jnp.sum((pred | tgt).astype(jnp.float32))
    return jnp.stack([bce, correct, inter, union, ybce])


# ---- differentiable public op ----


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def bce_sums(logits: jax.Array, labels: jax.Array, impl: str = "jnp") -> jax.Array:
    """``[bce_sum, n_correct, iou_inter, iou_union, pos_bce_sum]`` as one
    float32 vector (``pos_bce_sum`` = BCE summed over crack pixels only, the
    building block of a class-weighted loss).

    ``impl``: ``"pallas"`` (compiled TPU kernel), ``"interpret"`` (Pallas
    interpreter, any backend — for tests), ``"jnp"`` (pure XLA reference).
    Differentiable in ``logits``/``labels`` through the two BCE-sum
    components; the count statistics are piecewise constant with zero
    gradient.
    """
    return _dispatch(logits, labels, impl)


def _dispatch(logits, labels, impl):
    if impl == "pallas":
        return _sums_pallas(logits, labels, interpret=False)
    if impl == "interpret":
        return _sums_pallas(logits, labels, interpret=True)
    if impl == "jnp":
        return _sums_jnp(logits, labels)
    raise ValueError(f"unknown impl {impl!r}")


def _bce_sums_fwd(logits, labels, impl):
    return _dispatch(logits, labels, impl), (logits, labels)


def _bce_sums_bwd(impl, residuals, g):
    x, y = residuals
    x32 = x.astype(jnp.float32)
    y32 = y.astype(jnp.float32)
    # d(bce_sum)/dx = sigmoid(x) - y ; d(bce_sum)/dy = -x.
    # d(pos_bce_sum)/dx = y * (sigmoid(x) - y) ;
    # d(pos_bce_sum)/dy = bce + y * d(bce)/dy = bce - y*x.
    # Count statistics (g[1:4]) are piecewise constant: zero gradient.
    sig_minus_y = jax.nn.sigmoid(x32) - y32
    dx = ((g[0] + g[4] * y32) * sig_minus_y).astype(x.dtype)
    bce = jnp.maximum(x32, 0.0) - x32 * y32 + jnp.log1p(jnp.exp(-jnp.abs(x32)))
    dy = (g[0] * (-x32) + g[4] * (bce - y32 * x32)).astype(y.dtype)
    return dx, dy


bce_sums.defvjp(_bce_sums_fwd, _bce_sums_bwd)


def default_impl() -> str:
    """XLA everywhere: a pre-round A/B (removed in PR 21, in git history)
    found no gain from the kernel — the pad/reshape to (rows, 128) lane
    tiles is a materialization boundary that blocks XLA from fusing the
    reductions into the ops producing the logits. Not measured on today's
    code (ROADMAP Design D4 decides its fate). ``FEDCRACK_BCE_IMPL=pallas``
    opts in, and tests pin its numerics so the option cannot rot."""
    import os

    forced = os.environ.get("FEDCRACK_BCE_IMPL")
    if forced:
        return forced
    return "jnp"


def fused_segmentation_metrics(
    logits: jax.Array,
    labels: jax.Array,
    impl: str | None = None,
    pos_weight: jax.Array | float | None = None,
) -> dict[str, jax.Array]:
    """Drop-in fused equivalent of ``ops.losses.segmentation_metrics``.

    ``pos_weight`` > 1 up-weights crack pixels in the loss (mean of
    ``(1 + (pos_weight-1)*y) * bce``) — the standard counter to the ~7%
    foreground imbalance of crack masks, where plain BCE converges to
    low-confidence predictions that threshold poorly. ``None``/1.0 is the
    reference's plain BCE (client_fit_model.py:157). Traced, never a
    compile-time constant: sweeping it does not recompile.
    """
    from fedcrack_tpu.ops.losses import iou_from_counts

    sums = bce_sums(logits, labels, impl or default_impl())
    n = jnp.float32(logits.size)
    loss = sums[0] / n
    if pos_weight is not None:
        loss = loss + (jnp.asarray(pos_weight, jnp.float32) - 1.0) * sums[4] / n
    return {
        "loss": loss,
        "pixel_acc": sums[1] / n,
        "iou": iou_from_counts(sums[2], sums[3]),
        "iou_inter": sums[2],
        "iou_union": sums[3],
    }
