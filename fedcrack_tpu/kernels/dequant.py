"""Pallas fused dequantize kernels: int8/fp8 codes straight into the MXU.

r17's quantized predict is weight-only: the codes live in HBM as int8 but
XLA expands ``q * scale`` to a float32 weight tensor before the conv/matmul,
so every MAC still runs in the serving compute dtype. The kernels here fold
the dequantize into the weight LOAD — each ``(bk, bn)`` code block is cast
to float32 in VMEM, contracted on the MXU with float32 accumulation, and the
per-output-channel scale multiplies the finished accumulator ONCE per output
block (the scale factors out of the K-contraction exactly, so
``(x @ q) * scale == x @ (q * scale)`` up to float reassociation). The same
kernel serves both code dtypes: int8 symmetric codes (r17's
``quantize_leaf``) and fp8 e4m3 codes (``quantize_leaf_fp8``) differ only in
the in-VMEM cast.

Twin discipline (same contract as ops/pallas_bce.py): every kernel has an
interpret-mode CPU twin (``impl="interpret"`` — the Pallas interpreter runs
the SAME kernel body) and a pure-XLA reference (``impl="reference"`` — the
r17 dequantize-then-contract order). Tests pin the fused result within one
per-channel scale of the reference per entry, and deterministic run-to-run;
``chip_smoke.py`` holds the COMPILED kernels to the same bound on the chip
(against the reference at ``highest`` matmul precision — the device's default
rounds f32 operands to bfloat16). ``default_impl`` picks the compiled kernel
on TPU and the interpreter elsewhere; ``FEDCRACK_KERNEL_IMPL`` overrides for
A/B runs.

The training-side transform (``fake_quant_params``) is the straight-through
estimator over the SAME quantize/dequantize math: weights pass through
``dequant_codes`` in-graph, gradients flow to the float32 master copy
(Dettmers et al.'s weight-only fused-compute progression, applied to the
fedavg step). It rides the reference twin — the step runs inside shard_map
where the interpreter is not a supported lowering — so the trajectory claim
is about the quantization math, not the kernel; the kernel's numerics are
pinned by the serve-plane twin tests against the identical math.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
# 128x128 blocks satisfy every dtype's minimum tile in one shape: f32 (8,128),
# int8/fp8 (32,128). VMEM per grid step: x 64 KiB + q 16 KiB + out 64 KiB.
BLOCK = 128


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(n: int, m: int) -> int:
    return _cdiv(n, m) * m


def default_impl() -> str:
    """Compiled kernel on TPU, Pallas interpreter elsewhere (the CPU twin
    validates the machinery; no speed has been measured). The serve engine
    records the outcome as ``kernel_impl`` and warns when it is not the
    compiled kernel. ``FEDCRACK_KERNEL_IMPL`` forces a variant (no caller in
    the tree sets it: ROADMAP D6)."""
    forced = os.environ.get("FEDCRACK_KERNEL_IMPL")
    if forced:
        return forced
    return "pallas" if jax.default_backend() == "tpu" else "interpret"


def _check_codes(q: jax.Array) -> None:
    kind = jnp.dtype(q.dtype).kind
    # int8 symmetric codes, or any fp8 flavor ('V' pre-numpy-2 ml_dtypes
    # registration, 'f' itemsize 1 after).
    if q.dtype == jnp.int8:
        return
    if jnp.dtype(q.dtype).itemsize == 1 and kind in ("V", "f"):
        return
    raise TypeError(f"dequant kernels want int8/fp8 codes, got {q.dtype}")


# ---- fused dequant-matmul ----


def _matmul_kernel(x_ref, q_ref, s_ref, o_ref, *, k_blocks: int):
    k = pl.program_id(2)
    # Explicit precision: at the MXU's default an f32 dot rounds its
    # operands to bfloat16, and the compiled kernel then misses the twin
    # bound its interpreter run meets (on a v5e: 0.022 against a 0.002
    # per-channel scale at K=576). The kernel's contract is an f32
    # contraction, so it says so rather than inherit the backend's default.
    part = jnp.dot(
        x_ref[:],
        q_ref[:].astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    @pl.when(k == 0)
    def _init():
        o_ref[:] = part

    @pl.when(k > 0)
    def _accumulate():
        o_ref[:] = o_ref[:] + part

    @pl.when(k == k_blocks - 1)
    def _scale():
        o_ref[:] = o_ref[:] * s_ref[0:1, :]


def _dequant_matmul_pallas(
    x: jax.Array, q: jax.Array, scale: jax.Array, interpret: bool
) -> jax.Array:
    m, kk = x.shape
    _, n = q.shape
    bm = min(BLOCK, _round_up(m, 8))
    mp = _round_up(m, bm)
    kp = _round_up(kk, BLOCK)
    np_ = _round_up(n, BLOCK)
    xp = jnp.pad(x.astype(jnp.float32), ((0, mp - m), (0, kp - kk)))
    qp = jnp.pad(q, ((0, kp - kk), (0, np_ - n)))
    # Pad channels with scale 1.0 (dequant of the zero-padded codes stays 0);
    # 8 replicated sublanes keep the block tile-aligned.
    sp = jnp.pad(scale.astype(jnp.float32), (0, np_ - n), constant_values=1.0)
    sp = jnp.broadcast_to(sp[None, :], (8, np_))
    k_blocks = kp // BLOCK

    spec_kw = {"memory_space": pltpu.VMEM}
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, k_blocks=k_blocks),
        grid=(mp // bm, np_ // BLOCK, k_blocks),
        in_specs=[
            pl.BlockSpec((bm, BLOCK), lambda i, j, k: (i, k), **spec_kw),
            pl.BlockSpec((BLOCK, BLOCK), lambda i, j, k: (k, j), **spec_kw),
            pl.BlockSpec((8, BLOCK), lambda i, j, k: (0, j), **spec_kw),
        ],
        out_specs=pl.BlockSpec((bm, BLOCK), lambda i, j, k: (i, j), **spec_kw),
        out_shape=jax.ShapeDtypeStruct(
            (mp, np_), jnp.float32, vma=jax.typeof(x).vma
        ),
        interpret=interpret,
    )(xp, qp, sp)
    return out[:m, :n]


def _dequant_matmul_reference(x, q, scale):
    # The r17 order: expand the float32 weights, then contract.
    return x.astype(jnp.float32) @ (q.astype(jnp.float32) * scale)


def dequant_matmul(
    x: jax.Array, q: jax.Array, scale: jax.Array, *, impl: str | None = None
) -> jax.Array:
    """``[M, K] @ dequant([K, N] codes, [N] scales) -> [M, N]`` float32.

    ``impl``: ``"pallas"`` (compiled TPU kernel), ``"interpret"`` (Pallas
    interpreter, any backend), ``"reference"`` (pure XLA, the r17
    dequantize-then-matmul order). Fused vs reference differ only by the
    scale's association with the K-sum — per entry within one per-channel
    scale (test-pinned, far tighter in practice)."""
    if x.ndim != 2 or q.ndim != 2 or x.shape[1] != q.shape[0]:
        raise ValueError(f"bad matmul shapes: x {x.shape}, q {q.shape}")
    if scale.shape != (q.shape[1],):
        raise ValueError(f"scale {scale.shape} != per-channel ({q.shape[1]},)")
    _check_codes(q)
    impl = impl or default_impl()
    if impl == "pallas":
        return _dequant_matmul_pallas(x, q, scale, interpret=False)
    if impl == "interpret":
        return _dequant_matmul_pallas(x, q, scale, interpret=True)
    if impl == "reference":
        return _dequant_matmul_reference(x, q, scale)
    raise ValueError(f"unknown impl {impl!r}")


# ---- elementwise dequant (weight expansion without a contraction) ----


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[:] = q_ref[:].astype(jnp.float32) * s_ref[0:1, :]


def _dequant_codes_pallas(q: jax.Array, scale: jax.Array, interpret: bool):
    shape = q.shape
    n = shape[-1]
    r = max(q.size // n, 1)
    q2 = q.reshape(r, n)
    br = min(256, _round_up(r, 32))  # int8 sublane tile
    rp = _round_up(r, br)
    np_ = _round_up(n, BLOCK)
    qp = jnp.pad(q2, ((0, rp - r), (0, np_ - n)))
    sp = jnp.pad(scale.astype(jnp.float32), (0, np_ - n), constant_values=1.0)
    sp = jnp.broadcast_to(sp[None, :], (8, np_))

    spec_kw = {"memory_space": pltpu.VMEM}
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(rp // br, np_ // BLOCK),
        in_specs=[
            pl.BlockSpec((br, BLOCK), lambda i, j: (i, j), **spec_kw),
            pl.BlockSpec((8, BLOCK), lambda i, j: (0, j), **spec_kw),
        ],
        out_specs=pl.BlockSpec((br, BLOCK), lambda i, j: (i, j), **spec_kw),
        out_shape=jax.ShapeDtypeStruct(
            (rp, np_), jnp.float32, vma=jax.typeof(q).vma
        ),
        interpret=interpret,
    )(qp, sp)
    return out[:r, :n].reshape(shape)


def dequant_codes(
    q: jax.Array, scale: jax.Array, *, impl: str = "reference"
) -> jax.Array:
    """Expand ``[..., N]`` codes with per-last-axis scales to float32 —
    traceable twin of ``serve.quant.dequantize_variables``'s leaf expansion,
    shared by the depthwise-conv path (no matmul to fuse into) and the
    training fake-quant transform. Reference impl by default: callers inside
    shard_map (the training step) must not enter the interpreter."""
    if scale.shape != (q.shape[-1],):
        raise ValueError(f"scale {scale.shape} != per-channel ({q.shape[-1]},)")
    _check_codes(q)
    if impl == "reference":
        return q.astype(jnp.float32) * scale
    if impl == "pallas":
        return _dequant_codes_pallas(q, scale, interpret=False)
    if impl == "interpret":
        return _dequant_codes_pallas(q, scale, interpret=True)
    raise ValueError(f"unknown impl {impl!r}")


# ---- training-side straight-through fake-quant ----


def fake_quant_leaf(w: jax.Array) -> jax.Array:
    """Straight-through int8 fake-quant of one weight tensor (traceable).

    Same math as ``serve.quant.quantize_leaf`` + ``dequant_codes``: symmetric
    per-last-axis-channel codes, all-zero channels scale 1.0. The forward
    sees the dequantized int8 projection; the gradient passes straight
    through to the float32 master weights."""
    w32 = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=tuple(range(w.ndim - 1)))
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w32 / scale), -127.0, 127.0).astype(jnp.int8)
    wq = dequant_codes(q, scale, impl="reference")
    return (w32 + jax.lax.stop_gradient(wq - w32)).astype(w.dtype)


def fake_quant_params(params):
    """Apply :func:`fake_quant_leaf` to every channel-structured leaf
    (ndim >= 2) of a params tree — the same leaf set ``quantize_variables``
    quantizes, so the training forward computes with exactly the weights the
    fused serve plane would load. Biases/BN affines pass through."""
    return jax.tree_util.tree_map(
        lambda w: fake_quant_leaf(w) if getattr(w, "ndim", 0) >= 2 else w, params
    )
