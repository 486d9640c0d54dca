"""Analytic FLOPs model of the crack U-Net + MFU accounting.

Round 1 measured wall-clock only; a per-step time is uninterpretable without
knowing how much of the chip's peak it represents. This module walks the exact
topology of SURVEY.md §2.3 (reference: client_fit_model.py:92-150) and counts
matmul-equivalent FLOPs — the convolutions, which carry >99% of the arithmetic
and are the only ops that land on the MXU. Elementwise work (BN, ReLU,
residual adds, sigmoid/loss) is O(HW·C) against the convs' O(HW·C²·K²) and is
deliberately excluded; the analytic total is cross-checked against XLA's own
HLO cost analysis in tests/test_flops.py.

MFU is reported against the chip's **bf16 MXU peak** for both dtypes (the
standard convention — float32 runs the same systolic array via multi-pass,
so "fraction of the machine's ceiling" stays comparable across dtypes).

CANONICAL FLOPs, by design: this model deliberately ignores
``ModelConfig.stem_layout`` / ``res_layout``. The layout transforms
(models/resunet.py) re-express the same math with zero-extended kernels —
e.g. the packed residual projection nominally multiplies 4x the input
channels, 3/4 of them structural zeros — and counting those zero MACs
would inflate "achieved FLOP/s" for the transformed variants. Every
layout is charged the REFERENCE topology's FLOPs, so an A/B's MFU column
moves only when wall-clock does (pinned by tests/test_flops.py).

The same discipline covers the round-20 kernel planes
(``ServeConfig.kernel_plane``): a fused-int8 or fp8 forward changes bytes
moved and bit-width per MAC, not canonical MACs — every plane is charged
the reference topology's FLOPs so bf16-vs-int8-vs-fp8 MFU columns stay
comparable. Which plane actually answered is exported separately as the
``serve_kernel_plane_info`` labeled gauge (:func:`export_kernel_plane`).
"""

from __future__ import annotations

import jax

from fedcrack_tpu.configs import ModelConfig

# One SGD step ≈ forward + backward; for conv stacks the backward pass is two
# conv-shaped passes (grad wrt activations + grad wrt kernels), so train-step
# FLOPs ≈ 3x forward. Optimizer/BN/loss work is elementwise and excluded.
TRAIN_STEP_FLOPS_MULTIPLIER = 3.0

# Per-jax.Device dense peak (TFLOP/s, bf16 on the MXU), keyed by substrings
# of jax.Device.device_kind. On v4+/v5e/v6e JAX exposes one device per chip,
# so these are per-chip numbers. On v2/v3 JAX exposes one device per CORE
# (two cores per chip), so those rows are per-core (half the often-quoted
# per-chip figure) to keep mfu() honest at jax.Device granularity.
_PEAK_TFLOPS_BF16 = (
    ("v6e", 918.0),
    ("v6 lite", 918.0),
    ("v5p", 459.0),
    ("v5e", 197.0),
    ("v5 lite", 197.0),
    ("v5lite", 197.0),
    ("v4i", 138.0),
    ("v4", 275.0),
    ("v3", 61.5),   # per core: 123 TFLOP/s per chip / 2 cores
    ("v2", 22.5),   # per core: 45 TFLOP/s per chip / 2 cores
)


def _conv_flops(out_hw: int, c_in: int, c_out: int, k: int) -> float:
    """Dense KxK conv at SAME padding: 2 FLOPs (mul+add) per MAC."""
    return 2.0 * out_hw * out_hw * c_out * (k * k * c_in)


def resunet_forward_flops(config: ModelConfig | None = None, batch_size: int = 1) -> float:
    """Forward-pass FLOPs for one batch through the residual U-Net.

    Mirrors models/resunet.py layer by layer: stem conv /2; encoder blocks
    (depthwise 3x3 + pointwise 1x1) x2 + pool /2 + strided 1x1 residual;
    decoder blocks (3x3 transpose-conv, stride 1 == plain conv) x2 +
    1x1 residual + upsample x2; 1x1 head.

    Layout flags (stem_layout/res_layout) are intentionally NOT consulted:
    transformed variants are charged the same canonical FLOPs (module
    docstring).
    """
    cfg = config or ModelConfig()
    s = cfg.img_size // 2  # after the stride-2 stem
    c = cfg.stem_features
    total = _conv_flops(s, cfg.in_channels, c, 3)

    for feat in cfg.encoder_features:
        # SeparableConv = depthwise 3x3 (per-channel) + pointwise 1x1.
        # Canonical: below 128 input channels the model executes ONE
        # composed dense conv, more arithmetic for fewer bytes (resunet.py,
        # "The encoder's separable convolutions"); this count does not move.
        total += 2.0 * s * s * c * 9  # depthwise on c channels
        total += _conv_flops(s, c, feat, 1)  # pointwise c -> feat
        total += 2.0 * s * s * feat * 9
        total += _conv_flops(s, feat, feat, 1)
        s //= 2  # MaxPool(3x3, stride 2)
        # Residual: 1x1 stride-2 conv from the block input (c channels).
        total += _conv_flops(s, c, feat, 1)
        c = feat

    for feat in cfg.decoder_features:
        # Stride-1 ConvTranspose(3x3, SAME) costs the same as a 3x3 conv.
        total += _conv_flops(s, c, feat, 3)
        # Canonical here too: the last block runs this conv on the packed
        # `[N,h,w,4C]` through a `[3,3,4C,4C]` kernel, three quarters of its
        # blocks exact zeros (resunet.py, "The decoder's upsample"); the
        # zeros are not counted, so MFU moves only when wall-clock does.
        total += _conv_flops(s, feat, feat, 3)
        # Residual 1x1 conv at the block's own resolution, before the
        # block's upsample (a 1x1 conv commutes with nearest upsampling, so
        # Keras's upsample-then-conv is not charged its 4x). Canonical like
        # convT1 above: the model executes `dec{i}_res` on the previous
        # block's low-resolution output, a quarter of these pixels, and
        # `dec{i}_convT1` as one low-resolution conv into 4x the channels
        # (resunet.py, "The decoder's upsample"); neither moves this count
        # (tests/test_flops.py).
        total += _conv_flops(s, c, feat, 1)
        s *= 2  # UpSampling2D(2)
        c = feat

    # The head's 1x1 conv is ALSO deferred past the final upsample (same
    # commute, resunet.py), so count it at img_size/2. (It executes on the
    # last block's pack, block-diagonal, at img_size/4; the count stays.)
    total += _conv_flops(s // 2, c, cfg.num_classes, 1)
    return total * float(batch_size)


def train_step_flops(config: ModelConfig | None = None, batch_size: int = 1) -> float:
    """FLOPs for one SGD step (forward + backward) at the given batch size."""
    return TRAIN_STEP_FLOPS_MULTIPLIER * resunet_forward_flops(config, batch_size)


def device_peak_flops(device: jax.Device | None = None) -> float | None:
    """Per-``jax.Device`` bf16 dense peak in FLOP/s, or None when the kind
    is unknown. One device = one chip on v4+/v5e/v6e, one CORE on v2/v3
    (see the table above), so dividing achieved FLOP/s on one device by
    this is always apples-to-apples.
    """
    if device is None:
        device = jax.devices()[0]
    kind = (getattr(device, "device_kind", "") or "").lower()
    for needle, tflops in _PEAK_TFLOPS_BF16:
        if needle in kind:
            return tflops * 1e12
    return None


def mfu(step_time_s: float, flops_per_step: float, device: jax.Device | None = None) -> float | None:
    """Model FLOPs utilization: achieved FLOP/s over the chip's bf16 peak.

    None when the peak is unknown (non-TPU host, unrecognized device kind).
    """
    peak = device_peak_flops(device)
    if peak is None or step_time_s <= 0.0:
        return None
    return (flops_per_step / step_time_s) / peak


def export_kernel_plane(
    effective: str, *, requested: str | None = None, registry=None
) -> None:
    """Export which kernel plane answers quantized traffic as the
    ``serve_kernel_plane_info`` labeled gauge (Prometheus info-metric idiom:
    constant 1, state in the labels). The ``requested`` label keeps an
    fp8-request-degraded-to-reference visible in a scrape; earlier states'
    series drop to 0 so exactly one ``plane`` reads 1."""
    from fedcrack_tpu.obs.registry import REGISTRY

    reg = registry if registry is not None else REGISTRY
    fam = reg.gauge(
        "serve_kernel_plane_info",
        "which quantized-predict kernel plane is compiled in (constant-1 "
        "info gauge; plane=effective program body, requested=the "
        "ServeConfig ask — they differ when fp8 degraded to the r17 "
        "reference path on a backend without fp8 support)",
        labels=("plane", "requested"),
    )
    req = requested if requested is not None else effective
    for key, child in fam._series():
        if key != (effective, req):
            child.set(0)
    fam.labels(plane=effective, requested=req).set(1)
