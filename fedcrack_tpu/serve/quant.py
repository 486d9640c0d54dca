"""Post-training int8 weight quantization for the serve plane (round 17).

Weight-only, per-channel symmetric (Jacob et al., CVPR 2018, §2 without the
activation half): every params leaf with a channel axis is stored as int8
codes plus one float32 scale per output channel, computed DETERMINISTICALLY
from the weight tensor alone — ``scale_c = max(|w[..., c]|) / 127`` — so no
calibration data is needed and the same weights always produce the same
quantized program (byte-determinism discipline). Biases and batch-norm
statistics stay float32 (they are O(channels) bytes and quantizing them buys
nothing). The predict program dequantizes in-graph (``q * scale``), so the
device-resident weights are int8: 4x smaller than float32, which is the
weight-load bandwidth lever forward inference cares about.

The optional activation fake-quant (``ServeConfig.quant_act_fakequant``)
applies dynamic per-tensor symmetric int8 quantize-dequantize to the
pre-sigmoid logits — a deterministic function of the inputs (no calibration),
measuring the activation-quant accuracy headroom at the program boundary.
Interior activations stay in the serving compute dtype; quantizing them is
kernel work queued behind the ROADMAP's hardware session.

The A/B gate (:func:`quant_gate`) is the install-time contract: the
quantized program must reproduce the reference program's masks on a seeded
probe batch at every bucket size (mask IoU >= ``ServeConfig.quant_iou_floor``)
or the install is REFUSED loudly and the replica keeps serving the reference
program — never a silent accuracy cliff. FLOPs honesty: a quantized forward
charges the SAME canonical FLOPs as the reference program
(``obs.flops.resunet_forward_flops``) — int8 does fewer effective bit-ops,
not fewer canonical MACs, so MFU comparisons across the bf16/int8 grid stay
apples-to-apples.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

# Quantized leaves are dicts with exactly these keys; everything else in the
# tree passes through untouched. A dict is a pytree, so the quantized tree
# jits/device_puts like any variables tree. Round 20 adds an fp8 leaf flavor
# (e4m3 codes, same per-channel scale sidecar) for the kernel plane; a tree
# holds ONE flavor, decided by the plane that quantized it.
QKEY, SKEY = "int8_code", "scale"
QKEY_FP8 = "fp8_code"

# fp8 e4m3 (4 exponent / 3 mantissa bits): max finite magnitude 448 — the
# symmetric-scale analog of int8's 127.
FP8_E4M3_MAX = 448.0


class QuantizedVariables:
    """Marker wrapper around a quantized variables pytree.

    The batcher's weights snapshot carries either a plain variables tree
    (reference program) or one of these (quantized program); the engine
    routes on the type, so ONE snapshot-per-batch barrier covers both paths
    and a swap can change program *and* weights atomically.
    """

    def __init__(self, tree: Any):
        self.tree = tree


def _is_qleaf(node: Any) -> bool:
    return isinstance(node, dict) and (
        set(node.keys()) == {QKEY, SKEY} or set(node.keys()) == {QKEY_FP8, SKEY}
    )


def quantize_leaf(w: np.ndarray) -> dict:
    """Per-channel symmetric int8 codes + scales for one weight tensor.

    The LAST axis is the output-channel axis (flax conv kernels are HWIO,
    dense kernels are IO). All-zero channels get scale 1.0 so dequantize is
    exact (0 * 1.0) and never divides by zero."""
    w = np.asarray(w, np.float32)
    absmax = np.max(np.abs(w), axis=tuple(range(w.ndim - 1)))
    scale = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.rint(w / scale), -127, 127).astype(np.int8)
    return {QKEY: q, SKEY: scale}


def quantize_variables(variables: Any) -> QuantizedVariables:
    """Quantize every params leaf with a channel structure (ndim >= 2);
    biases, BN scales and batch statistics stay float32. Pure function of
    the weights — same tree in, byte-identical quantized tree out."""

    def walk(node, in_params: bool):
        if isinstance(node, dict):
            return {k: walk(v, in_params or k == "params") for k, v in node.items()}
        arr = np.asarray(node)
        if in_params and arr.ndim >= 2:
            return quantize_leaf(arr)
        return arr

    return QuantizedVariables(walk(variables, False))


def quantize_leaf_fp8(w: np.ndarray) -> dict:
    """Per-channel symmetric fp8 e4m3 codes + scales for one weight tensor
    (Micikevicius et al.'s weight format: e4m3 for weights, e5m2 reserved for
    gradients). Same scale discipline as :func:`quantize_leaf` with 448 (the
    e4m3 finite max) in place of 127; all-zero channels get scale 1.0.
    Callers resolve the plane first (``jaxcompat.fp8_supported``)."""
    import jax.numpy as jnp

    w = np.asarray(w, np.float32)
    absmax = np.max(np.abs(w), axis=tuple(range(w.ndim - 1)))
    scale = np.where(absmax > 0, absmax / FP8_E4M3_MAX, 1.0).astype(np.float32)
    code = np.asarray((w / scale), np.float32).astype(jnp.float8_e4m3fn)
    return {QKEY_FP8: code, SKEY: scale}


def quantize_variables_fp8(variables: Any) -> QuantizedVariables:
    """fp8 twin of :func:`quantize_variables`: same leaf selection (params,
    ndim >= 2), e4m3 codes instead of int8."""

    def walk(node, in_params: bool):
        if isinstance(node, dict):
            return {k: walk(v, in_params or k == "params") for k, v in node.items()}
        arr = np.asarray(node)
        if in_params and arr.ndim >= 2:
            return quantize_leaf_fp8(arr)
        return arr

    return QuantizedVariables(walk(variables, False))


def quantize_for_plane(variables: Any, kernel_plane: str) -> QuantizedVariables:
    """The quantized tree a kernel plane consumes: int8 codes for
    ``reference``/``fused_int8`` (the r17 format), e4m3 codes for ``fp8``.
    Callers pass the engine's EFFECTIVE plane — an fp8 request on a backend
    without fp8 support has already degraded to ``reference`` there, so the
    tree and the compiled program always agree."""
    if kernel_plane == "fp8":
        return quantize_variables_fp8(variables)
    if kernel_plane in ("reference", "fused_int8"):
        return quantize_variables(variables)
    raise ValueError(f"unknown kernel_plane {kernel_plane!r}")


def dequantize_variables(qtree: Any) -> Any:
    """Inverse projection: the float32 tree the quantized program computes
    with. Traceable — called inside the jitted predict program, so XLA sees
    int8 (or fp8) weight inputs and fuses the ``q * scale`` expansion."""

    def walk(node):
        if _is_qleaf(node):
            code = node[QKEY] if QKEY in node else node[QKEY_FP8]
            return code.astype("float32") * node[SKEY]
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(qtree)


def quantized_bytes(qtree: Any) -> tuple[int, int]:
    """(quantized_bytes, reference_bytes) over the tree — the memory /
    weight-bandwidth claim, computed not asserted."""
    import jax

    q_bytes = ref_bytes = 0
    for leaf in jax.tree_util.tree_leaves(qtree):
        arr = np.asarray(leaf)
        q_bytes += arr.nbytes
        ref_bytes += arr.size * (4 if arr.dtype == np.int8 else arr.itemsize)
    return q_bytes, ref_bytes


def fake_quant_activations(x):
    """Dynamic per-tensor symmetric int8 quantize-dequantize (traceable).
    Scale is max|x|/127 computed in-graph — deterministic per input, no
    calibration state."""
    import jax.numpy as jnp

    absmax = jnp.max(jnp.abs(x))
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def mask_iou(probs_a: np.ndarray, probs_b: np.ndarray, threshold: float = 0.5) -> float:
    """Intersection-over-union of the thresholded masks; both-empty = 1.0
    (two programs agreeing there is no crack DO agree)."""
    a = np.asarray(probs_a) > threshold
    b = np.asarray(probs_b) > threshold
    union = np.logical_or(a, b).sum()
    if union == 0:
        return 1.0
    return float(np.logical_and(a, b).sum() / union)


@dataclasses.dataclass(frozen=True)
class QuantGateResult:
    """The install-time A/B verdict: per-bucket mask IoU of the quantized
    program vs the reference oracle on the seeded probe batch."""

    passed: bool
    iou: float                    # min over buckets — the gating number
    floor: float
    per_bucket: dict              # {bucket_size: iou}
    probe_batch: int
    probe_seed: int

    def to_json(self) -> dict:
        return {
            "passed": self.passed,
            "iou": round(self.iou, 6),
            "floor": self.floor,
            "per_bucket": {str(k): round(v, 6) for k, v in self.per_bucket.items()},
            "probe_batch": self.probe_batch,
            "probe_seed": self.probe_seed,
        }


def probe_images(size: int, n: int, seed: int) -> np.ndarray:
    """The seeded probe batch for one bucket: synthetic crack images in
    uint8 transport form — same generator the load/test planes use, so the
    gate exercises crack-shaped inputs, not noise."""
    from fedcrack_tpu.data.pipeline import to_uint8_transport
    from fedcrack_tpu.data.synthetic import synth_crack_batch

    imgs_f, msks_f = synth_crack_batch(n, img_size=size, seed=seed)
    imgs_u8, _ = to_uint8_transport(imgs_f, msks_f)
    return imgs_u8


def quant_gate(
    engine: Any,
    reference_variables: Any,
    quantized_variables: QuantizedVariables,
    *,
    floor: float | None = None,
    probe_batch: int | None = None,
    probe_seed: int | None = None,
) -> QuantGateResult:
    """Run the A/B gate: both programs over the seeded probe batch at every
    bucket size; the min per-bucket mask IoU must clear the floor.

    Both argument trees must already be device-placed (``engine.prepare`` /
    ``engine.prepare_quantized``) — the gate is called from the install
    path, off the serving path, where placement already happened."""
    cfg = engine.serve_config
    floor = cfg.quant_iou_floor if floor is None else floor
    n = cfg.quant_probe_batch if probe_batch is None else probe_batch
    seed = cfg.quant_probe_seed if probe_seed is None else probe_seed
    per_bucket: dict[int, float] = {}
    for size in engine.bucket_sizes:
        batch = probe_images(size, min(n, engine.max_batch), seed)
        ref = engine.predict_bucket(reference_variables, batch)
        quant = engine.predict_bucket(quantized_variables, batch)
        per_bucket[size] = mask_iou(ref, quant)
    worst = min(per_bucket.values())
    return QuantGateResult(
        passed=worst >= floor,
        iou=worst,
        floor=floor,
        per_bucket=per_bucket,
        probe_batch=n,
        probe_seed=seed,
    )
