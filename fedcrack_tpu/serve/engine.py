"""Device-resident inference engine: pre-compiled predict programs per
(image-size, dtype) bucket, plus tiled sliding-window inference for images
larger than any bucket.

The reference's inference path is a one-shot script that rebuilds the Keras
model per run (test/Segmentation2.py; SURVEY §2.1 C4b). Here the ResUNet
stays device-resident and every served shape is ONE compiled XLA program,
built at startup:

- ``fn(variables, images_u8[max_batch, S, S, 3]) -> probs_f32[..., 1]`` per
  bucket size S — uint8 transport bytes in (1/4 the host->device traffic,
  same trick as the training plane), on-device ``normalize_images``, sigmoid
  probabilities out. The model config's PR-1 layout flags
  (``stem_layout``/``res_layout``) apply unchanged: transformed kernels are
  derived in-forward, so the served weights are layout-blind.
- Requests smaller than a bucket are spatially zero-padded into the smallest
  bucket that holds them and the output is cropped back (SAME-padded convs
  make the crop a policy choice, not an equivalence; the bucket contract is
  exact for images AT a bucket size).
- Images larger than the largest bucket run **tiled sliding-window
  inference**: overlapping S x S tiles batched through the bucket program,
  blended with a deterministic separable ramp. The tile schedule and the
  float32 host accumulation are fixed functions of (H, W, S, overlap), so
  tiled output is byte-deterministic run to run (test-pinned).
- With a multi-device mesh (``parallel.mesh.make_mesh``), the batch lane of
  each bucket is sharded over the ``batch`` axis (variables replicated) —
  data-parallel serving on the same mesh machinery the training plane uses.
"""

from __future__ import annotations

import logging
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from fedcrack_tpu.configs import ModelConfig, ServeConfig
from fedcrack_tpu.data.pipeline import normalize_images
from fedcrack_tpu.models import ResUNet

BATCH_AX = "batch"

log = logging.getLogger("fedcrack.serve.engine")


def tile_plan(extent: int, tile: int, overlap: int) -> list[int]:
    """Deterministic 1-D tile offsets covering ``[0, extent)`` with ``tile``-
    sized windows and at least ``overlap`` shared pixels between neighbors;
    the final window is clamped to the extent (its overlap grows). Requires
    ``extent >= tile``."""
    if extent < tile:
        raise ValueError(f"extent {extent} < tile {tile}")
    stride = tile - overlap
    if stride <= 0:
        raise ValueError(f"overlap {overlap} must be < tile {tile}")
    offsets = list(range(0, max(extent - tile, 0) + 1, stride))
    if offsets[-1] != extent - tile:
        offsets.append(extent - tile)
    return offsets


def _ramp_weights(tile: int, overlap: int, has_before: bool, has_after: bool) -> np.ndarray:
    """1-D blend weights for one tile: 1.0 in the interior, linearly ramping
    down to 1/(overlap+1) over the ``overlap`` pixels facing a neighboring
    tile; image-border edges stay at full weight so un-overlapped pixels are
    single-source."""
    w = np.ones(tile, np.float32)
    if overlap > 0:
        ramp = np.linspace(1.0, 1.0 / (overlap + 1), overlap, dtype=np.float32)
        if has_before:
            w[:overlap] = ramp[::-1]
        if has_after:
            w[-overlap:] = ramp
    return w


class InferenceEngine:
    """Owns the compiled bucket programs and the tiling/padding routing.

    Stateless w.r.t. weights: every predict call takes a ``variables``
    pytree (use :meth:`prepare` to place it on device once) — the hot-swap
    manager owns WHICH weights are current, the engine only computes. That
    split is what makes swap semantics easy to pin: a batch computes with
    exactly the snapshot it was handed.
    """

    def __init__(
        self,
        model_config: ModelConfig | None = None,
        serve_config: ServeConfig | None = None,
        mesh: Any | None = None,
    ):
        self.model_config = model_config or ModelConfig()
        self.serve_config = serve_config or ServeConfig()
        if self.model_config.in_channels != 3:
            raise ValueError("serving assumes 3-channel RGB inputs")
        self._mesh = mesh
        self._sharding = None
        self._rep_sharding = None
        if mesh is not None and self.serve_config.mesh_batch > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            if BATCH_AX not in mesh.shape:
                raise ValueError(f"mesh {mesh.axis_names} has no '{BATCH_AX}' axis")
            if mesh.shape[BATCH_AX] != self.serve_config.mesh_batch:
                raise ValueError(
                    f"mesh batch axis {mesh.shape[BATCH_AX]} != "
                    f"serve mesh_batch {self.serve_config.mesh_batch}"
                )
            self._sharding = NamedSharding(mesh, P(BATCH_AX))
            self._rep_sharding = NamedSharding(mesh, P())
        model = ResUNet(config=self._bucket_model_config())

        def _predict(variables, images_u8):
            x = normalize_images(images_u8)
            logits = model.apply(variables, x, train=False)
            return jax.nn.sigmoid(logits).astype(jnp.float32)

        # One jit wrapper serves every bucket: jax.jit specializes and
        # caches per input shape, so each bucket size still gets (and keeps)
        # its own compiled XLA program.
        kwargs = {}
        if self._sharding is not None:
            kwargs = {
                "in_shardings": (self._rep_sharding, self._sharding),
                "out_shardings": self._sharding,
            }
        self._fn = jax.jit(_predict, **kwargs)
        # Round 17: the int8-quantized predict program — weights arrive as
        # the quantized pytree (int8 codes + per-channel scales), are
        # dequantized IN-GRAPH (XLA sees int8 inputs and fuses q*scale into
        # the weight loads), and the optional activation fake-quant applies
        # at the logits boundary. Same canonical FLOPs as the reference
        # program (obs/flops) — int8 changes bytes moved, not MACs charged.
        #
        # Round 20: ServeConfig.kernel_plane selects the BODY of this
        # program. The quant_gate calls predict_bucket with
        # QuantizedVariables, which routes here — so whichever plane built
        # _fn_q is exactly the program the gate probes, and a fused plane
        # inherits the r17 install contract (IoU floor, loud bf16 refusal)
        # with zero gate changes. "fp8" on a backend without fp8 support
        # degrades to "reference" at build time: the SAME closure as r17,
        # bit-exact by construction (test-pinned). Neither resolution is
        # silent: what was asked for and what was built are both attributes
        # (kernel_plane / effective_kernel_plane, kernel_impl) and any
        # difference from the compiled-on-device program is a WARNING.
        self.kernel_plane = self.serve_config.kernel_plane
        self.effective_kernel_plane = self.kernel_plane
        if self.kernel_plane == "fp8":
            from fedcrack_tpu import jaxcompat

            if not jaxcompat.fp8_supported():
                self.effective_kernel_plane = "reference"
                log.warning(
                    "kernel_plane='fp8' degraded to 'reference': backend %s "
                    "does not round-trip fp8 codes",
                    jax.default_backend(),
                )
        # Which dequant-kernel implementation the fused planes run: "pallas"
        # (compiled), "interpret" (the Pallas interpreter — off-TPU only) or
        # None when no fused plane is built.
        self.kernel_impl = None
        self._fn_q = None
        if self.serve_config.quant == "int8":
            from fedcrack_tpu.serve.quant import (
                dequantize_variables,
                fake_quant_activations,
            )

            act_fq = self.serve_config.quant_act_fakequant

            if self.effective_kernel_plane == "reference":

                def _predict_q(qtree, images_u8):
                    x = normalize_images(images_u8)
                    logits = model.apply(dequantize_variables(qtree), x, train=False)
                    if act_fq:
                        logits = fake_quant_activations(logits)
                    return jax.nn.sigmoid(logits).astype(jnp.float32)

            else:
                from fedcrack_tpu.kernels.dequant import default_impl
                from fedcrack_tpu.kernels.forward import fused_predict_logits

                fused_config = self._bucket_model_config()
                if (
                    fused_config.stem_layout != "reference"
                    or fused_config.res_layout != "reference"
                ):
                    raise ValueError(
                        f"kernel_plane={self.kernel_plane!r} supports only the "
                        "reference parameter layouts (kernels/forward.py); got "
                        f"stem_layout={fused_config.stem_layout!r} "
                        f"res_layout={fused_config.res_layout!r}"
                    )
                impl = self.kernel_impl = default_impl()
                if impl != "pallas":
                    log.warning(
                        "kernel_plane=%r runs the %r dequant kernels, not the "
                        "compiled ones (backend %s)",
                        self.effective_kernel_plane,
                        impl,
                        jax.default_backend(),
                    )

                def _predict_q(qtree, images_u8):
                    x = normalize_images(images_u8)
                    logits = fused_predict_logits(qtree, x, fused_config, impl=impl)
                    if act_fq:
                        logits = fake_quant_activations(logits)
                    return jax.nn.sigmoid(logits).astype(jnp.float32)

            self._fn_q = jax.jit(_predict_q, **kwargs)
        self._max_batch = self.serve_config.max_batch

    def _bucket_model_config(self) -> ModelConfig:
        """The served model config: training-time layout flags kept, serving
        dtype applied. img_size is irrelevant to apply (fully convolutional)
        but kept coherent with the largest bucket."""
        import dataclasses

        return dataclasses.replace(
            self.model_config,
            img_size=max(self.serve_config.bucket_sizes),
            compute_dtype=self.serve_config.compute_dtype,
        )

    # ---- weights placement ----

    def prepare(self, variables: Any) -> Any:
        """Place a host variables pytree on device (replicated over the mesh
        when sharded serving is on). Called once per hot-swap, off the
        serving path."""
        from fedcrack_tpu.serve.quant import QuantizedVariables

        if isinstance(variables, QuantizedVariables):
            return self.prepare_quantized(variables)
        if self._rep_sharding is not None:
            out = jax.device_put(variables, self._rep_sharding)
        else:
            out = jax.device_put(variables)
        jax.block_until_ready(out)
        return out

    def prepare_quantized(self, quantized: Any) -> Any:
        """Device-place a quantized weights wrapper (int8 codes + scales
        land on device as-is; dequantize happens in-program)."""
        from fedcrack_tpu.serve.quant import QuantizedVariables

        if not isinstance(quantized, QuantizedVariables):
            raise TypeError(
                f"prepare_quantized wants QuantizedVariables, got "
                f"{type(quantized).__name__}"
            )
        if self._fn_q is None:
            raise ValueError(
                "engine was built with quant='none'; rebuild with "
                "ServeConfig.quant='int8' to serve quantized weights"
            )
        if self._rep_sharding is not None:
            tree = jax.device_put(quantized.tree, self._rep_sharding)
        else:
            tree = jax.device_put(quantized.tree)
        jax.block_until_ready(tree)
        return QuantizedVariables(tree)

    # ---- bucket routing ----

    @property
    def bucket_sizes(self) -> tuple[int, ...]:
        return tuple(self.serve_config.bucket_sizes)

    @property
    def max_batch(self) -> int:
        return self._max_batch

    def bucket_for(self, h: int, w: int) -> int | None:
        """Smallest bucket that holds (h, w); None -> tiled path."""
        for size in self.serve_config.bucket_sizes:
            if h <= size and w <= size:
                return size
        return None

    def warmup(self, variables: Any) -> None:
        """Compile every bucket program before traffic arrives (first-request
        latency must not pay XLA compile). A quantized weights wrapper warms
        the quantized programs; a plain tree warms the reference programs —
        a fleet serving both warms both."""
        from fedcrack_tpu.serve.quant import QuantizedVariables

        fn = self._fn_q if isinstance(variables, QuantizedVariables) else self._fn
        tree = variables.tree if isinstance(variables, QuantizedVariables) else variables
        for size in self.serve_config.bucket_sizes:
            dummy = np.zeros((self._max_batch, size, size, 3), np.uint8)
            jax.block_until_ready(fn(tree, self._stage(dummy)))

    def _stage(self, images_u8: np.ndarray):
        if self._sharding is not None:
            return jax.device_put(images_u8, self._sharding)
        return jax.device_put(images_u8)

    def predict_bucket(self, variables: Any, images_u8: np.ndarray) -> np.ndarray:
        """Run one micro-batch through its bucket program.

        ``images_u8``: [B, S, S, 3] uint8 with B <= max_batch and S a bucket
        size; the batch lane is zero-padded to the compiled max_batch (pad
        lanes are discarded — inference-mode BN normalizes with running
        stats, so lanes are independent). Returns [B, S, S, 1] float32
        probabilities on host."""
        b, h, w, c = images_u8.shape
        if h != w or h not in self.serve_config.bucket_sizes:
            raise ValueError(f"not a compiled bucket shape: {images_u8.shape}")
        if b > self._max_batch:
            raise ValueError(f"batch {b} exceeds compiled max_batch {self._max_batch}")
        if images_u8.dtype != np.uint8:
            raise ValueError(f"expected uint8 transport bytes, got {images_u8.dtype}")
        if b < self._max_batch:
            pad = np.zeros((self._max_batch - b, h, w, c), np.uint8)
            images_u8 = np.concatenate([images_u8, pad], axis=0)
        from fedcrack_tpu.serve.quant import QuantizedVariables

        if isinstance(variables, QuantizedVariables):
            if self._fn_q is None:
                raise ValueError(
                    "quantized weights handed to an engine built with "
                    "quant='none'"
                )
            probs = self._fn_q(variables.tree, self._stage(images_u8))
        else:
            probs = self._fn(variables, self._stage(images_u8))
        return np.asarray(jax.device_get(probs))[:b]

    def predict_image(self, variables: Any, image_u8: np.ndarray) -> np.ndarray:
        """Serve one [H, W, 3] uint8 image at any size: direct bucket, padded
        bucket, or tiled sliding window. Returns [H, W, 1] float32 probs."""
        h, w, _ = image_u8.shape
        bucket = self.bucket_for(h, w)
        if bucket is not None:
            canvas = np.zeros((1, bucket, bucket, 3), np.uint8)
            canvas[0, :h, :w] = image_u8
            probs = self.predict_bucket(variables, canvas)
            return probs[0, :h, :w]
        return self.predict_tiled(variables, image_u8)

    # ---- tiled sliding-window inference ----

    def predict_tiled(self, variables: Any, image_u8: np.ndarray) -> np.ndarray:
        """Overlap-blended sliding-window inference for images beyond the
        largest bucket. Deterministic by construction: tile offsets, batch
        grouping, blend weights, and the float32 accumulation order are all
        fixed functions of (H, W, tile, overlap) — two runs produce
        byte-identical output (test-pinned)."""
        tile = max(self.serve_config.bucket_sizes)
        overlap = self.serve_config.tile_overlap
        h, w, _ = image_u8.shape
        # Pad either undersized dim up to one tile (cropped at the end).
        ph, pw = max(h, tile), max(w, tile)
        if (ph, pw) != (h, w):
            padded = np.zeros((ph, pw, 3), np.uint8)
            padded[:h, :w] = image_u8
            image_u8 = padded
        ys = tile_plan(ph, tile, overlap)
        xs = tile_plan(pw, tile, overlap)
        acc = np.zeros((ph, pw, 1), np.float32)
        wacc = np.zeros((ph, pw, 1), np.float32)
        tiles, spans = [], []
        for yi, y in enumerate(ys):
            for xi, x in enumerate(xs):
                tiles.append(image_u8[y : y + tile, x : x + tile])
                wy = _ramp_weights(tile, overlap, yi > 0, yi + 1 < len(ys))
                wx = _ramp_weights(tile, overlap, xi > 0, xi + 1 < len(xs))
                spans.append((y, x, np.outer(wy, wx)[..., None]))
        # Fixed-order batches of max_batch tiles; accumulation stays host-
        # side float32 in schedule order — determinism over speed of the
        # final reduce (the device work is still the batched bucket fn).
        for start in range(0, len(tiles), self._max_batch):
            chunk = np.stack(tiles[start : start + self._max_batch])
            probs = self.predict_bucket(variables, chunk)
            for i, (y, x, wgt) in enumerate(spans[start : start + self._max_batch]):
                acc[y : y + tile, x : x + tile] += probs[i] * wgt
                wacc[y : y + tile, x : x + tile] += wgt
        out = acc / wacc
        return out[:h, :w]

    def n_tiles(self, h: int, w: int) -> int:
        """How many tiles a (h, w) image costs on the tiled path (capacity
        accounting for the batcher/load-gen)."""
        tile = max(self.serve_config.bucket_sizes)
        overlap = self.serve_config.tile_overlap
        ph, pw = max(h, tile), max(w, tile)
        return len(tile_plan(ph, tile, overlap)) * len(tile_plan(pw, tile, overlap))


def watch_recompiles(engine: "InferenceEngine", registry: Any = None):
    """Export the engine's jit-cache stability as the serve plane's
    ``serve_recompiles_total`` gauge (a collect-time callback over a
    :class:`~fedcrack_tpu.analysis.sanitizers.RecompileSentry`).

    Call AFTER ``engine.warmup(...)``: the sentry marks the post-warmup
    cache size as steady state, so every scrape reports recompiles SINCE
    warmup — the steady-state/hot-swap contract says that number is 0, and
    tests/test_serve.py pins it through a real ``/metrics`` scrape. On jax
    builds without ``_cache_size`` the gauge reports -1 (unknown), never a
    false 0. Returns the sentry for direct assertions."""
    from fedcrack_tpu.analysis.sanitizers import RecompileSentry
    from fedcrack_tpu.obs.registry import REGISTRY

    sentry = RecompileSentry()
    supported = RecompileSentry.supported(engine._fn)
    if supported:
        sentry.watch("serve.predict", engine._fn)
        if engine._fn_q is not None:
            sentry.watch("serve.predict_int8", engine._fn_q)
        sentry.mark()
    reg = registry if registry is not None else REGISTRY
    reg.gauge(
        "serve_recompiles_total",
        "XLA recompiles of the serve predict program since warmup "
        "(steady-state contract: 0 across any number of hot swaps; "
        "-1 = this jax build exposes no jit cache size)",
    ).set_function(
        (lambda: sum(sentry.deltas().values())) if supported else (lambda: -1)
    )
    return sentry
