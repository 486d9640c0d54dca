"""Spatial context parallelism: the U-Net sharded over image height with
halo exchange on the ICI mesh.

The reference has no sequence axis to parallelize (conv net on fixed
128x128 crops — SURVEY.md §5.7); the TPU-native analog of ring-attention /
sequence parallelism for this model family is **sharding the spatial H axis
across a ``space`` mesh axis** so arbitrarily tall images (large survey
photos, stitched crack panoramas) train and infer without replicating the
full activation map on any chip. Every 3x3 window that straddles a shard
boundary is fed by a one-row **halo exchange** (`lax.ppermute` with
neighbor permutation — zeros arrive at the global edges, which is exactly
SAME zero padding), so the sharded forward is numerically identical to the
single-device model: it consumes the *same* ``{'params', 'batch_stats'}``
pytree as :class:`fedcrack_tpu.models.ResUNet` and matches its output.

Per-op halo geometry (H axis; W stays shard-local), derived from the
reference architecture (client_fit_model.py:92-150):

- 3x3 stride-1 conv / depthwise / ConvTranspose, SAME: halo 1 up + 1 down
  (Keras/XLA pad (1,1)).
- 3x3 stride-2 conv (stem) and 3x3/2 max-pool, SAME on even H: XLA pads
  (0,1), so halo 1 *down* only; the pool's bottom-edge pad is -inf, not 0.
- 1x1 convs (residual projections, head) and x2 nearest upsampling: purely
  local — shard row offsets stay even because per-shard H is a multiple
  of 16 (stem /2 + three pools /2).

Training mode is **sync-BN**: batch moments are ``pmean``-ed over the
``space`` (and optional ``data``) axes, so the sharded train step computes
bit-for-bit the same update as the single-device
:func:`fedcrack_tpu.train.local.train_step` (gradients of the halo exchange
flow back through the transposed permutation automatically).
"""

from __future__ import annotations

import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from fedcrack_tpu.configs import ModelConfig
from fedcrack_tpu.models.resunet import _BN_EPSILON, _BN_MOMENTUM, upsample2x
from fedcrack_tpu.ops.pallas_bce import fused_segmentation_metrics
from fedcrack_tpu.train.local import make_optimizer

SPACE, DATA = "space", "data"

_DIMNUMS = ("NHWC", "HWIO", "NHWC")


def halo_exchange(
    x: jax.Array,
    axis_name: str,
    axis_size: int,
    up: int = 1,
    down: int = 1,
    fill: float = 0.0,
) -> jax.Array:
    """Concatenate ``up`` rows from the previous shard and ``down`` rows from
    the next shard onto the H axis (axis 1 of NHWC). Global edges receive
    ``fill`` (0 for SAME conv padding, -inf for max-pool padding)."""
    parts = []
    if up:
        recv = _shift(x[:, -up:], axis_name, axis_size, toward="down")
        if fill != 0.0:
            is_first = lax.axis_index(axis_name) == 0
            recv = jnp.where(is_first, jnp.full_like(recv, fill), recv)
        parts.append(recv)
    parts.append(x)
    if down:
        recv = _shift(x[:, :down], axis_name, axis_size, toward="up")
        if fill != 0.0:
            is_last = lax.axis_index(axis_name) == axis_size - 1
            recv = jnp.where(is_last, jnp.full_like(recv, fill), recv)
        parts.append(recv)
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else x


def _shift(rows: jax.Array, axis_name: str, axis_size: int, toward: str) -> jax.Array:
    """ppermute neighbor shift; destinations with no source get zeros."""
    if axis_size == 1:
        return jnp.zeros_like(rows)
    if toward == "down":  # shard s receives shard s-1's rows
        perm = [(i, i + 1) for i in range(axis_size - 1)]
    else:  # shard s receives shard s+1's rows
        perm = [(i + 1, i) for i in range(axis_size - 1)]
    return lax.ppermute(rows, axis_name, perm)


def _conv(x, kernel, bias=None, *, strides=(1, 1), padding, groups=1):
    kernel = kernel.astype(x.dtype)
    bias = None if bias is None else bias.astype(x.dtype)
    y = lax.conv_general_dilated(
        x,
        kernel,
        window_strides=strides,
        padding=padding,
        dimension_numbers=_DIMNUMS,
        feature_group_count=groups,
    )
    if bias is not None:
        y = y + bias
    return y


def _conv3x3_s1(x, p, axis_name, axis_size, *, groups=1):
    """SAME stride-1 3x3 (plain, depthwise, or ConvTranspose — all reduce to
    pad-(1,1) cross-correlation; Flax ConvTranspose with stride 1 does not
    flip the kernel)."""
    xp = halo_exchange(x, axis_name, axis_size, up=1, down=1)
    return _conv(
        x=xp,
        kernel=p["kernel"],
        bias=p.get("bias"),
        padding=[(0, 0), (1, 1)],
        groups=groups,
    )


def _conv3x3_s2(x, p, axis_name, axis_size):
    """SAME stride-2 3x3 on even H: XLA pads (0, 1) so only a bottom halo."""
    xp = halo_exchange(x, axis_name, axis_size, up=0, down=1)
    return _conv(
        x=xp,
        kernel=p["kernel"],
        bias=p.get("bias"),
        strides=(2, 2),
        padding=[(0, 0), (0, 1)],
    )


def _conv1x1(x, p, *, strides=(1, 1)):
    return _conv(
        x=x, kernel=p["kernel"], bias=p.get("bias"), strides=strides, padding=[(0, 0), (0, 0)]
    )


def _maxpool3x3_s2(x, axis_name, axis_size):
    """SAME 3x3/2 max-pool; the implicit SAME padding value is -inf."""
    neg = float(jnp.finfo(x.dtype).min)
    xp = halo_exchange(x, axis_name, axis_size, up=0, down=1, fill=neg)
    return lax.reduce_window(
        xp,
        -jnp.inf,
        lax.max,
        window_dimensions=(1, 3, 3, 1),
        window_strides=(1, 2, 2, 1),
        padding=[(0, 0), (0, 0), (0, 1), (0, 0)],
    )


def _bn(x, params, stats, *, train, sync_axes):
    """Keras-default BatchNorm (momentum 0.99, eps 1e-3). In train mode the
    batch moments are pmean-synchronized over ``sync_axes`` so sharded
    normalization equals the single-device op; returns updated running
    stats (train) or None (inference).

    Dtype handling mirrors flax.linen.BatchNorm: moments are computed in
    (at least) float32, normalization runs in the activation dtype with
    params/stats cast down, and running stats stay in their storage dtype —
    so bfloat16 compute configs behave like the single-device model instead
    of silently promoting everything to float32."""
    dtype = x.dtype
    scale, bias = params["scale"].astype(dtype), params["bias"].astype(dtype)
    if not train:
        # Association matches flax.linen.BatchNorm exactly:
        # (x - mean) * (rsqrt(var + eps) * scale) + bias.
        var = stats["var"].astype(dtype)
        mean = stats["mean"].astype(dtype)
        mul = lax.rsqrt(var + jnp.asarray(_BN_EPSILON, dtype)) * scale
        return (x - mean) * mul + bias, None
    axes = (0, 1, 2)
    stats_dtype = jnp.promote_types(jnp.float32, dtype)
    xs = x.astype(stats_dtype)
    mean = jnp.mean(xs, axes)
    mean2 = jnp.mean(jnp.square(xs), axes)
    if sync_axes:
        # One collective per layer: stack both moments into a single pmean.
        mean, mean2 = lax.pmean(jnp.stack([mean, mean2]), sync_axes)
    var = mean2 - jnp.square(mean)
    y = (x - mean.astype(dtype)) * (
        lax.rsqrt(var.astype(dtype) + jnp.asarray(_BN_EPSILON, dtype))
        * scale
    ) + bias
    new_stats = {
        "mean": _BN_MOMENTUM * stats["mean"] + (1.0 - _BN_MOMENTUM) * mean.astype(stats["mean"].dtype),
        "var": _BN_MOMENTUM * stats["var"] + (1.0 - _BN_MOMENTUM) * var.astype(stats["var"].dtype),
    }
    return y, new_stats


def spatial_apply(
    variables: dict,
    x: jax.Array,
    *,
    config: ModelConfig | None = None,
    axis_name: str = SPACE,
    axis_size: int,
    train: bool = False,
    sync_axes: Sequence[str] | None = None,
):
    """H-sharded forward of the crack U-Net (reference architecture:
    client_fit_model.py:92-150), consuming :class:`ResUNet` variables
    unchanged. Call inside ``shard_map`` with ``x`` sharded on axis 1.

    Returns logits (``train=False``) or ``(logits, new_batch_stats)``
    (``train=True``, sync-BN over ``sync_axes`` — defaults to the space
    axis).
    """
    cfg = config or ModelConfig()
    if cfg.stem_layout != "reference" or cfg.res_layout != "reference":
        # The per-op halo geometry above is derived for the reference ops;
        # silently computing the reference program under a transformed-layout
        # config would make the flag a no-op here. (Parameter shapes are
        # layout-invariant, so the VALUES would even be right — but a config
        # that claims a layout must either run it or refuse.)
        raise ValueError(
            "spatial_apply supports the reference layout only; got "
            f"stem_layout={cfg.stem_layout!r}, res_layout={cfg.res_layout!r}"
        )
    p = variables["params"]
    bs = variables["batch_stats"]
    sync = tuple(sync_axes) if sync_axes is not None else (axis_name,)
    new_stats: dict[str, Any] = {}
    bn = functools.partial(_bn, train=train, sync_axes=sync)
    x = x.astype(jnp.dtype(cfg.compute_dtype))

    def apply_bn(x, name):
        y, updated = bn(x, p[name], bs[name])
        if updated is not None:
            new_stats[name] = updated
        return y

    # Stem: /2.
    x = _conv3x3_s2(x, p["stem_conv"], axis_name, axis_size)
    x = apply_bn(x, "stem_bn")
    x = jax.nn.relu(x)
    previous = x

    # Encoder.
    for i, _features in enumerate(cfg.encoder_features):
        x = jax.nn.relu(x)
        x = _sepconv(x, p[f"enc{i}_sep1"], axis_name, axis_size)
        x = apply_bn(x, f"enc{i}_bn1")
        x = jax.nn.relu(x)
        x = _sepconv(x, p[f"enc{i}_sep2"], axis_name, axis_size)
        x = apply_bn(x, f"enc{i}_bn2")
        x = _maxpool3x3_s2(x, axis_name, axis_size)
        residual = _conv1x1(previous, p[f"enc{i}_res"], strides=(2, 2))
        x = x + residual
        previous = x

    # Decoder.
    for i, _features in enumerate(cfg.decoder_features):
        x = jax.nn.relu(x)
        x = _conv3x3_s1(x, p[f"dec{i}_convT1"], axis_name, axis_size)
        x = apply_bn(x, f"dec{i}_bn1")
        x = jax.nn.relu(x)
        x = _conv3x3_s1(x, p[f"dec{i}_convT2"], axis_name, axis_size)
        x = apply_bn(x, f"dec{i}_bn2")
        # Same algebraic fusion as models/resunet.py: the 1x1 residual conv
        # commutes with nearest upsampling, so conv + add happen pre-upsample
        # and one broadcast replaces two (also halves the halo shard's HBM
        # traffic here).
        residual = _conv1x1(previous, p[f"dec{i}_res"])
        x = x + residual
        if i + 1 < len(cfg.decoder_features):
            x = upsample2x(x)
            previous = x
        # else: final upsample deferred past the head, as in resunet.py.

    # Head at half resolution, then upsample the single logit channel —
    # the same head/upsample commute as models/resunet.py (upsampling is
    # shard-local: it only replicates within rows this shard owns).
    logits = upsample2x(_conv1x1(x.astype(jnp.float32), jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), p["head"]
    )))
    if not train:
        return logits
    return logits, new_stats


def _sepconv(x, p, axis_name, axis_size):
    """Keras SeparableConv2D: bias-free depthwise 3x3 + biased pointwise."""
    c = x.shape[-1]
    x = _conv3x3_s1(x, p["depthwise"], axis_name, axis_size, groups=c)
    return _conv1x1(x, p["pointwise"])


def _validate_shape(h: int, w: int, axis_size: int) -> None:
    # Per-shard H must survive stem /2 + three pools /2 with even alignment
    # at every stage, i.e. be a multiple of 16 (ModelConfig.__post_init__'s
    # single-device constraint, applied per shard). W stays local but the
    # hardcoded even-size SAME pads need the same /16 divisibility.
    if h % (16 * axis_size) != 0:
        raise ValueError(
            f"image height {h} must be a multiple of 16 x {axis_size} shards "
            f"= {16 * axis_size} for the spatially-sharded U-Net"
        )
    if w % 16 != 0:
        raise ValueError(
            f"image width {w} must be a multiple of 16 for the U-Net"
        )


def _image_spec(mesh: Mesh, batch_axis: str, space_axis: str) -> P:
    if space_axis not in mesh.shape:
        raise ValueError(f"mesh {mesh.axis_names} has no '{space_axis}' axis")
    batch = batch_axis if batch_axis in mesh.shape else None
    return P(batch, space_axis)


def build_spatial_predict(
    mesh: Mesh,
    config: ModelConfig | None = None,
    batch_axis: str = DATA,
    space_axis: str = SPACE,
):
    """Compile-once sharded inference: ``fn(variables, images[B,H,W,3]) ->
    sigmoid probabilities [B,H,W,1]``, H sharded over ``space_axis`` (and B
    over ``batch_axis`` when the mesh has one). Output equals
    :func:`fedcrack_tpu.models.predict` on one device."""
    cfg = config or ModelConfig()
    s = mesh.shape[space_axis]
    spec = _image_spec(mesh, batch_axis, space_axis)

    def fwd(variables, images):
        logits = spatial_apply(
            variables, images, config=cfg, axis_name=space_axis, axis_size=s
        )
        return jax.nn.sigmoid(logits)

    jitted = jax.jit(
        jax.shard_map(fwd, mesh=mesh, in_specs=(P(), spec), out_specs=spec)
    )

    def predict_fn(variables, images):
        _validate_shape(images.shape[1], images.shape[2], s)
        return jitted(variables, images)

    return predict_fn


def build_spatial_train_step(
    mesh: Mesh,
    config: ModelConfig | None = None,
    learning_rate: float = 1e-3,
    batch_axis: str = DATA,
    space_axis: str = SPACE,
    tx: optax.GradientTransformation | None = None,
    pos_weight: float = 1.0,
):
    """Compile-once sharded train step, numerically equivalent to the
    single-device :func:`fedcrack_tpu.train.local.train_step` (Adam + fused
    BCE, sync-BN): ``step(params, batch_stats, opt_state, images, masks) ->
    (params, batch_stats, opt_state, metrics)`` with images/masks sharded
    ``P(batch_axis?, space_axis)`` and all states replicated.

    ``tx`` overrides the default Adam (e.g. SGD for gradient-parity tests).
    Use ``step_fn.tx.init(params)`` for the initial ``opt_state``.
    """
    cfg = config or ModelConfig()
    tx = tx if tx is not None else make_optimizer(learning_rate)
    s = mesh.shape[space_axis]
    spec = _image_spec(mesh, batch_axis, space_axis)
    sync = tuple(a for a in (batch_axis, space_axis) if a in mesh.shape)
    pw = float(pos_weight)

    def step(params, batch_stats, opt_state, images, masks):
        def loss_fn(prm):
            logits, new_stats = spatial_apply(
                {"params": prm, "batch_stats": batch_stats},
                images,
                config=cfg,
                axis_name=space_axis,
                axis_size=s,
                train=True,
                sync_axes=sync,
            )
            m = fused_segmentation_metrics(
                logits, masks, pos_weight=jnp.asarray(pw, jnp.float32)
            )
            return m["loss"], (m, new_stats)

        (_, (m, new_stats)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params
        )
        # `params` is replicated (unvarying) over the mesh, so shard_map's AD
        # already psums the per-shard cotangents to keep the gradient
        # replicated; with equal-sized shards dividing by the shard count
        # turns that sum of local-mean gradients into the gradient of the
        # global-mean loss.
        n_shards = 1
        for a in sync:
            n_shards *= mesh.shape[a]
        grads = jax.tree_util.tree_map(lambda g: g / n_shards, grads)
        updates, new_opt_state = tx.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        metrics = {
            "loss": lax.pmean(m["loss"], sync),
            "pixel_acc": lax.pmean(m["pixel_acc"], sync),
            "iou_inter": lax.psum(m["iou_inter"], sync),
            "iou_union": lax.psum(m["iou_union"], sync),
        }
        return new_params, new_stats, new_opt_state, metrics

    jitted = jax.jit(
        jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(P(), P(), P(), spec, spec),
            out_specs=(P(), P(), P(), P()),
        )
    )

    def step_fn(params, batch_stats, opt_state, images, masks):
        _validate_shape(images.shape[1], images.shape[2], s)
        return jitted(params, batch_stats, opt_state, images, masks)

    step_fn.tx = tx
    return step_fn


def make_spatial_mesh(
    n_space: int,
    n_data: int = 1,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Mesh with axes ``('data', 'space')`` for spatially-sharded jobs."""
    from fedcrack_tpu.parallel.mesh import make_mesh

    return make_mesh(n_data, n_space, devices, axis_names=(DATA, SPACE))
