"""Plain float32 reference of one federated round of the crack U-Net.

Straightforward ``jax.numpy``: the network's train-mode forward, the mean
sigmoid BCE, its gradients, Adam, the BatchNorm running statistics and the
sample-weighted client average. It imports nothing of ``fedcrack_tpu`` and
takes nothing that the program has made; weights and data come from the
benchmark's seed (``init_variables`` here, ``lib/datagen.py``).

It follows the published network (reference repo ``client_fit_model.py:92-150``:
stem Conv(32, 3x3, /2)+BN+ReLU; encoder blocks (64, 128, 256) of two
ReLU -> SeparableConv -> BN, MaxPool(3x3, /2, SAME) and a strided 1x1
residual; decoder blocks (256, 128, 64, 32) of two ReLU -> ConvT(3x3) -> BN,
nearest x2 upsampling and a 1x1 residual of the upsampled block input; a 1x1
sigmoid head) in the order it is written there, with Keras' BN defaults
(momentum 0.99, eps 1e-3). One departure: the decoder's residual 1x1
convolution runs before its upsampling, not after; the values are the same
to the bit (see ``forward_train``). A stride-1 transposed
3x3 convolution is written as the plain convolution it equals under the
kernel layout the configuration's weights use ([kh, kw, in, out], unflipped).

``operands`` selects the precision every convolution's operands are rounded
to, in its forward and in both of its backward passes, before an exact
float32 accumulation; everything between the convolutions stays float32:

- ``None``: float32, nothing rounded (the reference proper; run it under
  ``jax.default_matmul_precision("highest")``);
- ``"bfloat16"``: the operand type the configuration states;
- ``"float8_e4m3fn"``: the control, the nearest precision below bfloat16 as
  an fp8 training path computes: e4m3 activations and kernels, e5m2
  gradients, each with a per-tensor scale to the format's range.

``fault`` plants the faults the check has to catch into the reference put in
the program's place: ``"half_batch"`` drops the second half of every batch
and takes every mean over the rest.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

BN_MOMENTUM = 0.99
BN_EPS = 1e-3
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-7
_DN = ("NHWC", "HWIO", "NHWC")


# ---- weights from a seed -------------------------------------------------


def _layer_shapes(cfg: dict) -> list[tuple[str, tuple, str]]:
    """``(path, shape, kind)`` of every parameter leaf, in a fixed order."""
    out = []
    c_in, c = cfg["in_channels"], cfg["stem_features"]
    out += [("stem_conv/kernel", (3, 3, c_in, c), "w"), ("stem_conv/bias", (c,), "0")]
    out += _bn_shapes("stem_bn", c)
    for i, f in enumerate(cfg["encoder_features"]):
        for j, cin in ((1, c), (2, f)):
            out += [
                (f"enc{i}_sep{j}/depthwise/kernel", (3, 3, 1, cin), "w"),
                (f"enc{i}_sep{j}/pointwise/kernel", (1, 1, cin, f), "w"),
                (f"enc{i}_sep{j}/pointwise/bias", (f,), "0"),
            ]
            out += _bn_shapes(f"enc{i}_bn{j}", f)
        out += [(f"enc{i}_res/kernel", (1, 1, c, f), "w"), (f"enc{i}_res/bias", (f,), "0")]
        c = f
    for i, f in enumerate(cfg["decoder_features"]):
        for j, cin in ((1, c), (2, f)):
            out += [
                (f"dec{i}_convT{j}/kernel", (3, 3, cin, f), "w"),
                (f"dec{i}_convT{j}/bias", (f,), "0"),
            ]
            out += _bn_shapes(f"dec{i}_bn{j}", f)
        out += [(f"dec{i}_res/kernel", (1, 1, c, f), "w"), (f"dec{i}_res/bias", (f,), "0")]
        c = f
    n_cls = cfg["num_classes"]
    out += [("head/kernel", (1, 1, c, n_cls), "w"), ("head/bias", (n_cls,), "0")]
    return out


def _bn_shapes(name: str, c: int) -> list[tuple[str, tuple, str]]:
    return [(f"{name}/scale", (c,), "1"), (f"{name}/bias", (c,), "0")]


def _set(tree: dict, path: str, value) -> None:
    keys = path.split("/")
    for k in keys[:-1]:
        tree = tree.setdefault(k, {})
    tree[keys[-1]] = value


def init_variables(seed_words, cfg: dict) -> dict:
    """``{"params", "batch_stats"}`` from a seed given as two uint32 words
    (low, high); traceable, so one jitted call makes the whole model on the
    device. Kernels are Glorot uniform over their receptive field's fan-in
    and fan-out, biases 0, BN scale 1, running mean 0 and variance 1 (the
    Keras defaults the source trains from)."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), seed_words[0]), seed_words[1])
    params: dict = {}
    stats: dict = {}
    for n, (path, shape, kind) in enumerate(_layer_shapes(cfg)):
        if kind == "w":
            field = shape[0] * shape[1]
            limit = (6.0 / (field * shape[2] + field * shape[3])) ** 0.5
            leaf = jax.random.uniform(
                jax.random.fold_in(key, n), shape, jnp.float32, -limit, limit
            )
        else:
            leaf = jnp.full(shape, float(kind), jnp.float32)
        _set(params, path, leaf)
        if path.endswith("/scale"):
            bn = path.rsplit("/", 1)[0]
            _set(stats, f"{bn}/mean", jnp.zeros(shape, jnp.float32))
            _set(stats, f"{bn}/var", jnp.ones(shape, jnp.float32))
    return {"params": params, "batch_stats": stats}


def make_variables(seed: int, cfg: dict) -> dict:
    """:func:`init_variables` in one jitted call, for any non-negative seed."""
    import numpy as np

    words = np.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], np.uint32)
    return jax.jit(lambda w: init_variables(w, cfg))(words)


# ---- forward ---------------------------------------------------------------


def _round_to(x, dtype):
    """``x`` rounded to ``dtype`` and back to float32. The float8 formats get
    a per-tensor scale to their range, as an fp8 path carries one."""
    if dtype == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    fmt = {"float8_e4m3fn": jnp.float8_e4m3fn, "float8_e5m2": jnp.float8_e5m2}[dtype]
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(fmt).max)
    return (x / scale).astype(fmt).astype(jnp.float32) * scale


def _plain_conv(x, k, stride, groups):
    pad = "SAME" if k.shape[0] > 1 else "VALID"
    return lax.conv_general_dilated(
        x, k, (stride, stride), pad, dimension_numbers=_DN, feature_group_count=groups
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rounded_conv(x, k, stride, groups, operands):
    """A convolution all of whose operands are rounded, in the backward
    passes as in the forward: activations and kernel to ``operands``, the
    incoming gradient to the format's gradient type (e5m2 beside e4m3)."""
    return _plain_conv(_round_to(x, operands), _round_to(k, operands), stride, groups)


def _rounded_conv_fwd(x, k, stride, groups, operands):
    xr, kr = _round_to(x, operands), _round_to(k, operands)
    return _plain_conv(xr, kr, stride, groups), (xr, kr)


def _rounded_conv_bwd(stride, groups, operands, residuals, g):
    xr, kr = residuals
    grad_type = "float8_e5m2" if operands == "float8_e4m3fn" else operands
    _, vjp = jax.vjp(lambda a, b: _plain_conv(a, b, stride, groups), xr, kr)
    return vjp(_round_to(g, grad_type))


_rounded_conv.defvjp(_rounded_conv_fwd, _rounded_conv_bwd)


def _conv(x, layer, operands, *, stride=1, groups=1):
    if operands is None:
        y = _plain_conv(x, layer["kernel"], stride, groups)
    else:
        y = _rounded_conv(x, layer["kernel"], stride, groups, operands)
    return y + layer["bias"] if "bias" in layer else y


def _batch_norm(x, p, s):
    """Train mode: normalise by the batch's own moments; returns the output
    and the moved running statistics."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    y = (x - mean) * lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]
    new = {
        "mean": BN_MOMENTUM * s["mean"] + (1.0 - BN_MOMENTUM) * mean,
        "var": BN_MOMENTUM * s["var"] + (1.0 - BN_MOMENTUM) * var,
    }
    return y, new


def _max_pool(x):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )


def _upsample2(x):
    """Nearest-neighbour x2: every pixel becomes a 2x2 block."""
    n, h, w, c = x.shape
    return jnp.broadcast_to(x[:, :, None, :, None, :], (n, h, 2, w, 2, c)).reshape(n, 2 * h, 2 * w, c)


def forward_train(params, stats, images, cfg: dict, operands=None):
    """Logits ``[N, H, W, 1]`` and the new running statistics."""
    new_stats = {}

    def bn(name, x):
        y, new_stats[name] = _batch_norm(x, params[name], stats[name])
        return y

    def sep(name, x):
        p = params[name]
        x = _conv(x, p["depthwise"], operands, groups=x.shape[-1])
        return _conv(x, p["pointwise"], operands)

    x = _conv(images, params["stem_conv"], operands, stride=2)
    x = jax.nn.relu(bn("stem_bn", x))
    previous = x
    for i in range(len(cfg["encoder_features"])):
        x = bn(f"enc{i}_bn1", sep(f"enc{i}_sep1", jax.nn.relu(x)))
        x = bn(f"enc{i}_bn2", sep(f"enc{i}_sep2", jax.nn.relu(x)))
        x = _max_pool(x)
        x = x + _conv(previous, params[f"enc{i}_res"], operands, stride=2)
        previous = x
    for i in range(len(cfg["decoder_features"])):
        x = bn(f"dec{i}_bn1", _conv(jax.nn.relu(x), params[f"dec{i}_convT1"], operands))
        x = bn(f"dec{i}_bn2", _conv(jax.nn.relu(x), params[f"dec{i}_convT2"], operands))
        # The source upsamples both branches and then projects the residual
        # with its 1x1 convolution; a 1x1 convolution gives every copy of a
        # pixel the same dot product, so projecting first is the same values
        # to the bit at a quarter of the traffic.
        x = _upsample2(x) + _upsample2(_conv(previous, params[f"dec{i}_res"], operands))
        previous = x
    return _conv(x, params["head"], operands), new_stats


def bce_mean(logits, masks):
    """Mean over all pixels of the sigmoid binary cross-entropy."""
    z = logits
    return jnp.mean(jnp.maximum(z, 0.0) - z * masks + jnp.log1p(jnp.exp(-jnp.abs(z))))


# ---- one client's local fit and the average --------------------------------


def _adam(params, grads, m, v, t, lr):
    m = jax.tree_util.tree_map(lambda a, g: ADAM_B1 * a + (1 - ADAM_B1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda a, g: ADAM_B2 * a + (1 - ADAM_B2) * g * g, v, grads)
    c1, c2 = 1 - ADAM_B1**t, 1 - ADAM_B2**t
    params = jax.tree_util.tree_map(
        lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + ADAM_EPS), params, m, v
    )
    return params, m, v


@functools.partial(jax.jit, static_argnames=("shape", "cfg_key", "lr", "operands", "fault"), donate_argnums=(0,))
def _step(carry, images, masks, *, shape, cfg_key, lr, operands, fault):
    """One SGD step on one batch of uint8 transport bytes, handed over flat
    (a uint8 array whose last axis is 3 is transposed on the host on its way
    to the chip) and given its ``shape`` ``(B, H, W)`` back here. The carry
    also sums the step's loss, accuracy and every leaf's gradient norm."""
    cfg = dict(cfg_key)
    params, stats, m, v, t, sums = carry
    imgs = images.reshape(shape + (cfg["in_channels"],)).astype(jnp.float32) * jnp.float32(1.0 / 255.0)
    msks = masks.reshape(shape + (cfg["num_classes"],)).astype(jnp.float32)
    if fault == "half_batch":
        half = imgs.shape[0] // 2
        imgs, msks = imgs[:half], msks[:half]

    def loss_fn(p):
        logits, new_stats = forward_train(p, stats, imgs, cfg, operands)
        return bce_mean(logits, msks), (new_stats, logits)

    (loss, (new_stats, logits)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    t = t + 1.0
    params, m, v = _adam(params, grads, m, v, t, lr)
    acc = jnp.mean(((logits > 0) == (msks > 0.5)).astype(jnp.float32))
    sums = {
        "loss": sums["loss"] + loss,
        "pixel_acc": sums["pixel_acc"] + acc,
        "grad_norms": jax.tree_util.tree_map(
            lambda a, g: a + jnp.sqrt(jnp.sum(g * g)), sums["grad_norms"], grads
        ),
    }
    return params, new_stats, m, v, t, sums


def client_round(variables, images, masks, cfg: dict, lr: float, *, operands=None, fault=None, device=None):
    """One client's local epoch over ``images``/``masks`` ``[steps, B, H, W, C]``
    (uint8 transport bytes on the host), Adam starting fresh, one batch at a
    time so that only a step's activations are ever on the device. Returns
    the client's variables and the means over the steps of its ``loss``,
    ``pixel_acc`` and every parameter leaf's gradient norm (``grad_norms``),
    all still on the device."""
    cfg_key = tuple(
        (k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
        for k in ("in_channels", "stem_features", "encoder_features", "decoder_features", "num_classes")
    )
    params, stats = jax.device_put((variables["params"], variables["batch_stats"]), device)
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
    scalar = lambda: jax.device_put(jnp.float32(0.0), device)
    sums = {"loss": scalar(), "pixel_acc": scalar(), "grad_norms": jax.tree_util.tree_map(lambda p: scalar(), params)}
    # The carry is donated step by step, so the caller's variables are copied.
    carry = (jax.tree_util.tree_map(jnp.copy, params), jax.tree_util.tree_map(jnp.copy, stats), zeros(), zeros(), scalar(), sums)
    steps = images.shape[0]
    with jax.default_matmul_precision("highest"):
        for s in range(steps):
            batch = jax.device_put((images[s].reshape(-1), masks[s].reshape(-1)), device)
            carry = _step(
                carry, *batch, shape=tuple(images.shape[1:4]), cfg_key=cfg_key,
                lr=float(lr), operands=operands, fault=fault,
            )
    means = jax.tree_util.tree_map(lambda x: x / steps, carry[5])
    return {"params": carry[0], "batch_stats": carry[1]}, means


def weighted_average(client_variables: list, weights: list) -> dict:
    """FedAvg: the sample-weighted mean of the clients' parameters and BN
    statistics, in float32 on the host."""
    import numpy as np

    total = float(sum(weights))
    return jax.tree_util.tree_map(
        lambda *leaves: sum(np.float32(w / total) * np.asarray(x, np.float32) for w, x in zip(weights, leaves)),
        *client_variables,
    )
