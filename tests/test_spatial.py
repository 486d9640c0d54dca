"""Spatial context parallelism: halo-exchange sharded U-Net vs one device.

The sharded forward/train step must be numerically identical to the
single-device model on the SAME variables pytree (parallel/spatial.py);
these are the golden cross-checks (SURVEY.md §4 pattern: mesh == host)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from fedcrack_tpu.configs import ModelConfig
from fedcrack_tpu.models.resunet import init_variables, predict
from fedcrack_tpu.parallel.spatial import (
    build_spatial_predict,
    build_spatial_train_step,
    halo_exchange,
    make_spatial_mesh,
)
from fedcrack_tpu.train.local import create_train_state, train_step

CFG = ModelConfig(img_size=64)


def _variables_and_batch(batch=2, h=64, w=64, seed=0):
    rng = jax.random.key(seed)
    variables = init_variables(rng, CFG)
    kimg, kmask = jax.random.split(jax.random.key(seed + 1))
    images = jax.random.uniform(kimg, (batch, h, w, 3), jnp.float32)
    masks = (jax.random.uniform(kmask, (batch, h, w, 1)) > 0.7).astype(jnp.float32)
    return variables, np.asarray(images), np.asarray(masks)


def test_halo_exchange_neighbor_rows_and_edge_fill():
    mesh = make_spatial_mesh(4)
    x = np.arange(8 * 2, dtype=np.float32).reshape(1, 8, 2, 1)

    def body(xs):
        return halo_exchange(xs, "space", 4, up=1, down=1, fill=0.0)

    out = jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=P(None, "space"), out_specs=P(None, "space")
        )
    )(x)
    out = np.asarray(out).reshape(4, 4, 2)  # 4 shards x (1 up + 2 own + 1 down)
    ref = x.reshape(8, 2)
    for s in range(4):
        own = ref[2 * s : 2 * s + 2]
        up = ref[2 * s - 1] if s > 0 else np.zeros(2, np.float32)
        down = ref[2 * s + 2] if s < 3 else np.zeros(2, np.float32)
        np.testing.assert_array_equal(out[s], np.stack([up, *own, down]))


def test_spatial_predict_matches_single_device():
    variables, images, _ = _variables_and_batch()
    want = np.asarray(predict(variables, images, CFG))

    mesh = make_spatial_mesh(4)
    predict_fn = build_spatial_predict(mesh, CFG)
    got = np.asarray(predict_fn(variables, images))

    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_spatial_predict_with_data_axis():
    variables, images, _ = _variables_and_batch(batch=2)
    want = np.asarray(predict(variables, images, CFG))

    mesh = make_spatial_mesh(4, n_data=2)
    predict_fn = build_spatial_predict(mesh, CFG)
    got = np.asarray(predict_fn(variables, images))

    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_spatial_predict_bfloat16_config():
    """bf16 compute configs must track the single-device bf16 model (loose
    tolerance — bf16 rounding), not silently promote to float32."""
    cfg = ModelConfig(img_size=64, compute_dtype="bfloat16")
    variables, images, _ = _variables_and_batch()
    want = np.asarray(predict(variables, images, cfg), np.float32)

    mesh = make_spatial_mesh(4)
    got = np.asarray(build_spatial_predict(mesh, cfg)(variables, images), np.float32)

    np.testing.assert_allclose(got, want, rtol=0.1, atol=0.05)


def test_spatial_predict_rejects_misaligned_height():
    mesh = make_spatial_mesh(4)
    predict_fn = build_spatial_predict(mesh, CFG)
    variables, _, _ = _variables_and_batch()
    bad = np.zeros((1, 48, 64, 3), np.float32)  # 48 % (16*4) != 0
    with pytest.raises(ValueError, match="multiple of 16"):
        predict_fn(variables, bad)


def test_spatial_train_step_matches_single_device():
    """Gradient + sync-BN parity. The sharded step runs with SGD(1.0) so the
    param delta IS the (pmean-ed) gradient — Adam's g/|g| normalization
    would amplify fp-associativity noise on near-zero gradients into
    arbitrary relative error, which tests nothing."""
    variables, images, masks = _variables_and_batch()

    # Single-device reference: gradient of the identical loss.
    from fedcrack_tpu.models import ResUNet
    from fedcrack_tpu.ops.pallas_bce import fused_segmentation_metrics

    model = ResUNet(config=CFG)

    def loss_fn(params):
        logits, mutated = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            images,
            train=True,
            mutable=["batch_stats"],
        )
        m = fused_segmentation_metrics(logits, jnp.asarray(masks))
        return m["loss"], (m["loss"], mutated["batch_stats"])

    (_, (ref_loss, ref_stats)), ref_grads = jax.jit(
        jax.value_and_grad(loss_fn, has_aux=True)
    )(variables["params"])

    # Sharded step over 4 spatial shards on the same variables.
    import optax

    mesh = make_spatial_mesh(4)
    step_fn = build_spatial_train_step(mesh, CFG, tx=optax.sgd(1.0))
    opt_state = step_fn.tx.init(variables["params"])
    new_params, new_stats, _, metrics = step_fn(
        variables["params"], variables["batch_stats"], opt_state, images, masks
    )
    sharded_grads = jax.tree_util.tree_map(
        lambda old, new: old - new, variables["params"], new_params
    )

    np.testing.assert_allclose(
        float(metrics["loss"]), float(ref_loss), rtol=1e-5, atol=1e-6
    )
    # Both sides are float32 renditions of the same math (verified exact to
    # 5e-9 against a float64 oracle), each ~1e-5 relative-L2 from the true
    # gradient — so compare norms per leaf, not elements: elementwise ratios
    # are meaningless where the true gradient is ~0 (e.g. conv biases feeding
    # BatchNorm, whose gradient cancels exactly).
    def assert_close_norm(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        err = np.linalg.norm(a - b)
        assert err <= 5e-3 * np.linalg.norm(b) + 1e-5, (
            f"gradient leaf off by ||d||={err:.3e} vs ||ref||={np.linalg.norm(b):.3e}"
        )

    jax.tree_util.tree_map(assert_close_norm, sharded_grads, ref_grads)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        ),
        new_stats,
        ref_stats,
    )
