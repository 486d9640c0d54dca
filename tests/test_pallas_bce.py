"""Pallas fused BCE+stats kernel: numerics parity vs the XLA reference.

Runs the kernel under the Pallas interpreter (the suite is on the virtual
CPU mesh; the compiled path exercises the identical kernel body on real TPU).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedcrack_tpu.ops.losses import segmentation_metrics
from fedcrack_tpu.ops.pallas_bce import (
    bce_sums,
    default_impl,
    fused_segmentation_metrics,
)


def _data(n_elems: int, seed: int = 0):
    rng = np.random.RandomState(seed)
    logits = jnp.asarray(rng.normal(0, 2, (n_elems,)).astype(np.float32))
    masks = jnp.asarray((rng.uniform(size=(n_elems,)) > 0.7).astype(np.float32))
    return logits, masks


@pytest.mark.parametrize(
    "n", [1, 100, 128, 32768, 32769, 100_000]
)  # below/at/above one 256x128 block, plus ragged tails
def test_sums_parity_interpret_vs_jnp(n):
    logits, masks = _data(n, seed=n % 97)
    ref = bce_sums(logits, masks, "jnp")
    ker = bce_sums(logits, masks, "interpret")
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), rtol=1e-5, atol=1e-3)


def test_fused_metrics_match_reference_metrics():
    rng = np.random.RandomState(3)
    logits = jnp.asarray(rng.normal(0, 2, (2, 32, 32, 1)).astype(np.float32))
    masks = jnp.asarray((rng.uniform(size=(2, 32, 32, 1)) > 0.8).astype(np.float32))
    for pw in (None, 4.0):
        ref = segmentation_metrics(logits, masks, pos_weight=pw)
        fused = fused_segmentation_metrics(
            logits, masks, impl="interpret", pos_weight=pw
        )
        for key in ref:
            np.testing.assert_allclose(
                float(fused[key]), float(ref[key]), rtol=1e-5, atol=1e-5,
                err_msg=f"{key} pw={pw}",
            )


def test_gradient_matches_reference():
    logits, masks = _data(4096, seed=11)

    def loss_fused(x):
        return bce_sums(x, masks, "interpret")[0] / x.size

    def loss_ref(x):
        import optax

        return jnp.mean(optax.sigmoid_binary_cross_entropy(x, masks))

    g_fused = jax.grad(loss_fused)(logits)
    g_ref = jax.grad(loss_ref)(logits)
    np.testing.assert_allclose(
        np.asarray(g_fused), np.asarray(g_ref), rtol=1e-5, atol=1e-6
    )


def test_label_gradient_is_correct():
    logits, masks = _data(512, seed=5)

    def loss_fused(y):
        return bce_sums(logits, y, "interpret")[0]

    def loss_ref(y):
        import optax

        return jnp.sum(optax.sigmoid_binary_cross_entropy(logits, y))

    g_fused = jax.grad(loss_fused)(masks)
    g_ref = jax.grad(loss_ref)(masks)
    np.testing.assert_allclose(
        np.asarray(g_fused), np.asarray(g_ref), rtol=1e-5, atol=1e-4
    )


def test_pos_weight_loss_matches_weighted_bce():
    """pos_weight composes from the kernel's pos_bce_sum lane: the loss must
    equal mean((1 + (pw-1)*y) * bce) exactly, and pw=1 must be plain BCE."""
    import optax

    logits, masks = _data(4096, seed=23)
    pw = 3.0
    w = 1.0 + (pw - 1.0) * masks
    ref_loss = jnp.mean(w * optax.sigmoid_binary_cross_entropy(logits, masks))
    for impl in ("interpret", "jnp"):
        fused = fused_segmentation_metrics(logits, masks, impl=impl, pos_weight=pw)
        np.testing.assert_allclose(float(fused["loss"]), float(ref_loss), rtol=1e-5)
        one = fused_segmentation_metrics(logits, masks, impl=impl, pos_weight=1.0)
        plain = fused_segmentation_metrics(logits, masks, impl=impl)
        np.testing.assert_allclose(float(one["loss"]), float(plain["loss"]), rtol=1e-6)
        # counts are weight-independent
        assert float(fused["iou_inter"]) == float(plain["iou_inter"])


def test_pos_weight_gradient_matches_reference():
    import optax

    logits, masks = _data(2048, seed=29)
    pw = jnp.float32(5.0)
    w = 1.0 + (pw - 1.0) * masks

    def loss_fused(x):
        return fused_segmentation_metrics(
            x, masks, impl="interpret", pos_weight=pw
        )["loss"]

    def loss_ref(x):
        return jnp.mean(w * optax.sigmoid_binary_cross_entropy(x, masks))

    np.testing.assert_allclose(
        np.asarray(jax.grad(loss_fused)(logits)),
        np.asarray(jax.grad(loss_ref)(logits)),
        rtol=1e-5,
        atol=1e-6,
    )


def test_bfloat16_inputs_accumulate_in_f32():
    logits, masks = _data(8192, seed=7)
    ker = bce_sums(logits.astype(jnp.bfloat16), masks.astype(jnp.bfloat16), "interpret")
    ref = bce_sums(logits, masks, "jnp")
    assert ker.dtype == jnp.float32
    # bf16 quantization of inputs dominates the error budget
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), rtol=2e-2, atol=30.0)


def test_default_impl_on_cpu_is_jnp():
    assert default_impl() == "jnp"  # suite runs on the CPU mesh


def test_under_shard_map():
    """The kernel runs inside the mesh round's shard_map (fedavg_mesh.py).

    The Pallas *interpreter* does not propagate vma onto kernel-internal
    constants (iota/literals), so check_vma is disabled here — the compiled
    TPU path propagates vma via the out_shape annotation (pallas_bce.py) and
    runs under the mesh round's default-checked shard_map (not run on a chip
    since the pre-chip harness went; chip_smoke.py compiles the kernel alone)."""
    from jax.sharding import Mesh, PartitionSpec as P
    from functools import partial

    shard_map = partial(jax.shard_map, check_vma=False)

    devices = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devices, ("clients",))
    logits, masks = _data(4 * 1024, seed=17)
    logits = logits.reshape(4, 1024)
    masks = masks.reshape(4, 1024)

    def per_client(x, y):
        return bce_sums(x[0], y[0], "interpret")[None]

    fn = jax.jit(
        shard_map(
            per_client,
            mesh=mesh,
            in_specs=(P("clients"), P("clients")),
            out_specs=P("clients"),
        )
    )
    out = np.asarray(fn(logits, masks))
    for c in range(4):
        ref = np.asarray(bce_sums(logits[c], masks[c], "jnp"))
        np.testing.assert_allclose(out[c], ref, rtol=1e-5, atol=1e-3)


def test_jit_and_under_vmap():
    logits, masks = _data(2048, seed=13)
    jitted = jax.jit(lambda x, y: bce_sums(x, y, "interpret"))
    np.testing.assert_allclose(
        np.asarray(jitted(logits, masks)),
        np.asarray(bce_sums(logits, masks, "jnp")),
        rtol=1e-5,
        atol=1e-3,
    )
