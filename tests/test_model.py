"""ResUNet shape/structure parity with SURVEY.md §2.3, plus the layout-
transform invariants (round 6): the space-to-depth stem and channel-packed
residual projections are exact re-expressions of the reference math over the
SAME parameter tree — reverting or degrading a transform fails here, not
just in a benchmark. The decoder's folded upsample (PR 27) and the encoder's
composed separable convolutions (PR 29) are held to the forms Keras states
the same way, and the last decoder block's packed tail (PR 31) to the parent's
unpacked one, at the end of this file."""

import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from treecmp import bn_shadowed_bias

from fedcrack_tpu.configs import ModelConfig
from fedcrack_tpu.models import ResUNet, get_model
from fedcrack_tpu.ops.pooling import max_pool_width_folded
from fedcrack_tpu.models.resunet import (
    PackedConvT,
    PhaseBatchNorm,
    PhaseConv1x1,
    SeparableConv,
    UpsampledConvT,
    compose_separable_kernel,
    depth_to_space,
    fold_kernel_phases,
    fold_kernel_width,
    fold_upsample_into_kernel,
    fold_stem_kernel_s2d,
    fold_stem_kernel_s2d_full,
    init_variables,
    pack_res_kernel,
    predict,
    space_to_depth,
    unfold_stem_kernel_s2d,
    unfold_stem_kernel_s2d_full,
    unpack_res_kernel,
    upsample2x,
)


@pytest.fixture(scope="module")
def variables():
    return init_variables(jax.random.key(0))


def test_output_shape_matches_mask(variables):
    """128x128x3 in -> 128x128x1 logits out (full-resolution masks)."""
    model = ResUNet()
    x = jnp.zeros((2, 128, 128, 3))
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 128, 128, 1)


def test_bottleneck_spatial_bookkeeping():
    """Stem /2 and three pools /2: 128 -> 8 at the bottleneck (SURVEY §2.3)."""
    assert 128 // 2 // 2 // 2 // 2 == 8


def test_train_mode_updates_batch_stats(variables):
    model = ResUNet()
    x = jax.random.normal(jax.random.key(1), (2, 128, 128, 3))
    logits, mutated = model.apply(
        variables, x, train=True, mutable=["batch_stats"]
    )
    assert logits.shape == (2, 128, 128, 1)
    # running stats must actually move
    old = jax.tree_util.tree_leaves(variables["batch_stats"])
    new = jax.tree_util.tree_leaves(mutated["batch_stats"])
    assert any(
        not jnp.allclose(o, n) for o, n in zip(old, new)
    ), "batch_stats unchanged in train mode"


def test_param_structure_matches_reference_layer_inventory(variables):
    """One stem, 3 encoder blocks, 4 decoder blocks, 1 head (client_fit_model.py:92-150)."""
    params = variables["params"]
    names = set(params.keys())
    assert "stem_conv" in names and "stem_bn" in names and "head" in names
    for i in range(3):
        for suffix in ("sep1", "bn1", "sep2", "bn2", "res"):
            assert f"enc{i}_{suffix}" in names, f"missing enc{i}_{suffix}"
    for i in range(4):
        for suffix in ("convT1", "bn1", "convT2", "bn2", "res"):
            assert f"dec{i}_{suffix}" in names, f"missing dec{i}_{suffix}"
    # encoder separable convs: depthwise has no bias, pointwise does (Keras parity)
    sep = params["enc0_sep1"]
    assert "bias" not in sep["depthwise"]
    assert "bias" in sep["pointwise"]


def test_param_count_matches_keras_reference(variables):
    """The Keras builder reports 2,054,369 trainable params + 3,776 BN moving
    stats for this net (measured by building client_fit_model.py:92-150's
    architecture in Keras)."""
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(variables["params"]))
    n_stats = sum(p.size for p in jax.tree_util.tree_leaves(variables["batch_stats"]))
    assert n_params == 2_054_369, f"got {n_params}"
    assert n_stats == 3_776, f"got {n_stats}"


def test_predict_in_unit_interval(variables):
    x = jax.random.normal(jax.random.key(2), (1, 128, 128, 3))
    probs = predict(variables, x)
    assert probs.shape == (1, 128, 128, 1)
    assert float(probs.min()) >= 0.0 and float(probs.max()) <= 1.0


def test_upsample2x_nearest():
    x = jnp.arange(4.0).reshape(1, 2, 2, 1)
    y = upsample2x(x)
    assert y.shape == (1, 4, 4, 1)
    assert float(y[0, 0, 0, 0]) == 0.0 and float(y[0, 1, 1, 0]) == 0.0
    assert float(y[0, 3, 3, 0]) == 3.0


def test_registry_accepts_legacy_alias():
    """The reference advertises 'mobilenet_v2' (fl_server.py:75) but means the U-Net."""
    m = get_model("mobilenet_v2")
    assert isinstance(m, ResUNet)
    with pytest.raises(KeyError):
        get_model("resnet50")


def test_bf16_compute_f32_params():
    cfg = ModelConfig(compute_dtype="bfloat16")
    v = init_variables(jax.random.key(0), cfg)
    leaves = jax.tree_util.tree_leaves(v["params"])
    assert all(p.dtype == jnp.float32 for p in leaves)
    model = ResUNet(config=cfg)
    logits = model.apply(v, jnp.zeros((1, 128, 128, 3)), train=False)
    assert logits.dtype == jnp.float32  # head promotes to f32 for the loss


def test_jit_compiles_once_static_shapes(variables):
    model = ResUNet()
    fn = jax.jit(lambda v, x: model.apply(v, x, train=False))
    x = jnp.zeros((1, 128, 128, 3))
    fn(variables, x).block_until_ready()
    assert fn._cache_size() == 1
    fn(variables, x + 1).block_until_ready()
    assert fn._cache_size() == 1


# ---- layout transforms (round 6) -------------------------------------------


def _layout_cfg(img_size=128, **kw):
    return ModelConfig(img_size=img_size, **kw)


def test_space_to_depth_channel_order_and_inverse():
    """Packed channel = (di*2+dj)*C + c — the documented block-position-major
    order every fold/packing helper and the host-side stager rely on."""
    x = jnp.arange(2 * 2 * 3, dtype=jnp.float32).reshape(1, 2, 2, 3)
    p = space_to_depth(x)
    assert p.shape == (1, 1, 1, 12)
    for di in range(2):
        for dj in range(2):
            for c in range(3):
                assert float(p[0, 0, 0, (di * 2 + dj) * 3 + c]) == float(
                    x[0, di, dj, c]
                )
    assert jnp.array_equal(depth_to_space(p), x)


def test_host_and_device_space_to_depth_agree():
    """data.pipeline.space_to_depth_images (staging twin) must pack
    identically to the model's device-side transform — on batch arrays AND
    the [C, steps, B, ...] round layout, uint8 and float32."""
    from fedcrack_tpu.data.pipeline import space_to_depth_images

    rng = np.random.default_rng(0)
    batch = rng.integers(0, 255, (2, 32, 32, 3), dtype=np.uint8)
    assert np.array_equal(
        space_to_depth_images(batch), np.asarray(space_to_depth(jnp.asarray(batch)))
    )
    stacked = rng.random((2, 3, 2, 32, 32, 3), dtype=np.float32)
    packed = space_to_depth_images(stacked)
    assert packed.shape == (2, 3, 2, 16, 16, 12)
    assert np.array_equal(
        packed[1, 2], np.asarray(space_to_depth(jnp.asarray(stacked[1, 2])))
    )


def test_fold_unfold_round_trips_are_exact(variables):
    """The weight-export inverses recover the reference kernels bitwise."""
    k = variables["params"]["stem_conv"]["kernel"]
    assert jnp.array_equal(unfold_stem_kernel_s2d(fold_stem_kernel_s2d(k)), k)
    assert jnp.array_equal(
        unfold_stem_kernel_s2d_full(fold_stem_kernel_s2d_full(k)), k
    )
    r = variables["params"]["enc0_res"]["kernel"]
    assert jnp.array_equal(unpack_res_kernel(pack_res_kernel(r)), r)


def test_layout_flags_do_not_change_params(variables):
    """Initialization is IDENTICAL across layouts (same param tree, same RNG
    folds) — the property that keeps h5 import/export, FedAvg, the wire
    format and checkpoints layout-blind."""
    for stem, res in (("s2d", "reference"), ("s2d_full", "packed"), ("s2d", "packed")):
        cfg = _layout_cfg(stem_layout=stem, res_layout=res)
        v = init_variables(jax.random.key(0), cfg)
        ref_leaves = jax.tree_util.tree_leaves(variables)
        for a, b in zip(ref_leaves, jax.tree_util.tree_leaves(v)):
            assert a.shape == b.shape
            assert jnp.array_equal(a, b)


# 128 px (the flagship size class) stays tier-1; the 256 px
# belt-and-suspenders variant is slow-marked (round-14 budget re-balance —
# a second full-size forward-parity compile, same code path).
@pytest.mark.parametrize(
    "img", [128, pytest.param(256, marks=pytest.mark.slow)]
)
def test_s2d_layout_bit_exact_random_and_fixture_inputs(variables, img):
    """THE transform pin (ISSUE r6): stem_layout='s2d' + res_layout='packed'
    reproduce the reference layout's logits BIT-EXACTLY at 128 and 256 px,
    on random inputs and on the synthetic crack fixtures — same weights,
    different executed program. (Weights are resolution-independent, so the
    module fixture serves both sizes.)"""
    from fedcrack_tpu.data.synthetic import synth_crack_batch

    ref_model = ResUNet(config=_layout_cfg(img))
    s2d_model = ResUNet(
        config=_layout_cfg(img, stem_layout="s2d", res_layout="packed")
    )

    rand = jax.random.uniform(jax.random.key(7), (2, img, img, 3), jnp.float32)
    fixture, _ = synth_crack_batch(2, img_size=img, seed=3)
    for x in (rand, jnp.asarray(fixture)):
        ref = ref_model.apply(variables, x, train=False)
        out = s2d_model.apply(variables, x, train=False)
        assert jnp.array_equal(ref, out), "s2d layout diverged from reference"


def test_s2d_layout_accepts_packed_input(variables):
    """The staged-packed input path ([N,H/2,W/2,12], space_to_depth) is the
    same program family and stays bit-exact for both s2d variants."""
    x = jax.random.uniform(jax.random.key(9), (2, 128, 128, 3), jnp.float32)
    xp = space_to_depth(x)
    ref = ResUNet(config=_layout_cfg()).apply(variables, x, train=False)
    for stem in ("s2d", "s2d_full"):
        model = ResUNet(config=_layout_cfg(stem_layout=stem))
        unpacked = model.apply(variables, x, train=False)
        packed = model.apply(variables, xp, train=False)
        assert jnp.array_equal(unpacked, packed)
        if stem == "s2d":
            assert jnp.array_equal(ref, packed)


def test_s2d_train_mode_forward_bit_exact(variables):
    """Train-mode forward (BN batch moments) is bit-exact too — the property
    that made the mesh-round Adam step reproduce reference-layout weights
    bitwise in the cross-plane check."""
    x = jax.random.uniform(jax.random.key(11), (2, 128, 128, 3), jnp.float32)
    ref_logits, ref_state = ResUNet(config=_layout_cfg()).apply(
        variables, x, train=True, mutable=["batch_stats"]
    )
    s2d_logits, s2d_state = ResUNet(
        config=_layout_cfg(stem_layout="s2d", res_layout="packed")
    ).apply(variables, x, train=True, mutable=["batch_stats"])
    assert jnp.array_equal(ref_logits, s2d_logits)
    for a, b in zip(
        jax.tree_util.tree_leaves(ref_state), jax.tree_util.tree_leaves(s2d_state)
    ):
        assert jnp.array_equal(a, b)


def test_s2d_full_is_exact_arithmetic_but_reassociated(variables):
    """stem_layout='s2d_full' computes the same math (same multiplies plus
    exact zero taps) but XLA reassociates the longer contraction: agreement
    is ulp-level, NOT bitwise — the documented reason the fully folded
    stride-1 stem is an A/B probe while 's2d' is the bit-exact default
    transform (models/resunet.py module docstring)."""
    x = jax.random.uniform(jax.random.key(13), (2, 128, 128, 3), jnp.float32)
    ref = ResUNet(config=_layout_cfg()).apply(variables, x, train=False)
    out = ResUNet(config=_layout_cfg(stem_layout="s2d_full")).apply(
        variables, x, train=False
    )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=1e-4, rtol=1e-4)


def test_invalid_layout_flags_rejected():
    with pytest.raises(ValueError, match="stem_layout"):
        ModelConfig(stem_layout="nope")
    with pytest.raises(ValueError, match="res_layout"):
        ModelConfig(res_layout="nope")


def test_s2d_rejects_wrong_channel_count():
    cfg = _layout_cfg(stem_layout="s2d")
    v = init_variables(jax.random.key(0), cfg)
    model = ResUNet(config=cfg)
    with pytest.raises(ValueError, match="channels"):
        model.apply(v, jnp.zeros((1, 64, 64, 5)), train=False)


def test_head_commutes_with_final_upsample(variables):
    """The round-5 fusion invariant, pinned on the MODEL's actual op order:
    the head must execute BEFORE the final upsample (the deferral is real,
    not just documented) and, since PR 31, on the last block's packed output:
    four logits a low-resolution pixel at a QUARTER of the resolution. The
    model output must be exactly the unpack and the nearest-neighbor upsample
    of that, and the literal Keras/reference order (head AFTER the upsample)
    must reproduce the same logits bit-for-bit — replicated pixels produce
    replicated dot products."""
    config = ModelConfig(img_size=32)
    model = ResUNet(config=config)
    rng = jax.random.PRNGKey(3)
    images = jax.random.uniform(rng, (2, 32, 32, 3), jnp.float32)
    logits, state = model.apply(
        variables,
        images,
        train=False,
        capture_intermediates=True,
        mutable=["intermediates"],
    )
    head_out = state["intermediates"]["head"]["__call__"][0]

    # The deferral is in effect: the head ran on the pack, and the model's
    # last ops are exactly the unpack and one nearest-neighbor upsample.
    assert head_out.shape == (2, 8, 8, 4)
    assert logits.shape == (2, 32, 32, 1)
    assert jnp.array_equal(logits, upsample2x(depth_to_space(head_out)))

    # Keras/reference order on the same weights: a hand-built 1x1 head
    # applied AFTER upsampling commutes bit-exactly, so the deferred model
    # and the literal op order agree for any feature map.
    head_k = variables["params"]["head"]["kernel"].astype(jnp.float32)
    head_b = variables["params"]["head"]["bias"].astype(jnp.float32)
    f = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 16, head_k.shape[2]))

    def head(x):
        return jnp.tensordot(x, head_k[0, 0], axes=[[3], [0]]) + head_b

    assert jnp.array_equal(head(upsample2x(f)), upsample2x(head(f)))


# ---- the decoder's folded upsample (PR 27) ----------------------------------
# The model never builds `upsample2x(dec{i-1} output)`: `dec{i}_convT1` is one
# conv of the low-resolution tensor with a phase-folded kernel and
# `dec{i}_res` runs before the replication. Everything below holds that form
# to the upsample-then-convolve one over the SAME parameters. Agreement is
# `atol`, not bitwise: the folded taps are sums (k1+k2, k0+k1), so the float
# contraction reassociates.

_DEC_CHANNELS = [(64, 32), (128, 64), (256, 128)]  # (Cin, Cout) of dec3, dec2, dec1
_DEC_GRIDS = [(5, 7), (6, 8)]  # odd and even h,w; small, so every pixel is near a border


def _unfolded_convT(variables, z):
    """`ConvTranspose(3x3, SAME)(relu(upsample2x(z)))`: the form Keras states."""
    features = variables["params"]["kernel"].shape[-1]
    return nn.ConvTranspose(features, (3, 3), padding="SAME").apply(
        variables, nn.relu(upsample2x(z))
    )


def _folded_convT(variables, z):
    features = variables["params"]["kernel"].shape[-1]
    return depth_to_space(UpsampledConvT(features).apply(variables, nn.relu(z)))


def _convT_case(cin, cout, h, w):
    kz, kk, kb = jax.random.split(jax.random.key(cin + h), 3)
    z = jax.random.normal(kz, (2, h, w, cin), jnp.float32)
    variables = {"params": {
        "kernel": jax.random.normal(kk, (3, 3, cin, cout), jnp.float32) / (3.0 * cin**0.5),
        "bias": jax.random.normal(kb, (cout,), jnp.float32),
    }}
    return variables, z


@pytest.mark.parametrize("h,w", _DEC_GRIDS)
@pytest.mark.parametrize("cin,cout", _DEC_CHANNELS)
def test_folded_upsample_conv_matches_upsample_then_conv(cin, cout, h, w):
    """(a) One conv with the folded `[3,3,Cin,4*Cout]` kernel + depth_to_space
    IS the 3x3 conv of the upsampled image, zero-padded borders included.
    (256, 128) runs the four-`[2,2,Cin,Cout]`-conv form, the others the dense."""
    variables, z = _convT_case(cin, cout, h, w)
    assert fold_upsample_into_kernel(variables["params"]["kernel"]).shape == (3, 3, cin, 4 * cout)
    want = _unfolded_convT(variables, z)
    got = _folded_convT(variables, z)
    assert got.shape == want.shape == (2, 2 * h, 2 * w, cout)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1e-5)


@pytest.mark.parametrize("h,w", _DEC_GRIDS)
@pytest.mark.parametrize("cin,cout", _DEC_CHANNELS)
def test_folded_upsample_conv_gradients_match(cin, cout, h, w):
    """(b) The fold is linear, so autodiff lands the same gradient on the
    `[3,3,Cin,Cout]` parameter (and the bias, and `z`) as the unfolded form."""
    variables, z = _convT_case(cin, cout, h, w)
    cot = jax.random.normal(jax.random.key(99), (2, 2 * h, 2 * w, cout), jnp.float32)

    def grads(fn):
        return jax.grad(lambda v, x: jnp.sum(fn(v, x) * cot), argnums=(0, 1))(variables, z)

    for got, want in zip(
        jax.tree_util.tree_leaves(grads(_folded_convT)),
        jax.tree_util.tree_leaves(grads(_unfolded_convT)),
    ):
        want = np.asarray(want)
        np.testing.assert_allclose(
            np.asarray(got), want, rtol=1e-5, atol=1e-5 * float(np.abs(want).max())
        )


def _unfold_width(x):
    """`[N,H,W/2,2C] -> [N,H,W,C]`: the row-major reshape the width fold is."""
    n, h, w2, c2 = x.shape
    return x.reshape(n, h, 2 * w2, c2 // 2)


@pytest.mark.parametrize("train", [True, False])
@pytest.mark.parametrize("phases,unpack", [(4, depth_to_space), (2, _unfold_width)], ids=["dec4", "enc2"])
def test_phase_batchnorm_is_batchnorm_of_the_unpacked_tensor(phases, unpack, train):
    """`PhaseBatchNorm` on the packed tensor (`[N,h,w,4C]` of the decoder's
    phase conv, `[N,H,W/2,2C]` of the encoder's width fold) is `nn.BatchNorm`
    on the unpacked one: output, updated running statistics and gradients,
    from the same variables, in train mode (batch moments) and eval mode
    (running statistics). Moments reassociate (per lane, then over the phase
    groups), hence `atol`."""
    kw = dict(use_running_average=not train, momentum=0.99, epsilon=1e-3)
    c = 32
    keys = jax.random.split(jax.random.key(21), 5)
    x = 3.0 * jax.random.normal(keys[0], (2, 5, 6, phases * c), jnp.float32) + 1.5
    variables = {
        "params": {"scale": 1.0 + 0.1 * jax.random.normal(keys[1], (c,)),
                   "bias": jax.random.normal(keys[2], (c,))},
        "batch_stats": {"mean": jax.random.normal(keys[3], (c,)),
                        "var": 1.0 + jax.random.uniform(keys[4], (c,))},
    }
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: a.shape == b.shape and a.dtype == b.dtype,
        PhaseBatchNorm(phases=phases, **kw).init(jax.random.key(0), x),
        nn.BatchNorm(**kw).init(jax.random.key(0), unpack(x)),
    ))

    def packed(v, x):
        y, state = PhaseBatchNorm(phases=phases, **kw).apply(v, x, mutable=["batch_stats"])
        return unpack(y), state

    def unpacked(v, x):
        return nn.BatchNorm(**kw).apply(v, unpack(x), mutable=["batch_stats"])

    (got, got_state), (want, want_state) = packed(variables, x), unpacked(variables, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1e-5)
    if train:
        for a, b in zip(jax.tree_util.tree_leaves(got_state), jax.tree_util.tree_leaves(want_state)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)
    cot = jax.random.normal(jax.random.key(22), want.shape)
    for a, b in zip(*(
        jax.tree_util.tree_leaves(jax.grad(lambda v, x: jnp.sum(fn(v, x)[0] * cot), argnums=(0, 1))(variables, x))
        for fn in (packed, unpacked)
    )):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4 * float(jnp.abs(b).max()))


class _TwoConvSeparable(nn.Module):
    """`SeparableConv` as Keras states it and as the parent ran it: a
    depthwise `nn.Conv` then a pointwise one, under the model's names."""

    features: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        kw = dict(padding="SAME", kernel_init=nn.initializers.glorot_uniform(), dtype=self.dtype)
        c = x.shape[-1]
        x = nn.Conv(c, (3, 3), feature_group_count=c, use_bias=False, name="depthwise", **kw)(x)
        return nn.Conv(self.features, (1, 1), name="pointwise", **kw)(x)


class _UpsampleThenConvResUNet(nn.Module):
    """The forward as Keras states it (reference layouts): the decoder loop as
    it stood before PR 27 (every block but the last upsamples its output and
    the next block's `relu -> convT1` and `res` both read the upsampled
    tensor) and the encoder's separable convolutions as they stood before PR
    29 (two convolutions each, unfolded BatchNorm, `nn.max_pool`). Same module
    names and initializers, so it runs on the model's own variables."""

    config: ModelConfig = ModelConfig()

    @nn.compact
    def __call__(self, x, *, train=False):
        cfg = self.config
        kw = dict(padding="SAME", kernel_init=nn.initializers.glorot_uniform())

        def bn(name):
            return nn.BatchNorm(
                use_running_average=not train, momentum=0.99, epsilon=1e-3, name=name
            )

        with jax.named_scope("stem"):
            x = nn.Conv(cfg.stem_features, (3, 3), strides=(2, 2), name="stem_conv", **kw)(x)
            x = nn.relu(bn("stem_bn")(x))
        previous = x
        for i, features in enumerate(cfg.encoder_features):
            with jax.named_scope(f"enc{i}"):
                for j in (1, 2):
                    x = _TwoConvSeparable(features, name=f"enc{i}_sep{j}")(nn.relu(x))
                    x = bn(f"enc{i}_bn{j}")(x)
                x = nn.max_pool(x, window_shape=(3, 3), strides=(2, 2), padding="SAME")
                x = x + nn.Conv(features, (1, 1), strides=(2, 2), name=f"enc{i}_res", **kw)(previous)
            previous = x
        for i, features in enumerate(cfg.decoder_features):
            with jax.named_scope(f"dec{i}"):
                for j in (1, 2):
                    x = nn.ConvTranspose(features, (3, 3), name=f"dec{i}_convT{j}", **kw)(nn.relu(x))
                    x = bn(f"dec{i}_bn{j}")(x)
                x = x + nn.Conv(features, (1, 1), name=f"dec{i}_res", **kw)(previous)
                if i + 1 < len(cfg.decoder_features):
                    x = upsample2x(x)
                    previous = x
        with jax.named_scope("head"):
            return upsample2x(nn.Conv(cfg.num_classes, (1, 1), name="head", **kw)(x))


@pytest.mark.parametrize("img,batch", [(128, 2), (256, 1)])
def test_train_forward_matches_upsample_then_conv_decoder(variables, img, batch):
    """(c) Train-mode forward of the whole model, logits AND updated
    batch_stats (BatchNorm moments of every decoder block), against the
    parent's decoder loop on the same variables."""
    cfg = ModelConfig(img_size=img)
    x = jax.random.uniform(jax.random.key(img), (batch, img, img, 3), jnp.float32)
    want_logits, want_state = _UpsampleThenConvResUNet(config=cfg).apply(
        variables, x, train=True, mutable=["batch_stats"]
    )
    got_logits, got_state = ResUNet(config=cfg).apply(
        variables, x, train=True, mutable=["batch_stats"]
    )
    scale = float(jnp.abs(want_logits).max())
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(want_logits), rtol=0, atol=1e-5 * max(scale, 1.0)
    )
    want_leaves = jax.tree_util.tree_leaves_with_path(want_state)
    got_leaves = jax.tree_util.tree_leaves_with_path(got_state)
    assert [p for p, _ in got_leaves] == [p for p, _ in want_leaves]
    for (_, got), (_, want) in zip(got_leaves, want_leaves):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("twin", ["_UpsampleThenConvResUNet", "_UnpackedTailResUNet"])
def test_variables_identical_to_upsample_then_conv_model(variables, twin):
    """(d) Names, shapes and init values under a fixed key are byte-identical
    to the Keras-order model's and to PR 30's (the in-test twins further
    down): FedAvg, the wire format, h5 import/export and checkpoints cannot
    tell that the decoder runs another program. `UpsampledConvT`,
    `PackedConvT` and `PhaseConv1x1` declare what `nn.ConvTranspose` and
    `nn.Conv` declared, under their names."""
    cfg = ModelConfig()
    parent = globals()[twin](config=cfg).init(
        jax.random.key(0), jnp.zeros((1, *cfg.input_shape), jnp.float32), train=False
    )
    want = jax.tree_util.tree_leaves_with_path(parent)
    got = jax.tree_util.tree_leaves_with_path(variables)
    assert [jax.tree_util.keystr(p) for p, _ in got] == [jax.tree_util.keystr(p) for p, _ in want]
    for (path, a), (_, b) in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, jax.tree_util.keystr(path)
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), jax.tree_util.keystr(path)


_HLO_RESULT = re.compile(r"=\s*\(?[a-z]+[0-9]+[a-z0-9]*\[([0-9,]*)\]")


def _scoped_results(model, variables, cfg, batch, forbidden):
    """Instructions of the compiled train step (forward and backward, fused
    computations included) whose scope (`dec<i>` or `head`) is a key of
    `forbidden` and whose result has the shape it gives there."""

    def loss(params, x):
        logits, _ = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"],
        )
        return jnp.mean(logits**2)

    x = jnp.zeros((batch, *cfg.input_shape), jnp.float32)
    text = jax.jit(jax.grad(loss)).lower(variables["params"], x).compile().as_text()
    hits = []
    for line in text.splitlines():
        scope = re.search(r'op_name="[^"]*/(dec[0-9]+|head)/', line)
        result = _HLO_RESULT.search(line)
        if scope and result and forbidden.get(scope.group(1)) == result.group(1):
            hits.append(line.strip()[:160])
    return hits


def _upsampled_input_results(model, variables, cfg, batch):
    """Those in scope `dec{i}`, i >= 1, whose result has the shape of that
    block's upsampled input, `[N,2h,2w,Cin]`."""
    bottleneck = cfg.img_size // 2 // 2 ** len(cfg.encoder_features)
    return _scoped_results(model, variables, cfg, batch, {
        f"dec{i}": f"{batch},{bottleneck * 2**i},{bottleneck * 2**i},{cfg.decoder_features[i - 1]}"
        for i in range(1, len(cfg.decoder_features))
    })


def test_compiled_train_step_never_builds_the_upsampled_tensor(variables):
    """(e) The counter that says the mechanism engaged: the fold is
    unconditional, so what can fail silently is the compiler (or a later
    edit) rebuilding `[N,2h,2w,Cin]`. The parent's decoder loop has such
    results (its `relu` of the upsampled tensor at the least), which also
    shows that the search finds them."""
    cfg = ModelConfig(img_size=64)
    assert _upsampled_input_results(_UpsampleThenConvResUNet(config=cfg), variables, cfg, 2)
    assert _upsampled_input_results(ResUNet(config=cfg), variables, cfg, 2) == []


# ---- the encoder's composed separable convolutions (PR 29) -------------------
# Below 128 input channels `SeparableConv` runs ONE conv with the kernel
# `depthwise x pointwise`, and where two columns of its output fit the MXU it
# writes them width-folded; `bn`, `relu`, the second conv and the pool read
# the fold. Everything below holds those forms to the two-convolution one
# over the SAME parameters. `atol`, not bitwise: the composed conv sums
# `dw*pw*x` over (kh, kw, c) in one contraction where the separable one
# rounds the depthwise sum first.

# form -> (W, in_fold): what the layer is handed, which decides its form.
_SEP_FORMS = {
    "unpacked": (7, 1),       # odd width: [3,3,C,F] on [N,H,W,C]
    "fold_out": (8, 1),       # even width: [3,4,C,2F] at stride (1,2) writes the fold
    "fold_in_out": (8, 2),    # reads the fold: [3,3,2C,2F]
}


def _sep_case(cin, cout, w, dtype):
    keys = jax.random.split(jax.random.key(cin + w), 4)
    x = jax.random.normal(keys[0], (2, 6, w, cin), jnp.float32).astype(dtype)
    variables = {"params": {
        "depthwise": {"kernel": jax.random.normal(keys[1], (3, 3, 1, cin), jnp.float32) / 3.0},
        "pointwise": {"kernel": jax.random.normal(keys[2], (1, 1, cin, cout), jnp.float32) / cin**0.5,
                      "bias": jax.random.normal(keys[3], (cout,), jnp.float32)},
    }}
    return variables, x


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("form", list(_SEP_FORMS))
@pytest.mark.parametrize("cin,cout", [(32, 64), (64, 64)])
def test_composed_separable_conv_matches_depthwise_then_pointwise(cin, cout, form, dtype):
    """(a) Values and gradients (`x`, `depthwise/kernel`, `pointwise/kernel`,
    `pointwise/bias`) of each composed form against `pointwise(depthwise(x))`
    from the same parameters. float32 to 1e-5 of the largest value; in bf16
    both sides round (the separable one its depthwise output, the composed
    one its kernel), so they agree to a few bf16 steps (2**-8 each)."""
    w, in_fold = _SEP_FORMS[form]
    variables, x = _sep_case(cin, cout, w, dtype)
    composed = SeparableConv(cout, in_fold=in_fold, dtype=dtype)
    folded_out = form != "unpacked"

    def got_fn(v, x):
        xin = x.reshape(2, 6, w // 2, 2 * cin) if in_fold == 2 else x
        y = composed.apply(v, xin)
        assert y.shape == ((2, 6, w // 2, 2 * cout) if folded_out else (2, 6, w, cout)) and y.dtype == dtype
        return _unfold_width(y) if folded_out else y

    def want_fn(v, x):
        return _TwoConvSeparable(cout, dtype=dtype).apply(v, x)

    tol = 1e-5 if dtype == jnp.float32 else 2.0**-6
    want = np.asarray(want_fn(variables, x), np.float32)
    np.testing.assert_allclose(np.asarray(got_fn(variables, x), np.float32), want, rtol=0, atol=tol * np.abs(want).max())

    cot = jax.random.normal(jax.random.key(7), want.shape, jnp.float32)

    def grads(fn):
        return jax.grad(lambda v, x: jnp.sum(fn(v, x).astype(jnp.float32) * cot), argnums=(0, 1))(variables, x)

    got_leaves = jax.tree_util.tree_leaves_with_path(grads(got_fn))
    want_leaves = jax.tree_util.tree_leaves_with_path(grads(want_fn))
    assert [p for p, _ in got_leaves] == [p for p, _ in want_leaves]
    for (path, got), (_, want) in zip(got_leaves, want_leaves):
        want = np.asarray(want, np.float32)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), want, rtol=0, atol=tol * np.abs(want).max(),
            err_msg=jax.tree_util.keystr(path),
        )


def test_separable_conv_from_128_channels_on_stays_two_convolutions():
    """(b) The bypass: from 128 input channels on the layer IS the two
    convolutions, bit for bit (`enc1_sep2`, `enc2`)."""
    variables, x = _sep_case(128, 128, 8, jnp.float32)
    got = SeparableConv(128).apply(variables, x)
    want = _TwoConvSeparable(128).apply(variables, x)
    assert got.shape == want.shape == (2, 6, 8, 128)
    assert jnp.array_equal(got, want)


def test_composed_and_folded_kernels_hold_the_taps_where_the_docstring_says():
    """(c) `compose_separable_kernel` is the outer product a channel, and the
    two width folds place each tap at `kw = u - dj` / `kw = 2b + di - dj + 1`
    with exact zeros elsewhere (half of the `[3,3,2C,2F]` kernel)."""
    dw = jnp.arange(1.0, 19.0).reshape(3, 3, 1, 2)
    pw = jnp.arange(1.0, 7.0).reshape(1, 1, 2, 3)
    k = compose_separable_kernel(dw, pw)
    assert k.shape == (3, 3, 2, 3)
    np.testing.assert_array_equal(np.asarray(k), np.einsum("hwc,cf->hwcf", np.asarray(dw[:, :, 0]), np.asarray(pw[0, 0])))
    strided = np.asarray(fold_kernel_width(k, folded_input=False))
    assert strided.shape == (3, 4, 2, 6)
    for u in range(4):
        for dj in (0, 1):
            want = np.asarray(k[:, u - dj]) if 0 <= u - dj <= 2 else np.zeros((3, 2, 3))
            np.testing.assert_array_equal(strided[:, u, :, 3 * dj : 3 * dj + 3], want)
    dense = np.asarray(fold_kernel_width(k, folded_input=True))
    assert dense.shape == (3, 3, 4, 6)
    zeros = 0
    for b in (-1, 0, 1):
        for di in (0, 1):
            for dj in (0, 1):
                kw = 2 * b + di - dj + 1
                block = dense[:, b + 1, 2 * di : 2 * di + 2, 3 * dj : 3 * dj + 3]
                if 0 <= kw <= 2:
                    np.testing.assert_array_equal(block, np.asarray(k[:, kw]))
                else:
                    zeros += 1
                    assert not block.any()
    assert zeros == 6
    with pytest.raises(ValueError, match="3x3"):
        fold_kernel_width(jnp.zeros((2, 2, 4, 4)), folded_input=True)
    with pytest.raises(ValueError, match="expected"):
        compose_separable_kernel(jnp.zeros((3, 3, 1, 4)), jnp.zeros((1, 1, 5, 4)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("h,w", [(10, 12), (7, 6)])
def test_width_folded_pool_is_the_unfolded_pool_values_and_routing(h, w, dtype):
    """(d) `max_pool_width_folded` on `[N,H,W/2,2C]` is `nn.max_pool(3x3, 2,
    SAME)` of the unfolded tensor, bit for bit, and routes each output's
    cotangent to the same input pixel: the input is quantised so that nearly
    every window holds ties, and the cotangent is made of eighths so that
    sums of overlapping windows are exact in either dtype."""
    x = (jnp.round(2.0 * jax.random.normal(jax.random.key(h), (2, h, w, 8))) / 2.0).astype(dtype)
    cot = jnp.round(8.0 * jax.random.normal(jax.random.key(w), (2, -(-h // 2), w // 2, 8))) / 8.0

    def folded(x):
        return max_pool_width_folded(x.reshape(2, h, w // 2, 16))

    def unfolded(x):
        return nn.max_pool(x, (3, 3), (2, 2), "SAME")

    assert jnp.array_equal(folded(x), unfolded(x))
    got, want = (
        jax.grad(lambda x: jnp.sum(fn(x).astype(jnp.float32) * cot))(x) for fn in (folded, unfolded)
    )
    assert got.dtype == want.dtype == dtype
    assert jnp.array_equal(got, want)


def _grouped_convolutions(img):
    """`(groups, operand and result shapes)` of every grouped convolution
    (`feature_group_count` or `batch_group_count` above 1) in the lowered
    text of the bf16 training step, forward and backward."""
    cfg = ModelConfig(img_size=img, compute_dtype="bfloat16")
    model = ResUNet(config=cfg)
    shapes = jax.eval_shape(lambda: init_variables(jax.random.key(0), cfg))

    def loss(params, stats, x):
        logits, _ = model.apply({"params": params, "batch_stats": stats}, x, train=True, mutable=["batch_stats"])
        return jnp.mean(logits**2)

    x = jax.ShapeDtypeStruct((1, *cfg.input_shape), jnp.float32)
    text = jax.jit(jax.grad(loss)).lower(shapes["params"], shapes["batch_stats"], x).as_text()
    found = []
    for line in text.splitlines():
        if "stablehlo.convolution" not in line:
            continue
        groups = max(int(g) for g in re.findall(r"(?:feature|batch)_group_count = (\d+)", line))
        if groups > 1:
            found.append((groups, re.findall(r"tensor<([0-9x]+)x[a-z]+[0-9]+>", line)))
    return found


@pytest.mark.parametrize("img", [256, 512])
def test_lowered_train_step_has_no_grouped_convolution_below_128_channels(img):
    """(e) Engagement and bypass on the program's text, at the benchmark's two
    image sizes: the choice is static, so what can fail silently is a later
    edit. No grouped convolution (depthwise forward, its two backward forms)
    touches the full-resolution grid `img/2` or has fewer than 128 groups;
    the grouped convolutions of `enc1_sep2`, `enc2_sep1` (128 channels in)
    and `enc2_sep2` (256) are all still there, three each (forward, d-input,
    d-kernel)."""
    grouped = _grouped_convolutions(img)
    full = str(img // 2)
    on_full_grid = [g for g in grouped if any(shape.split("x")[1] == full for shape in g[1])]
    assert on_full_grid == []
    assert sorted(g for g, _ in grouped) == [128] * 6 + [256] * 3


# ---- the last decoder block packed to the end (PR 31) ------------------------
# `dec3` keeps `convT1`'s `[N,h,w,4C]` output through `convT2`, `bn2`, the
# residual add and the head; what is unpacked is the head's logits. Everything
# below holds that tail to the unpacked one over the SAME parameters. The
# packed `convT2` kernel holds each original tap once (no tap sums), so the
# products are the same and only the order of accumulation differs.


def _packed_convT_case(c, h, w, dtype):
    kx, kk, kb = jax.random.split(jax.random.key(c + h), 3)
    x = jax.random.normal(kx, (2, 2 * h, 2 * w, c), jnp.float32).astype(dtype)
    variables = {"params": {
        "kernel": jax.random.normal(kk, (3, 3, c, c), jnp.float32) / (3.0 * c**0.5),
        "bias": jax.random.normal(kb, (c,), jnp.float32),
    }}
    return variables, x


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("h,w", _DEC_GRIDS)
@pytest.mark.parametrize("c", [32, 64])
def test_packed_convT_matches_conv_transpose_of_the_unpacked_tensor(c, h, w, dtype):
    """(a) `PackedConvT` on the pack is `nn.ConvTranspose(3x3, SAME)` itself on
    the unpacked tensor, zero-padded borders included: values and gradients
    w.r.t. input, kernel and bias from the same parameters. float32 to 1e-5 of
    the largest value; in bf16 both round the same kernel once and differ by
    the order of accumulation, a bf16 step or two of the result."""
    variables, x = _packed_convT_case(c, h, w, dtype)

    def got_fn(v, x):
        y = PackedConvT(c, dtype=dtype).apply(v, space_to_depth(x))
        assert y.shape == (2, h, w, 4 * c) and y.dtype == dtype
        return depth_to_space(y)

    def want_fn(v, x):
        return nn.ConvTranspose(c, (3, 3), padding="SAME", dtype=dtype).apply(v, x)

    tol = 1e-5 if dtype == jnp.float32 else 2.0**-6
    want = np.asarray(want_fn(variables, x), np.float32)
    np.testing.assert_allclose(np.asarray(got_fn(variables, x), np.float32), want, rtol=0, atol=tol * np.abs(want).max())

    cot = jax.random.normal(jax.random.key(31), want.shape, jnp.float32)

    def grads(fn):
        return jax.grad(lambda v, x: jnp.sum(fn(v, x).astype(jnp.float32) * cot), argnums=(0, 1))(variables, x)

    got_leaves = jax.tree_util.tree_leaves_with_path(grads(got_fn))
    want_leaves = jax.tree_util.tree_leaves_with_path(grads(want_fn))
    assert [p for p, _ in got_leaves] == [p for p, _ in want_leaves]
    for (path, got), (_, want) in zip(got_leaves, want_leaves):
        key, want = jax.tree_util.keystr(path), np.asarray(want, np.float32)
        # The bias gradient is a sum of hundreds of cotangents that both forms
        # accumulate in the compute dtype, in another order.
        leaf_tol = 2.0**-4 if dtype == jnp.bfloat16 and key.endswith("'bias']") else tol
        np.testing.assert_allclose(
            np.asarray(got, np.float32), want, rtol=0, atol=leaf_tol * np.abs(want).max(), err_msg=key
        )


def test_packed_kernel_holds_each_tap_once_where_the_docstring_says():
    """(b) `fold_kernel_phases`: an axis at a time output phase `d` and tap `a`
    read offset `o` and input phase `e` with `2o + e = d + a - 1`; block
    `[o_h+1, o_w+1, e_h*2+e_w, d_h*2+d_w]` of the `[3,3,4C,4F]` kernel IS tap
    `(a_h, a_w)`, 36 blocks of 144, the others exact zeros."""
    c, f = 2, 3
    k = jnp.arange(1.0, 1.0 + 9 * c * f).reshape(3, 3, c, f)
    packed = np.asarray(fold_kernel_phases(k))
    assert packed.shape == (3, 3, 4 * c, 4 * f)
    rule = {(0, 0): (-1, 1), (0, 1): (0, 0), (0, 2): (0, 1), (1, 0): (0, 0), (1, 1): (0, 1), (1, 2): (1, 0)}
    assert all(2 * o + e == d + a - 1 for (d, a), (o, e) in rule.items())
    filled = np.zeros((3, 3, 4, 4), bool)
    for (dh, ah), (oh, eh) in rule.items():
        for (dw, aw), (ow, ew) in rule.items():
            e, d = eh * 2 + ew, dh * 2 + dw
            block = packed[oh + 1, ow + 1, e * c : (e + 1) * c, d * f : (d + 1) * f]
            np.testing.assert_array_equal(block, np.asarray(k[ah, aw]))
            assert not filled[oh + 1, ow + 1, e, d]
            filled[oh + 1, ow + 1, e, d] = True
    assert filled.sum() == 36
    empty = packed.reshape(3, 3, 4, c, 4, f).transpose(0, 1, 2, 4, 3, 5)[~filled]
    assert empty.shape == (108, c, f) and not empty.any()
    with pytest.raises(ValueError, match="3x3"):
        fold_kernel_phases(jnp.zeros((2, 2, 4, 4)))


@pytest.mark.parametrize(
    "c,f,phases,replicate", [(32, 1, 1, 1), (32, 1, 4, 1), (64, 32, 1, 2)],
    ids=["head_unpacked", "head_packed", "residual_tiled"],
)
def test_phase_conv1x1_is_the_1x1_conv_of_the_unpacked_tensor(c, f, phases, replicate):
    """(c), (d) `PhaseConv1x1` against `nn.Conv(1x1)` of the unpacked tensor
    from the same parameters, values and gradients (float32 products, as the
    head runs). The packed head reads `[N,h,w,4C]` through a block-diagonal
    kernel and writes the result packed; the last block's residual reads the
    low-resolution tensor through its kernel tiled 4 times and writes the
    pack of its own `upsample2x`; on an unpacked tensor with no replication
    it is `nn.Conv` bit for bit. A zero block adds exact zeros, so only the
    order of accumulation differs."""
    keys = jax.random.split(jax.random.key(41), 4)
    x = jax.random.normal(keys[0], (2, 10, 14, c), jnp.float32).astype(jnp.bfloat16)
    variables = {"params": {"kernel": jax.random.normal(keys[1], (1, 1, c, f), jnp.float32) / c**0.5,
                            "bias": jax.random.normal(keys[2], (f,), jnp.float32)}}

    def got_fn(v, x):
        y = PhaseConv1x1(f, phases, replicate, dtype=jnp.float32).apply(v, space_to_depth(x) if phases == 4 else x)
        assert y.dtype == jnp.float32 and y.shape[-1] == phases * replicate**2 * f
        return depth_to_space(y) if phases * replicate**2 == 4 else y

    def want_fn(v, x):
        y = nn.Conv(f, (1, 1), dtype=jnp.float32).apply(v, x.astype(jnp.float32))
        return upsample2x(y) if replicate == 2 else y

    got, want = got_fn(variables, x), want_fn(variables, x)
    assert got.shape == want.shape == (2, 10 * replicate, 14 * replicate, f)
    if phases == replicate == 1:
        assert jnp.array_equal(got, want)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1e-5 * float(jnp.abs(want).max()))
    cot = jax.random.normal(keys[3], want.shape, jnp.float32)
    for a, b in zip(*(
        jax.tree_util.tree_leaves(jax.grad(lambda v, x: jnp.sum(fn(v, x) * cot), argnums=(0, 1))(variables, x))
        for fn in (got_fn, want_fn)
    )):
        assert a.dtype == b.dtype
        b = np.asarray(b, np.float32)
        np.testing.assert_allclose(np.asarray(a, np.float32), b, rtol=0, atol=2.0**-7 * np.abs(b).max())


class _UnpackedTailResUNet(nn.Module):
    """The parent's forward (PR 30's tree, reference layouts): stem, encoder and
    `convT1` + `bn1` + `relu` through the model's own modules as they stood,
    then the decoder tail as it stood before PR 31: `depth_to_space` in every
    block after `dec0`, `convT2`, `bn2`, the residual add and the head on the
    unpacked `[N,2h,2w,C]`. Same module names and initializers, so it runs on
    the model's own variables."""

    config: ModelConfig = ModelConfig()

    @nn.compact
    def __call__(self, x, *, train=False):
        cfg = self.config
        kw = dict(padding="SAME", kernel_init=nn.initializers.glorot_uniform())

        def bn(name, phases=1):
            cls, extra = (nn.BatchNorm, {}) if phases == 1 else (PhaseBatchNorm, {"phases": phases})
            return cls(**extra, use_running_average=not train, momentum=0.99, epsilon=1e-3, name=name)

        with jax.named_scope("stem"):
            x = nn.Conv(cfg.stem_features, (3, 3), strides=(2, 2), name="stem_conv", **kw)(x)
            x = nn.relu(bn("stem_bn")(x))
        previous = x
        for i, features in enumerate(cfg.encoder_features):
            with jax.named_scope(f"enc{i}"):
                x = SeparableConv(features, name=f"enc{i}_sep1")(nn.relu(x))
                fold = x.shape[-1] // features
                x = nn.relu(bn(f"enc{i}_bn1", fold)(x))
                x = bn(f"enc{i}_bn2", fold)(SeparableConv(features, in_fold=fold, name=f"enc{i}_sep2")(x))
                x = max_pool_width_folded(x) if fold == 2 else nn.max_pool(x, (3, 3), (2, 2), "SAME")
                x = x + nn.Conv(features, (1, 1), strides=(2, 2), name=f"enc{i}_res", **kw)(previous)
            previous = x
        for i, features in enumerate(cfg.decoder_features):
            with jax.named_scope(f"dec{i}"):
                residual = nn.Conv(features, (1, 1), name=f"dec{i}_res", **kw)(x)
                x = nn.relu(x)
                if i == 0:
                    x = nn.relu(bn("dec0_bn1")(nn.ConvTranspose(features, (3, 3), name="dec0_convT1", **kw)(x)))
                else:
                    residual = upsample2x(residual)
                    x = UpsampledConvT(features, name=f"dec{i}_convT1")(x)
                    x = depth_to_space(nn.relu(bn(f"dec{i}_bn1", 4)(x)))
                x = nn.ConvTranspose(features, (3, 3), name=f"dec{i}_convT2", **kw)(x)
                x = bn(f"dec{i}_bn2")(x) + residual
        with jax.named_scope("head"):
            return upsample2x(nn.Conv(cfg.num_classes, (1, 1), name="head", **kw)(x))


@pytest.mark.parametrize("img,batch", [(128, 2), (256, 1)])
def test_train_step_matches_the_unpacked_decoder_tail(variables, img, batch):
    """(e) The whole model in train mode against the parent's decoder tail on
    the same variables: logits, updated batch_stats and the gradient of every
    parameter. A conv bias that a BatchNorm shadows has a true gradient of 0;
    there both sides must read noise, small against the tree's gradients."""
    cfg = ModelConfig(img_size=img)
    x = jax.random.uniform(jax.random.key(img + 1), (batch, img, img, 3), jnp.float32)
    cot = jax.random.normal(jax.random.key(img + 2), (batch, img, img, 1), jnp.float32)

    def run(model):
        def loss(params):
            logits, state = model.apply(
                {"params": params, "batch_stats": variables["batch_stats"]},
                x, train=True, mutable=["batch_stats"],
            )
            return jnp.mean(logits * cot), (logits, state)

        (_, (logits, state)), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(variables["params"])
        return logits, state, grads

    got_logits, got_state, got_grads = run(ResUNet(config=cfg))
    want_logits, want_state, want_grads = run(_UnpackedTailResUNet(config=cfg))
    scale = float(jnp.abs(want_logits).max())
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(want_logits), rtol=0, atol=1e-5 * max(scale, 1.0)
    )
    for (path, got), (_, want) in zip(
        jax.tree_util.tree_leaves_with_path(got_state), jax.tree_util.tree_leaves_with_path(want_state)
    ):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6, err_msg=jax.tree_util.keystr(path)
        )
    want_leaves = jax.tree_util.tree_leaves_with_path(want_grads)
    largest = max(float(jnp.abs(w).max()) for _, w in want_leaves)
    for (path, got), (_, want) in zip(jax.tree_util.tree_leaves_with_path(got_grads), want_leaves):
        key = jax.tree_util.keystr(path)
        want = np.asarray(want)
        atol = 1e-4 * (largest if bn_shadowed_bias(key) else float(np.abs(want).max()))
        np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=atol, err_msg=key)


def test_compiled_train_step_never_builds_the_unpacked_last_block(variables):
    """(g) The counter that says the mechanism engaged: the pack is kept by a
    rule on static shapes, so what can fail silently is the compiler (or a
    later edit) rebuilding the `[N,2h,2w,C]` feature tensor under the last
    block or the head. The parent's tail has such results, which also shows
    that the search finds them."""
    cfg = ModelConfig(img_size=64)
    last = len(cfg.decoder_features) - 1
    unpacked = f"2,{cfg.img_size // 2},{cfg.img_size // 2},{cfg.decoder_features[-1]}"
    forbidden = {f"dec{last}": unpacked, "head": unpacked}
    assert _scoped_results(_UnpackedTailResUNet(config=cfg), variables, cfg, 2, forbidden)
    assert _scoped_results(ResUNet(config=cfg), variables, cfg, 2, forbidden) == []


def test_a_wider_last_block_unpacks_before_its_second_conv(variables):
    """(h) The bypass: the pack is kept only where it fills the lanes exactly
    or less, `4*Cout <= 128`. A decoder whose last block has 64 channels runs
    the parent's tail, a feature `depth_to_space` and `nn.ConvTranspose`, and
    its head reads the unpacked tensor (`phases` 1: one logit a pixel)."""
    cfg = ModelConfig(img_size=32, decoder_features=(256, 128, 64))
    v = init_variables(jax.random.key(0), cfg)
    x = jax.random.uniform(jax.random.key(8), (2, 32, 32, 3), jnp.float32)
    logits, state = ResUNet(config=cfg).apply(
        v, x, train=False, capture_intermediates=True, mutable=["intermediates"]
    )
    assert state["intermediates"]["head"]["__call__"][0].shape == (2, 8, 8, 1)
    assert state["intermediates"]["dec2_convT2"]["__call__"][0].shape == (2, 8, 8, 64)
    want = _UnpackedTailResUNet(config=cfg).apply(v, x, train=False)
    assert jnp.array_equal(logits, want)
