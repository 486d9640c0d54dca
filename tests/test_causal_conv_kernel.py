"""The Gated DeltaNet's convolution kernels (``kernels/causal_conv.py``) in the
interpreter: values and both gradients against the XLA form they replace on
the chip (``gdn_moe.causal_conv`` and SiLU under autodiff), across blocks of
tokens and of channels; where the kernels engage in the hybrid model, and
what its gradient holds with each form."""

import collections
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from fedcrack_tpu.kernels import causal_conv as K
from fedcrack_tpu.models import gdn_moe as M

from test_delta_rule_kernel import _under_scope
from test_gdn_moe import _close, small_config

WIDTH = 128


def _xla(x, taps):
    """Today's form, as ``_gdn_inputs`` calls it off the chip."""
    channels = taps.shape[0]
    return jax.nn.silu(M.causal_conv(x[..., :channels].astype(jnp.float32), taps)).astype(x.dtype)


def _kernel(x, taps):
    return K.causal_conv_silu(x, taps, interpret=True)


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 32 tokens in strips of 16, and of 128 channels, so that a
    short sequence crosses several of each."""
    monkeypatch.setattr(K, "ROWS", 32)
    monkeypatch.setattr(K, "STRIP", 16)
    monkeypatch.setattr(K, "LANES", 128)


def _inputs(dtype, taps_n, seq_len=96, channels=256, width=384, seed=0):
    """Two sequences of ``width`` lanes, of which the first ``channels`` are
    convolved (the rest stand for the projection's ``z``)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(2, seq_len, width)), dtype)
    taps = jnp.asarray(0.5 * rng.normal(size=(channels, taps_n)), jnp.float32)
    return x, taps


class TestTheKernels:
    @pytest.mark.parametrize("taps_n", [4, 2])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
    def test_values_and_gradients_against_the_xla_form(self, small_blocks, dtype, taps_n):
        """Three blocks of tokens, two strips each, and two blocks of
        channels: the halo crosses every strip and block edge forward (``x``)
        and backward (``da``)."""
        x, taps = _inputs(dtype, taps_n)
        target = jnp.asarray(np.random.default_rng(1).normal(size=(2, 96, 256)), jnp.float32)

        def through(form):
            def loss(x, taps):
                y = form(x, taps)
                return jnp.sum(y.astype(jnp.float32) * target), y
            return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)

        (_, ours), (dx, dtaps) = through(_kernel)(x, taps)
        (_, xla), (xla_dx, xla_dtaps) = through(_xla)(x, taps)
        assert ours.shape == (2, 96, 256) and ours.dtype == dtype and dx.dtype == dtype and dtaps.dtype == jnp.float32
        tol = 1e-6 if dtype == jnp.float32 else 8e-3  # a bf16 rounding of the same float32 sum
        _close(ours, xla, tol)
        _close(dx, xla_dx, tol)
        _close(dtaps, xla_dtaps, 1e-5)
        # The lanes past the convolved channels take no cotangent from it.
        assert float(jnp.max(jnp.abs(dx[..., 256:]))) == 0.0 and float(jnp.max(jnp.abs(dx[..., :256]))) > 0

    def test_the_first_token_sees_zero_history_and_no_token_sees_a_later_one(self, small_blocks):
        x, taps = _inputs(jnp.float32, 4)
        y = _kernel(x, taps)
        np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(jax.nn.silu(x[:, 0, :256] * taps[:, 3])), rtol=1e-6)
        # A change at token 30 (the first block's last strip) moves nothing
        # before it and tokens 30..33 after it: across the edge into the
        # second block only as far as the taps reach.
        later = x.at[:, 30].add(1.0)
        moved = np.max(np.abs(np.asarray(_kernel(later, taps) - y)), axis=(0, 2))
        assert np.all(moved[:30] == 0) and np.all(moved[30:34] > 0) and np.all(moved[34:] == 0)

    def test_the_kernels_take_whole_lane_tiles_of_channels_and_whole_tiles_of_tokens(self):
        x = jax.ShapeDtypeStruct((1, 128, 384), jnp.bfloat16)
        assert K.fits(x, jax.ShapeDtypeStruct((256, 4), jnp.float32))
        assert not K.fits(x, jax.ShapeDtypeStruct((96, 4), jnp.float32))  # not whole lane tiles
        assert not K.fits(jax.ShapeDtypeStruct((1, 120, 384), jnp.bfloat16), jax.ShapeDtypeStruct((256, 4), jnp.float32))
        assert not K.fits(x, jax.ShapeDtypeStruct((256, K.HALO + 2), jnp.float32))  # more taps than the halo holds


def _config(head_dim, **over):
    return small_config(
        linear_num_key_heads=1, linear_num_value_heads=2, linear_key_head_dim=head_dim,
        linear_value_head_dim=head_dim, **over,
    )


@pytest.mark.parametrize(
    "kernels,head_dim,expected",
    [
        ("pallas", WIDTH, {"causal_conv_fwd": 9, "causal_conv_bwd": 3}),
        ("interpret", 8, {}),  # 2 x 8 + 16 = 32 channels: not a lane tile
        ("xla", WIDTH, {}),
    ],
    ids=["kernels", "channels_not_lane_tiles", "xla"],
)
def test_the_models_gradient_holds_the_convolutions_kernels_or_its_xla_form(kernels, head_dim, expected):
    """Traced only. Three Gated DeltaNet layers, each running what comes before
    the rule forward three times (the step, the block's rematerialisation and
    the inner one) and backward once."""
    config = _config(head_dim, compute_dtype="bfloat16")
    model = M.GdnMoe(config, kernels=kernels)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    ids = jax.ShapeDtypeStruct((2, config.seq_len), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, ids: jnp.sum(model.apply(p, ids)["nll_next"])))(params, ids).jaxpr
    conv = _under_scope(jaxpr, "gdn_conv", collections.Counter())
    assert {name: n for (prim, name), n in conv.items() if prim == "pallas_call"} == expected
    assert sum(n for (prim, _), n in conv.items() if prim == "logistic") == (0 if expected else 9)


def test_the_models_gradient_is_the_same_with_the_kernels_and_the_xla_forms():
    """One Gated DeltaNet layer at 128-lane heads, float32: every gradient leaf
    with the convolution's and the rule's kernels (in the interpreter) against
    their XLA forms, within the rule kernels' own tolerance against theirs."""
    config = _config(WIDTH, num_hidden_layers=1)
    rng = np.random.default_rng(3)
    ids = jnp.asarray(rng.integers(0, config.vocab_held, (2, config.seq_len)), jnp.int32)
    params = M.GdnMoe(config).init(jax.random.key(4))
    params["layer0"]["conv"] = jnp.asarray(0.5 * rng.normal(size=params["layer0"]["conv"].shape), jnp.float32)

    def grads(kernels):
        model = M.GdnMoe(config, kernels=kernels)
        return jax.grad(lambda p: jnp.mean(model.apply(p, ids)["nll_next"]))(params)

    with jax.default_matmul_precision("highest"):
        ours, xla = grads("interpret"), grads("xla")
    flat, _ = jax.tree_util.tree_flatten_with_path(ours)
    for (path, g), x in zip(flat, jax.tree_util.tree_leaves(xla)):
        assert float(jnp.max(jnp.abs(x))) > 0, path
        _close(g, x, 1e-5)


def test_the_kernels_compile_for_the_chip_at_the_cells_widths():
    """One sequence of 8,192 tokens, the bf16 ``[q | k | v | z]`` projection
    12,288 lanes wide of which 8,192 are convolved, 4 taps, forward and
    backward: what the chip's compiler refuses shows here at no chip time."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one_chip = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((1, 8192, 12288), jnp.bfloat16, sharding=one_chip)
    taps = jax.ShapeDtypeStruct((8192, 4), jnp.float32, sharding=one_chip)

    def loss(x, taps):
        return jnp.sum(K.causal_conv_silu(x, taps).astype(jnp.float32))

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(x, taps).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    calls = [line.split(" = ")[0] for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    kernels = collections.Counter(re.search(r"causal_conv_[a-z]+", name).group(0) for name in calls)
    assert kernels == {"causal_conv_fwd": 1, "causal_conv_bwd": 1}, kernels
