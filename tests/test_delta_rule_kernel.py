"""The gated delta rule's Pallas kernels (``kernels/delta_rule.py``) in the
interpreter, at the kernels' 128-lane head width: values and all five
gradients against the token-by-token reference (``REF.delta_rule``) and
against the XLA form they replace on the chip (``chunked_delta_rule``); and
what the hybrid model's gradient holds under ``gdn_rule`` with each."""

import collections
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from fedcrack_tpu.configs import GDN_CHUNK
from fedcrack_tpu.kernels import delta_rule as K
from fedcrack_tpu.models import gdn_moe as M

from test_gdn_moe import REF, _close, _rule_inputs, small_config

WIDTH = 128


def _inputs(seq_len, decay, per_key, seed=None):
    """Two value heads of 128; ``q`` and ``k`` a key head each, or one
    shared by both."""
    q, k, v, a, b = _rule_inputs(seq_len if seed is None else seed, seq_len, decay, heads=2, d_k=WIDTH, d_v=WIDTH)
    return q[:, : 2 // per_key], k[:, : 2 // per_key], v, a, b


def _kernel(q, k, v, log_decay, beta, compute_dtype=jnp.float32):
    return K.delta_rule(q[None], k[None], v[None], log_decay[None], beta[None], compute_dtype=compute_dtype, interpret=True)[0]


def _xla(q, k, v, log_decay, beta, compute_dtype=jnp.float32):
    q, k = (jnp.repeat(t, v.shape[1] // t.shape[1], axis=1) for t in (q, k))
    return M.chunked_delta_rule(q[None], k[None], v[None], log_decay[None], beta[None], compute_dtype=compute_dtype)[0]


class TestTheKernels:
    @pytest.mark.parametrize("chunks", [2, 4])
    @pytest.mark.parametrize("decay", [0.01, 3.0], ids=["weak_decay", "strong_decay"])
    @pytest.mark.parametrize("per_key", [1, 2], ids=["a_key_head_each", "a_key_head_shared"])
    def test_values_and_gradients_against_the_recurrence_and_the_xla_form(self, chunks, decay, per_key):
        q, k, v, a, b = _inputs(chunks * GDN_CHUNK, decay, per_key)
        target = jnp.asarray(np.random.default_rng(1).normal(size=v.shape), jnp.float32)

        def through(rule):
            def loss(q, k, v, a, b):
                o = rule(q, k, v, -jnp.exp(a), jax.nn.sigmoid(b))
                return jnp.sum(o * target), o
            return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)

        def token_by_token(q, k, v, log_decay, beta):
            q, k = (jnp.repeat(t, 2 // t.shape[1], axis=1) for t in (q, k))
            return REF.delta_rule(q, k, v, jnp.exp(log_decay), beta)

        with jax.default_matmul_precision("highest"):
            (_, ours), grads = through(_kernel)(q, k, v, a, b)
            (_, xla), xla_grads = through(_xla)(q, k, v, a, b)
            (_, theirs), ref_grads = through(token_by_token)(q, k, v, a, b)
        assert ours.shape == (chunks * GDN_CHUNK, 2, WIDTH) and float(jnp.max(jnp.abs(theirs))) > 0.01
        _close(ours, theirs, 2e-5)
        _close(ours, xla, 1e-6)
        for name, g, x, r in zip("qkvab", grads, xla_grads, ref_grads):
            assert g.shape == r.shape and float(jnp.max(jnp.abs(r))) > 0, name
            _close(g, r, 1e-4)
            _close(g, x, 1e-5)

    def test_a_long_memory_carries_the_first_chunk_into_the_last(self):
        """With ``alpha`` near 1 the last chunk's output depends on the first
        chunk's values; with ``alpha`` near 0 it does not."""
        q, k, v, a, b = _inputs(4 * GDN_CHUNK, 1.0, 2, seed=3)

        def last(v, log_decay):
            o = _kernel(q, k, v, jnp.full(a.shape, log_decay), jax.nn.sigmoid(b))
            return jnp.sum(o[-GDN_CHUNK:] ** 2)

        reach = lambda log_decay: float(jnp.max(jnp.abs(jax.grad(last)(v, log_decay)[:GDN_CHUNK])))
        assert reach(-1e-3) > 1e-4 and reach(-20.0) == 0.0

    def test_the_forward_substitution_is_the_inverse(self):
        rng = np.random.default_rng(0)
        a = np.tril(rng.normal(size=(256, 64, 64)) * 0.3, -1).astype(np.float32)
        t = K.pl.pallas_call(
            K._inverse_kernel, out_shape=jax.ShapeDtypeStruct((64, 64, 256), jnp.float32), interpret=True
        )(jnp.asarray(a.transpose(1, 2, 0)))
        expected = np.linalg.inv(np.eye(64) + a.astype(np.float64))
        np.testing.assert_allclose(np.asarray(t).transpose(2, 0, 1), expected, rtol=2e-5, atol=2e-6)

    def test_bf16_products_stay_as_near_as_the_xla_forms(self):
        """In the compute dtype of the cell the kernels and the XLA form read
        alike against the float32 rule."""
        q, k, v, a, b = _inputs(2 * GDN_CHUNK, 0.5, 2, seed=7)
        log_decay, beta = -jnp.exp(a), jax.nn.sigmoid(b)
        with jax.default_matmul_precision("highest"):
            exact = _xla(q, k, v, log_decay, beta)
        bf = [t.astype(jnp.bfloat16) for t in (q, k, v)]
        ours = _kernel(*bf, log_decay, beta, jnp.bfloat16)
        xla = _xla(*bf, log_decay, beta, jnp.bfloat16)
        err = lambda o: float(jnp.linalg.norm(o - exact) / jnp.linalg.norm(exact))
        assert err(ours) < 0.02 and err(ours) < 1.5 * err(xla)

    def test_the_kernels_take_128_lane_heads_and_whole_chunks_only(self):
        q = jax.ShapeDtypeStruct((1, 128, 1, WIDTH), jnp.bfloat16)
        assert K.fits(q, jax.ShapeDtypeStruct((1, 128, 2, WIDTH), jnp.bfloat16))
        assert not K.fits(jax.ShapeDtypeStruct((1, 128, 1, 16), jnp.bfloat16), jax.ShapeDtypeStruct((1, 128, 2, 16), jnp.bfloat16))
        assert not K.fits(jax.ShapeDtypeStruct((1, 96, 1, WIDTH), jnp.bfloat16), jax.ShapeDtypeStruct((1, 96, 2, WIDTH), jnp.bfloat16))


def _under_scope(jaxpr, scope, out, stack=""):
    """(primitive, kernel name) of every equation whose name stack holds
    ``scope``, through nested jaxprs but not into a kernel's body."""
    for eqn in jaxpr.eqns:
        here = "/".join(filter(None, (stack, str(eqn.source_info.name_stack))))
        if scope in here.split("/"):
            out[(eqn.primitive.name, eqn.params.get("name") if eqn.primitive.name == "pallas_call" else None)] += 1
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _under_scope(sub, scope, out, here)
    return out


@pytest.mark.parametrize("kernels", ["pallas", "xla"])
def test_the_models_gradient_holds_the_rules_kernels_or_its_xla_form(kernels, monkeypatch):
    """Traced only: ``kernels="pallas"`` builds the program the chip runs
    without running it. Three Gated DeltaNet layers: a forward pass, a
    rematerialised one and a backward each."""
    config = small_config(
        linear_num_key_heads=1, linear_num_value_heads=2, linear_key_head_dim=WIDTH, linear_value_head_dim=WIDTH,
        compute_dtype="bfloat16",
    )
    model = M.GdnMoe(config, kernels=kernels)
    called = []
    xla_rule = M.chunked_delta_rule
    monkeypatch.setattr(M, "chunked_delta_rule", lambda *a, **kw: called.append(1) or xla_rule(*a, **kw))
    params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    ids = jax.ShapeDtypeStruct((2, config.seq_len), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, ids: jnp.sum(model.apply(p, ids)["nll_next"])))(params, ids).jaxpr
    rule = _under_scope(jaxpr, "gdn_rule", collections.Counter())
    kernel_calls = {name: n for (prim, name), n in rule.items() if prim == "pallas_call"}
    loops = sum(n for (prim, _), n in rule.items() if prim in ("scan", "while"))
    if kernels == "pallas":
        assert kernel_calls == {"delta_rule_inverse": 6, "delta_rule_fwd": 6, "delta_rule_dstate": 3, "delta_rule_grads": 3}
        assert loops == 0 and not called
    else:
        assert kernel_calls == {} and loops == 9 and len(called) == 3


def test_the_kernels_compile_for_the_chip_at_the_cells_widths():
    """One sequence of 8,192 tokens, 16 key and 32 value heads of 128, bf16,
    forward and backward: what the chip's compiler refuses (tiling, fast
    memory, a layout Mosaic cannot take) shows here at no chip time."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one_chip = SingleDeviceSharding(topo.devices[0])
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    q = spec((1, 8192, 16, WIDTH), jnp.bfloat16)
    v = spec((1, 8192, 32, WIDTH), jnp.bfloat16)
    a = spec((1, 8192, 32), jnp.float32)

    def loss(q, k, v, log_decay, beta):
        return jnp.sum(K.delta_rule(q, k, v, log_decay, beta, compute_dtype=jnp.bfloat16))

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(q, q, v, a, a).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    calls = [line.split(" = ")[0] for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    kernels = collections.Counter(re.search(r"delta_rule_[a-z]+", name).group(0) for name in calls)
    assert kernels == {"delta_rule_inverse": 1, "delta_rule_fwd": 1, "delta_rule_dstate": 1, "delta_rule_grads": 1}, kernels
