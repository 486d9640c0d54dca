"""Low-precision kernel plane (round 20): fused dequant kernels, the
engine's kernel_plane selection, fp8 degradation, and the training-side
fake-quant twin.

The load-bearing claims, each pinned here:

- the fused dequant-matmul's interpret-mode twin matches the r17 reference
  dequantize path within the per-channel scale PER ENTRY, across dtypes
  and ragged shapes, deterministically (same inputs -> byte-identical
  outputs across runs);
- the fused predict program (kernel_plane="fused_int8") agrees with the
  r17 reference plane's program at mask level and clears the production
  install gate; the fp8 program agrees with ITS own dequantize oracle
  (e4m3 rounding is the model delta, not the kernel's);
- requesting fp8 on a backend without fp8 dtypes degrades to the r17
  reference plane BIT-exactly (same closure, test-pinned), visible via
  ``effective_kernel_plane``;
- a garbage quantized build fails the gate on EVERY fused plane and the
  fleet keeps serving the reference program bit-exactly (the r17 refusal
  contract re-pinned through the new selection path);
- ServeConfig.kernel_plane validates at construction (unknown plane,
  fused plane without int8 quant);
- the serve_kernel_plane_info gauge exports exactly one current series;
- the training-side straight-through fake-quant transform bounds its
  weight error by the per-channel scale, passes gradients, and the
  lowp="null" build is byte-identical to a knob-free build (trajectory
  tolerance vs the null oracle is the slow-marked mesh test, the r12
  precedent).
"""

import dataclasses

import numpy as np
import pytest

pytestmark = pytest.mark.kernels

TINY_KW = dict(
    img_size=32, stem_features=4, encoder_features=(8,), decoder_features=(8, 4)
)
BUCKET = 32


def _serve_config(**over):
    from fedcrack_tpu.configs import ServeConfig

    kw = dict(
        bucket_sizes=(BUCKET,), max_batch=4, max_delay_ms=10.0, tile_overlap=4
    )
    kw.update(over)
    return ServeConfig(**kw)


@pytest.fixture(scope="module")
def kstack():
    """Shared tiny model + per-plane engines (bucket compiles dominate)."""
    import jax

    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.models.resunet import init_variables
    from fedcrack_tpu.serve.engine import InferenceEngine

    model_config = ModelConfig(**TINY_KW)
    variables = init_variables(jax.random.key(0), model_config)
    engines = {
        plane: InferenceEngine(
            model_config, _serve_config(quant="int8", kernel_plane=plane)
        )
        for plane in ("reference", "fused_int8")
    }
    return model_config, variables, engines


# ---- fused dequant kernel twins ----

# Ragged channel counts and sub-tile rows on purpose: the kernel pads to
# (8,128)/(32,128) tiles internally and must slice back exactly.
SWEEP_SHAPES = [(4, 7, 5), (8, 128, 128), (33, 130, 129), (1, 256, 3), (16, 9, 17)]


@pytest.mark.parametrize("shape", SWEEP_SHAPES, ids=[str(s) for s in SWEEP_SHAPES])
def test_dequant_matmul_interpret_twin_error_bound(shape):
    """Interpret-mode fused matmul vs the r17 reference dequantize order:
    per-entry error <= the per-channel scale (the documented bound — the
    two orders differ only by float reassociation), deterministic."""
    from fedcrack_tpu.kernels.dequant import dequant_matmul
    from fedcrack_tpu.serve.quant import QKEY, SKEY, quantize_leaf

    m, k, n = shape
    rng = np.random.default_rng(hash(shape) % (2**31))
    x = rng.normal(0, 1.0, (m, k)).astype(np.float32)
    w = rng.normal(0, 0.1, (k, n)).astype(np.float32)
    leaf = quantize_leaf(w)
    q, scale = leaf[QKEY], leaf[SKEY]

    ref = np.asarray(dequant_matmul(x, q, scale, impl="reference"))
    # The reference impl IS the r17 order — pin that before trusting it as
    # the oracle.
    np.testing.assert_allclose(
        ref, x @ (q.astype(np.float32) * scale), rtol=1e-5, atol=1e-5
    )
    out = np.asarray(dequant_matmul(x, q, scale, impl="interpret"))
    assert np.all(np.abs(out - ref) <= scale[None, :] + 1e-6), (
        f"per-entry error exceeds the per-channel scale at {shape}"
    )
    out2 = np.asarray(dequant_matmul(x, q, scale, impl="interpret"))
    np.testing.assert_array_equal(out, out2)  # deterministic run-to-run


def test_dequant_matmul_fp8_codes_through_same_kernel():
    from fedcrack_tpu import jaxcompat
    from fedcrack_tpu.kernels.dequant import dequant_matmul
    from fedcrack_tpu.serve.quant import QKEY_FP8, SKEY, quantize_leaf_fp8

    if not jaxcompat.fp8_supported():
        pytest.skip("backend has no fp8 dtypes")
    rng = np.random.default_rng(7)
    x = rng.normal(0, 1.0, (9, 37)).astype(np.float32)
    w = rng.normal(0, 0.1, (37, 11)).astype(np.float32)
    leaf = quantize_leaf_fp8(w)
    q, scale = leaf[QKEY_FP8], leaf[SKEY]
    ref = np.asarray(dequant_matmul(x, q, scale, impl="reference"))
    out = np.asarray(dequant_matmul(x, q, scale, impl="interpret"))
    assert np.all(np.abs(out - ref) <= scale[None, :] + 1e-6)


def test_dequant_codes_twin_matches_reference():
    from fedcrack_tpu.kernels.dequant import dequant_codes
    from fedcrack_tpu.serve.quant import QKEY, SKEY, quantize_leaf

    rng = np.random.default_rng(11)
    w = rng.normal(0, 0.1, (130, 17)).astype(np.float32)
    leaf = quantize_leaf(w)
    ref = np.asarray(dequant_codes(leaf[QKEY], leaf[SKEY], impl="reference"))
    out = np.asarray(dequant_codes(leaf[QKEY], leaf[SKEY], impl="interpret"))
    np.testing.assert_allclose(out, ref, rtol=0, atol=1e-7)


def test_dequant_matmul_validates_shapes():
    from fedcrack_tpu.kernels.dequant import dequant_matmul

    x = np.zeros((4, 8), np.float32)
    q = np.zeros((8, 3), np.int8)
    with pytest.raises(ValueError):
        dequant_matmul(x, q, np.ones(4, np.float32))  # scale != n
    with pytest.raises(ValueError):
        dequant_matmul(x, np.zeros((7, 3), np.int8), np.ones(3, np.float32))
    with pytest.raises(TypeError):
        dequant_matmul(x, q.astype(np.int32), np.ones(3, np.float32))


# ---- engine plane selection ----


def test_fused_int8_plane_matches_reference_plane_and_gates(kstack):
    """The fused predict program vs the r17 reference plane's program over
    the SAME int8 tree: near-identical probabilities and a green
    production-floor install gate (the gate runs the FUSED program — the
    selection point is inside the engine's quantized closure)."""
    from fedcrack_tpu.serve import quant as quant_mod

    _, variables, engines = kstack
    qv = quant_mod.quantize_variables(variables)
    batch = quant_mod.probe_images(BUCKET, 4, 0)
    outs = {}
    for plane, engine in engines.items():
        assert engine.effective_kernel_plane == plane
        payload = engine.prepare_quantized(qv)
        gate = quant_mod.quant_gate(engine, engine.prepare(variables), payload)
        assert gate.passed, f"{plane} gate refused: {gate.to_json()}"
        outs[plane] = engine.predict_bucket(payload, batch)
    diff = np.max(
        np.abs(
            np.asarray(outs["fused_int8"], np.float64)
            - np.asarray(outs["reference"], np.float64)
        )
    )
    assert diff < 1e-3, f"fused_int8 vs reference plane prob diff {diff}"
    assert quant_mod.mask_iou(outs["fused_int8"], outs["reference"]) >= 0.99


def test_fp8_plane_matches_its_dequantize_oracle(kstack):
    """fp8 numerics are the MODEL's delta (e4m3 rounding); the KERNEL must
    match the plain-XLA forward over the dequantized fp8 weights tightly."""
    from fedcrack_tpu import jaxcompat
    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.serve import quant as quant_mod
    from fedcrack_tpu.serve.engine import InferenceEngine

    if not jaxcompat.fp8_supported():
        pytest.skip("backend has no fp8 dtypes")
    model_config, variables, _ = kstack
    engine = InferenceEngine(
        model_config, _serve_config(quant="int8", kernel_plane="fp8")
    )
    assert engine.effective_kernel_plane == "fp8"
    qv = quant_mod.quantize_for_plane(variables, "fp8")
    batch = quant_mod.probe_images(BUCKET, 4, 0)
    got = engine.predict_bucket(engine.prepare_quantized(qv), batch)
    # The bare tree: handed the wrapper, dequantize_variables returns it
    # unchanged and the "oracle" would be the fp8 program itself.
    oracle_vars = quant_mod.dequantize_variables(qv.tree)
    assert not isinstance(oracle_vars, quant_mod.QuantizedVariables)
    want = engine.predict_bucket(engine.prepare(oracle_vars), batch)
    diff = np.max(np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64)))
    assert diff < 1e-3, f"fp8 kernel vs its dequantize oracle diff {diff}"
    assert quant_mod.mask_iou(got, want) >= 0.99


def test_fp8_unsupported_backend_degrades_to_reference_bit_exactly(
    kstack, monkeypatch, caplog
):
    """kernel_plane="fp8" without backend fp8 support = the r17 reference
    closure, BIT-exact (not merely close), and the degradation is never
    silent: effective_kernel_plane says so and a WARNING is logged. The
    same holds for WHICH dequant kernels a fused plane runs (kernel_impl:
    the interpreter on this CPU suite, warned about)."""
    import logging

    from fedcrack_tpu.serve import quant as quant_mod
    from fedcrack_tpu.serve.engine import InferenceEngine

    monkeypatch.setattr("fedcrack_tpu.jaxcompat.fp8_supported", lambda: False)
    model_config, variables, engines = kstack
    with caplog.at_level(logging.WARNING, logger="fedcrack.serve.engine"):
        engine = InferenceEngine(
            model_config, _serve_config(quant="int8", kernel_plane="fp8")
        )
        fused = InferenceEngine(
            model_config, _serve_config(quant="int8", kernel_plane="fused_int8")
        )
    assert engine.kernel_plane == "fp8"
    assert engine.effective_kernel_plane == "reference"
    assert engine.kernel_impl is None
    assert fused.kernel_impl == "interpret"
    warned = [r.getMessage() for r in caplog.records]
    assert any("degraded to 'reference'" in m for m in warned)
    assert any("'interpret' dequant kernels" in m for m in warned)
    qv = quant_mod.quantize_for_plane(variables, engine.effective_kernel_plane)
    batch = quant_mod.probe_images(BUCKET, 4, 0)
    got = engine.predict_bucket(engine.prepare_quantized(qv), batch)
    want = engines["reference"].predict_bucket(
        engines["reference"].prepare_quantized(quant_mod.quantize_variables(variables)),
        batch,
    )
    np.testing.assert_array_equal(got, want)


def _garbage_for_plane(monkeypatch, quant_mod):
    """Monkeypatch quantize_for_plane to zero every code leaf — the gate
    must refuse the resulting build regardless of plane."""
    real = quant_mod.quantize_for_plane

    def garbage(variables, plane):
        q = real(variables, plane)

        def zero(node):
            if isinstance(node, dict) and quant_mod.SKEY in node:
                key = quant_mod.QKEY if quant_mod.QKEY in node else quant_mod.QKEY_FP8
                if key in node:
                    return {key: np.zeros_like(node[key]), quant_mod.SKEY: node[quant_mod.SKEY]}
            if isinstance(node, dict):
                return {k: zero(v) for k, v in node.items()}
            return node

        return quant_mod.QuantizedVariables(zero(q.tree))

    monkeypatch.setattr("fedcrack_tpu.serve.quant.quantize_for_plane", garbage)


@pytest.mark.parametrize("plane", ["fused_int8", "fp8"])
def test_gate_refusal_keeps_serving_reference_per_plane(kstack, monkeypatch, plane):
    """The r17 refusal contract re-pinned THROUGH the kernel-plane
    selection path: a garbage quantized build on a fused plane fails the
    gate, the fleet serves the un-quantized reference program bit-exactly,
    and the refusal names the plane."""
    from fedcrack_tpu import jaxcompat
    from fedcrack_tpu.serve import quant as quant_mod
    from fedcrack_tpu.serve.fleet import ServeFleet
    from fedcrack_tpu.serve.quant import QuantizedVariables

    if plane == "fp8" and not jaxcompat.fp8_supported():
        pytest.skip("backend has no fp8 dtypes")
    model_config, variables, engines = kstack
    from fedcrack_tpu.serve.engine import InferenceEngine

    engine = (
        engines[plane]
        if plane in engines
        else InferenceEngine(model_config, _serve_config(quant="int8", kernel_plane=plane))
    )
    _garbage_for_plane(monkeypatch, quant_mod)
    cfg = _serve_config(quant="int8", kernel_plane=plane, replicas=2)
    fleet = ServeFleet(
        model_config, cfg, variables, shared_engine=engine, warmup=False
    )
    try:
        gate = fleet.manager.last_quant_gate
        assert gate is not None and gate["passed"] is False
        _, payload = fleet.manager.snapshot_for(0)
        assert not isinstance(payload, QuantizedVariables)
        rng = np.random.default_rng(0)
        img = rng.integers(0, 256, (BUCKET, BUCKET, 3), dtype=np.uint8)
        got = fleet.submit(img).result(timeout=60)
        want = engine.predict_bucket(engine.prepare(variables), img[None])
        np.testing.assert_array_equal(got.probs, want[0])
    finally:
        fleet.close()


# ---- config validation + gauge ----


def test_serve_config_kernel_plane_validation():
    from fedcrack_tpu.configs import ServeConfig

    _serve_config(quant="int8", kernel_plane="fused_int8")  # valid
    with pytest.raises(ValueError):
        _serve_config(kernel_plane="fused_bf4")
    with pytest.raises(ValueError):
        _serve_config(quant="none", kernel_plane="fused_int8")
    with pytest.raises(ValueError):
        _serve_config(quant="none", kernel_plane="fp8")


def test_serve_kernel_plane_info_gauge_single_current_series():
    from fedcrack_tpu.obs.flops import export_kernel_plane
    from fedcrack_tpu.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    export_kernel_plane("reference", requested="fp8", registry=reg)
    expo = reg.exposition()
    assert "serve_kernel_plane_info" in expo
    assert 'plane="reference"' in expo and 'requested="fp8"' in expo
    # A plane change zeroes the stale series: exactly one reads 1.
    export_kernel_plane("fused_int8", registry=reg)
    lines = [
        l
        for l in reg.exposition().splitlines()
        if l.startswith("serve_kernel_plane_info{")
    ]
    ones = [l for l in lines if l.rstrip().endswith(" 1") or l.rstrip().endswith(" 1.0")]
    assert len(lines) == 2 and len(ones) == 1
    assert 'plane="fused_int8"' in ones[0]


def test_quantize_for_plane_rejects_unknown_plane():
    import jax

    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.models.resunet import init_variables
    from fedcrack_tpu.serve import quant as quant_mod

    variables = init_variables(jax.random.key(0), ModelConfig(**TINY_KW))
    with pytest.raises(ValueError):
        quant_mod.quantize_for_plane(variables, "bf4")
    tree = quant_mod.quantize_for_plane(variables, "fused_int8").tree
    # int8 tree for both int8 planes; fp8 tree carries the fp8 leaf key.
    flavors = set()

    def walk(node):
        if quant_mod._is_qleaf(node):
            flavors.update(k for k in node if k != quant_mod.SKEY)
            return
        if isinstance(node, dict):
            for v in node.values():
                walk(v)

    walk(tree)
    assert flavors == {quant_mod.QKEY}


# ---- training-side fake-quant twin ----


def test_fake_quant_params_bounded_and_differentiable():
    """The straight-through transform: weight error <= per-channel scale,
    ndim<2 leaves (biases, BN) untouched, gradients pass through as
    identity (the stop_gradient contract)."""
    import jax
    import jax.numpy as jnp

    from fedcrack_tpu.kernels.dequant import fake_quant_params

    rng = np.random.default_rng(5)
    params = {
        "conv": {"kernel": jnp.asarray(rng.normal(0, 0.1, (3, 3, 4, 7)), jnp.float32),
                 "bias": jnp.asarray(rng.normal(0, 0.1, (7,)), jnp.float32)},
        "bn": {"scale": jnp.ones((4,), jnp.float32)},
    }
    fq = fake_quant_params(params)
    w = np.asarray(params["conv"]["kernel"])
    wq = np.asarray(fq["conv"]["kernel"])
    scale = np.max(np.abs(w.reshape(-1, 7)), axis=0) / 127.0
    assert np.all(np.abs(wq - w) <= scale + 1e-9)
    assert not np.array_equal(wq, w)  # it DID quantize
    np.testing.assert_array_equal(np.asarray(fq["conv"]["bias"]), np.asarray(params["conv"]["bias"]))
    np.testing.assert_array_equal(np.asarray(fq["bn"]["scale"]), np.asarray(params["bn"]["scale"]))

    def loss(p):
        return jnp.sum(fake_quant_params(p)["conv"]["kernel"] ** 2)

    g = jax.grad(loss)(params)["conv"]["kernel"]
    # Straight-through: d/dw sum(fq(w)^2) = 2*fq(w), finite everywhere.
    np.testing.assert_allclose(np.asarray(g), 2 * wq, rtol=1e-6, atol=1e-6)


def test_build_federated_round_lowp_validation():
    from fedcrack_tpu.parallel import build_federated_round, make_mesh
    from fedcrack_tpu.configs import ModelConfig

    mesh = make_mesh(1, 1)
    tiny = ModelConfig(
        img_size=16, stem_features=4, encoder_features=(8,), decoder_features=(8, 4)
    )
    with pytest.raises(ValueError):
        build_federated_round(
            mesh, tiny, learning_rate=1e-3, local_epochs=1, lowp="int4"
        )


@pytest.mark.slow
def test_lowp_fake_quant_trajectory_within_tolerance():
    """3 mesh rounds per arm: lowp="null" is BIT-identical to a knob-free
    build (the escape hatch), lowp="fake_quant_int8" completes with finite
    weights and a per-round IoU within 0.15 absolute of the null oracle —
    the r12 int8-mesh-twin tolerance, now covering
    the fused-dequant training step. Slow-marked (three round-program
    compilations; the r9/r12 tier-1-budget precedent) — the value-level
    twin stays tier-1 via test_fake_quant_params_bounded_and_differentiable."""
    import jax

    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.data.synthetic import synth_crack_batch
    from fedcrack_tpu.parallel import (
        build_federated_round,
        make_mesh,
        run_mesh_federation,
        stack_client_data,
    )
    from fedcrack_tpu.train.local import create_train_state

    tiny = ModelConfig(
        img_size=16, stem_features=4, encoder_features=(8,), decoder_features=(8, 4)
    )
    steps, batch, n_rounds = 2, 4, 3
    mesh = make_mesh(2, 1)
    per_client = [
        synth_crack_batch(steps * batch, img_size=16, seed=i) for i in range(2)
    ]
    images, masks = stack_client_data(per_client, steps, batch)
    active = np.ones(2, np.float32)
    ns = np.full(2, float(steps * batch), np.float32)
    state0 = create_train_state(jax.random.key(0), tiny)
    data_fn = lambda r: (images, masks, active, ns) if r == 0 else None

    runs = {}
    for lowp in (None, "null", "fake_quant_int8"):
        rf = build_federated_round(
            mesh, tiny, learning_rate=1e-3, local_epochs=1, lowp=lowp
        )
        assert rf.lowp == ("null" if lowp is None else lowp)
        vars_, recs = run_mesh_federation(
            rf, state0.variables, data_fn, n_rounds, mesh
        )
        runs[lowp] = (jax.device_get(vars_), recs)

    for a, b in zip(
        jax.tree_util.tree_leaves(runs[None][0]),
        jax.tree_util.tree_leaves(runs["null"][0]),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    vars_fq, recs_fq = runs["fake_quant_int8"]
    assert all(
        np.isfinite(np.asarray(l)).all()
        for l in jax.tree_util.tree_leaves(vars_fq)
    )
    null_iou = [float(np.mean(r.metrics["iou"])) for r in runs["null"][1]]
    fq_iou = [float(np.mean(r.metrics["iou"])) for r in recs_fq]
    assert max(abs(a - b) for a, b in zip(fq_iou, null_iou)) < 0.15
