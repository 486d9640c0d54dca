"""Serve fleet + quantized predict (round 17): quant A/B gate, fleet-wide
two-phase hot swap, router admission control, replica crash failover, load
profiles, and the persistent-compile-cache warm boot.

The load-bearing claims, each pinned here:

- int8 weight quantization is deterministic (same weights -> byte-identical
  codes/scales) with per-entry error bounded by scale/2, and the install
  gate REFUSES a quantized build whose probe mask IoU falls below the floor
  — the fleet keeps serving the reference program (bf16 fallback), outputs
  bit-equal to a never-quantized fleet;
- the fleet swap is torn-version-free: after ``install`` returns, every
  request on every replica answers from the new version, and a batch that
  snapshotted before the commit answers entirely from its snapshot (the
  straddle contract);
- admission control sheds loudly (LoadShedError / RESOURCE_EXHAUSTED over
  gRPC) on queue bound and rolling-p95 breach, and NEVER sheds an already
  accepted request;
- a killed replica's queued requests reroute to survivors with their
  original futures — zero accepted requests dropped, swap still lands;
- a second engine build against the same persistent compilation cache adds
  zero new cache entries (the warm-boot claim).
"""

import os
import threading
import time

import numpy as np
import pytest

pytestmark = pytest.mark.serve

TINY_KW = dict(
    img_size=32, stem_features=4, encoder_features=(8,), decoder_features=(8, 4)
)
BUCKETS = (16, 32)


def _serve_config(**over):
    from fedcrack_tpu.configs import ServeConfig

    kw = dict(
        bucket_sizes=BUCKETS, max_batch=4, max_delay_ms=10.0, tile_overlap=4
    )
    kw.update(over)
    return ServeConfig(**kw)


@pytest.fixture(scope="module")
def stack():
    """Shared compiled engines (reference + int8) and two weight versions —
    the bucket compiles dominate test cost; every test takes fresh fleets
    over the same engines."""
    import jax

    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.models.resunet import init_variables
    from fedcrack_tpu.serve import InferenceEngine

    model_config = ModelConfig(**TINY_KW)
    engine_ref = InferenceEngine(model_config, _serve_config())
    engine_q = InferenceEngine(model_config, _serve_config(quant="int8"))
    var0 = init_variables(jax.random.key(0), model_config)
    var1 = init_variables(jax.random.key(1), model_config)
    return model_config, engine_ref, engine_q, var0, var1


def _fleet(stack, *, quant="none", replicas=2, chaos=None, **cfg_over):
    from fedcrack_tpu.serve import ServeFleet

    model_config, engine_ref, engine_q, var0, _ = stack
    cfg = _serve_config(quant=quant, replicas=replicas, **cfg_over)
    return ServeFleet(
        model_config,
        cfg,
        var0,
        shared_engine=engine_q if quant == "int8" else engine_ref,
        chaos=chaos,
        warmup=False,
    )


def _img(size, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (size, size, 3), dtype=np.uint8)


# ---- quantization units ----


def test_quantize_leaf_deterministic_and_bounded():
    from fedcrack_tpu.serve.quant import QKEY, SKEY, quantize_leaf

    rng = np.random.default_rng(3)
    w = rng.normal(0, 0.1, (3, 3, 8, 16)).astype(np.float32)
    a, b = quantize_leaf(w), quantize_leaf(w)
    assert np.array_equal(a[QKEY], b[QKEY]) and np.array_equal(a[SKEY], b[SKEY])
    assert a[QKEY].dtype == np.int8 and a[SKEY].shape == (16,)
    # Per-entry dequantization error <= half a quantization step.
    deq = a[QKEY].astype(np.float32) * a[SKEY]
    assert np.all(np.abs(deq - w) <= a[SKEY] / 2 + 1e-9)


def test_quantize_leaf_zero_channel_is_exact():
    from fedcrack_tpu.serve.quant import QKEY, SKEY, quantize_leaf

    w = np.zeros((3, 3, 2, 4), np.float32)
    w[..., 1] = 0.5  # one live channel among dead ones
    q = quantize_leaf(w)
    assert np.all(q[SKEY][[0, 2, 3]] == 1.0)  # dead channels: scale 1, code 0
    deq = q[QKEY].astype(np.float32) * q[SKEY]
    assert np.array_equal(deq[..., 0], w[..., 0])


def test_quantize_variables_selects_kernels_only(stack):
    import jax

    from fedcrack_tpu.serve.quant import quantize_variables

    _, _, _, var0, _ = stack
    q = quantize_variables(var0)
    # batch_stats stay raw float arrays; params kernels become q-leaves.
    flat_ref = jax.tree_util.tree_leaves(var0)
    flat_q = jax.tree_util.tree_leaves(q.tree)
    assert any(leaf.dtype == np.int8 for leaf in flat_q)
    n_kernels = sum(1 for leaf in flat_ref if leaf.ndim >= 2)
    assert sum(1 for leaf in flat_q if leaf.dtype == np.int8) == n_kernels
    from fedcrack_tpu.serve.quant import quantized_bytes

    q_bytes, ref_bytes = quantized_bytes(q.tree)
    assert q_bytes < ref_bytes / 2  # int8 kernels dominate the tree


def test_mask_iou_units():
    from fedcrack_tpu.serve.quant import mask_iou

    a = np.zeros((4, 4, 1), np.float32)
    b = np.zeros((4, 4, 1), np.float32)
    assert mask_iou(a, b) == 1.0  # both empty = agreement
    a[0, 0] = 1.0
    assert mask_iou(a, b) == 0.0
    b[0, 0] = 1.0
    assert mask_iou(a, b) == 1.0
    b[1, 1] = 1.0
    assert mask_iou(a, b) == pytest.approx(0.5)


# ---- the A/B gate ----


def test_quant_gate_passes_on_tiny_model(stack):
    from fedcrack_tpu.serve.quant import quant_gate, quantize_variables

    _, _, engine_q, var0, _ = stack
    ref = engine_q.prepare(var0)
    qv = engine_q.prepare_quantized(quantize_variables(var0))
    gate = quant_gate(engine_q, ref, qv, floor=0.5)
    assert gate.passed and 0.5 <= gate.iou <= 1.0
    assert set(gate.per_bucket) == set(BUCKETS)
    # Deterministic: the same gate re-run returns the same IoU.
    gate2 = quant_gate(engine_q, ref, qv, floor=0.5)
    assert gate2.iou == gate.iou


def test_quant_gate_failure_refuses_and_serves_bf16(stack, monkeypatch):
    """A garbage quantized build (codes zeroed) must fail the gate; the
    fleet REFUSES it and serves the reference program — outputs equal a
    never-quantized fleet's, and the refusal is recorded loudly."""
    from fedcrack_tpu.serve import quant as quant_mod

    real_quantize = quant_mod.quantize_variables

    def garbage_quantize(variables):
        q = real_quantize(variables)

        def zero(node):
            if isinstance(node, dict) and set(node) == {quant_mod.QKEY, quant_mod.SKEY}:
                return {
                    quant_mod.QKEY: np.zeros_like(node[quant_mod.QKEY]),
                    quant_mod.SKEY: node[quant_mod.SKEY],
                }
            if isinstance(node, dict):
                return {k: zero(v) for k, v in node.items()}
            return node

        return quant_mod.QuantizedVariables(zero(q.tree))

    monkeypatch.setattr(
        "fedcrack_tpu.serve.quant.quantize_variables", garbage_quantize
    )
    fleet = _fleet(stack, quant="int8")
    try:
        gate = fleet.manager.last_quant_gate
        assert gate is not None and gate["passed"] is False
        # bf16 fallback: the served payload is NOT a quantized wrapper...
        from fedcrack_tpu.serve.quant import QuantizedVariables

        _, payload = fleet.manager.snapshot_for(0)
        assert not isinstance(payload, QuantizedVariables)
        # ...and answers match the reference program bit-for-bit.
        img = _img(16)
        got = fleet.submit(img).result(timeout=60)
        _, _, engine_q, var0, _ = stack
        want = engine_q.predict_bucket(engine_q.prepare(var0), img[None])
        np.testing.assert_array_equal(got.probs, want[0])
    finally:
        fleet.close()


def test_quant_gate_pass_serves_quantized(stack):
    fleet = _fleet(stack, quant="int8")
    try:
        gate = fleet.manager.last_quant_gate
        assert gate is not None
        from fedcrack_tpu.serve.quant import QuantizedVariables

        _, payload = fleet.manager.snapshot_for(0)
        if gate["passed"]:
            assert isinstance(payload, QuantizedVariables)
        else:  # honest refuse on this seed: fallback contract instead
            assert not isinstance(payload, QuantizedVariables)
        # Either way requests answer.
        res = fleet.submit(_img(16)).result(timeout=60)
        assert res.probs.shape == (16, 16, 1)
        # The IoU gauge carries the measured ratio.
        from fedcrack_tpu.obs.registry import REGISTRY

        g = REGISTRY.gauge("serve_quant_iou_ratio", "")
        assert g.value == pytest.approx(gate["iou"], abs=1e-6)
    finally:
        fleet.close()


def test_quantized_predict_deterministic(stack):
    """Two runs of the quantized program on the same inputs are
    byte-identical (the serve plane's determinism discipline survives
    quantization)."""
    from fedcrack_tpu.serve.quant import quantize_variables

    _, _, engine_q, var0, _ = stack
    qv = engine_q.prepare_quantized(quantize_variables(var0))
    batch = np.stack([_img(32, seed=i) for i in range(3)])
    a = engine_q.predict_bucket(qv, batch)
    b = engine_q.predict_bucket(qv, batch)
    np.testing.assert_array_equal(a, b)


# ---- fleet two-phase swap ----


def test_fleet_swap_zero_torn_versions(stack):
    """After install() returns, every request on every replica answers v1;
    pre-install responses were all v0. The commit barrier, measured."""
    _, _, _, _, var1 = stack
    fleet = _fleet(stack, replicas=3)
    try:
        img = _img(16)
        pre = [fleet.submit(img) for _ in range(9)]
        pre_versions = {f.result(timeout=60).model_version for f in pre}
        assert pre_versions == {0}
        assert fleet.install(1, var1)
        post = [fleet.submit(img) for _ in range(9)]
        post_versions = {f.result(timeout=60).model_version for f in post}
        assert post_versions == {1}, f"torn versions: {post_versions}"
        assert fleet.manager.last_swap["pause_ms"] is not None
        # Re-installing an older or equal version is a no-op.
        assert not fleet.install(1, var1)
        assert not fleet.install(0, var1)
    finally:
        fleet.close()


def test_fleet_swap_straddling_batch_answers_from_snapshot(stack):
    """A batch whose snapshot was taken BEFORE the commit must answer from
    that snapshot even though the fleet-wide flip lands while it is in
    flight — the r10 torn-read barrier, fleet edition. The chaos hook runs
    between snapshot and dispatch: exactly the straddle window."""
    _, _, _, var0, var1 = stack
    fired = {"done": False}
    holder = {}

    class SwapMidBatch:
        def on_batch(self, bucket, batch_index, attempt):
            if not fired["done"] and holder.get("fleet") is not None:
                fired["done"] = True
                assert holder["fleet"].install(1, var1)

    fleet = _fleet(stack, replicas=2, chaos=SwapMidBatch())
    holder["fleet"] = fleet
    try:
        res = fleet.submit(_img(16)).result(timeout=60)
        assert fired["done"]
        # Snapshot was v0; the fleet is ALREADY v1 when the answer lands.
        assert res.model_version == 0
        assert fleet.manager.version == 1
        after = fleet.submit(_img(16)).result(timeout=60)
        assert after.model_version == 1
    finally:
        fleet.close()


def test_fleet_poll_installs_from_statefile(stack, tmp_path):
    """The fleet manager watches the same federation outputs as the r10
    manager (shared WeightSourceWatcher): a published statefile swaps every
    replica."""
    from fedcrack_tpu.serve import ServeFleet
    from fedcrack_tpu.serve.hot_swap import publish_statefile

    model_config, engine_ref, _, var0, var1 = stack
    state = tmp_path / "state.msgpack"
    fleet = ServeFleet(
        model_config,
        _serve_config(replicas=2),
        var0,
        shared_engine=engine_ref,
        state_path=str(state),
        template=var0,
        warmup=False,
    )
    try:
        assert not fleet.manager.poll_once()  # nothing published yet
        publish_statefile(str(state), var1, model_version=7)
        assert fleet.manager.poll_once()
        assert fleet.manager.version == 7
        for i in range(2):
            v, _ = fleet.manager.snapshot_for(i)
            assert v == 7
    finally:
        fleet.close()


# ---- router: dispatch + admission control ----


def test_router_least_outstanding_deterministic(stack):
    fleet = _fleet(stack, replicas=3)
    try:
        router = fleet.router
        # Idle fleet: ties break to the lowest index.
        assert router._pick(16).index == 0
        futs = [fleet.submit(_img(16)) for _ in range(6)]
        [f.result(timeout=60) for f in futs]
        counts = [r.batcher.stats()["completed"] for r in fleet.replicas]
        assert sum(counts) == 6
        assert all(c > 0 for c in counts)  # load spread, not pinned to one
    finally:
        fleet.close()


def test_router_sheds_on_queue_bound(stack):
    """With queues artificially backed up past queue_bound, the next submit
    raises LoadShedError(queue_bound) — and metric + counter agree."""
    from fedcrack_tpu.obs.registry import REGISTRY
    from fedcrack_tpu.serve.router import SHED_QUEUE_BOUND, LoadShedError

    class SlowBatches:
        def on_batch(self, bucket, batch_index, attempt):
            time.sleep(0.15)

    fleet = _fleet(stack, replicas=2, chaos=SlowBatches(), queue_bound=2)
    try:
        m = REGISTRY.counter("serve_shed_total", "", labels=("reason",))
        before = m.labels(reason=SHED_QUEUE_BOUND).value
        accepted = []
        shed = 0
        for _ in range(24):
            try:
                accepted.append(fleet.submit(_img(16)))
            except LoadShedError as e:
                assert e.reason == SHED_QUEUE_BOUND
                shed += 1
        assert shed > 0, "queue bound never tripped"
        # Every ACCEPTED request still answers — shedding is accept-time only.
        for f in accepted:
            assert f.result(timeout=60).probs.shape == (16, 16, 1)
        assert fleet.router.shed_counts()[SHED_QUEUE_BOUND] == shed
        assert m.labels(reason=SHED_QUEUE_BOUND).value == before + shed
    finally:
        fleet.close()


def test_router_sheds_on_p95_slo(stack):
    from fedcrack_tpu.serve.router import (
        MIN_SHED_SAMPLES,
        SHED_P95_SLO,
        LoadShedError,
    )

    fleet = _fleet(stack, replicas=2, slo_p95_ms=50.0)
    try:
        # Below the arming threshold nothing sheds even with slow samples.
        for _ in range(MIN_SHED_SAMPLES - 1):
            fleet.router.rolling.add(500.0)
        fleet.submit(_img(16)).result(timeout=60)
        # Armed + breaching: the next submit sheds with the p95 reason.
        for _ in range(MIN_SHED_SAMPLES):
            fleet.router.rolling.add(500.0)
        with pytest.raises(LoadShedError) as err:
            fleet.submit(_img(16))
        assert err.value.reason == SHED_P95_SLO
    finally:
        fleet.close()


def test_rolling_percentiles_window_forgets():
    from fedcrack_tpu.serve.router import RollingPercentiles

    rp = RollingPercentiles(window_s=0.05, capacity=128)
    for _ in range(32):
        rp.add(1000.0)
    assert rp.percentile(95.0) == pytest.approx(1000.0)
    # Two window rotations later the breach has aged out entirely.
    time.sleep(0.12)
    rp.add(1.0)  # rotation happens on access
    time.sleep(0.12)
    for _ in range(8):
        rp.add(1.0)
    assert rp.percentile(95.0) == pytest.approx(1.0)
    with pytest.raises(ValueError):
        RollingPercentiles(window_s=0.0)


# ---- replica crash failover ----


def test_replica_crash_reroutes_queued_requests(stack):
    """Kill a replica with a queued backlog: drained requests reroute to
    the survivor with their ORIGINAL futures, zero accepted requests drop,
    and the fleet swap still lands on the survivors."""
    from fedcrack_tpu.chaos.plan import SERVE_REPLICA_CRASH, Fault, FaultPlan

    _, _, _, _, var1 = stack

    class SlowBatches:
        def on_batch(self, bucket, batch_index, attempt):
            time.sleep(0.08)

    plan = FaultPlan([Fault(kind=SERVE_REPLICA_CRASH, round=1)])
    fleet = _fleet(stack, replicas=2, chaos=SlowBatches())
    try:
        img = _img(16)
        futs = [fleet.submit(img) for _ in range(16)]
        assert plan.take(SERVE_REPLICA_CRASH, round=1) is not None
        out = fleet.router.kill_replica(1)
        assert out["failed"] == 0
        results = [f.result(timeout=120) for f in futs]
        assert len(results) == 16  # zero dropped
        assert out["rerouted"] > 0, "kill landed after the queue drained"
        # Dead replica is out of rotation; new traffic still flows.
        assert fleet.router.live_replicas()[0].index == 0
        assert fleet.submit(img).result(timeout=60).probs.shape == (16, 16, 1)
        # The fleet swap still lands on the degraded fleet.
        assert fleet.install(1, var1)
        assert fleet.submit(img).result(timeout=60).model_version == 1
        # Double-kill is a no-op; killing the last replica leaves nothing.
        assert fleet.router.kill_replica(1)["already_dead"] is True
    finally:
        fleet.close()


def test_replica_crash_fault_kind_registered():
    from fedcrack_tpu.chaos.plan import (
        ALL_KINDS,
        FLEET_KINDS,
        SERVE_REPLICA_CRASH,
        Fault,
    )

    assert SERVE_REPLICA_CRASH in FLEET_KINDS and SERVE_REPLICA_CRASH in ALL_KINDS
    Fault(kind=SERVE_REPLICA_CRASH, round=0)  # constructs clean


# ---- gRPC shed e2e ----


def test_grpc_shed_path_e2e(stack):
    """Front-door overload: an open-loop RAMP injects past the (chaos-
    slowed) fleet's service rate over the real socket; admission control
    sheds with RESOURCE_EXHAUSTED, load_gen counts shed apart from drops
    and rejects (per phase), and zero accepted requests drop. Open loop is
    the shape that CAN overload: injection is schedule-driven over parallel
    streams, not completion-paced like the closed loop."""
    from fedcrack_tpu.serve import ServeServer, ServeServerThread, ServeService
    from fedcrack_tpu.tools.load_gen import run_load

    class SlowBatches:
        def on_batch(self, bucket, batch_index, attempt):
            time.sleep(0.25)

    fleet = _fleet(stack, replicas=2, chaos=SlowBatches(), queue_bound=2)
    server = ServeServer(
        ServeService(fleet.engine, fleet.router, fleet.manager), port=0
    )
    try:
        with ServeServerThread(server) as thread:
            summary = run_load(
                f"127.0.0.1:{thread.port}",
                mode="open",
                profile="ramp",
                n_requests=32,
                rate_rps=120.0,
                concurrency=8,
                sizes=(16,),
                seed=0,
                timeout_s=120.0,
            )
    finally:
        fleet.close()
    assert summary["shed"] > 0, "admission control never fired"
    assert summary["dropped"] == 0
    assert summary["rejected"] == 0  # sheds are NOT rejects
    assert summary["completed"] + summary["shed"] == 32
    assert server.service.shed == summary["shed"]
    phases = summary["per_phase"]
    assert [p["phase"] for p in phases] == [
        "ramp_0.25x", "ramp_0.5x", "ramp_1x", "ramp_2x",
    ]
    assert sum(p["shed"] for p in phases) == summary["shed"]
    # The overload lives in the ramp's tail, not its warmup.
    assert sum(p["shed"] for p in phases[2:]) > 0


# ---- load profiles ----


def test_arrival_schedule_const():
    from fedcrack_tpu.tools.load_gen import arrival_schedule

    offsets, phases, meta = arrival_schedule("const", 10, 20.0, seed=3)
    assert offsets == [i * 0.05 for i in range(10)]
    assert phases == [0] * 10
    assert meta[0]["phase"] == "const" and meta[0]["requests"] == 10


def test_arrival_schedule_ramp_seeded_and_shaped():
    from fedcrack_tpu.tools.load_gen import RAMP_PHASES, arrival_schedule

    a = arrival_schedule("ramp", 40, 10.0, seed=7)
    b = arrival_schedule("ramp", 40, 10.0, seed=7)
    assert a == b  # seeded: replayable schedule
    c = arrival_schedule("ramp", 40, 10.0, seed=8)
    assert a[0] != c[0]  # different seed, different gaps
    offsets, phases, meta = a
    assert len(offsets) == 40 and sorted(offsets) == offsets
    assert [m["requests"] for m in meta] == [10, 10, 10, 10]
    rates = [m["target_rps"] for m in meta]
    assert rates == [10.0 * m for _, m in RAMP_PHASES]
    # Phase indices are contiguous and ordered.
    assert phases == sorted(phases) and set(phases) == {0, 1, 2, 3}


def test_arrival_schedule_diurnal_and_validation():
    from fedcrack_tpu.tools.load_gen import DIURNAL_PHASES, arrival_schedule

    offsets, phases, meta = arrival_schedule("diurnal", 21, 5.0, seed=0)
    assert len(offsets) == 21
    assert [m["phase"] for m in meta] == [n for n, _ in DIURNAL_PHASES]
    assert sum(m["requests"] for m in meta) == 21
    with pytest.raises(ValueError):
        arrival_schedule("sawtooth", 10, 5.0)
    with pytest.raises(ValueError):
        arrival_schedule("ramp", 0, 5.0)
    with pytest.raises(ValueError):
        arrival_schedule("ramp", 10, 0.0)


def test_run_load_profile_needs_open_mode():
    from fedcrack_tpu.tools.load_gen import run_load

    with pytest.raises(ValueError):
        run_load("127.0.0.1:1", mode="closed", profile="ramp")


# ---- compile cache warm boot ----


def test_compile_cache_warm_boot(tmp_path):
    """Second engine build against the same persistent cache adds ZERO new
    cache entries — every program is a hit (the replica warm-boot claim;
    cross-process reuse follows because the cache is keyed on the program,
    not the process)."""
    import jax
    from jax._src import compilation_cache

    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.models.resunet import init_variables
    from fedcrack_tpu.serve import InferenceEngine

    # A private directory with the floors at zero, so this tiny model's
    # sub-second programs are written at all; the cache latched the suite's
    # directory at the first compile, hence the resets. All restored below.
    cache_dir = str(tmp_path / "xla_cache")
    knobs = {
        "jax_compilation_cache_dir": cache_dir,
        "jax_persistent_cache_min_compile_time_secs": 0,
        "jax_persistent_cache_min_entry_size_bytes": -1,
    }
    prev = {k: getattr(jax.config, k) for k in knobs}
    for k, v in knobs.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    try:
        # A config no other test compiles, so the first build is cold.
        model_config = ModelConfig(
            img_size=16, stem_features=2, encoder_features=(4,),
            decoder_features=(4, 2),
        )
        serve_config = _serve_config(bucket_sizes=(16,), max_batch=2)
        var = init_variables(jax.random.key(0), model_config)

        def cache_entries():
            return sorted(
                f for f in os.listdir(cache_dir) if f.endswith("-cache")
            )

        e1 = InferenceEngine(model_config, serve_config)
        e1.warmup(e1.prepare(var))
        first = cache_entries()
        assert first, "no cache entries written on the cold build"
        t0 = time.perf_counter()
        e2 = InferenceEngine(model_config, serve_config)
        e2.warmup(e2.prepare(var))
        warm_s = time.perf_counter() - t0
        assert cache_entries() == first, "warm build missed the cache"
        assert warm_s < 60.0  # sanity: the warm path must not re-pay compile
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


# ---- config validation ----


def test_serve_config_fleet_validation():
    from fedcrack_tpu.configs import ServeConfig

    _serve_config(replicas=4, quant="int8", slo_p95_ms=100.0, queue_bound=64)
    with pytest.raises(ValueError):
        _serve_config(replicas=0)
    with pytest.raises(ValueError):
        _serve_config(quant="fp8")
    with pytest.raises(ValueError):
        _serve_config(quant_iou_floor=0.0)
    with pytest.raises(ValueError):
        _serve_config(quant_iou_floor=1.5)
    with pytest.raises(ValueError):
        _serve_config(quant_probe_batch=0)
    with pytest.raises(ValueError):
        _serve_config(slo_p95_ms=-1.0)
    with pytest.raises(ValueError):
        _serve_config(queue_bound=-1)
    assert ServeConfig().replicas == 1 and ServeConfig().quant == "none"


def test_c14_preset_round_trips():
    from fedcrack_tpu.configs import FedConfig

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "configs", "c14_serve_fleet.json")) as f:
        fed = FedConfig.from_json(f.read())
    assert fed.serve.replicas == 4
    assert fed.serve.quant == "int8"
    assert fed.serve.queue_bound == 256
    assert fed.serve.slo_p95_ms == 250.0
    assert FedConfig.from_json(fed.to_json()) == fed


# ---- fleet metrics ----


def test_fleet_replicas_gauge_tracks_kills(stack):
    from fedcrack_tpu.obs.registry import REGISTRY

    fleet = _fleet(stack, replicas=3)
    try:
        g = REGISTRY.gauge("serve_fleet_replicas", "")
        assert g.value == 3
        fleet.router.kill_replica(2)
        assert g.value == 2
    finally:
        fleet.close()


def test_fleet_swap_pause_histogram_recorded(stack):
    from fedcrack_tpu.obs.registry import REGISTRY

    _, _, _, _, var1 = stack
    fleet = _fleet(stack, replicas=2)
    try:
        h = REGISTRY.histogram("serve_fleet_swap_pause_seconds", "")
        before = h.snapshot()["count"]
        assert fleet.install(1, var1)
        assert h.snapshot()["count"] == before + 1
    finally:
        fleet.close()
