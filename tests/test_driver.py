"""Tests for the double-buffered multi-round mesh federation driver.

The load-bearing property (round-3 verdict "what's weak" #2): staging round
r+1 while round r computes must be a pure latency optimization — the final
global weights are bit-identical to sequential staging, because staging is
data-independent of the in-flight round.
"""

import collections

import jax
import numpy as np
import pytest

from fedcrack_tpu.configs import ModelConfig
from fedcrack_tpu.data.synthetic import synth_crack_batch
from fedcrack_tpu.parallel import (
    build_federated_round,
    make_mesh,
    run_mesh_federation,
    shuffled_epoch_data,
    stack_client_data,
)
from treecmp import assert_trees_equal as _assert_trees_equal

TINY = ModelConfig(
    img_size=16, stem_features=4, encoder_features=(8,), decoder_features=(8, 4)
)
STEPS, BATCH, N_CLIENTS, ROUNDS = 2, 4, 2, 3


@pytest.fixture(scope="module")
def round_fn_and_mesh():
    mesh = make_mesh(N_CLIENTS, 1)
    round_fn = build_federated_round(mesh, TINY, learning_rate=1e-3, local_epochs=1)
    return round_fn, mesh


def _fresh_data_fn(seed0=0):
    """Deterministic per-round data: a new shard every round (forces
    restaging), same values for every caller."""

    def data_fn(r):
        per_client = [
            synth_crack_batch(
                STEPS * BATCH, img_size=TINY.img_size, seed=seed0 + 10 * r + i
            )
            for i in range(N_CLIENTS)
        ]
        images, masks = stack_client_data(per_client, STEPS, BATCH)
        active = np.ones(N_CLIENTS, np.float32)
        n_samples = np.full(N_CLIENTS, float(STEPS * BATCH), np.float32)
        return images, masks, active, n_samples

    return data_fn


def _init_vars():
    from fedcrack_tpu.train.local import create_train_state

    return create_train_state(jax.random.key(0), TINY).variables


def test_overlap_matches_sequential(round_fn_and_mesh):
    round_fn, mesh = round_fn_and_mesh
    v_overlap, rec_overlap = run_mesh_federation(
        round_fn, _init_vars(), _fresh_data_fn(), ROUNDS, mesh, overlap_staging=True
    )
    v_seq, rec_seq = run_mesh_federation(
        round_fn, _init_vars(), _fresh_data_fn(), ROUNDS, mesh, overlap_staging=False
    )
    _assert_trees_equal(v_overlap, v_seq)
    for ro, rs in zip(rec_overlap, rec_seq):
        for k in ro.metrics:
            np.testing.assert_array_equal(ro.metrics[k], rs.metrics[k])
    # All but the last round staged the next round's data concurrently.
    assert [r.overlapped for r in rec_overlap] == [True, True, False]
    assert all(not r.overlapped for r in rec_seq)
    # staging_s is the host-blocking staging paid for THIS round's data
    # (round-7 boundary-term fix): the initial transfer lands on the first
    # record in BOTH modes; after that, overlap mode hides staging (0.0)
    # while sequential mode pays it for every round — so sequential session
    # totals now account for exactly one staging period per round, none
    # dropped at either boundary.
    assert rec_overlap[0].staging_s > 0.0
    assert all(r.staging_s == 0.0 for r in rec_overlap[1:])
    assert all(r.staging_s > 0.0 for r in rec_seq)


# A span and the counter beside it are two clock reads apart (``_host_phase``
# reads ``perf_counter`` outside the span it enters, and the recorder writes the
# span's JSONL line in between), so under six busy xdist workers any one pair
# can sit a descheduling apart: milliseconds, where a warm toy round is 8 ms.
# What holds on a loaded host: the counter is never shorter than its span, and
# in the quietest of SPAN_ROUNDS rounds the two agree to SPAN_GAP_SHARE of that
# round's wall. A counter that timed something other than its span breaks the
# first in one direction or the second in every round.
SPAN_ROUNDS = 4
SPAN_GAP_SHARE = 0.1
ROUNDING = 1e-5  # spans are written rounded to a microsecond

HOST_KEYS = {"dispatch", "feed", "stage", "barrier", "handoff"}


def _warm(round_fn, mesh):
    """A share of a round's wall means nothing of a round that compiles:
    seconds of wall would hide any gap. Compile before the rounds that count."""
    run_mesh_federation(round_fn, _init_vars(), _fresh_data_fn(), 1, mesh)


@pytest.mark.parametrize("overlap", [True, False], ids=["overlapped", "sequential"])
def test_host_s_splits_the_round_wall(round_fn_and_mesh, overlap):
    """RoundRecord.host_s: five keys; dispatch + feed + stage + barrier lie
    inside wall_clock_s and sum to it; handoff is the gap before the round's
    dispatch. Staging that hides under a round is counted where it ran
    (host_s["stage"] of the round before), which staging_s == 0.0 is not."""
    round_fn, mesh = round_fn_and_mesh
    _warm(round_fn, mesh)
    _, records = run_mesh_federation(
        round_fn, _init_vars(), _fresh_data_fn(), ROUNDS, mesh, overlap_staging=overlap
    )
    unphased = []  # the share of a round's wall outside its four phases
    for rec in records:
        assert set(rec.host_s) == HOST_KEYS
        inner = sum(rec.host_s[k] for k in ("dispatch", "feed", "stage", "barrier"))
        # Disjoint intervals inside the wall: never more than it, and all but
        # a few statements of it in the quietest round (a loaded host can
        # deschedule the driver between two phases of any one round).
        assert inner <= rec.wall_clock_s + 1e-9
        unphased.append((rec.wall_clock_s - inner) / rec.wall_clock_s)
        assert rec.host_s["dispatch"] > 0.0 and rec.host_s["barrier"] > 0.0
    assert min(unphased) <= SPAN_GAP_SHARE, unphased
    assert records[0].host_s["handoff"] == 0.0
    assert all(rec.host_s["handoff"] > 0.0 for rec in records[1:])
    if overlap:
        # Staged under the round exactly where the next round's data landed
        # during it; those are the rounds whose successor reports 0.0.
        assert [rec.host_s["stage"] > 0.0 for rec in records] == [
            rec.overlapped for rec in records
        ]
        assert [rec.host_s["feed"] > 0.0 for rec in records] == [True, True, False]
        for before, rec in zip(records, records[1:]):
            assert rec.staging_s == 0.0 and before.host_s["stage"] > 0.0
            assert rec.data_fn_s == before.host_s["feed"]
    else:
        # Sequential: feed and staging run after the barrier, outside the
        # wall, inside the next round's handoff; staging_s is still charged.
        for before, rec in zip(records, records[1:]):
            assert before.host_s["feed"] == 0.0 == before.host_s["stage"]
            assert rec.staging_s > 0.0
            assert rec.host_s["handoff"] >= rec.staging_s + rec.data_fn_s


@pytest.mark.parametrize("overlap", [True, False], ids=["overlapped", "sequential"])
def test_stage_splits_the_staging_where_the_runtime_takes_over(round_fn_and_mesh, overlap):
    """RoundRecord.stage, for the staging that ran under the round (what
    host_s["stage"] times): the bytes put on each mesh device sum to the next
    round's staged_bytes, land_s holds one stamp a mesh device, in mesh order
    and never decreasing, and put_s and the last stamp lie inside
    host_s["stage"]. {} where no slab was staged under the round: the last
    round, and every round of sequential mode."""
    round_fn, mesh = round_fn_and_mesh
    _, records = run_mesh_federation(
        round_fn, _init_vars(), _fresh_data_fn(), ROUNDS, mesh, overlap_staging=overlap
    )
    assert records[-1].stage == {}
    for rec, after in zip(records, records[1:]):
        if not overlap:
            assert rec.stage == {}
            continue
        assert set(rec.stage) == {"put_s", "land_s", "bytes"}
        assert len(rec.stage["bytes"]) == len(rec.stage["land_s"]) == mesh.devices.size
        assert sum(rec.stage["bytes"]) == after.staged_bytes > 0
        assert all(b > 0 for b in rec.stage["bytes"])
        stamps = rec.stage["land_s"]
        assert stamps == sorted(stamps)
        assert 0.0 < rec.stage["put_s"] <= stamps[0] <= stamps[-1] <= rec.host_s["stage"]


def test_proc_and_device_memory_of_a_round(round_fn_and_mesh):
    """RoundRecord.proc: what the process spent between a round's dispatch
    and its barrier, three numbers that never fall; a round that computes on
    this host burns CPU. RoundRecord.device_memory is {} on a backend that
    reports no memory (the CPU)."""
    round_fn, mesh = round_fn_and_mesh
    _, records = run_mesh_federation(round_fn, _init_vars(), _fresh_data_fn(), ROUNDS, mesh)
    for rec in records:
        assert set(rec.proc) == {"cpu_s", "nivcsw", "majflt"}
        assert all(v >= 0 for v in rec.proc.values())
        assert rec.device_memory == {}
    assert sum(rec.proc["cpu_s"] for rec in records) > 0.0


def test_step_loss_is_the_curve_behind_loss(round_fn_and_mesh):
    """metrics["step_loss"] is every step's loss, [C, epochs, steps]; the
    last epoch's mean over steps is the round's "loss"."""
    round_fn, mesh = round_fn_and_mesh
    _, records = run_mesh_federation(
        round_fn, _init_vars(), _fresh_data_fn(), 1, mesh
    )
    metrics = records[0].metrics
    curve = metrics["step_loss"]
    assert curve.shape == (N_CLIENTS, 1, STEPS) and curve.dtype == np.float32
    assert np.all(np.isfinite(curve))
    np.testing.assert_allclose(curve[:, -1].mean(axis=-1), metrics["loss"], atol=1e-6)


DRIVER_SPANS = {"driver.round", "driver.dispatch", "driver.feed", "driver.stage", "driver.barrier"}
STAGE_SPANS = {"driver.stage.put", "driver.stage.land"}


def test_span_recorder_holds_the_round_and_its_phases(round_fn_and_mesh, tmp_path):
    """With a SpanRecorder installed the driver's spans enclose real work:
    driver.round is the parent of dispatch / feed / stage / barrier, under
    the trace id round-<r>; driver.stage is the parent of one
    driver.stage.put and one driver.stage.land a mesh device, whose ends are
    RoundRecord.stage's clock reads; driver.handoff sits between rounds; each
    span's duration is the counter's (host_s, wall_clock_s) of the same round."""
    from fedcrack_tpu.obs import spans as tracing

    round_fn, mesh = round_fn_and_mesh
    _warm(round_fn, mesh)
    path = tmp_path / "spans.jsonl"
    tracing.install(path)
    try:
        _, records = run_mesh_federation(
            round_fn, _init_vars(), _fresh_data_fn(), SPAN_ROUNDS, mesh
        )
    finally:
        tracing.uninstall()
    spans = tracing.read_spans(path)
    gap_shares = collections.defaultdict(list)  # span name -> (counter - span) / wall, one a round
    for r, rec in enumerate(records):
        in_trace = [s for s in spans if s["trace"] == f"round-{r}"]
        in_round = [s for s in in_trace if s["name"] not in STAGE_SPANS]
        by_name = {s["name"]: s for s in in_round}
        last = r + 1 == SPAN_ROUNDS
        staging = [s for s in in_trace if s["name"] in STAGE_SPANS]
        if last:
            assert staging == []
        else:
            # In the order they ran: the put, then a wait a device in mesh order.
            assert [s["name"] for s in staging] == ["driver.stage.put"] + ["driver.stage.land"] * mesh.devices.size
            assert [s["device"] for s in staging[1:]] == [d.id for d in mesh.devices.flat]
            stage = by_name["driver.stage"]
            put, lands = staging[0], staging[1:]
            for child in staging:
                assert child["parent"] == stage["span"]
                assert stage["t"] <= child["t"]
                assert child["t"] + child["dur_s"] <= stage["t"] + stage["dur_s"] + ROUNDING
            # One measurement, two sinks: the record's clock encloses the span.
            assert put["dur_s"] <= rec.stage["put_s"] + ROUNDING
            for land, stamp in zip(lands, rec.stage["land_s"]):
                assert land["t"] + land["dur_s"] - put["t"] <= stamp + ROUNDING
        # One trace a round, one span of each name in it; the last round
        # stages nothing: no feed, no stage span.
        expected = DRIVER_SPANS | {"driver.handoff"}
        if last:
            expected = expected - {"driver.feed", "driver.stage"}
        assert sorted(s["name"] for s in in_round) == sorted(expected)
        parent = by_name["driver.round"]
        wall = rec.wall_clock_s
        assert parent["parent"] is None
        assert parent["wall_s"] == pytest.approx(wall, abs=ROUNDING)
        # The wall's clock starts before the span opens and stops before it
        # closes: either may be the longer.
        gap_shares["driver.round"].append(abs(parent["dur_s"] - wall) / wall)
        for name in by_name.keys() - {"driver.round", "driver.handoff"}:
            child = by_name[name]
            assert child["parent"] == parent["span"], name
            # Same monotonic clock, strictly nested: only rounding is allowed.
            assert parent["t"] <= child["t"], name
            assert child["t"] + child["dur_s"] <= parent["t"] + parent["dur_s"] + ROUNDING, name
            # One measurement, two sinks: the counter encloses the span.
            counter = rec.host_s[name.split(".")[1]]
            assert child["dur_s"] <= counter + ROUNDING, (name, r)
            gap_shares[name].append((counter - child["dur_s"]) / wall)
        # The handoff follows the round it is named after and is counted in
        # the next round's record.
        handoff = by_name["driver.handoff"]
        assert handoff["parent"] is None
        assert handoff["t"] >= parent["t"] + parent["dur_s"] - ROUNDING
        if not last:
            counter = records[r + 1].host_s["handoff"]
            assert handoff["dur_s"] <= counter + ROUNDING, r
            gap_shares["driver.handoff"].append((counter - handoff["dur_s"]) / wall)
    assert set(gap_shares) == DRIVER_SPANS | {"driver.handoff"}
    for name, shares in gap_shares.items():
        assert min(shares) <= SPAN_GAP_SHARE, (name, shares)


def test_profiler_trace_holds_the_driver_spans_on_the_host_plane(round_fn_and_mesh, tmp_path):
    """Under a jax.profiler session the same spans land on /host:CPU, on the
    profiler's clock: driver.round encloses its four phases."""
    import glob

    round_fn, mesh = round_fn_and_mesh
    run_mesh_federation(round_fn, _init_vars(), _fresh_data_fn(), 1, mesh)  # warm
    jax.profiler.start_trace(str(tmp_path))
    try:
        run_mesh_federation(round_fn, _init_vars(), _fresh_data_fn(), 2, mesh)
    finally:
        jax.profiler.stop_trace()
    (pb,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    profile = jax.profiler.ProfileData.from_file(pb)
    events = [
        (e.name, e.start_ns, e.start_ns + e.duration_ns)
        for plane in profile.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events if e.name.startswith("driver.")
    ]
    names = {name for name, _, _ in events}
    assert DRIVER_SPANS | STAGE_SPANS | {"driver.handoff"} <= names
    rounds = sorted((lo, hi) for name, lo, hi in events if name == "driver.round")
    assert len(rounds) == 2
    lo, hi = rounds[0]
    for phase in DRIVER_SPANS - {"driver.round"}:
        inside = [(a, b) for name, a, b in events if name == phase and lo <= a and b <= hi]
        assert len(inside) == 1, phase
    # The staging's own spans lie inside the round's driver.stage.
    (lo, hi), = [(a, b) for name, a, b in events if name == "driver.stage" and lo <= a and b <= hi]
    inside = [name for name, a, b in events if name in STAGE_SPANS and lo <= a and b <= hi]
    assert sorted(inside) == ["driver.stage.land"] * mesh.devices.size + ["driver.stage.put"]


def test_none_data_reuses_buffers(round_fn_and_mesh):
    """data_fn returning None after round 0 must train on the same staged
    shard every round — equal to a data_fn that re-returns the same arrays."""
    round_fn, mesh = round_fn_and_mesh
    fixed = _fresh_data_fn()(0)

    v_reuse, rec_reuse = run_mesh_federation(
        round_fn, _init_vars(), lambda r: fixed if r == 0 else None, ROUNDS, mesh
    )
    v_reship, _ = run_mesh_federation(
        round_fn, _init_vars(), lambda r: fixed, ROUNDS, mesh
    )
    _assert_trees_equal(v_reuse, v_reship)
    # Only the first round shipped bytes; no round after it overlapped
    # (there was nothing to stage).
    assert rec_reuse[0].staged_bytes > 0
    assert all(r.staged_bytes == 0 for r in rec_reuse[1:])
    assert all(not r.overlapped for r in rec_reuse)


def test_on_round_hook_sees_every_round(round_fn_and_mesh):
    round_fn, mesh = round_fn_and_mesh
    seen = []

    def hook(record, variables):
        # The hook's variables are the round's output, still usable on
        # device: a metrics sink / checkpointer can device_get them.
        loss = float(np.asarray(record.metrics["loss"])[0])
        seen.append((record.round_idx, loss, variables))

    final_vars, records = run_mesh_federation(
        round_fn, _init_vars(), _fresh_data_fn(), ROUNDS, mesh, on_round=hook
    )
    assert [s[0] for s in seen] == list(range(ROUNDS))
    assert len(records) == ROUNDS
    assert all(np.isfinite(s[1]) for s in seen)
    # The hook sees each round's OUTPUT: the last hook call's variables are
    # exactly what the driver returns as the final global model.
    _assert_trees_equal(seen[-1][2], final_vars)
    # And the rounds actually chain: consecutive hook variables differ.
    l0 = jax.tree_util.tree_leaves(seen[0][2])
    l1 = jax.tree_util.tree_leaves(seen[1][2])
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(l0, l1)
    )


def test_cohort_change_between_rounds(round_fn_and_mesh):
    """data_fn can shrink the cohort mid-federation (a client drops out):
    the masked psum divisor follows the new active mask, no recompilation."""
    round_fn, mesh = round_fn_and_mesh
    base = _fresh_data_fn()

    def data_fn(r):
        images, masks, active, n_samples = base(r)
        if r >= 1:
            active = active.copy()
            active[1] = 0.0  # client 1 silent from round 1 on
        return images, masks, active, n_samples

    v, records = run_mesh_federation(round_fn, _init_vars(), data_fn, 2, mesh)
    assert len(records) == 2
    assert list(records[1].metrics["active"]) == [1.0, 0.0]
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree_util.tree_leaves(v))


def test_first_round_data_required(round_fn_and_mesh):
    round_fn, mesh = round_fn_and_mesh
    with pytest.raises(ValueError, match="first round has no data"):
        run_mesh_federation(round_fn, _init_vars(), lambda r: None, 1, mesh)
    with pytest.raises(ValueError, match="n_rounds"):
        run_mesh_federation(round_fn, _init_vars(), _fresh_data_fn(), 0, mesh)


# Tier-1 budget re-balance (round 14, r4/r9/r12/r13 precedent): the
# spatial round PROGRAM's numerics stay tier-1 in test_spatial +
# test_parallel; this is the driver-integration twin (~16 s of spatial
# compiles) and the driver loop itself is tier-1-pinned by six other
# tests in this module.
@pytest.mark.slow
def test_driver_drives_spatial_federated_round():
    """The driver's ``image_spec`` parameter composes with the
    spatially-sharded round builder: a Mesh(('clients','space')) federation
    where each client's fit is halo-exchange sharded over image height,
    driven for 2 rounds with per-round restaging."""
    import jax
    from jax.sharding import PartitionSpec as P

    from fedcrack_tpu.parallel import build_spatial_federated_round, make_mesh
    from fedcrack_tpu.train.local import create_train_state

    n_clients, n_space, steps, batch = 2, 2, 2, 2
    # H=32 satisfies the 16 x n_space divisibility contract.
    cfg = ModelConfig(
        img_size=32, stem_features=4, encoder_features=(8,), decoder_features=(8, 4)
    )
    mesh = make_mesh(n_clients, n_space, axis_names=("clients", "space"))
    round_fn = build_spatial_federated_round(
        mesh, cfg, learning_rate=1e-3, local_epochs=1
    )
    spec = P("clients", None, None, "space")

    def data_fn(r):
        per_client = [
            synth_crack_batch(steps * batch, img_size=32, seed=40 + 10 * r + i)
            for i in range(n_clients)
        ]
        images, masks = stack_client_data(per_client, steps, batch)
        active = np.ones(n_clients, np.float32)
        n_samples = np.full(n_clients, float(steps * batch), np.float32)
        return images, masks, active, n_samples

    tmpl = create_train_state(jax.random.key(0), cfg)
    variables, records = run_mesh_federation(
        round_fn, tmpl.variables, data_fn, 2, mesh, image_spec=spec
    )
    assert len(records) == 2
    assert records[0].overlapped and not records[1].overlapped
    for rec in records:
        assert np.isfinite(rec.metrics["loss"]).all()
    assert all(
        np.isfinite(np.asarray(l)).all() for l in jax.tree_util.tree_leaves(variables)
    )


@pytest.mark.slow
def test_mesh_program_reaches_absolute_iou_floor():
    """Quality THROUGH the mesh program (round-3 verdict item 4): every
    earlier quality number flowed through the host plane, with the mesh rows
    borrowing IoU via the bit-equality cross-check. Here the flagship
    artifact itself — ``build_federated_round``'s output, driven by
    ``run_mesh_federation`` — must land at held-out IoU >= 0.35 after
    3 rounds, the same calibrated floor as the host-plane twin
    (test_train.py::test_federated_reaches_absolute_iou_floor; calibrated on
    CPU in round 3: rounds read 0.42 / 0.50 / 0.48). 2 clients x 1 device on
    the virtual mesh (the other 6 devices stay idle — collectives spin-wait
    on this 1-core host, and a 2-device program halves that contention)."""
    import jax

    from fedcrack_tpu.data.pipeline import ArrayDataset
    from fedcrack_tpu.train.local import (
        create_train_state,
        evaluate,
        recalibrate_batch_stats,
    )

    model_cfg = ModelConfig(img_size=64)
    steps, batch, n_clients, rounds = 6, 8, 2, 3
    mesh = make_mesh(n_clients, 1)
    round_fn = build_federated_round(
        mesh, model_cfg, learning_rate=1e-3, local_epochs=3, pos_weight=5.0
    )
    pools = [
        synth_crack_batch(steps * batch, 64, seed=10 + i, min_thickness=3)
        for i in range(n_clients)
    ]
    rngs = [np.random.default_rng(100 + i) for i in range(n_clients)]
    active = np.ones(n_clients, np.float32)
    n_samples = np.full(n_clients, float(steps * batch), np.float32)

    def data_fn(r):
        # Fresh per-round shuffle of each client's fixed pool (the host twin
        # reshuffles per epoch via ArrayDataset; per round is the mesh
        # plane's granularity — batches inside a round are a scan).
        parts = [
            shuffled_epoch_data(p[0], p[1], steps, batch, rng)
            for p, rng in zip(pools, rngs)
        ]
        images = np.concatenate([x[0] for x in parts])
        masks = np.concatenate([x[1] for x in parts])
        return images, masks, active, n_samples

    tmpl = create_train_state(jax.random.key(0), model_cfg)
    variables, records = run_mesh_federation(
        round_fn, tmpl.variables, data_fn, rounds, mesh
    )

    # Train-mode IoU (final local epoch, cohort mean) must improve across
    # rounds — the federation is learning, not just averaging.
    mean_iou = [float(np.mean(r.metrics["iou"])) for r in records]
    assert mean_iou[-1] > mean_iou[0], f"no IoU improvement: {mean_iou}"

    # Held-out absolute floor on the aggregated global model, BN-recalibrated
    # (the server's eval path), at the training pos_weight.
    ev_i, ev_m = synth_crack_batch(32, 64, seed=999, min_thickness=3)
    eval_ds = ArrayDataset(ev_i, ev_m, batch_size=8, shuffle=False, drop_last=False)
    st = tmpl.replace_variables(jax.device_get(variables))
    st = recalibrate_batch_stats(st, eval_ds, model_cfg)
    m = evaluate(st, eval_ds, pos_weight=5.0)
    assert m["iou"] >= 0.35, (
        f"mesh-program federated held-out IoU {m['iou']:.3f} under the 0.35 floor "
        f"(train IoU trajectory {mean_iou})"
    )


def test_shuffled_epoch_data_layout():
    rng = np.random.default_rng(0)
    pool_i, pool_m = synth_crack_batch(10, img_size=16, seed=0)
    images, masks = shuffled_epoch_data(pool_i, pool_m, steps=2, batch_size=4, rng=rng)
    assert images.shape == (1, 2, 4, 16, 16, 3)
    assert masks.shape == (1, 2, 4, 16, 16, 1)
    # Samples are drawn without replacement from the pool.
    flat = images.reshape(8, -1)
    pool_flat = pool_i.reshape(10, -1)
    matches = (flat[:, None, :] == pool_flat[None, :, :]).all(-1)
    assert (matches.sum(axis=1) == 1).all()
    assert matches.any(axis=0).sum() == 8  # 8 distinct pool rows used
    with pytest.raises(ValueError, match="pool has"):
        shuffled_epoch_data(pool_i, pool_m, steps=4, batch_size=4, rng=rng)
