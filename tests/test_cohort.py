"""Cohort scale (round 13): time-multiplexed mesh groups + the tree.

The two non-negotiable gates:

1. **Grouped == single-group, BITWISE.** A cohort executed as ceil(C/G)
   sequential groups over a narrower mesh must reproduce the single-group
   C-wide round byte for byte — weights AND metrics — because the
   aggregation is an ordered client fold (one expression tree regardless
   of the split), not a psum (whose reduction order is backend-defined
   and does NOT compose across groups; measured in fedavg_mesh).
2. **The tree closes a 1,024-simulated-client round at O(fan-in) root
   memory**, every tier routing uploads through the shared
   decode_and_validate_update gate, trajectory bit-reproducible from the
   cohort seed.
"""

import hashlib
import os

import jax
import numpy as np
import pytest

from fedcrack_tpu.configs import ModelConfig
from fedcrack_tpu.data.pipeline import SamplePool
from fedcrack_tpu.data.synthetic import synth_crack_batch
from fedcrack_tpu.fed import rounds as R
from fedcrack_tpu.fed.algorithms import fedavg, sample_cohort
from fedcrack_tpu.fed.serialization import tree_from_bytes, tree_to_bytes
from fedcrack_tpu.fed.tree import (
    EdgeAggregator,
    partition_cohort,
    run_tree_federation,
)
from fedcrack_tpu.parallel import (
    CohortRound,
    build_federated_cohort_round,
    build_federated_round,
    make_mesh,
    run_cohort_federation,
    stack_client_data,
)
from fedcrack_tpu.train.local import create_train_state

TINY = ModelConfig(
    img_size=16, stem_features=4, encoder_features=(8,), decoder_features=(8, 4)
)
STEPS, BATCH, COHORT_C, EPOCHS = 2, 4, 4, 2


@pytest.fixture(scope="module")
def cohort_data():
    per_client = [
        synth_crack_batch(STEPS * BATCH, img_size=TINY.img_size, seed=i)
        for i in range(COHORT_C)
    ]
    images, masks = stack_client_data(per_client, STEPS, BATCH)
    active = np.ones(COHORT_C, np.float32)
    # Distinct weights so the sample-weighted fold is load-bearing.
    n_samples = np.array([8.0, 16.0, 8.0, 24.0], np.float32)
    return images, masks, active, n_samples


@pytest.fixture(scope="module")
def variables():
    return create_train_state(jax.random.key(0), TINY).variables


@pytest.fixture(scope="module")
def oracle_result(cohort_data, variables):
    """The single-group mesh round over the full C-wide cohort — the
    byte-identity oracle for every group split."""
    mesh = make_mesh(COHORT_C, 1)
    round_fn = build_federated_round(
        mesh, TINY, learning_rate=1e-3, local_epochs=EPOCHS
    )
    new_vars, metrics = round_fn(variables, *cohort_data)
    return (
        jax.tree_util.tree_map(np.asarray, new_vars),
        jax.tree_util.tree_map(np.asarray, metrics),
    )


@pytest.fixture(scope="module")
def cohort_round_g2():
    """The flagship grouped build: G=2 mesh, 2 groups, segments=2 (the
    'with segments > 0' arm of the acceptance pin), shared by the
    byte-identity test and the driver test."""
    mesh = make_mesh(2, 1)
    cr = build_federated_cohort_round(
        mesh, TINY, learning_rate=1e-3, local_epochs=EPOCHS, segments=2
    )
    return mesh, cr


def _assert_trees_bytes_equal(got, want):
    gl = jax.tree_util.tree_leaves_with_path(got)
    wl = jax.tree_util.tree_leaves(want)
    assert len(gl) == len(wl)
    for (path, g), w in zip(gl, wl):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w), err_msg=jax.tree_util.keystr(path)
        )


# groups=2 (the load-bearing split: a real carry crosses real group
# boundaries on a narrower mesh) stays tier-1; groups=1 (degenerate: one
# group on the C-wide mesh, isolating the partial/finish program split)
# and groups=4 (G=1: every client its own dispatch) are slow-marked —
# each group count is a fresh set of XLA compiles and the tier-1
# wall-clock budget is the binding constraint (r7 precedent).
@pytest.mark.parametrize(
    "n_groups",
    [
        pytest.param(1, marks=pytest.mark.slow),
        2,
        pytest.param(4, marks=pytest.mark.slow),
    ],
)
def test_grouped_round_byte_identical(
    cohort_data, variables, oracle_result, cohort_round_g2, n_groups
):
    """Time-multiplexed execution is byte-identical (weights AND metrics)
    to the single-group mesh round, for groups in {1, 2, 4}, with
    segments=2 > 0."""
    if n_groups == 2:
        mesh, cr = cohort_round_g2
    else:
        g = COHORT_C // n_groups
        mesh = make_mesh(g, 1)
        cr = build_federated_cohort_round(
            mesh, TINY, learning_rate=1e-3, local_epochs=EPOCHS, segments=2
        )
    assert isinstance(cr, CohortRound)
    assert cr.group_size == COHORT_C // n_groups
    assert cr.n_groups(COHORT_C) == n_groups
    new_vars, metrics = cr(variables, *cohort_data)
    _assert_trees_bytes_equal(new_vars, oracle_result[0])
    _assert_trees_bytes_equal(metrics, oracle_result[1])


def test_cohort_driver_per_group_staging(
    cohort_data, variables, oracle_result, cohort_round_g2
):
    """run_cohort_federation — per-group staged slabs, explicit release,
    group timeline — reproduces the direct __call__ (and therefore the
    single-group oracle) byte for byte, and never holds more than ~2
    group slices of staged data."""
    mesh, cr = cohort_round_g2
    data_fn = lambda r: cohort_data
    out_vars, records = run_cohort_federation(cr, variables, data_fn, 1, mesh)
    _assert_trees_bytes_equal(out_vars, oracle_result[0])
    for k, leaf in records[0].metrics.items():
        np.testing.assert_array_equal(leaf, oracle_result[1][k], err_msg=k)
    rec = records[0]
    assert rec.host_s == rec.stage == rec.device_memory == rec.proc == {}  # the monolithic round's
    assert len(rec.segments) == 2  # ceil(4/2) group dispatches
    assert all(e["staged_bytes"] > 0 for e in rec.segments)
    group_bytes = rec.segments[0]["staged_bytes"]
    assert rec.staged_bytes == sum(e["staged_bytes"] for e in rec.segments)
    # 2-group-slice peak: group g+1 staged under group g, never a third.
    assert 0 < rec.max_live_staged_bytes <= 2 * group_bytes
    assert rec.max_live_staged_bytes == 2 * group_bytes


def test_cohort_driver_round_overlap_bit_identical(
    cohort_data, variables, cohort_round_g2
):
    """Round-overlap (round 14): overlapping round N+1's data/first-group
    staging AND first-group dispatch with round N's aggregation tail is
    pure host scheduling — weights and metrics byte-identical to the
    unoverlapped schedule, with the pipelined group visible in the
    consuming round's timeline."""
    mesh, cr = cohort_round_g2
    data_fn = lambda r: cohort_data
    v_plain, rec_plain = run_cohort_federation(cr, variables, data_fn, 2, mesh)
    v_pipe, rec_pipe = run_cohort_federation(
        cr, variables, data_fn, 2, mesh, round_overlap=True
    )
    _assert_trees_bytes_equal(v_pipe, v_plain)
    for rp, rq in zip(rec_plain, rec_pipe):
        for k, leaf in rq.metrics.items():
            np.testing.assert_array_equal(leaf, rp.metrics[k], err_msg=k)
    assert [e["group"] for e in rec_pipe[1].segments if e.get("pipelined")] == [0]
    assert not any(e.get("pipelined") for e in rec_pipe[0].segments)
    # The pipelined round still stages/accounts every group.
    assert rec_pipe[1].staged_bytes == rec_plain[1].staged_bytes


@pytest.mark.slow
def test_grouped_round_pads_ragged_cohort(variables):
    """C=3 on a G=2 mesh: the last group pads with an inactive zero-weight
    client — a bitwise no-op in the ordered fold — and the result equals
    the 3-wide single-group round exactly (weights and the [3] metrics)."""
    per_client = [
        synth_crack_batch(STEPS * BATCH, img_size=TINY.img_size, seed=10 + i)
        for i in range(3)
    ]
    images, masks = stack_client_data(per_client, STEPS, BATCH)
    active = np.ones(3, np.float32)
    n_samples = np.array([8.0, 16.0, 24.0], np.float32)
    mesh3 = make_mesh(3, 1)
    oracle = build_federated_round(
        mesh3, TINY, learning_rate=1e-3, local_epochs=EPOCHS
    )
    want_v, want_m = oracle(variables, images, masks, active, n_samples)
    mesh2 = make_mesh(2, 1)
    cr = build_federated_cohort_round(
        mesh2, TINY, learning_rate=1e-3, local_epochs=EPOCHS, segments=1
    )
    assert cr.n_groups(3) == 2
    got_v, got_m = cr(variables, images, masks, active, n_samples)
    _assert_trees_bytes_equal(got_v, want_v)
    _assert_trees_bytes_equal(got_m, want_m)
    assert np.asarray(got_m["loss"]).shape == (3,)


@pytest.mark.slow
def test_cohort_round_resident_pool_matches_streamed(cohort_data, variables):
    """The resident cohort plane — per-group pool slices + gather plans —
    is byte-identical to the streamed grouped round over pool[idx] (the
    r9 contract, generalized to group grain), through the driver's
    per-group stage/release path."""
    images, masks, active, n_samples = cohort_data
    # Pool = the slab's samples, per client; the plan re-draws exactly the
    # slab layout so streamed and resident consume identical bytes.
    pool = SamplePool(
        images.reshape(COHORT_C, STEPS * BATCH, *images.shape[3:]),
        masks.reshape(COHORT_C, STEPS * BATCH, *masks.shape[3:]),
    )
    idx = np.broadcast_to(
        np.arange(STEPS * BATCH, dtype=np.int32).reshape(1, 1, STEPS, BATCH),
        (COHORT_C, EPOCHS, STEPS, BATCH),
    )
    mesh = make_mesh(2, 1)
    streamed = build_federated_cohort_round(
        mesh, TINY, learning_rate=1e-3, local_epochs=EPOCHS, segments=1
    )
    want_v, want_m = streamed(variables, *cohort_data)
    resident = build_federated_cohort_round(
        mesh,
        TINY,
        learning_rate=1e-3,
        local_epochs=EPOCHS,
        segments=1,
        data_placement="resident",
    )
    got_v, got_m = resident(
        variables, (pool.images, pool.masks), idx, active, n_samples
    )
    _assert_trees_bytes_equal(got_v, want_v)
    _assert_trees_bytes_equal(got_m, want_m)
    # And through the driver, with per-group pool staging.
    data_fn = lambda r: (idx, active, n_samples)
    drv_v, records = run_cohort_federation(
        resident, variables, data_fn, 1, mesh, sample_pool=pool
    )
    _assert_trees_bytes_equal(drv_v, want_v)
    assert records[0].data_placement == "resident"
    assert all(e["staged_bytes"] > 0 for e in records[0].segments)


def test_cohort_driver_contract_mismatches(cohort_round_g2, variables):
    mesh, cr = cohort_round_g2
    pool = SamplePool(
        np.zeros((2, 4, 16, 16, 3), np.uint8), np.zeros((2, 4, 16, 16, 1), np.uint8)
    )
    with pytest.raises(ValueError, match="streamed"):
        run_cohort_federation(
            cr, variables, lambda r: None, 1, mesh, sample_pool=pool
        )
    with pytest.raises(ValueError, match="positive"):
        run_cohort_federation(cr, variables, lambda r: None, 0, mesh)


# ---------- seeded cohort sampling + partitioning ----------


def test_partition_cohort_deterministic_and_complete():
    cohort = sample_cohort(1000, 100, 3, seed=9)
    shards = partition_cohort(cohort, 8)
    assert len(shards) == 8
    flat = np.concatenate(shards)
    np.testing.assert_array_equal(flat, cohort)
    shards2 = partition_cohort(cohort, 8)
    for a, b in zip(shards, shards2):
        np.testing.assert_array_equal(a, b)
    # More edges than leaves: degenerate split, no empty shards.
    small = partition_cohort([1, 2], 8)
    assert [len(s) for s in small] == [1, 1]
    with pytest.raises(ValueError, match="n_edges"):
        partition_cohort(cohort, 0)


# ---------- the hierarchical aggregation tree ----------


def _vars(value: float):
    return {"params": {"w": np.full((4, 4), value, np.float32)}}


def _make_update(idx, r, base_blob, base_version):
    rng = np.random.default_rng([11, idx, r])
    base = tree_from_bytes(base_blob)
    tree = {
        "params": {
            "w": np.asarray(base["params"]["w"], np.float32)
            + rng.standard_normal((4, 4)).astype(np.float32) * 0.01
        }
    }
    return tree_to_bytes(tree), int(rng.integers(1, 50))


def test_tree_1024_clients_closes_at_fan_in_memory():
    """THE cohort-scale smoke: a 1,024-simulated-client round closes
    through a 2-level tree with root peak resident update blobs <= fan-in,
    and the whole trajectory is bit-reproducible from the cohort seed."""
    kwargs = dict(
        n_clients=4096,
        cohort_size=1024,
        n_rounds=2,
        n_edges=32,
        cohort_seed=5,
    )
    res = run_tree_federation(_vars(0.0), _make_update, **kwargs)
    assert res.state.phase == R.PHASE_FINISHED
    assert res.root_peak_blobs <= res.n_edges == 32
    assert res.edge_peak_blobs <= res.max_leaf_fan_in == 32
    assert res.leaf_updates == 2048 and res.leaf_rejections == 0
    # The whole point: root wire traffic is fan-in-sized, not cohort-sized.
    assert res.bytes_at_root < res.bytes_flat_equiv / 8
    res2 = run_tree_federation(_vars(0.0), _make_update, **kwargs)
    assert res.global_sha256 == res2.global_sha256
    assert res.cohorts == res2.cohorts
    # A different seed is a different trajectory (the seed is load-bearing).
    res3 = run_tree_federation(
        _vars(0.0), _make_update, **{**kwargs, "cohort_seed": 6}
    )
    assert res3.global_sha256 != res.global_sha256


def test_tree_matches_flat_fedavg():
    """One tree round == the flat sample-weighted FedAvg over the same
    cohort (weighted-mean associativity), to float re-association."""
    res = run_tree_federation(
        _vars(0.0),
        _make_update,
        n_clients=256,
        cohort_size=64,
        n_rounds=1,
        n_edges=8,
        cohort_seed=3,
    )
    cohort = sample_cohort(256, 64, 0, 3)
    base_blob = tree_to_bytes(_vars(0.0))
    trees, counts = [], []
    for i in cohort:
        blob, ns = _make_update(int(i), 0, base_blob, 0)
        trees.append(tree_from_bytes(blob))
        counts.append(ns)
    flat = fedavg(trees, counts)
    got = tree_from_bytes(res.state.global_blob)["params"]["w"]
    np.testing.assert_allclose(
        got, np.asarray(flat["params"]["w"]), rtol=0, atol=1e-6
    )


def test_edge_sanitizes_every_leaf_update():
    """Every tier routes through the shared acceptance gate: a NaN update,
    a wrong-shape tree and a truncated blob are all rejected AT THE EDGE
    (recorded, never averaged), and the partial equals the weighted mean
    of the clean leaves only."""
    template = tree_from_bytes(tree_to_bytes(_vars(0.0)))
    edge = EdgeAggregator("edge-0", template, quorum_fraction=0.5)
    edge.begin_round(1, tree_to_bytes(_vars(0.0)), 0, ["a", "b", "nan", "shape", "trunc"])
    assert edge.offer("a", tree_to_bytes(_vars(1.0)), 10)[0]
    bad_nan = {"params": {"w": np.full((4, 4), np.nan, np.float32)}}
    ok, reason = edge.offer("nan", tree_to_bytes(bad_nan), 10)
    assert not ok and "non-finite" in reason
    bad_shape = {"params": {"w": np.zeros((2, 2), np.float32)}}
    ok, reason = edge.offer("shape", tree_to_bytes(bad_shape), 10)
    assert not ok and "shape" in reason
    blob = tree_to_bytes(_vars(9.0))
    ok, reason = edge.offer("trunc", blob[: len(blob) // 2], 10)
    assert not ok and "undecodable" in reason
    ok, reason = edge.offer("outsider", tree_to_bytes(_vars(5.0)), 10)
    assert not ok and "not in this edge's shard" in reason
    assert edge.offer("b", tree_to_bytes(_vars(3.0)), 30)[0]
    assert not edge.quorum_met()  # 2 accepted < ceil(0.5 * 5) = 3
    assert sorted(edge.rejected) == ["nan", "shape", "trunc"]
    partial, total = edge.partial()
    got = tree_from_bytes(partial)["params"]["w"]
    np.testing.assert_allclose(got, (10 * 1.0 + 30 * 3.0) / 40, atol=1e-6)
    assert total == 40


def test_edge_quorum_is_k_of_n():
    template = tree_from_bytes(tree_to_bytes(_vars(0.0)))
    edge = EdgeAggregator("e", template, quorum_fraction=0.5)
    edge.begin_round(1, tree_to_bytes(_vars(0.0)), 0, ["a", "b", "c", "d"])
    assert edge.quorum == 2
    assert not edge.quorum_met()
    edge.offer("a", tree_to_bytes(_vars(1.0)), 1)
    assert not edge.quorum_met()
    edge.offer("b", tree_to_bytes(_vars(2.0)), 1)
    assert edge.quorum_met()


def test_edge_statefile_kill_restart_resumes_round(tmp_path):
    """An edge killed mid-round resumes the SAME round from its statefile:
    already-received updates intact, base preserved, and the completed
    partial is EXACTLY what the unkilled edge would have produced."""
    template = tree_from_bytes(tree_to_bytes(_vars(0.0)))
    path = str(tmp_path / "edge.msgpack")
    edge = EdgeAggregator("edge-7", template, state_path=path)
    base = tree_to_bytes(_vars(0.0))
    edge.begin_round(3, base, 2, ["a", "b", "c"])
    edge.offer("a", tree_to_bytes(_vars(1.0)), 10)
    edge.offer("b", tree_to_bytes(_vars(2.0)), 10)
    del edge  # the kill

    restored = EdgeAggregator.restore(path, template)
    assert restored is not None
    assert restored.edge_id == "edge-7"
    assert restored.round == 3 and restored.base_version == 2
    assert sorted(restored.received) == ["a", "b"]
    assert restored.leaves == frozenset({"a", "b", "c"})
    restored.offer("c", tree_to_bytes(_vars(6.0)), 20)
    partial, total = restored.partial()
    clean = EdgeAggregator("edge-7", template)
    clean.begin_round(3, base, 2, ["a", "b", "c"])
    clean.offer("a", tree_to_bytes(_vars(1.0)), 10)
    clean.offer("b", tree_to_bytes(_vars(2.0)), 10)
    clean.offer("c", tree_to_bytes(_vars(6.0)), 20)
    want, want_total = clean.partial()
    assert partial == want and total == want_total
    # Missing / corrupt statefiles degrade to None, never raise.
    assert EdgeAggregator.restore(str(tmp_path / "nope"), template) is None
    with open(path, "wb") as f:
        f.write(b"garbage")
    assert EdgeAggregator.restore(path, template) is None


def test_tree_with_compressed_edge_hop():
    """Edge→root re-encoding with the r12 codecs: the partial crosses as a
    CRC'd delta frame the root's existing frame decode + sanitation
    accepts, and the frame is smaller than the dense partial."""
    res = run_tree_federation(
        _vars(0.0),
        _make_update,
        n_clients=64,
        cohort_size=16,
        n_rounds=2,
        n_edges=4,
        cohort_seed=1,
        update_codec="int8",
    )
    assert res.state.phase == R.PHASE_FINISHED
    for entry in res.state.history:
        # The root saw FRAMES (codec recorded per edge) and accounted the
        # wire bytes separately from the decoded reconstruction. (On this
        # toy 4x4 tree the frame manifest outweighs the payload, so no
        # size inequality is asserted — the >=10x ratio at model scale is
        # test_compress territory.)
        assert set(entry["codecs"].values()) == {"int8"}
        assert entry["bytes_received"] != entry["decoded_bytes_received"]
        assert entry["rejected"] == {}
    # Same federation, null codec: trajectories agree loosely (int8 is
    # quantized) but both close and reproduce deterministically.
    dense = run_tree_federation(
        _vars(0.0),
        _make_update,
        n_clients=64,
        cohort_size=16,
        n_rounds=2,
        n_edges=4,
        cohort_seed=1,
    )
    a = tree_from_bytes(res.state.global_blob)["params"]["w"]
    b = tree_from_bytes(dense.state.global_blob)["params"]["w"]
    np.testing.assert_allclose(a, b, atol=0.05)


def test_tree_statefiles_per_tier(tmp_path):
    """state_dir arms one statefile per edge; mid-federation they exist
    and restore."""
    res = run_tree_federation(
        _vars(0.0),
        _make_update,
        n_clients=32,
        cohort_size=8,
        n_rounds=1,
        n_edges=2,
        cohort_seed=2,
        state_dir=str(tmp_path),
    )
    assert res.state.phase == R.PHASE_FINISHED
    for e in range(2):
        path = os.path.join(str(tmp_path), f"edge-{e}.msgpack")
        assert os.path.exists(path)
        template = tree_from_bytes(tree_to_bytes(_vars(0.0)))
        restored = EdgeAggregator.restore(path, template)
        assert restored is not None and restored.edge_id == f"edge-{e}"


def test_edge_crash_drill_end_to_end():
    """tools/chaos_drill.run_edge_crash_drill: the scripted mid-round edge
    kill→restart against a REAL gRPC root — statefile resume, quorum
    close, exact recovered averages, fault recorded by the chaos plan."""
    from fedcrack_tpu.tools.chaos_drill import run_edge_crash_drill

    out = run_edge_crash_drill()
    assert out["fault_fired"]
    assert out["resumed_mid_round"]
    assert out["edge_partial_exact"]
    assert out["root_round_closed"]
    assert out["root_avg_exact"]
    assert out["root_clients"] == ["edge-0", "edge-1"]


def test_grouped_weights_stable_fingerprint(cohort_data, variables, cohort_round_g2):
    """Belt-and-suspenders determinism: two runs of the same grouped round
    produce identical bytes (no hidden RNG/state in the group loop)."""
    mesh, cr = cohort_round_g2
    v1, _ = cr(variables, *cohort_data)
    v2, _ = cr(variables, *cohort_data)
    s1 = hashlib.sha256(tree_to_bytes(jax.device_get(v1))).hexdigest()
    s2 = hashlib.sha256(tree_to_bytes(jax.device_get(v2))).hexdigest()
    assert s1 == s2


def test_tree_rejects_fewer_leaves_than_edges():
    """cohort_size < n_edges is a misconfiguration (some edges would have
    no shard and the root barrier could never close) — a ValueError at
    entry, not an IndexError mid-round (review fix)."""
    with pytest.raises(ValueError, match="cohort_size"):
        run_tree_federation(
            _vars(0.0),
            _make_update,
            n_clients=8,
            cohort_size=2,
            n_rounds=1,
            n_edges=4,
        )


def test_edge_codec_instance_survives_rounds():
    """The edge's upload codec lives for the EDGE's lifetime, like the leaf
    client's: topk_delta's error-feedback residual is cross-round state — a
    per-round codec would drop every round's unsent delta mass forever
    (review fix)."""
    template = tree_from_bytes(tree_to_bytes(_vars(0.0)))
    edge = EdgeAggregator(
        "e", template, update_codec="topk_delta", topk_fraction=0.5
    )
    base = tree_to_bytes(_vars(0.0))
    edge.begin_round(1, base, 0, ["a"])
    edge.offer("a", tree_to_bytes(_vars(1.0)), 10)
    edge.partial()
    first = edge._codec
    assert first is not None
    edge.end_round()
    edge.begin_round(2, base, 1, ["a"])
    edge.offer("a", tree_to_bytes(_vars(2.0)), 10)
    edge.partial()
    assert edge._codec is first  # same instance — residual carried
