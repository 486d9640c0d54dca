"""Round 21: the aggregation algebra and its Byzantine-robust combines.

Three contract families:

1. **Null-instance bitwise pins** — the FedAvg algebra instance must be
   byte-identical to the historical direct ``fedavg`` fold on every plane
   that was rewritten through it (rounds barrier, buffered flush, edge
   partial, mesh ordered fold), including through the FedOpt server step
   (fedadam). "Refactor" means ZERO numeric drift.

2. **Robust combines, closed form** — trimmed-mean / coordinate-median /
   Krum / Multi-Krum against hand-computed 3–5 client cohorts, plus the
   properties that make them safe to deploy: client-reported weights are
   IGNORED (self-reported ``ns`` is attack surface), arrival order never
   changes a byte (canonical tie-breaks), selection returns trees
   VERBATIM.

3. **Ledger-coupled quarantine** — a robust-z-flagged update is excluded
   from the fold (not just flagged), the exclusion is visible in history
   + ledger, and the excluded flush-trigger is resynced with the direct
   ``NOT_WAIT`` + clean-weights reply that fires the client-side EF
   rollback — on both the sync barrier and the buffered flush.
"""

import itertools

import numpy as np
import pytest

from fedcrack_tpu.configs import FedConfig
from fedcrack_tpu.fed import aggregation as A
from fedcrack_tpu.fed import rounds as R
from fedcrack_tpu.fed.algorithms import (
    apply_server_opt,
    fedavg,
    make_server_optimizer,
)
from fedcrack_tpu.fed.serialization import tree_from_bytes, tree_to_bytes


def _tree(seed: int):
    rng = np.random.default_rng(seed)
    return {
        "params": {
            "w": rng.normal(size=(3, 4)).astype(np.float32),
            "b": rng.normal(size=(4,)).astype(np.float32),
        },
        "batch_stats": {"m": rng.normal(size=(2,)).astype(np.float32)},
    }


def _flat(value: float):
    return {"params": {"w": np.full((4, 4), value, np.float32)}}


# ---------- the algebra's null instance: bitwise FedAvg ----------

def test_fedavg_instance_bitwise_matches_primitive():
    trees = [_tree(s) for s in (1, 2, 3)]
    counts = [10, 30, 20]
    triples = list(zip(("a", "b", "c"), counts, trees))
    got = A.fold(A.FedAvg(), triples)
    want = fedavg(trees, counts)
    for g, w in zip(*(t["params"].values() for t in (got, want))):
        np.testing.assert_array_equal(g, w)
    np.testing.assert_array_equal(
        got["batch_stats"]["m"], want["batch_stats"]["m"]
    )


def test_fedavg_instance_zero_weights_degenerates_unweighted():
    # The historical gate: all-zero counts (edge pad cohorts) fall back to
    # the unweighted mean rather than dividing by zero.
    trees = [_tree(s) for s in (4, 5)]
    got = A.fold(A.FedAvg(), [("a", 0, trees[0]), ("b", 0, trees[1])])
    want = fedavg(trees, None)
    np.testing.assert_array_equal(got["params"]["w"], want["params"]["w"])


def test_fold_rejects_empty():
    with pytest.raises(ValueError):
        A.fold(A.FedAvg(), [])


# ---------- robust combines, closed form ----------

def test_trimmed_mean_closed_form():
    trees = [_flat(1.0), _flat(2.0), _flat(1000.0)]
    triples = list(zip("abc", (10, 10, 10), trees))
    got = A.fold(A.TrimmedMean(0.34), triples)  # k = floor(.34*3) = 1
    np.testing.assert_array_equal(got["params"]["w"], _flat(2.0)["params"]["w"])
    # beta=0 trims nothing: the plain unweighted mean.
    got0 = A.fold(A.TrimmedMean(0.0), triples)
    np.testing.assert_allclose(
        got0["params"]["w"], np.full((4, 4), (1.0 + 2.0 + 1000.0) / 3.0)
    )


def test_trimmed_mean_is_per_coordinate():
    # The trimmed tail differs per coordinate: each coordinate drops ITS
    # own extremes, not one global outlier client.
    t1 = {"w": np.array([0.0, 100.0], np.float32)}
    t2 = {"w": np.array([1.0, 1.0], np.float32)}
    t3 = {"w": np.array([100.0, 0.0], np.float32)}
    got = A.fold(A.TrimmedMean(0.34), [("a", 1, t1), ("b", 1, t2), ("c", 1, t3)])
    np.testing.assert_array_equal(got["w"], np.array([1.0, 1.0], np.float32))


def test_coordinate_median_closed_form():
    trees = [_flat(1.0), _flat(2.0), _flat(-1000.0)]
    got = A.fold(A.CoordinateMedian(), list(zip("abc", (1, 1, 1), trees)))
    np.testing.assert_array_equal(got["params"]["w"], _flat(1.0)["params"]["w"])


@pytest.mark.parametrize(
    "make",
    [
        lambda: A.TrimmedMean(0.34),
        lambda: A.CoordinateMedian(),
        lambda: A.Krum(1),
        lambda: A.Krum(1, multi=True),
    ],
)
def test_robust_combines_ignore_reported_weights(make):
    # A Byzantine client's self-reported sample count must buy it nothing.
    trees = [_flat(1.0), _flat(2.0), _flat(1000.0)]
    lo = A.fold(make(), list(zip("abc", (1, 1, 1), trees)))
    hi = A.fold(make(), list(zip("abc", (1, 1, 10**9), trees)))
    np.testing.assert_array_equal(lo["params"]["w"], hi["params"]["w"])


def test_krum_selects_honest_verbatim():
    honest = [_tree(1), _tree(2), _tree(3), _tree(4)]
    poisoned = {
        k: {n: a * 1000.0 for n, a in sub.items()}
        for k, sub in _tree(1).items()
    }
    triples = list(zip("abcde", (1, 1, 1, 1, 1), honest + [poisoned]))
    got = A.fold(A.Krum(1), triples)
    # Krum returns ONE submitted tree verbatim — bitwise, never a blend.
    assert any(
        all(
            np.array_equal(got[k][n], t[k][n])
            for k, sub in t.items()
            for n in sub
        )
        for t in honest
    )
    assert not np.array_equal(got["params"]["w"], poisoned["params"]["w"])


def test_krum_tiebreak_by_name_is_deterministic():
    # Two identical low-score candidates: the lexicographically-first name
    # wins, independent of arrival order.
    t = _flat(1.0)
    far = _flat(500.0)
    for perm in itertools.permutations([("b", 1, t), ("a", 1, t), ("z", 1, far)]):
        got = A.fold(A.Krum(1), list(perm))
        np.testing.assert_array_equal(got["params"]["w"], t["params"]["w"])


def test_multi_krum_closed_form():
    trees = [_flat(1.0), _flat(3.0), _flat(1000.0)]
    got = A.fold(A.Krum(1, multi=True), list(zip("abc", (7, 13, 10**6), trees)))
    # m = n - f = 2 survivors (the honest pair), UNWEIGHTED mean.
    np.testing.assert_allclose(got["params"]["w"], np.full((4, 4), 2.0))


def test_single_update_passthrough_every_combine():
    t = _tree(9)
    for name in A.AGGREGATIONS:
        cfg = _root_cfg(aggregation=name)
        got = A.fold(A.from_config(cfg), [("only", 5, t)])
        if name in ("krum", "multi_krum"):
            # Selection combines return the submitted tree VERBATIM.
            np.testing.assert_array_equal(got["params"]["w"], t["params"]["w"])
        else:
            # Mean-family combines run the arithmetic (weighted divide /
            # f32 stack) even at n=1 — value-identical, not bit-identical.
            np.testing.assert_allclose(
                got["params"]["w"], t["params"]["w"], rtol=1e-6
            )


def test_from_config_dispatch():
    assert isinstance(A.from_config(_root_cfg()), A.FedAvg)
    assert isinstance(
        A.from_config(_root_cfg(aggregation="trimmed_mean")), A.TrimmedMean
    )
    for alias in ("median", "coordinate_median"):
        assert isinstance(
            A.from_config(_root_cfg(aggregation=alias)), A.CoordinateMedian
        )
    krum = A.from_config(_root_cfg(aggregation="krum", byzantine_f=2))
    assert isinstance(krum, A.Krum) and not krum.multi and krum.byzantine_f == 2
    mk = A.from_config(_root_cfg(aggregation="multi_krum"))
    assert isinstance(mk, A.Krum) and mk.multi


# ---------- arrival-order independence for EVERY combine ----------

@pytest.mark.parametrize("aggregation", A.AGGREGATIONS)
def test_rounds_plane_arrival_order_independent(aggregation):
    """Permuted cross-client upload orders produce a BYTE-identical global
    under every combine — the sync barrier sorts by name before the fold,
    and the robust combines' internal orders are canonical."""
    def drive(order):
        cfg = _root_cfg(
            aggregation=aggregation, cohort_size=3, max_rounds=1
        )
        st = R.initial_state(cfg, _flat(0.0))
        now = 0.0
        for c in ("a", "b", "c"):
            now += 1e-3
            st, rep = R.transition(st, R.Ready(cname=c, now=now))
            assert rep.status == R.SW
        values = {"a": 1.0, "b": 1.2, "c": 1.1}
        ns = {"a": 10, "b": 30, "c": 20}
        for c in order:
            now += 1e-3
            st, _ = R.transition(
                st,
                R.TrainDone(
                    cname=c, round=1, blob=tree_to_bytes(_flat(values[c])),
                    num_samples=ns[c], now=now,
                ),
            )
        return st.global_blob

    blobs = {drive(order) for order in itertools.permutations("abc")}
    assert len(blobs) == 1


# ---------- null bitwise pins on the four planes ----------

def test_null_pin_rounds_plane_bitwise():
    cfg = _root_cfg(cohort_size=2, max_rounds=1)
    st = R.initial_state(cfg, _tree(0))
    for i, c in enumerate(("a", "b")):
        st, _ = R.transition(st, R.Ready(cname=c, now=0.1 * (i + 1)))
    st, _ = R.transition(
        st, R.TrainDone(cname="b", round=1, blob=tree_to_bytes(_tree(2)),
                        num_samples=30, now=1.0),
    )
    st, _ = R.transition(
        st, R.TrainDone(cname="a", round=1, blob=tree_to_bytes(_tree(1)),
                        num_samples=10, now=2.0),
    )
    got = tree_from_bytes(st.global_blob)
    # The seed fold: sorted-by-name trees, sample-count weights.
    want = fedavg([_tree(1), _tree(2)], [10, 30])
    np.testing.assert_array_equal(got["params"]["w"], want["params"]["w"])
    np.testing.assert_array_equal(got["params"]["b"], want["params"]["b"])


def test_null_pin_fedadam_sync_bitwise():
    """The algebra feeds the FedOpt server step unchanged: a fedadam round
    lands bit-identical to fedavg + apply_server_opt computed by hand."""
    cfg = _root_cfg(
        cohort_size=2, max_rounds=1, server_optimizer="fedadam",
        server_lr=0.1, server_momentum=0.9,
    )
    base = _tree(0)
    st = R.initial_state(cfg, base)
    for i, c in enumerate(("a", "b")):
        st, _ = R.transition(st, R.Ready(cname=c, now=0.1 * (i + 1)))
    for c, seed, ns in (("a", 1, 10), ("b", 2, 30)):
        st, _ = R.transition(
            st, R.TrainDone(cname=c, round=1, blob=tree_to_bytes(_tree(seed)),
                            num_samples=ns, now=1.0),
        )
    got = tree_from_bytes(st.global_blob)
    avg = fedavg([_tree(1), _tree(2)], [10, 30])
    tx = make_server_optimizer("fedadam", 0.1, 0.9)
    base_rt = tree_from_bytes(tree_to_bytes(base))  # the wire round-trip
    want, _ = apply_server_opt(
        base_rt["params"], avg["params"], tx, tx.init(base_rt["params"])
    )
    np.testing.assert_array_equal(got["params"]["w"], want["w"])
    # BN stats bypass the optimizer: plain average.
    np.testing.assert_array_equal(got["batch_stats"]["m"], avg["batch_stats"]["m"])


def test_null_pin_buffered_plane_bitwise():
    from fedcrack_tpu.fed.buffered import fold_buffer, staleness_weight

    buffer = tuple(
        {"cname": c, "seq": i, "blob": tree_to_bytes(_tree(s)), "ns": ns,
         "staleness": stale, "weight": staleness_weight(stale, 0.5)}
        for i, (c, s, ns, stale) in enumerate(
            (("b", 2, 30, 1), ("a", 1, 10, 0), ("c", 3, 20, 2))
        )
    )
    avg, entries, counts, eff, trees = fold_buffer(buffer, _tree(0))
    order = sorted(buffer, key=lambda e: (e["cname"], e["seq"]))
    want = fedavg(
        [tree_from_bytes(e["blob"], template=_tree(0)) for e in order],
        [e["ns"] * e["weight"] for e in order],
    )
    np.testing.assert_array_equal(avg["params"]["w"], want["params"]["w"])


def test_null_pin_edge_partial_bitwise():
    from fedcrack_tpu.fed.tree import EdgeAggregator

    edge = EdgeAggregator("e0", _tree(0))
    edge.begin_round(1, tree_to_bytes(_tree(0)), 0, ["a", "b"])
    for c, seed, ns in (("b", 2, 30), ("a", 1, 10)):
        edge.offer(c, tree_to_bytes(_tree(seed)), ns)
    blob, total = edge.partial()
    want = fedavg([_tree(1), _tree(2)], [10, 30])  # sorted by name
    got = tree_from_bytes(blob, template=_tree(0))
    np.testing.assert_array_equal(got["params"]["w"], want["params"]["w"])
    assert total == 40


def test_edge_refuses_robust_combines():
    from fedcrack_tpu.fed.tree import EdgeAggregator

    for name in ("trimmed_mean", "median", "krum", "multi_krum"):
        with pytest.raises(ValueError, match="edge tier only supports"):
            EdgeAggregator("e0", _tree(0), aggregation=name)


def test_null_pin_mesh_fold_is_the_algebra():
    """The mesh plane's historical names ARE the algebra's mesh instance —
    alias identity keeps every traced program (and the r13 groups-bitwise
    pins that run over them) byte-for-byte unchanged."""
    from fedcrack_tpu.parallel import fedavg_mesh as M

    assert M._ordered_cohort_sums is A.mesh_ordered_fold
    assert M._zero_sums_like is A.mesh_zero_sums
    assert M._finish_cohort_mean is A.mesh_finish_cohort_mean


def test_mesh_fold_traces_invariant_and_is_the_numpy_left_fold_bitwise():
    """The fold's result must be typed invariant over ``clients`` — it
    leaves every round program under ``out_specs=P()`` with check_vma on
    (a varying gather changed the fori_loop carry's type and no mesh entry
    traced, PR 21) — and must be exactly ``(((0 + w0*x0) + w1*x1) + ...)``,
    continuing a carry the same way (the r13 group-composition contract)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from fedcrack_tpu.parallel import make_mesh

    n = 4
    mesh = make_mesh(n, 1)
    rng = np.random.default_rng(3)
    x = rng.normal(0, 1.0, (n, 5, 3)).astype(np.float32)
    w = np.array([2.0, 0.0, 3.0, 5.0], np.float32)  # one dropped client

    def per_client(xs, ws):
        tree = {"leaf": xs[0]}
        first = A.mesh_ordered_fold(tree, ws[0], A.mesh_zero_sums(tree))
        return first, A.mesh_ordered_fold(tree, ws[0], first)

    (num, den), (num2, den2) = jax.jit(
        jax.shard_map(
            per_client,
            mesh=mesh,
            in_specs=(P("clients"), P("clients")),
            out_specs=P(),
        )
    )(x, w)

    want_num, want_den = np.zeros((5, 3), np.float32), np.float32(0.0)
    for rounds, got_num, got_den in ((1, num, den), (2, num2, den2)):
        for i in range(n):
            want_num = want_num + w[i] * x[i]
            want_den = want_den + w[i]
        np.testing.assert_array_equal(np.asarray(got_num["leaf"]), want_num)
        assert np.asarray(got_den) == want_den, rounds
    assert num["leaf"].sharding.is_fully_replicated


# ---------- ledger-coupled quarantine ----------

def _root_cfg(**kw):
    base = dict(cohort_size=3, max_rounds=2, registration_window_s=3600.0)
    base.update(kw)
    return FedConfig(**base)


def test_quarantine_excludes_flagged_update_sync():
    cfg = _root_cfg(cohort_size=3, max_rounds=1, quarantine_z=3.5)
    st = R.initial_state(cfg, _flat(0.0))
    now = 0.0
    for c in ("a", "b", "c"):
        now += 1e-3
        st, _ = R.transition(st, R.Ready(cname=c, now=now))
    for c, v, ns in (("a", 1.0, 10), ("b", 1.2, 10)):
        now += 1e-3
        st, rep = R.transition(
            st, R.TrainDone(cname=c, round=1, blob=tree_to_bytes(_flat(v)),
                            num_samples=ns, now=now),
        )
        assert rep.status == R.RESP_ACY
    # The poisoned update closes the barrier -> it is excluded from the
    # fold it triggered and resynced NOT_WAIT with the CLEAN global (the
    # direct reply that fires the client-side EF rollback, not an RESP_ARY
    # claiming its update was averaged).
    st, rep = R.transition(
        st, R.TrainDone(cname="c", round=1, blob=tree_to_bytes(_flat(1100.0)),
                        num_samples=10, now=now + 1e-3),
    )
    assert rep.status == R.NOT_WAIT
    assert rep.blob  # clean weights attached for the resync
    got = tree_from_bytes(st.global_blob)
    np.testing.assert_allclose(got["params"]["w"], np.full((4, 4), 1.1))
    entry = st.history[0]
    assert list(entry["quarantined"]) == ["c"]
    assert entry["quarantined"]["c"] >= 3.5
    assert entry["clients"] == ["a", "b", "c"]  # who REPORTED, unchanged
    assert st.ledger["c"]["quarantined"] == 1
    assert st.ledger["a"]["quarantined"] == 0


def test_quarantine_never_empties_the_cohort():
    # If the gate would exclude EVERYONE, it excludes no one: a duel of
    # two scaled updates must not zero out the round.
    scores = {"a": 10.0, "b": 12.0}
    assert A.quarantine_set(scores, ["a", "b"], 3.5) == {}
    assert A.quarantine_set(scores, ["a", "b"], 0.0) == {}  # z<=0 disables
    assert A.quarantine_set({"a": 0.1, "b": 9.0}, ["a", "b"], 3.5) == {"b": 9.0}


def test_quarantine_excludes_flagged_update_buffered():
    cfg = FedConfig(
        cohort_size=3, max_rounds=2, registration_window_s=3600.0,
        mode="buffered", buffer_k=3, staleness_alpha=0.0, max_staleness=4,
        quarantine_z=3.5,
    )
    st = R.initial_state(cfg, _flat(0.0))
    now = 0.0
    for c in ("a", "b", "c"):
        now += 1e-3
        st, _ = R.transition(st, R.Ready(cname=c, now=now))
    for c in ("a", "b", "c"):
        now += 1e-3
        st, rep = R.transition(st, R.PullWeights(cname=c, now=now))
        assert rep.status == "OK"
    for c, v in (("a", 1.0), ("b", 1.2)):
        now += 1e-3
        st, rep = R.transition(
            st, R.TrainDone(cname=c, round=1, blob=tree_to_bytes(_flat(v)),
                            num_samples=10, now=now),
        )
        assert rep.status == R.RESP_ACY
    st, rep = R.transition(
        st, R.TrainDone(cname="c", round=1, blob=tree_to_bytes(_flat(1100.0)),
                        num_samples=10, now=now + 1e-3),
    )
    assert rep.status == R.NOT_WAIT and rep.blob
    got = tree_from_bytes(st.global_blob)
    np.testing.assert_allclose(got["params"]["w"], np.full((4, 4), 1.1))
    assert list(st.history[-1]["quarantined"]) == ["c"]
    assert st.ledger["c"]["quarantined"] == 1


def test_quarantine_zero_z_is_the_seed_behavior():
    # quarantine_z=0 (the default): nothing excluded even at huge z.
    cfg = _root_cfg(cohort_size=2, max_rounds=1)
    st = R.initial_state(cfg, _flat(0.0))
    for i, c in enumerate(("a", "b")):
        st, _ = R.transition(st, R.Ready(cname=c, now=0.1 * (i + 1)))
    st, _ = R.transition(
        st, R.TrainDone(cname="a", round=1, blob=tree_to_bytes(_flat(1.0)),
                        num_samples=10, now=1.0),
    )
    st, rep = R.transition(
        st, R.TrainDone(cname="b", round=1, blob=tree_to_bytes(_flat(1000.0)),
                        num_samples=10, now=2.0),
    )
    assert rep.status in (R.RESP_ARY, R.FIN)
    assert st.history[0]["quarantined"] == {}


# ---------- ledger wire compat (13 -> 14 fields) ----------

def test_ledger_wire_roundtrips_quarantined_and_reads_old_rows():
    from fedcrack_tpu.health import ledger as L

    led = {"a": L.new_record()}
    led = L.record_quarantine(led, "a")
    rows = L.ledger_to_wire(led)
    back = L.ledger_from_wire(rows)
    assert back["a"]["quarantined"] == 1
    # A pre-r21 13-field row restores with the counter defaulted to 0.
    old = [list(r)[:13] for r in rows]
    back_old = L.ledger_from_wire(old)
    assert back_old["a"]["quarantined"] == 0
    assert back_old["a"]["offers"] == back["a"]["offers"]


# ---------- config validation + round-trip ----------

def test_config_validates_aggregation_fields():
    with pytest.raises(ValueError, match="aggregation"):
        FedConfig(aggregation="geometric_median")
    with pytest.raises(ValueError, match="trim_fraction"):
        FedConfig(trim_fraction=0.5)
    with pytest.raises(ValueError, match="trim_fraction"):
        FedConfig(trim_fraction=-0.1)
    with pytest.raises(ValueError, match="byzantine_f"):
        FedConfig(byzantine_f=-1)
    with pytest.raises(ValueError, match="quarantine_z"):
        FedConfig(quarantine_z=-0.5)


def test_config_roundtrips_aggregation_fields():
    cfg = FedConfig(
        aggregation="multi_krum", trim_fraction=0.2, byzantine_f=2,
        quarantine_z=3.5,
    )
    back = FedConfig.from_json(cfg.to_json())
    assert back.aggregation == "multi_krum"
    assert back.trim_fraction == 0.2
    assert back.byzantine_f == 2
    assert back.quarantine_z == 3.5
    assert back == cfg
