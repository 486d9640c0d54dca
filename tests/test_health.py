"""Federation health plane (round 18): ledger, anomaly, canary, drift.

Pins the four health subsystems' contracts:

- the per-client ledger is DETERMINISTIC — permuted arrival orders produce
  byte-identical statefile snapshots, metric expositions, and JSONL
  exports — and it survives a mid-round kill bit-for-bit;
- anomaly scoring is the robust z (median/MAD) with the 3.5
  Iglewicz-Hoaglin alert, and it flags a scaled-but-sanitation-passing
  update while leaving honest cohort members unflagged;
- the canary evaluator can never fail or block an install (it runs at the
  TAIL of the swap, wrapped), and its reference/IoU bookkeeping is exact;
- drift PSI matches the closed form, and the health SLO rules
  (configs/slo_health.json) turn a canary IoU cliff + anomaly spike into
  a watchdog breach with a flight dump and the exit-3 verdict — proved
  end to end by the SCALED_UPDATE chaos drill.
"""

import json
import math
import os
import tempfile
import types

import numpy as np
import pytest

from fedcrack_tpu.configs import FedConfig
from fedcrack_tpu.fed import rounds as R
from fedcrack_tpu.fed.serialization import tree_from_bytes, tree_to_bytes
from fedcrack_tpu.health import ledger as hl
from fedcrack_tpu.health.drift import DriftMonitor, psi
from fedcrack_tpu.obs import flight
from fedcrack_tpu.obs.registry import MetricsRegistry
from fedcrack_tpu.obs.watchdog import BREACH_EXIT, Watchdog, load_rules

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HEALTH_RULES = os.path.join(REPO, "configs", "slo_health.json")


def _tree(v: float):
    return {"params": {"w": np.full((4, 4), float(v), np.float32)}}


def _cfg(**kw):
    defaults = dict(
        cohort_size=3, max_rounds=2, registration_window_s=100.0
    )
    defaults.update(kw)
    return FedConfig(**defaults)


def _run_round(ready_order, done_order, values):
    """One full FedAvg round driven through the pure state machine with the
    given arrival permutations; returns the post-aggregation state."""
    state = R.initial_state(_cfg(), _tree(0.0))
    for i, name in enumerate(ready_order):
        state, rep = R.transition(state, R.Ready(name, now=0.1 * i))
        assert rep.status == R.SW
    for i, name in enumerate(done_order):
        state, rep = R.transition(
            state,
            R.TrainDone(
                name,
                round=1,
                blob=tree_to_bytes(_tree(values[name])),
                num_samples=4,
                now=1.0 + 0.1 * i,
            ),
        )
    assert state.current_round == 2  # the round closed and aggregated
    return state


# ---------- ledger determinism ----------


def test_ledger_permuted_arrivals_byte_identical(tmp_path):
    """Arrival order must never leak into the ledger's three canonical
    serializations: the r8 statefile snapshot, the anomaly exposition, and
    the JSONL export are byte-identical across permutations."""
    from fedcrack_tpu.ckpt import save_state_file

    values = {"a": 1.0, "b": 1.2, "c": 0.9}
    s1 = _run_round(["a", "b", "c"], ["a", "b", "c"], values)
    s2 = _run_round(["c", "a", "b"], ["b", "c", "a"], values)

    assert hl.ledger_to_wire(s1.ledger) == hl.ledger_to_wire(s2.ledger)

    blobs = []
    for i, state in enumerate((s1, s2)):
        path = str(tmp_path / f"state_{i}.msgpack")
        save_state_file(path, state)
        with open(path, "rb") as f:
            blobs.append(f.read())
    assert blobs[0] == blobs[1]

    expositions = []
    for state in (s1, s2):
        reg = MetricsRegistry()
        hl.export_anomaly_metrics(state.ledger, registry=reg)
        expositions.append(reg.exposition())
    assert expositions[0] == expositions[1]
    assert "fed_client_anomaly_score_ratio" in expositions[0]
    assert "fed_client_anomaly_max_ratio" in expositions[0]

    jsonls = []
    for i, state in enumerate((s1, s2)):
        path = str(tmp_path / f"ledger_{i}.jsonl")
        hl.write_ledger_jsonl(state.ledger, path)
        with open(path, "rb") as f:
            jsonls.append(f.read())
    assert jsonls[0] == jsonls[1]
    assert hl.read_ledger_jsonl(str(tmp_path / "ledger_0.jsonl")) == {
        n: s1.ledger[n] for n in s1.ledger
    }


def test_ledger_conservation_after_round():
    state = _run_round(
        ["a", "b", "c"], ["c", "b", "a"], {"a": 1.0, "b": 1.2, "c": 0.9}
    )
    cons = hl.conservation(state.ledger)
    assert cons["clients"] == 3
    assert cons["violations"] == []
    for rec in state.ledger.values():
        assert rec["offers"] == rec["accepted"] == 1


# ---------- statefile round-trip across a mid-round kill ----------


def test_ledger_survives_midround_kill(tmp_path):
    """Kill mid-round with one accepted and one sanitation-rejected offer
    on the books: the restored ledger is exactly the pre-kill ledger, a
    re-snapshot is bit-identical, and the completed round conserves."""
    from fedcrack_tpu.ckpt import load_state_file, save_state_file

    cfg = _cfg(cohort_size=2)
    state = R.initial_state(cfg, _tree(0.0))
    state, _ = R.transition(state, R.Ready("a", now=0.0))
    state, _ = R.transition(state, R.Ready("b", now=0.1))
    state, rep = R.transition(
        state,
        R.TrainDone(
            "a", round=1, blob=tree_to_bytes(_tree(2.0)), num_samples=4,
            now=1.0,
        ),
    )
    assert rep.status == R.RESP_ACY
    nan_tree = _tree(1.0)
    nan_tree["params"]["w"][0, 0] = np.nan
    state, rep = R.transition(
        state,
        R.TrainDone(
            "b", round=1, blob=tree_to_bytes(nan_tree), num_samples=4,
            now=1.5,
        ),
    )
    assert rep.status == R.REJECTED
    assert state.ledger["b"]["rejected"]["sanitation"] == 1

    path = str(tmp_path / "state.msgpack")
    save_state_file(path, state)
    restored = load_state_file(path, cfg)
    assert hl.ledger_to_wire(restored.ledger) == hl.ledger_to_wire(
        state.ledger
    )
    resnap = str(tmp_path / "state2.msgpack")
    save_state_file(resnap, restored)
    with open(path, "rb") as f1, open(resnap, "rb") as f2:
        assert f1.read() == f2.read()

    restored, rep = R.transition(
        restored,
        R.TrainDone(
            "b", round=1, blob=tree_to_bytes(_tree(4.0)), num_samples=4,
            now=100.0,
        ),
    )
    assert rep.status == R.RESP_ARY
    cons = hl.conservation(restored.ledger)
    assert cons["violations"] == []
    assert restored.ledger["b"]["offers"] == 2
    assert restored.ledger["b"]["accepted"] == 1


# ---------- anomaly scoring ----------


def test_robust_z_closed_form():
    # med=4.8, MAD=0.8: z(v) = |v - 4.8| / (1.4826*0.8 + 1e-3*4.8)
    values = [4.0, 4.8, 1200.0]
    denom = 1.4826 * 0.8 + 1e-3 * 4.8
    z = hl.robust_z(values)
    assert z[0] == pytest.approx(0.8 / denom, abs=1e-4)
    assert z[1] == 0.0
    assert z[2] == pytest.approx(1195.2 / denom, rel=1e-4)
    # Degenerate windows never divide by zero and never score.
    assert hl.robust_z([]) == []
    assert hl.robust_z([3.0]) == [0.0]
    # MAD=0 collapses to the epsilon floor, capped at SCORE_CAP.
    assert all(s <= hl.SCORE_CAP for s in hl.robust_z([1.0, 1.0, 1e9]))


def test_observe_flush_flags_scaled_update_only():
    base = _tree(0.0)
    items = [
        ("a", _tree(1.0)),
        ("b", _tree(1.2)),
        ("c", _tree(300.0)),
    ]
    ledger = {}
    for name, tree in items:
        ledger = hl.record_offer(
            ledger, name, outcome="accepted", num_samples=4,
            wire_len=128, round=1, norm=hl.update_norm(tree, base),
        )
    ledger, scores = hl.observe_flush(ledger, items, base)
    assert scores["c"] >= hl.ANOMALY_ALERT
    assert max(scores["a"], scores["b"]) < hl.ANOMALY_ALERT
    assert ledger["c"]["flags"] == 1
    assert ledger["a"]["flags"] == ledger["b"]["flags"] == 0


def test_client_label_cardinality_bounded():
    names = [f"client_{i:03d}" for i in range(100)]
    labels = {hl.client_label(n, i) for i, n in enumerate(sorted(names))}
    assert "_overflow" in labels
    # Bounded: at most MAX_CLIENT_LABELS real names + the overflow bucket.
    assert len(labels) <= hl.MAX_CLIENT_LABELS + 1


# ---------- canary ----------


class _FakeEngine:
    """Minimal engine contract for CanaryEvaluator: fixed buckets, probs
    that are a pure function of the 'installed' variables."""

    bucket_sizes = (8,)
    max_batch = 2
    serve_config = types.SimpleNamespace(
        quant_probe_batch=2, quant_probe_seed=0
    )

    def predict_bucket(self, device_variables, images_u8):
        level = float(device_variables)
        return np.full(
            (images_u8.shape[0],) + images_u8.shape[1:3], level, np.float32
        )


def test_canary_reference_then_regression():
    from fedcrack_tpu.health.canary import CanaryEvaluator

    reg = MetricsRegistry()
    canary = CanaryEvaluator(_FakeEngine(), registry=reg)
    ref = canary.evaluate(0, 0.8)
    assert ref["iou"] == 1.0 and ref["reference_version"] == 0
    same = canary.evaluate(1, 0.9)  # same masks (both sides > 0.5)
    assert same["iou"] == 1.0
    cliff = canary.evaluate(2, 0.2)  # empty mask vs full mask
    assert cliff["iou"] == 0.0
    assert [h["version"] for h in canary.history] == [0, 1, 2]
    fam = reg.get("model_canary_iou_ratio")
    assert fam is not None
    audit = canary.audit()
    assert audit["evals"] == 3 and audit["all_finite_unit"]
    assert audit["min_iou"] == 0.0


def test_canary_failure_never_blocks_swap():
    """The swap contract: a raising canary is logged and swallowed — the
    install still flips the pointer and returns True."""
    import jax

    from fedcrack_tpu.models import ModelConfig
    from fedcrack_tpu.models.resunet import init_variables
    from fedcrack_tpu.serve.engine import InferenceEngine, ServeConfig
    from fedcrack_tpu.serve.hot_swap import ModelVersionManager

    model_cfg = ModelConfig(
        img_size=16, stem_features=4, encoder_features=(8,),
        decoder_features=(8, 4),
    )
    engine = InferenceEngine(
        model_cfg,
        ServeConfig(
            bucket_sizes=(16,), max_batch=2, max_delay_ms=30.0,
            tile_overlap=4,
        ),
    )
    v0 = init_variables(jax.random.key(0), model_cfg)

    class _Boom:
        calls = 0

        def evaluate(self, version, device_variables):
            _Boom.calls += 1
            raise RuntimeError("canary exploded")

    manager = ModelVersionManager(
        engine, v0, initial_version=0, canary=_Boom()
    )
    assert manager.install(1, v0) is True
    assert manager.version == 1
    assert _Boom.calls == 1
    # Stale versions are refused BEFORE the canary can run.
    assert manager.install(1, v0) is False
    assert _Boom.calls == 1


# ---------- drift PSI ----------


def test_psi_closed_form_and_units():
    ref = np.array([0.5, 0.5])
    assert psi(ref, ref) == pytest.approx(0.0, abs=1e-9)
    cur = np.array([0.9, 0.1])
    expected = (0.9 - 0.5) * math.log(0.9 / 0.5) + (0.1 - 0.5) * math.log(
        0.1 / 0.5
    )
    assert psi(ref, cur) == pytest.approx(expected, rel=1e-2)
    assert psi(ref, cur) == psi(cur, ref)  # symmetric in the closed form
    with pytest.raises(ValueError):
        psi(np.ones(3), np.ones(4))
    # Zero-mass bins are epsilon-smoothed, never inf/nan.
    assert math.isfinite(psi(np.array([1.0, 0.0]), np.array([0.0, 1.0])))


def test_drift_monitor_self_comparison_is_zero():
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, size=(4, 8, 8, 3), dtype=np.uint8)
    probs = rng.random((4, 8, 8)).astype(np.float32)
    ref = DriftMonitor()
    ref.observe(images, probs)
    mon = DriftMonitor(reference=ref.profile())
    mon.observe(images, probs)
    psis = mon.compare()
    assert psis  # at least input/confidence/entropy signals on one bucket
    assert all(v == pytest.approx(0.0, abs=1e-9) for v in psis.values())
    for key in psis:
        bucket, signal = key.split("/", 1)
        assert bucket == "8" and signal in (
            "input", "confidence", "entropy", "crack_fraction"
        )


# ---------- watchdog: health rules breach -> flight dump -> exit 3 ----------


def _armed_ring():
    ring = flight.current()
    if ring is not None:
        return ring, lambda: None
    tmp = tempfile.mkdtemp(prefix="health_flight_")
    flight.install(path=os.path.join(tmp, "flight.jsonl"), hooks=False)
    return flight.current(), flight.uninstall


def test_health_rules_breach_dumps_flight_and_exits_3():
    reg = MetricsRegistry()
    reg.gauge("model_canary_iou_ratio", "t").set(0.2)
    reg.gauge("fed_client_anomaly_max_ratio", "t").set(9.0)
    ring, cleanup = _armed_ring()
    try:
        before = len(ring.dumps)
        watchdog = Watchdog(load_rules(HEALTH_RULES), registry=reg)
        report = watchdog.enforce()
        assert sorted(b["rule"] for b in report["breaches"]) == [
            "canary_iou_floor", "client_anomaly_ceiling"
        ]
        assert len(ring.dumps) == before + 1
        assert "canary_iou_floor" in ring.dumps[-1]["reason"]
        assert BREACH_EXIT == 3  # the soak/CI exit contract
    finally:
        cleanup()


def test_health_rules_clean_and_skip_when_absent():
    rules = load_rules(HEALTH_RULES)
    reg = MetricsRegistry()
    reg.gauge("model_canary_iou_ratio", "t").set(0.97)
    reg.gauge("fed_client_anomaly_max_ratio", "t").set(1.2)
    report = Watchdog(rules, registry=reg).evaluate()
    assert report["breaches"] == []
    # on_missing=skip: a registry without the health plane stays
    # indeterminate instead of minting a false breach.
    empty = Watchdog(rules, registry=MetricsRegistry()).evaluate()
    assert empty["breaches"] == []


# ---------- the SCALED_UPDATE drill: the full chain, end to end ----------


def test_scaled_update_drill_end_to_end():
    """The round-18 acceptance chain in one artifact: FedAvg's sanitation
    gate ACCEPTS the scaled update (finite, well-formed), the ledger's
    robust z flags exactly the scaled client, the canary IoU cliffs on the
    poisoned install without blocking the swap or recompiling, and the
    health watchdog converts the pair of signals into a breach + flight
    dump + exit-3 verdict."""
    from fedcrack_tpu.tools.chaos_drill import run_scaled_update_drill

    out = run_scaled_update_drill()
    led = out["ledger"]
    assert led["fault_fired"] == "scaled_update"
    assert led["poisoned_accepted"] and led["honest_accepted"]
    assert led["nothing_rejected"]  # sanitation saw nothing wrong
    assert led["global_drag_matches_fedavg"]  # the poison really averaged in
    assert led["poisoned_flagged"] and led["honest_below_alert"]
    assert led["flagged_flushes"] >= 1

    can = out["canary"]
    assert can["reference_iou"] == 1.0
    assert can["iou_cliff"] and can["poisoned_iou"] < 0.5
    assert can["swap_still_installed"]
    assert can["recompiles_since_warmup"] == 0  # probes reuse bucket programs

    wd = out["watchdog"]
    assert wd["both_signals_breached"]
    assert wd["flight_dumped"]
    assert wd["would_exit"] == BREACH_EXIT == 3


# ---------- health_report: the joined artifact ----------


def test_health_report_round_trip(tmp_path):
    from fedcrack_tpu.tools import health_report

    state = _run_round(
        ["a", "b", "c"], ["a", "b", "c"], {"a": 1.0, "b": 1.2, "c": 0.9}
    )
    ledger_path = str(tmp_path / "ledger.jsonl")
    hl.write_ledger_jsonl(state.ledger, ledger_path)
    canary_path = str(tmp_path / "canary.json")
    with open(canary_path, "w", encoding="utf-8") as f:
        json.dump(
            {
                "history": [
                    {
                        "version": 0, "iou": 1.0, "per_bucket": {"16": 1.0},
                        "reference_version": 0, "probe_batch": 2,
                        "probe_seed": 0,
                    }
                ],
                "audit": {
                    "evals": 1, "reference_version": 0, "min_iou": 1.0,
                    "all_finite_unit": True,
                },
            },
            f,
        )
    out_path = str(tmp_path / "report.json")
    rc = health_report.main(
        ["--ledger", ledger_path, "--canary", canary_path, "--out", out_path]
    )
    assert rc == 0
    with open(out_path, encoding="utf-8") as f:
        report = json.load(f)
    assert health_report.validate_report(report) == []
    assert report["summary"]["clients"] == 3
    assert report["summary"]["conservation_violations"] == []
    # The guard trips loudly on a conservation break.
    broken = json.loads(json.dumps(report))
    next(iter(broken["clients"].values()))["offers"] = 99
    assert any(
        "conservation" in v for v in health_report.validate_report(broken)
    )


def test_health_report_joins_quarantine(tmp_path):
    """Round 21: a ledger carrying quarantine counters round-trips
    through the joined report — per-client counts typed, the summary's
    quarantines total + quarantined_clients join, schema clean — and a
    wrong-typed counter trips the guard."""
    from fedcrack_tpu.tools import health_report

    ledger = {"a": hl.new_record(), "b": hl.new_record()}
    ledger = hl.record_quarantine(ledger, "b")
    ledger = hl.record_quarantine(ledger, "b")
    ledger_path = str(tmp_path / "ledger.jsonl")
    hl.write_ledger_jsonl(ledger, ledger_path)
    report = health_report.build_report(ledger_path)
    assert health_report.validate_report(report) == []
    assert report["clients"]["b"]["quarantined"] == 2
    assert report["summary"]["quarantines"] == 2
    assert report["summary"]["quarantined_clients"] == ["b"]
    broken = json.loads(json.dumps(report))
    broken["clients"]["b"]["quarantined"] = "2"
    assert any(
        "quarantined" in v for v in health_report.validate_report(broken)
    )


def test_health_report_joins_privacy_summary(tmp_path):
    """Round 23: the privacy plane's summary (fed.rounds.privacy_summary)
    joins the report behind --privacy — dp/secagg blocks typed (real
    bools, not ints), per-client epsilon finite-nonnegative, and a
    headline max_epsilon that disagrees with its own per-client rows trips
    the guard (the one accounting-drift class this join exists to catch)."""
    from fedcrack_tpu.fed import rounds as R
    from fedcrack_tpu.fed.serialization import tree_to_bytes
    from fedcrack_tpu.tools import health_report

    cfg = FedConfig(
        cohort_size=2, max_rounds=2, registration_window_s=1.0,
        dp_clip_norm=1.0, dp_noise_multiplier=1.1, dp_sample_rate=0.01,
        dp_steps_per_round=4, dp_delta=1e-5,
    )
    state = R.initial_state(cfg, {"w": np.zeros(6, np.float32)})
    for n in ("a", "b"):
        state, _ = R.transition(state, R.Ready(cname=n, now=0.0))
    state = R._advance_time(state, 2.0)
    blob = tree_to_bytes({"w": np.full(6, 0.5, np.float32)})
    rnd = state.current_round
    for n in ("a", "b"):
        state, _ = R.transition(
            state,
            R.TrainDone(cname=n, blob=blob, num_samples=10, round=rnd, now=3.0),
        )
    ledger_path = str(tmp_path / "ledger.jsonl")
    hl.write_ledger_jsonl(state.ledger, ledger_path)
    privacy_path = str(tmp_path / "privacy.json")
    with open(privacy_path, "w", encoding="utf-8") as f:
        json.dump(R.privacy_summary(state), f)
    out_path = str(tmp_path / "report.json")
    rc = health_report.main(
        ["--ledger", ledger_path, "--privacy", privacy_path, "--out", out_path]
    )
    assert rc == 0
    with open(out_path, encoding="utf-8") as f:
        report = json.load(f)
    assert health_report.validate_report(report) == []
    dp = report["privacy"]["dp"]
    assert dp["enabled"] is True and dp["noise_multiplier"] == 1.1
    assert dp["clients"]["a"]["steps"] == 4
    assert dp["max_epsilon"] == max(
        c["epsilon"] for c in dp["clients"].values()
    )
    assert report["privacy"]["secagg"]["enabled"] is False
    # A report WITHOUT the artifact records absence, not a plausible block.
    assert health_report.build_report(ledger_path)["privacy"] is None
    # Headline/per-client disagreement is the accounting bug the guard
    # exists for.
    broken = json.loads(json.dumps(report))
    broken["privacy"]["dp"]["max_epsilon"] = 99.0
    assert any(
        "max_epsilon" in v for v in health_report.validate_report(broken)
    )
    # enabled must be a REAL bool — a 1 from a sloppy writer fails.
    intbool = json.loads(json.dumps(report))
    intbool["privacy"]["dp"]["enabled"] = 1
    assert any(
        "wants bool" in v for v in health_report.validate_report(intbool)
    )
    # Non-finite epsilon never ships.
    inf = json.loads(json.dumps(report))
    inf["privacy"]["dp"]["clients"]["a"]["epsilon"] = float("nan")
    assert any(
        "finite" in v for v in health_report.validate_report(inf)
    )


# ---------- the robust-aggregation A/B drill: response layer, end to end ----


def test_robust_aggregation_drill_end_to_end():
    """The round-21 acceptance chain in one artifact: the identical
    poisoned cohort cliffs the canary under FedAvg but holds IoU >= 0.9
    under trimmed-mean / Krum / the ledger-coupled quarantine, with drag
    cut >= 10x; the quarantined flush-trigger is resynced NOT_WAIT; the
    colluding-minority variant is beaten by every robust arm; and the
    exclusion shows up in the joined health report."""
    from fedcrack_tpu.tools.chaos_drill import run_robust_aggregation_drill

    out = run_robust_aggregation_drill()
    assert out["fedavg_cliffed"]
    assert out["robust_arms_hold"]
    assert out["drag_reduced_10x"]
    arms = out["arms"]
    assert arms["fedavg"]["canary_iou"] < 0.5 <= out["reference_iou"]
    for name in ("trimmed_mean", "krum", "fedavg_quarantine"):
        assert arms[name]["canary_iou"] >= 0.9
        assert arms[name]["drag_reduction_vs_fedavg"] >= 10.0
    q = arms["fedavg_quarantine"]
    assert q["quarantined"] and "c" in q["quarantined"]
    assert q["poisoned_resynced_not_wait"] and q["clean_global_attached"]
    assert q["ledger_quarantined_count"] == 1 and q["honest_not_quarantined"]
    assert all(out["colluding"]["colluders_beaten"].values())
    hp = out["health_report"]
    assert hp["schema_violations"] == [] and hp["exclusion_visible"]
