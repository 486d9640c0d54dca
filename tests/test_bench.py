"""bench.py must actually run, end to end — round 1's lesson is that code
that only ever executes on the driver's hardware is code that silently rots.
The smoke run uses tiny env knobs and the CPU backend; it checks the JSON
contract the driver parses, not performance."""

import json
import os
import subprocess
import sys

import pytest


@pytest.mark.slow
def test_bench_smoke_emits_driver_contract(tmp_path):
    env = dict(os.environ)
    env.update(
        FEDCRACK_BENCH_FORCE_CPU="1",
        FEDCRACK_BENCH_STEPS="2",
        FEDCRACK_BENCH_BATCH="4",
        FEDCRACK_BENCH_REPS="1",
        FEDCRACK_BENCH_SIZES="32",
        # Per-test artifact path: the default is a fixed /tmp file, which
        # two concurrent bench runs would race on.
        FEDCRACK_BENCH_OUT=str(tmp_path / "payload.json"),
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "bench.py")],
        capture_output=True,
        text=True,
        env=env,
        timeout=900,
        cwd=root,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    # Round-9 output contract: the FINAL line is the compact summary (small
    # enough to survive tail-capture), the full payload is the line before
    # it and is also written to the artifact path the summary points at.
    summary = json.loads(lines[-1])
    assert summary["compact"] is True
    assert set(summary) >= {"metric", "value", "unit", "vs_baseline", "artifact"}
    assert summary["unit"] == "ms"
    assert summary["value"] > 0
    assert summary["vs_baseline"] > 0
    out = json.loads(lines[-2])
    assert out["value"] == summary["value"]
    if summary["artifact"]:
        with open(summary["artifact"]) as f:
            assert json.load(f)["value"] == out["value"]

    # The driver's contract: one JSON line with these keys.
    assert set(out) >= {"metric", "value", "unit", "vs_baseline"}
    assert out["unit"] == "ms"
    assert out["value"] > 0
    assert out["vs_baseline"] > 0

    # The round-2 additions: full sweep + decomposed host plane.
    detail = out["detail"]
    assert set(detail["sweep"]) == {"float32_32", "bfloat16_32"}
    for point in detail["sweep"].values():
        # Slope-based per-step can be None when the two-point fit fails on a
        # noisy host; the naive fallback must always be there.
        assert (point["per_step_ms"] or point["naive_per_step_ms"]) > 0
        assert point["flops_per_step"] > 0
    # Round-6 layout A/B: on a host fast enough to fund it the section must
    # carry the ab_pallas_bce artifact schema (per-variant dicts under
    # "impls", ratios as sibling keys); when the budget excluded it, the
    # skip must be RECORDED — never silent absence.
    layout_points = detail.get("layout_ab", {})
    if layout_points:
        for point in layout_points.values():
            assert all(isinstance(v, dict) for v in point["impls"].values())
            assert "reference" in point["impls"]
            assert point["flops_per_step_canonical"] > 0
    else:
        assert any(
            s["section"].startswith("layout_ab_") for s in detail["skipped"]
        )
    host = detail["host_plane"]
    reconstructed = (
        detail["n_clients"] * detail["steps"] * host["per_step_compute_ms"]
        + host["serialization_ms"]
        + host["host_fedavg_ms"]
        + host["dispatch_overhead_ms"]
    )
    # The decomposition must account for the measured total: dispatch is the
    # max(0, residual), so the parts either sum to the total (residual
    # positive) or over-cover it (compute estimate overshot a tiny CPU run —
    # they can never under-explain the round).
    assert reconstructed >= host["round_ms"] * 0.98
    assert detail["vs_baseline_compute_only"] > 0
    # Round-8 chaos-recovery drill: present with verified semantics + real
    # timings, or a RECORDED budget skip — never silent absence.
    chaos = detail.get("chaos_recovery")
    if chaos is not None and "error" not in chaos:
        assert chaos["resumed_mid_round"] and chaos["received_preserved"]
        assert chaos["recovered_avg_exact"] and chaos["history_gapless"]
        assert chaos["restore_s"] >= 0 and chaos["kill_to_recover_s"] > 0
    else:
        assert chaos is not None or any(
            s["section"] == "chaos_recovery" for s in detail["skipped"]
        )
    # Round-12 update-compression A/B: present with the codec contract
    # intact (null byte-identical, compressed codecs strictly cheaper on
    # the wire at reference scale), or a RECORDED skip — never silent.
    comp = detail.get("update_compression")
    if comp is not None and "error" not in comp:
        assert comp["wire"]["null"]["null_identical"] is True
        assert comp["wire"]["null"]["bytes_per_round"] == comp["dense_update_bytes"]
        for codec in ("int8", "topk_delta"):
            assert comp["wire"][codec]["bytes_per_round"] < comp["dense_update_bytes"]
            assert comp["wire"][codec]["ratio_vs_null"] > 1.0
            assert len(comp["trajectory"][codec]["iou"]) == comp["rounds"]
    else:
        assert comp is not None or any(
            s["section"] == "update_compression" for s in detail["skipped"]
        )


@pytest.mark.slow
def test_bench_budget_skips_sections_but_still_emits(tmp_path):
    """The round-4 budget machinery under the round-5 section order: with an
    already-exhausted budget the mandatory flagship-size sweep still runs and
    the JSON still prints (rc 0), while every optional section — now
    INCLUDING the host plane, which round 5 demoted below the reference-scale
    headline (round-4 weak #1) — is skipped WITH a record under
    detail.skipped, never silently. vs_baseline is then honestly None rather
    than fabricated."""
    env = dict(os.environ)
    env.update(
        FEDCRACK_BENCH_FORCE_CPU="1",
        FEDCRACK_BENCH_STEPS="2",
        FEDCRACK_BENCH_BATCH="4",
        FEDCRACK_BENCH_REPS="1",
        FEDCRACK_BENCH_SIZES="32,48",  # 48 = the optional secondary size
        FEDCRACK_BENCH_BUDGET_S="1",  # exhausted before any optional section
        FEDCRACK_BENCH_OUT=str(tmp_path / "payload.json"),
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "bench.py")],
        capture_output=True,
        text=True,
        env=env,
        timeout=900,
        cwd=root,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    summary = json.loads(lines[-1])
    assert summary["compact"] is True and summary["vs_baseline"] is None
    out = json.loads(lines[-2])
    detail = out["detail"]
    # The mandatory sweep completed and priced the headline value.
    assert set(detail["sweep"]) == {"float32_32", "bfloat16_32"}
    assert out["value"] > 0
    # Exhausted budget: the host plane could not run, so the ratio is
    # honestly absent and the skip is RECORDED, not silently dropped.
    skipped = {s["section"]: s for s in detail["skipped"]}
    assert out["vs_baseline"] is None
    assert "host_plane" in skipped
    assert "sweep_48" in skipped
    assert "batch_curve" in skipped
    # The layout A/B prices a 2-variant comparison before spending anything
    # (even the long-scan tiling) and records its exclusion per dtype.
    assert "layout_ab_bfloat16_32" in skipped
    assert "layout_ab_float32_32" in skipped
    assert skipped["sweep_48"]["reason"] == "estimate exceeds remaining budget"
    assert detail["budget"]["budget_s"] == 1.0


# ---- tier-1-safe schema guards (round 7): artifact consumers key on these
# detail names; a rename must break CI here, not silently break dashboards
# downstream. No bench run needed — the module's
# declared schema is checked against its own emitting code and against the
# committed bench_runs/ artifacts. ----


def _import_bench():
    import importlib.util

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_module", os.path.join(root, "bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_detail_schema_declares_contract_keys():
    bench = _import_bench()
    required = {
        "sweep",
        "skipped",
        "budget",
        "reference_scale",
        "layout_ab",
        "segmented_pipeline",
        "resident_pool",
        "serving",
        "update_compression",
    }
    assert required <= set(bench.DETAIL_SCHEMA)
    # Round-10 serving arm: the SLO keys consumers read must be declared.
    assert {"throughput_rps", "latency_ms", "swap", "dropped"} <= set(
        bench.SERVING_SCHEMA
    )
    assert {"round_ms", "round_plus_restage_ms", "staging_hidden_frac"} <= set(
        bench.REF_POINT_SCHEMA
    )
    # Round-12 compression arm: the bytes/timing keys consumers read.
    assert {"dense_update_bytes", "rounds", "wire", "trajectory"} <= set(
        bench.COMPRESSION_SCHEMA
    )
    assert {"bytes_per_round", "ratio_vs_null", "encode_ms", "decode_ms"} <= set(
        bench.COMPRESSION_WIRE_SCHEMA
    )
    # Round-17 serve-fleet arm: the grid/swap/shed keys consumers read.
    assert {"grid", "swap", "shed", "quant_gate"} <= set(bench.SERVE_FLEET_SCHEMA)
    assert {"replicas", "quant", "throughput_rps", "p95_ms"} <= set(
        bench.SERVE_FLEET_ARM_SCHEMA
    )
    # Round-19 video-serving arm: the effective-throughput + identity keys.
    assert {
        "effective_speedup",
        "effective_img_per_s",
        "speedup_target_met",
        "identity",
        "swap",
        "metrics_in_exposition",
    } <= set(bench.VIDEO_SERVING_SCHEMA)
    # The schema cannot drift from the code that writes the payload: every
    # declared key must appear as a literal in bench.py's emitting code.
    with open(bench.__file__) as f:
        src = f.read()
    for key in (
        required
        | set(bench.REF_POINT_SCHEMA)
        | set(bench.SERVING_SCHEMA)
        | set(bench.COMPRESSION_SCHEMA)
        | set(bench.COMPRESSION_WIRE_SCHEMA)
        | set(bench.SERVE_FLEET_SCHEMA)
        | set(bench.SERVE_FLEET_ARM_SCHEMA)
        | set(bench.VIDEO_SERVING_SCHEMA)
    ):
        assert f'"{key}"' in src, f"schema key {key!r} never written by bench.py"


def test_validate_detail_typed_checks():
    bench = _import_bench()
    good = {
        "sweep": {"bfloat16_32": {}},
        "skipped": [],
        "budget": {"budget_s": 1.0},
        "reference_scale": {
            "bfloat16_128": {
                "round_ms": 7400.0,
                "round_plus_restage_ms": 20336.0,
                "staging_hidden_frac": 0.231,
            }
        },
        "segmented_pipeline": {
            "bfloat16_128": {
                "monolithic": {"round_ms": 7400.0, "staging_hidden_frac": 0.2},
                "segmented": {"round_ms": 7500.0, "staging_hidden_frac": None},
            }
        },
        "resident_pool": {
            "bfloat16_128": {
                "streamed": {"round_ms": 7400.0, "round_plus_restage_ms": 20336.0},
                "resident": {"round_ms": 7420.0, "round_plus_restage_ms": 7500.0},
            }
        },
        "serving": {
            "throughput_rps": 41.5,
            "latency_ms": {"p50": 120.0, "p95": 180.0, "p99": 220.0},
            "requests": {"total": 128, "completed": 128},
            "batcher": {"batches": 20},
            "swap": {"to_version": 1, "load_ms": 35.0, "gap_ms": 4.0},
            "dropped": 0,
        },
        "update_compression": {
            "dense_update_bytes": 8236134,
            "rounds": 3,
            "wire": {
                "null": {
                    "bytes_per_round": 8236134,
                    "ratio_vs_null": None,
                    "encode_ms": 0.001,
                    "decode_ms": 180.0,
                    "null_identical": True,
                },
                "int8": {
                    "bytes_per_round": 789082,
                    "ratio_vs_null": 10.44,
                    "encode_ms": 92.0,
                    "decode_ms": 20.0,
                },
            },
            "trajectory": {"null": {"iou": [0.1, 0.2, 0.3]}},
        },
    }
    assert bench.validate_detail(good) == []
    assert bench.validate_detail({}) == []  # every section is optional
    # A serving section that errored out is exempt from the typed contract…
    assert bench.validate_detail({"serving": {"error": "boom"}}) == []
    # …but a present one must carry every declared key with the right type.
    assert any(
        "serving" in v for v in bench.validate_detail({"serving": {"dropped": 0}})
    )
    bad_serving = dict(good, serving=dict(good["serving"], dropped="none"))
    assert any("serving['dropped']" in v for v in bench.validate_detail(bad_serving))
    bad = dict(good, skipped="oops")
    assert any("skipped" in v for v in bench.validate_detail(bad))
    bad2 = dict(
        good,
        reference_scale={"x": {"staging_hidden_frac": "0.2"}},
    )
    assert any("staging_hidden_frac" in v for v in bench.validate_detail(bad2))
    bad3 = dict(
        good,
        resident_pool={"x": {"resident": {"round_ms": "slow"}}},
    )
    assert any("resident_pool" in v for v in bench.validate_detail(bad3))
    # Round-17 serve-fleet arm: error-arm exempt, present arm fully typed,
    # per-arm grid points typed, non-dict points reported never crashed.
    assert bench.validate_detail({"serve_fleet": {"error": "boom"}}) == []
    fleet_ok = {
        "serve_fleet": {
            "buckets": [128, 256],
            "max_batch": 8,
            "grid": {
                "r2_int8": {
                    "replicas": 2,
                    "quant": "int8",
                    "served_quant": True,
                    "requests": 64,
                    "completed": 64,
                    "throughput_rps": 120.5,
                    "p50_ms": 30.0,
                    "p95_ms": 55.0,
                }
            },
            "swap": {"pause_ms": 0.3, "torn_versions": 0, "zero_torn": True},
            "shed": {"total": 7, "by_reason": {"queue_bound": 7}},
            "quant_gate": {"passed": True, "iou": 0.99},
        }
    }
    assert bench.validate_detail(fleet_ok) == []
    assert any(
        "serve_fleet" in v for v in bench.validate_detail({"serve_fleet": {"grid": {}}})
    )
    fleet_bad = {
        "serve_fleet": dict(
            fleet_ok["serve_fleet"], grid={"r1_bf16": {"replicas": "two"}}
        )
    }
    assert any(
        "serve_fleet.grid" in v for v in bench.validate_detail(fleet_bad)
    )
    fleet_bad2 = {
        "serve_fleet": dict(fleet_ok["serve_fleet"], grid={"r1_bf16": ["x"]})
    }
    assert any(
        "serve_fleet.grid['r1_bf16']" in v
        for v in bench.validate_detail(fleet_bad2)
    )
    # quant_gate None = quant disabled this run — legal.
    assert (
        bench.validate_detail(
            {"serve_fleet": dict(fleet_ok["serve_fleet"], quant_gate=None)}
        )
        == []
    )
    # Round-12 compression arm: error-arm exempt, present arm fully typed.
    assert bench.validate_detail({"update_compression": {"error": "boom"}}) == []
    assert any(
        "update_compression" in v
        for v in bench.validate_detail({"update_compression": {"wire": {}}})
    )
    bad4 = dict(
        good,
        update_compression=dict(
            good["update_compression"],
            wire={"int8": {"bytes_per_round": "many"}},
        ),
    )
    assert any("update_compression.wire" in v for v in bench.validate_detail(bad4))
    # a non-dict wire must be REPORTED, not crash the validator
    bad5 = dict(
        good,
        update_compression=dict(good["update_compression"], wire=["x"]),
    )
    assert any("wire" in v for v in bench.validate_detail(bad5))
    # ... and so must a non-dict per-codec wire POINT (r12 review fix:
    # previously a TypeError at `key not in point` aborted validation)
    bad6 = dict(
        good,
        update_compression=dict(good["update_compression"], wire={"int8": 42}),
    )
    assert any("update_compression.wire['int8']" in v
               for v in bench.validate_detail(bad6))
    # Round-15 observability arm: error-arm exempt; a present arm must carry
    # the soak contract (audit booleans typed, planes_covered a dict).
    assert bench.validate_detail({"observability": {"error": "boom"}}) == []
    assert any(
        "observability" in v
        for v in bench.validate_detail({"observability": {"audit": {}}})
    )
    obs_ok = {
        "observability": {
            "traffic_wall_s": 8.0,
            "storm_fired": True,
            "federation": {},
            "serve": {},
            "scrape": {"planes_covered": {"fed": True}},
            "spans": {},
            "audit": {
                "torn_versions": 0,
                "zero_torn_versions": True,
                "serve_healthy": True,
                "ef_mass_conserved": True,
                "statefile_restore_bit_identical": True,
                "watermarks_steady": True,
                "recompiles_since_warmup": 0,
                "clean": True,
            },
        }
    }
    assert bench.validate_detail(obs_ok) == []
    obs_bad = json.loads(json.dumps(obs_ok))
    obs_bad["observability"]["audit"]["torn_versions"] = "none"
    assert any(
        "observability.audit['torn_versions']" in v
        for v in bench.validate_detail(obs_bad)
    )
    obs_bad2 = json.loads(json.dumps(obs_ok))
    obs_bad2["observability"]["scrape"]["planes_covered"] = ["fed"]
    assert any(
        "planes_covered" in v for v in bench.validate_detail(obs_bad2)
    )
    # Round-16 tracing/watchdog arms: ABSENT is fine (r15 artifacts predate
    # them), but a present arm must carry the full sub-schema.
    assert bench.validate_detail(obs_ok) == []
    obs_r16 = json.loads(json.dumps(obs_ok))
    obs_r16["observability"]["tracing"] = {
        "records": 100, "traces": 5, "chains": 3, "n_complete": 1,
        "complete": True, "trace": "fedtr-v0",
        "planes_crossed": ["client", "fed", "serve"],
        "stages": ["client.push", "fed.flush", "serve.batch", "serve.swap"],
    }
    obs_r16["observability"]["watchdog"] = {
        "rules_evaluated": 6, "rules": ["a"], "evaluations": 9,
        "never_determinate": [], "all_rules_evaluated": True,
        "breaches": [], "clean": True,
    }
    assert bench.validate_detail(obs_r16) == []
    obs_r16_bad = json.loads(json.dumps(obs_r16))
    del obs_r16_bad["observability"]["tracing"]["complete"]
    assert any(
        "observability.tracing['complete']" in v
        for v in bench.validate_detail(obs_r16_bad)
    )
    obs_r16_bad2 = json.loads(json.dumps(obs_r16))
    obs_r16_bad2["observability"]["watchdog"]["breaches"] = 0
    assert any(
        "observability.watchdog['breaches']" in v
        for v in bench.validate_detail(obs_r16_bad2)
    )


def test_compact_summary_last_line_parses():
    """Round-9 tail-capture fix: whatever size the full payload grows to,
    the FINAL stdout line must be a small, self-contained JSON summary —
    a driver capture once parsed to null because the monolithic payload line
    was truncated by tail-capture. Exercised without a bench run: a
    deliberately bloated payload must compact to a bounded line carrying
    the driver-contract keys."""
    bench = _import_bench()
    fat_detail = {k: {} for k in bench.DETAIL_SCHEMA if k != "skipped"}
    fat_detail["sweep"] = {f"p{i}": {"blob": "x" * 4096} for i in range(64)}
    fat_detail["skipped"] = [{"section": f"s{i}"} for i in range(16)]
    payload = {
        "metric": "m" * 500,
        "value": 123.4,
        "unit": "ms",
        "vs_baseline": 2.5,
        "detail": fat_detail,
        "interrupted": "SIGTERM",
        "schema_violations": ["a", "b"],
    }
    line = json.dumps(bench.compact_summary(payload, "/tmp/art.json"))
    assert len(line) < 4096, f"compact line is {len(line)} bytes"
    summary = json.loads(line)
    assert summary["compact"] is True
    assert set(summary) >= {"metric", "value", "unit", "vs_baseline", "artifact"}
    assert summary["value"] == 123.4 and summary["artifact"] == "/tmp/art.json"
    assert "resident_pool" in summary["sections"]
    assert "detail" not in summary  # the tree is exactly what gets truncated
    assert summary["skipped_n"] == 16
    assert summary["interrupted"] == "SIGTERM"
    assert summary["schema_violations_n"] == 2


def test_emit_prints_compact_summary_as_final_line(tmp_path, capsys, monkeypatch):
    """_emit's stdout contract end to end (in-process): full payload line,
    then the compact summary as the LAST line, with the full payload also
    written to the artifact path the summary points at."""
    bench = _import_bench()
    art = tmp_path / "payload.json"
    monkeypatch.setattr(bench, "BENCH_OUT", str(art))
    bench._set_payload("metric-string", 42.0, 1.5, {"sweep": {}, "skipped": []})
    bench._emit()
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 2
    full = json.loads(lines[0])
    summary = json.loads(lines[-1])
    assert full["value"] == 42.0 and "detail" in full
    assert summary["compact"] is True and summary["value"] == 42.0
    assert summary["artifact"] == str(art)
    with open(art) as f:
        assert json.load(f) == full
    # Idempotence: a signal landing after the normal emit must not double-print.
    bench._emit()
    assert capsys.readouterr().out == ""


def test_committed_bench_artifacts_satisfy_schema():
    """Every committed bench_runs/ artifact that carries a detail payload
    must validate against the declared schema — the contract holds
    retroactively, so consumers can parse any round's artifact. (After
    PR 21 removed the pre-round captures none of the remaining records
    carries a payload; the guard stays for whatever is committed next.)"""
    bench = _import_bench()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    run_dir = os.path.join(root, "bench_runs")
    for name in sorted(os.listdir(run_dir)):
        if not name.endswith(".json"):
            continue
        with open(os.path.join(run_dir, name)) as f:
            try:
                art = json.load(f)
            except ValueError:
                continue
        detail = art.get("detail") if isinstance(art, dict) else None
        if not isinstance(detail, dict):
            continue
        bad = bench.validate_detail(detail)
        assert not bad, f"{name}: {bad}"


def test_cohort_scale_schema_guard():
    """Round-13 cohort_scale arm: declared in DETAIL_SCHEMA, its keys
    written by bench.py, typed checks enforced, error-arm exempt."""
    bench = _import_bench()
    assert "cohort_scale" in bench.DETAIL_SCHEMA
    assert {"groups", "tree", "flat"} <= set(bench.COHORT_SCALE_SCHEMA)
    assert {"round_wall_s", "group_dispatches"} <= set(bench.COHORT_GROUP_SCHEMA)
    with open(bench.__file__) as f:
        src = f.read()
    for key in set(bench.COHORT_SCALE_SCHEMA) | set(bench.COHORT_GROUP_SCHEMA):
        assert f'"{key}"' in src, f"schema key {key!r} never written by bench.py"
    good = {
        "cohort_scale": {
            "groups": {"2": {"round_wall_s": 1.5, "group_dispatches": 2}},
            "tree": {"root_peak_blobs": 32},
            "flat": {"root_peak_blobs": 1024},
        }
    }
    assert bench.validate_detail(good) == []
    # error arm exempt (a failed section still emits a valid artifact)
    assert bench.validate_detail({"cohort_scale": {"error": "boom"}}) == []
    # missing required key reported
    assert any(
        "cohort_scale['flat'] missing" in v
        for v in bench.validate_detail(
            {"cohort_scale": {"groups": {}, "tree": {}}}
        )
    )
    # typed per-group point; a non-dict point is REPORTED, never a crash
    bad = {
        "cohort_scale": {
            "groups": {"2": {"round_wall_s": "slow", "group_dispatches": 2}},
            "tree": {},
            "flat": {},
        }
    }
    assert any("round_wall_s" in v for v in bench.validate_detail(bad))
    bad2 = {"cohort_scale": {"groups": {"2": 42}, "tree": {}, "flat": {}}}
    assert any("groups['2']" in v for v in bench.validate_detail(bad2))
    # compact summary lists the section like any other schema section
    summary = bench.compact_summary({"detail": good})
    assert "cohort_scale" in summary["sections"]


def test_async_federation_schema_guard():
    """Round-14 async_federation arm: declared in DETAIL_SCHEMA, its keys
    written by bench.py, storm arms typed, error-arm exempt."""
    bench = _import_bench()
    assert "async_federation" in bench.DETAIL_SCHEMA
    assert {"storm", "sync_equivalence", "recovery", "trajectory"} <= set(
        bench.ASYNC_FEDERATION_SCHEMA
    )
    assert {"updates_per_sec", "versions_per_min", "accepted_updates"} <= set(
        bench.ASYNC_STORM_ARM_SCHEMA
    )
    with open(bench.__file__) as f:
        src = f.read()
    for key in set(bench.ASYNC_FEDERATION_SCHEMA):
        assert f'"{key}"' in src, f"schema key {key!r} never written by bench.py"
    arm = {
        "wall_s": 1.0,
        "accepted_updates": 6,
        "global_versions": 3,
        "updates_per_sec": 6.0,
        "versions_per_min": 180.0,
    }
    good = {
        "async_federation": {
            "storm": {"sync": dict(arm), "buffered": dict(arm)},
            "sync_equivalence": {"bit_identical": True},
            "recovery": {"global_blob_bit_identical": True},
            "trajectory": {"buffered_final_loss": 0.01},
        }
    }
    assert bench.validate_detail(good) == []
    assert bench.validate_detail({"async_federation": {"error": "boom"}}) == []
    assert any(
        "async_federation['recovery'] missing" in v
        for v in bench.validate_detail(
            {
                "async_federation": {
                    "storm": {"sync": dict(arm), "buffered": dict(arm)},
                    "sync_equivalence": {},
                    "trajectory": {},
                }
            }
        )
    )
    # A missing or mistyped storm arm is REPORTED, never a crash.
    bad = {
        "async_federation": {
            "storm": {"sync": 42, "buffered": dict(arm, updates_per_sec="x")},
            "sync_equivalence": {},
            "recovery": {},
            "trajectory": {},
        }
    }
    violations = bench.validate_detail(bad)
    assert any("storm['sync']" in v for v in violations)
    assert any("updates_per_sec" in v for v in violations)
    summary = bench.compact_summary({"detail": good})
    assert "async_federation" in summary["sections"]


def test_video_serving_schema_guard():
    """Round-19 video-serving arm: error-arm exempt, a present arm fully
    typed, mistyped values reported never crashed, and the compact summary
    lists the section."""
    bench = _import_bench()
    good = {
        "video_serving": {
            "frame": {"size": 192, "frames": 20, "overlap_fraction": 0.9583},
            "stateless": {"wall_s": 0.55, "img_per_s": 36.2},
            "session": {"wall_s": 0.16, "img_per_s": 122.3, "hit_ratio": 0.74},
            "effective_speedup": 4.43,
            "effective_img_per_s": 160.5,
            "speedup_target_met": True,
            "identity": {"frames_checked": 20, "mismatches": 0, "ok": True},
            "swap": {"frame": 13, "identity_after_swap": True},
            "metrics_in_exposition": True,
            "grpc_smoke": {"frames_dropped": 0, "audit": {"ok": True}},
        }
    }
    assert bench.validate_detail(good) == []
    assert bench.validate_detail({"video_serving": {"error": "boom"}}) == []
    # grpc_smoke is nullable (the smoke must not void the in-process A/B).
    nosmoke = dict(good["video_serving"], grpc_smoke=None)
    assert bench.validate_detail({"video_serving": nosmoke}) == []
    assert any(
        "video_serving['identity'] missing" in v
        for v in bench.validate_detail(
            {"video_serving": {k: v for k, v in good["video_serving"].items() if k != "identity"}}
        )
    )
    mistyped = dict(good["video_serving"], effective_speedup="fast")
    assert any(
        "video_serving['effective_speedup']" in v
        for v in bench.validate_detail({"video_serving": mistyped})
    )
    summary = bench.compact_summary({"detail": good})
    assert "video_serving" in summary["sections"]


def test_lowp_kernels_schema_guard():
    """Round-20 lowp_kernels arm: declared in DETAIL_SCHEMA, its keys
    written by bench.py, typed checks enforced, error-arm exempt, malformed
    per-impl points reported — never a TypeError (the r12 wire-map
    contract)."""
    bench = _import_bench()
    assert "lowp_kernels" in bench.DETAIL_SCHEMA
    assert {"impls", "speedup_vs_reference", "interpret_mode"} <= set(
        bench.LOWP_KERNELS_SCHEMA
    )
    assert {"parity_max_abs_diff", "gate"} <= set(bench.LOWP_IMPL_SCHEMA)
    with open(bench.__file__) as f:
        src = f.read()
    for key in set(bench.LOWP_KERNELS_SCHEMA) | set(bench.LOWP_IMPL_SCHEMA):
        assert f'"{key}"' in src, f"schema key {key!r} never written by bench.py"
    impl = {
        "round_s_short": 0.1,
        "round_s_long": 0.4,
        "per_step_ms": 10.0,
        "mfu": 0.01,
        "parity_max_abs_diff": 1e-6,
        "gate": {"passed": True},
    }
    good = {
        "lowp_kernels": {
            "img": 64,
            "interpret_mode": True,
            "fp8_supported": True,
            "flops_per_forward_canonical": 1e9,
            "impls": {"reference": impl, "fused_int8": impl},
            "speedup_vs_reference": {"fused_int8": 0.5},
        }
    }
    assert bench.validate_detail(good) == []
    assert bench.validate_detail({"lowp_kernels": {"error": "boom"}}) == []
    empty = dict(good["lowp_kernels"], impls={})
    assert any(
        "impls" in v for v in bench.validate_detail({"lowp_kernels": empty})
    )
    broken = dict(
        good["lowp_kernels"],
        impls={"reference": impl, "fused_int8": {"gate": "nope"}},
    )
    bad = bench.validate_detail({"lowp_kernels": broken})
    assert bad and all(isinstance(v, str) for v in bad)


def test_elastic_fleet_schema_guard():
    """Round-22 elastic-fleet section: error-arm exempt, a present section
    fully typed per arm (mistypes reported, never crashed), the shadow
    block required, and the compact summary lists the section."""
    bench = _import_bench()
    arm = {
        "replicas_band": [1, 3],
        "completed": 120,
        "shed": 0,
        "dropped": 0,
        "p95_ms": 233.1,
        "wall_s": 8.8,
        "replica_seconds": 13.9,
        "replicas_min": 1,
        "replicas_max": 3,
        "replicas_varied": True,
    }
    good = {
        "elastic_fleet": {
            "profile": "diurnal",
            "rate_rps": 24.0,
            "requests": 120,
            "slo_p95_ms": 1500.0,
            "queue_bound": 10,
            "arms": {
                "static_max": dict(arm, replicas_band=[3, 3], replicas_varied=False),
                "static_min": dict(arm, replicas_band=[1, 1], shed=8, replicas_varied=False),
                "autoscaled": arm,
            },
            "autoscaler": {"scale_ups": 2, "scale_downs": 2},
            "autoscaled_cheaper_than_static_max": True,
            "autoscaled_held_slo": True,
            "static_min_shed": True,
            "shadow": {
                "promote": {"verdict": "promote"},
                "rollback": {"verdict": "rollback"},
                "promoted": True,
                "rolled_back": True,
            },
        }
    }
    assert bench.validate_detail(good) == []
    assert bench.validate_detail({"elastic_fleet": {"error": "boom"}}) == []
    assert any(
        "elastic_fleet['shadow'] missing" in v
        for v in bench.validate_detail(
            {"elastic_fleet": {k: v for k, v in good["elastic_fleet"].items() if k != "shadow"}}
        )
    )
    noarms = dict(good["elastic_fleet"], arms={})
    assert any(
        "elastic_fleet['arms'] is empty" in v
        for v in bench.validate_detail({"elastic_fleet": noarms})
    )
    mistyped = dict(
        good["elastic_fleet"],
        arms=dict(good["elastic_fleet"]["arms"], autoscaled=dict(arm, shed="none")),
    )
    assert any(
        "elastic_fleet.arms['autoscaled']['shed']" in v
        for v in bench.validate_detail({"elastic_fleet": mistyped})
    )
    summary = bench.compact_summary({"detail": good})
    assert "elastic_fleet" in summary["sections"]


def test_privacy_schema_guard():
    """Round-23 privacy section: error-arm exempt, a present section fully
    typed (dp arms, secagg overhead, drill — mistypes reported, never
    crashed), the off arm's epsilon allowed to be None, and the compact
    summary lists the section."""
    bench = _import_bench()
    arm = {
        "noise_multiplier": 1.1,
        "clip_norm": 1.0,
        "epsilon": 1.129401,
        "val_iou": 0.18,
        "val_loss": 0.7,
        "weight_drift_vs_off": 0.17,
    }
    good = {
        "privacy": {
            "rounds": 2,
            "dp_utility": {
                "off": dict(arm, noise_multiplier=0.0, clip_norm=0.0,
                            epsilon=None, weight_drift_vs_off=0.0),
                "sigma_1.1": arm,
            },
            "secagg_overhead": {
                "n_params": 65536,
                "cohort": 3,
                "bits": 24,
                "plaintext_bytes": 262281,
                "masked_bytes": 524416,
                "wire_ratio": 2.0,
                "mask_ms": 1.7,
                "unmask_ms": 1.1,
                "exact_vs_plaintext": True,
            },
            "secagg_drill": {
                "fault_fired": True,
                "dropout_recovered": True,
                "exact_average_bit_for_bit": True,
                "torn_rounds": 0,
            },
            "bench_s": 69.0,
        }
    }
    assert bench.validate_detail(good) == []
    assert bench.validate_detail({"privacy": {"error": "boom"}}) == []
    empty = dict(good["privacy"], dp_utility={})
    assert any(
        "privacy['dp_utility'] is empty" in v
        for v in bench.validate_detail({"privacy": empty})
    )
    mistyped = dict(
        good["privacy"],
        dp_utility=dict(good["privacy"]["dp_utility"],
                        **{"sigma_1.1": dict(arm, epsilon="high")}),
    )
    assert any(
        "privacy.dp_utility['sigma_1.1']" in v
        for v in bench.validate_detail({"privacy": mistyped})
    )
    nodrill = {k: v for k, v in good["privacy"].items() if k != "secagg_drill"}
    assert any(
        "privacy['secagg_drill'] missing" in v
        for v in bench.validate_detail({"privacy": nodrill})
    )
    badbits = dict(
        good["privacy"],
        secagg_overhead=dict(good["privacy"]["secagg_overhead"], bits="24"),
    )
    assert any(
        "privacy.secagg_overhead['bits']" in v
        for v in bench.validate_detail({"privacy": badbits})
    )
    summary = bench.compact_summary({"detail": good})
    assert "privacy" in summary["sections"]
