"""Benchmark: per-step time + MFU sweep, host-plane decomposition, and the
reference-scale one-program round — under a wall-clock budget.

Round 3's lesson: a bench that only proves its claims given unbounded time
proves nothing under a driver — one early driver capture was an empty
timeout. This bench is budget-aware:

- **Sections run value-first**: the {f32, bf16} sweep at the flagship size
  always runs, the reference-scale points (the headline) run IMMEDIATELY
  after it, and the host plane runs after those — round 4's lesson: a slow
  host plane once starved the headline sections out of the driver's budget,
  so the headline now outranks it. The host plane, batch-scaling curve, and
  secondary-size sweep are each gated on a cost estimate fitting the
  remaining budget (the host plane degrades to fewer reps before skipping).
- **`FEDCRACK_BENCH_BUDGET_S`** (default 780 s) is the wall-clock budget.
  When a section doesn't fit, it is SKIPPED and recorded under
  `detail.skipped` with the estimate that excluded it — the JSON always
  prints with everything that WAS measured.
- **SIGTERM/SIGINT safety net**: if the driver kills the run anyway, the
  handler prints the partial JSON before exiting, so even a timeout captures
  every completed section.
- **Exit-code contract** (changed in round 5; the round-3 docs said rc 0 on
  TERM): an interrupted-but-emitted run exits **128+signum** (143 on TERM,
  130 on INT) with the partial JSON already printed and its payload marked
  ``interrupted: <SIGNAME>``. Drivers must treat 128+signum WITH a parsed
  JSON line as "partial artifact", not "failed run" — rc 0 now means only a
  run that completed inside its own budget. (The driver's own timeout
  killing us with SIGKILL still yields rc 137 and whatever was flushed.)
- Expensive measurements are shared: the f32 reference-scale point reuses
  the bf16 point's staged uint8 buffers (transport data is dtype-independent)
  and its staging timings; the sweep's long-scan arrays are tiled from the
  short-scan arrays ON DEVICE (no second host transfer); both dtypes at a
  sweep size share one staged data set.

Measurement design (unchanged from round 3, validated in bench_runs/):

1. **Sweep**: per-step time is the slope of a two-scan-length fit, so the
   fixed per-call dispatch cost is separated out. MFU from an analytic FLOPs model cross-checked against
   XLA's HLO cost analysis (obs/flops.py).
2. **Host plane**: the reference's architecture (Python-dispatched steps +
   serialized weight shipping + host FedAvg, fl_server.py:92-105 /
   fl_client.py:63) measured and decomposed into compute / serialization /
   aggregation / dispatch.
3. **Reference scale**: the reference's true workload — REF_EPOCHS x
   REF_STEPS steps of batch BATCH (client_fit_model.py:166,76) — as one
   program, with uint8 staging and the double-buffered next-round overlap
   driven through `parallel.driver.run_mesh_federation` (the production
   component, not a bench-local loop).
4. **Input pipeline** (round 5): the reference's synchronous per-batch cv2
   decode cost (client_fit_model.py:30-43 runs 16 imread+resize per step
   inside fit), measured on this host and folded into the host-plane
   reconstruction as a separate labeled term — the decode-inclusive
   co-located ratio the round-4 verdict asked for.
5. **Batch curve** (round 5): bf16 flagship per-step/MFU at batch {32, 64}
   from on-device regrouped sweep data — evidence for/against the
   width-bound MFU-ceiling claim (batch 16 stays the parity headline).
6. **Layout A/B** (round 6): the model-graph layout transforms
   (space-to-depth stem, channel-packed residual projections —
   models/resunet.py, exact re-expressions of the same math) vs the
   reference layout, interleaved over shared staged data at the flagship
   size (bf16 + f32) and each secondary size (bf16), with MFU charged on
   canonical reference-topology FLOPs for every variant. Variants via
   FEDCRACK_BENCH_LAYOUTS; artifact schema matches tools/ab_pallas_bce
   (per-variant dicts under "impls", ratios as sibling keys).
7. **Resident-pool A/B** (round 9, detail.resident_pool): streamed
   per-round slab restaging vs the device-resident sample pool with
   index-only uploads (parallel.driver data_placement="resident"), over
   byte-identical batches — the max(compute, staging) roofline collapsing
   to the compute term, with the production driver's RoundRecords pinning
   per-round staged bytes to the gather plan's kilobytes.
8. **Serving SLO** (round 10, detail.serving): the serving plane
   (fedcrack_tpu/serve — compiled per-bucket predict, dynamic
   micro-batching, hot-swap manager, gRPC front door) under tools/load_gen
   closed-loop traffic across every bucket, with one LIVE hot-swap
   installed mid-run — throughput img/s, latency p50/p95/p99, swap
   load/pause, zero-drop accounting.
9. **Update-compression A/B** (round 12, detail.update_compression): the
   three upload codecs (fedcrack_tpu/compress — null / int8 quantized
   delta / top-k sparsified delta with error feedback) priced on REAL
   frame bytes for one reference-scale round delta (encode/decode wall,
   bytes ratio vs the dense blob, null pinned byte-identical), plus the
   mesh twins' crack-IoU trajectory vs the NullCodec oracle with the
   driver's RoundRecord.bytes_per_round counter per codec.

Output contract (round 9): the full payload prints as one JSON line (value =
flagship one-program round wall-clock (ms) at reference scale when measured,
sweep scale otherwise; vs_baseline = host-plane / mesh-plane round time at
equal float32 dtype) and is ALSO written to ``FEDCRACK_BENCH_OUT`` (default
/tmp/fedcrack_bench_payload.json); the FINAL stdout line is a compact
single-line summary (headline metrics + artifact path, no detail tree) that
survives tail-capture — BENCH_r05.json's ``"parsed": null`` was the
monolithic payload line getting truncated. Parse the last line; follow its
``artifact`` pointer (or the second-to-last line) for the full detail.

Env knobs (smoke testing; defaults are the real bench):
FEDCRACK_BENCH_BUDGET_S=780 FEDCRACK_BENCH_STEPS=32 FEDCRACK_BENCH_BATCH=16
FEDCRACK_BENCH_REPS=3 FEDCRACK_BENCH_SIZES=128,256 FEDCRACK_BENCH_FIT_FACTOR=4
FEDCRACK_BENCH_REF_SCALE=auto|1|0 FEDCRACK_BENCH_REF_EPOCHS=10
FEDCRACK_BENCH_REF_STEPS=388 FEDCRACK_BENCH_REF_256=1 (opt-in: the ~10 min
bf16/256 reference-scale point)
FEDCRACK_BENCH_LAYOUTS=reference,s2d,s2d_full,respack,s2d+respack (layout
A/B variants; first is the ratio denominator)
FEDCRACK_BENCH_CHAOS=0 (skip the mid-round kill→restart recovery drill,
detail.chaos_recovery) FEDCRACK_BENCH_OUT=<full-payload artifact path>
(default /tmp/fedcrack_bench_payload.json; "" disables the file write)
FEDCRACK_BENCH_SERVING=0 (skip the serving-plane section)
FEDCRACK_BENCH_SERVE_SIZES=128,256 FEDCRACK_BENCH_SERVE_REQUESTS=128
FEDCRACK_BENCH_SERVE_MAX_BATCH=8 FEDCRACK_BENCH_SERVE_CONCURRENCY=8
FEDCRACK_BENCH_SERVE_FLEET=0 (skip the round-17 fleet/quant section)
FEDCRACK_BENCH_FLEET_REPLICAS=1,2 FEDCRACK_BENCH_FLEET_REQUESTS=64
FEDCRACK_BENCH_FLEET_SHED_RATE=40 (ramp-profile base rate, rps)
FEDCRACK_BENCH_ELASTIC=0 (skip the round-22 elastic-fleet diurnal A/B +
shadow-delivery section, detail.elastic_fleet)
FEDCRACK_BENCH_ELASTIC_REQUESTS=120 FEDCRACK_BENCH_ELASTIC_RATE=24
FEDCRACK_BENCH_COMPRESSION=0 (skip the update-compression A/B)
FEDCRACK_BENCH_COMPRESSION_ROUNDS=3 (mesh-twin trajectory rounds).
FEDCRACK_BENCH_OBSERVABILITY=0 (skip the round-15 concurrent mini-soak)
FEDCRACK_BENCH_SOAK_S=8 (the soak's traffic wall in seconds)
FEDCRACK_BENCH_HEALTH=0 (skip the round-18 federation-health drill,
detail.federation_health)
FEDCRACK_BENCH_ROBUST=0 (skip the round-21 robust-aggregation A/B drill,
detail.robust_aggregation)
FEDCRACK_BENCH_LOWP=0 (skip the round-20 low-precision kernel A/B,
detail.lowp_kernels) FEDCRACK_BENCH_LOWP_IMG=64 (its bucket size)
FEDCRACK_BENCH_LOWP_CALLS=2 (predict calls at the short length; the long
length is FIT_FACTOR x this)
FEDCRACK_BENCH_PRIVACY=0 (skip the round-23 privacy section,
detail.privacy) FEDCRACK_BENCH_PRIVACY_ROUNDS=2 (DP utility A/B rounds)
FEDCRACK_BENCH_PRIVACY_SIGMAS=0.5,1.1 (noise multipliers beside the off
arm)
"""

from __future__ import annotations

import json
import os
import signal
import time

import jax
import numpy as np

STEPS = int(os.environ.get("FEDCRACK_BENCH_STEPS", "32"))
BATCH = int(os.environ.get("FEDCRACK_BENCH_BATCH", "16"))
REPS = int(os.environ.get("FEDCRACK_BENCH_REPS", "3"))
SIZES = tuple(
    int(s) for s in os.environ.get("FEDCRACK_BENCH_SIZES", "128,256").split(",")
)
SEED = 0

# Reference-scale round (the reference's actual workload: 10 local epochs x
# ~388 steps of batch 16 over 6213 images, client_fit_model.py:166,76).
# "auto" runs it on TPU only — at 3,880 steps a CPU smoke run would take
# hours; "1"/"0" force it on/off.
REF_EPOCHS = int(os.environ.get("FEDCRACK_BENCH_REF_EPOCHS", "10"))
REF_STEPS = int(os.environ.get("FEDCRACK_BENCH_REF_STEPS", "388"))
REF_SCALE = os.environ.get("FEDCRACK_BENCH_REF_SCALE", "auto")
REF_256 = os.environ.get("FEDCRACK_BENCH_REF_256", "0") == "1"
# Segment count for the epoch-segmented execution A/B (round 7) and the
# chunked 256 px reference-scale point: K device-resident-carry programs of
# REF_EPOCHS/K epochs each (parallel.fedavg_mesh.SegmentedRound —
# bit-identical to the monolithic scan). Default: one segment per epoch.
SEGMENTS = int(os.environ.get("FEDCRACK_BENCH_SEGMENTS", str(REF_EPOCHS)))

# ---- artifact schema contract -----------------------------------------------
# Consumers (the driver's JSON parse, BASELINE.md updates, cross-round
# comparisons) key on these names; tests/test_bench.py::test_detail_schema_*
# guard them so a rename breaks CI instead of silently breaking artifact
# readers. Every key is OPTIONAL in any given run (budget gating skips
# sections) but, when present, must carry the declared type.
DETAIL_SCHEMA: dict = {
    "sweep": dict,
    "skipped": list,
    "budget": dict,
    "reference_scale": dict,
    "layout_ab": dict,
    "segmented_pipeline": dict,
    "resident_pool": dict,
    "host_plane": dict,
    "batch_curve": dict,
    "input_pipeline": dict,
    "chaos_recovery": dict,
    "serving": dict,
    "serve_fleet": dict,
    "elastic_fleet": dict,
    "update_compression": dict,
    "cohort_scale": dict,
    "async_federation": dict,
    "observability": dict,
    "federation_health": dict,
    "robust_aggregation": dict,
    "video_serving": dict,
    "lowp_kernels": dict,
    "privacy": dict,
}
# Typed keys of detail.observability (round 15): the concurrent mini-soak's
# contract — the self-scrape must cover all five instrumented planes and
# the end-of-soak invariant audit must hold (zero torn versions, EF mass
# conserved, bit-identical statefile restore, steady watermarks).
OBSERVABILITY_SCHEMA: dict = {
    "traffic_wall_s": (int, float),
    "storm_fired": bool,
    "federation": dict,
    "serve": dict,
    "scrape": dict,
    "spans": dict,
    "audit": dict,
}
# Required keys of detail.observability.audit — the gate bench readers and
# the tier-1 guard test read.
OBSERVABILITY_AUDIT_SCHEMA: dict = {
    "torn_versions": int,
    "zero_torn_versions": bool,
    "serve_healthy": bool,
    "ef_mass_conserved": bool,
    "statefile_restore_bit_identical": bool,
    "watermarks_steady": bool,
    "recompiles_since_warmup": int,
    "clean": bool,
}
# Additive round-16 arms of detail.observability — distributed tracing and
# the SLO watchdog. Typed (and sub-schema'd) whenever PRESENT; presence
# itself is required only from round 16 on (the committed r15 artifact
# predates them — the dedicated r16 artifact test pins presence AND the
# ≥3-planes single-trace chain).
OBSERVABILITY_R16_SCHEMA: dict = {
    "tracing": dict,
    "watchdog": dict,
}
# Required keys of detail.observability.tracing: the stitched-trace summary
# (tools/trace_stitch.py over the soak's span JSONL) — `complete` means one
# trace id followed client train → push → flush → swap → first served
# batch, `planes_crossed` lists the span-name planes on that chain.
OBSERVABILITY_TRACING_SCHEMA: dict = {
    "records": int,
    "traces": int,
    "chains": int,
    "n_complete": int,
    "complete": bool,
    "trace": (str, type(None)),
    "planes_crossed": list,
    "stages": list,
}
# Required keys of detail.observability.watchdog: the machine-checked SLO
# audit (obs/watchdog.py) — every rule evaluated, zero breaches = clean.
OBSERVABILITY_WATCHDOG_SCHEMA: dict = {
    "rules_evaluated": int,
    "rules": list,
    "evaluations": int,
    "never_determinate": list,
    "all_rules_evaluated": bool,
    "breaches": list,
    "clean": bool,
}
# Typed keys of detail.federation_health (round 18): the SCALED_UPDATE
# chaos drill — FedAvg's sanitation gate ACCEPTS the norm-bounded-but-
# scaled update (it is finite and well-formed), the per-client ledger's
# robust-z anomaly score flags it, the canary IoU falls off a cliff on the
# poisoned install, and the health SLO watchdog turns that into a breach +
# flight dump + exit-3 verdict. Three sub-blocks, one per plane.
FEDERATION_HEALTH_SCHEMA: dict = {
    "ledger": dict,
    "canary": dict,
    "watchdog": dict,
}
FEDERATION_HEALTH_LEDGER_SCHEMA: dict = {
    "fault_fired": str,
    "poisoned_accepted": bool,
    "honest_accepted": bool,
    "nothing_rejected": bool,
    "global_drag_matches_fedavg": bool,
    "anomaly_scores": dict,
    "alert_threshold": (int, float),
    "poisoned_flagged": bool,
    "honest_below_alert": bool,
    "flagged_flushes": int,
}
FEDERATION_HEALTH_CANARY_SCHEMA: dict = {
    "reference_iou": (int, float),
    "poisoned_iou": (int, float),
    "iou_cliff": bool,
    "swap_still_installed": bool,
    "recompiles_since_warmup": int,
}
FEDERATION_HEALTH_WATCHDOG_SCHEMA: dict = {
    "rules": list,
    "breached": list,
    "both_signals_breached": bool,
    "flight_dumped": bool,
    "breach_exit_code": int,
    "would_exit": int,
}
# Typed keys of detail.robust_aggregation (round 21): the r18
# SCALED_UPDATE scenario as a 4-arm A/B over real gRPC — identical
# poisoned cohort, the only delta being FedConfig.aggregation /
# quarantine_z — plus a 7-client colluding-minority variant and the
# health-report join proving the quarantine exclusion is visible there.
ROBUST_AGGREGATION_SCHEMA: dict = {
    "scale_factor": (int, float),
    "honest_mean": (int, float),
    "reference_iou": (int, float),
    "arms": dict,
    "fedavg_cliffed": bool,
    "robust_arms_hold": bool,
    "drag_reduced_10x": bool,
    "colluding": dict,
    "health_report": dict,
    "drill_s": (int, float),
}
# Keys every arm of detail.robust_aggregation.arms must carry (the
# quarantine arm adds its NOT_WAIT-resync extras on top; robust arms add
# drag_reduction_vs_fedavg — extras are allowed, absences are not).
ROBUST_AGGREGATION_ARM_SCHEMA: dict = {
    "aggregation": str,
    "quarantine_z": (int, float),
    "global_avg": (int, float),
    "drag": (int, float),
    "quarantined": dict,
    "canary_iou": (int, float),
    "serve_factor": (int, float),
}
ROBUST_AGGREGATION_HEALTH_SCHEMA: dict = {
    "schema_violations": list,
    "quarantines": int,
    "quarantined_clients": list,
    "exclusion_visible": bool,
}
# Typed keys of detail.privacy (round 23): the privacy plane's cost model —
# the DP-SGD utility/epsilon trade at 2-3 noise levels on the mesh twin
# (identical data/seeds, the only delta being the noise multiplier), the
# secagg masking overhead vs the plaintext wire (host math: fixed-point
# encode + pairwise pads, with the unmasked mean pinned EXACT against the
# plaintext weighted sum), and the real-gRPC dropped-masker drill.
PRIVACY_SCHEMA: dict = {
    "rounds": int,
    "dp_utility": dict,
    "secagg_overhead": dict,
    "secagg_drill": dict,
    "bench_s": (int, float),
}
# Keys every arm of detail.privacy.dp_utility must carry. `epsilon` is
# None only on the off arm (no noise, nothing to account).
PRIVACY_DP_ARM_SCHEMA: dict = {
    "noise_multiplier": (int, float),
    "clip_norm": (int, float),
    "epsilon": (int, float, type(None)),
    "val_iou": (int, float),
    "val_loss": (int, float),
    "weight_drift_vs_off": (int, float),
}
PRIVACY_SECAGG_OVERHEAD_SCHEMA: dict = {
    "n_params": int,
    "cohort": int,
    "bits": int,
    "plaintext_bytes": int,
    "masked_bytes": int,
    "wire_ratio": (int, float),
    "mask_ms": (int, float),
    "unmask_ms": (int, float),
    "exact_vs_plaintext": bool,
}
# The real-gRPC drill pins the section cannot ship without.
PRIVACY_DRILL_SCHEMA: dict = {
    "fault_fired": bool,
    "dropout_recovered": bool,
    "exact_average_bit_for_bit": bool,
    "torn_rounds": int,
}
# Typed keys of detail.async_federation (round 14): the buffered-async
# contract — the chaos straggler-storm sync-vs-buffered A/B at equal wall,
# the bit-exact sync-degeneration pin, the mid-buffer kill→restart drill,
# and the equal-wall trajectory simulation (the CPU proxy; real-model IoU
# at equal wall is TPU measurement item 7).
ASYNC_FEDERATION_SCHEMA: dict = {
    "storm": dict,
    "sync_equivalence": dict,
    "recovery": dict,
    "trajectory": dict,
}
# Per-arm keys of detail.async_federation.storm.{sync,buffered}.
ASYNC_STORM_ARM_SCHEMA: dict = {
    "wall_s": (int, float),
    "accepted_updates": int,
    "global_versions": int,
    "updates_per_sec": (int, float),
    "versions_per_min": (int, float),
}
# Typed keys of detail.cohort_scale (round 13): the time-multiplexed-cohort
# + hierarchical-tree contract — the group-count sweep's wall scaling, the
# 1,024-simulated-client tree round's memory/byte accounting, and the
# tree-vs-flat A/B.
COHORT_SCALE_SCHEMA: dict = {
    "groups": dict,
    "tree": dict,
    "flat": dict,
}
# Per-point keys of detail.cohort_scale.groups.*.
COHORT_GROUP_SCHEMA: dict = {
    "round_wall_s": (int, float),
    "group_dispatches": int,
}
# Typed keys of detail.update_compression (round 12): the compressed-
# transport A/B contract — real wire bytes + codec timings at reference
# scale, and the mesh-twin crack-IoU trajectory vs the NullCodec oracle.
COMPRESSION_SCHEMA: dict = {
    "dense_update_bytes": int,
    "rounds": int,
    "wire": dict,
    "trajectory": dict,
}
# Per-codec keys of detail.update_compression.wire.*.
COMPRESSION_WIRE_SCHEMA: dict = {
    "bytes_per_round": int,
    "ratio_vs_null": (int, float, type(None)),
    "encode_ms": (int, float),
    "decode_ms": (int, float),
}
# Typed keys of detail.serving (round 10): the serving-plane SLO contract —
# throughput, latency percentiles, zero-drop accounting and the hot-swap
# record that BASELINE.md "Serving SLO" reads.
SERVING_SCHEMA: dict = {
    "throughput_rps": (int, float, type(None)),
    "latency_ms": dict,
    "requests": dict,
    "batcher": dict,
    "swap": (dict, type(None)),
    "dropped": int,
}
# Typed keys of detail.serve_fleet (round 17): the fleet scale-out +
# quantized-predict contract — the replicas x {bf16,int8} throughput/p95
# grid, the fleet-wide two-phase swap (pause + zero torn versions), the
# admission-control shed run under a ramp arrival profile, and the int8
# install gate's verdict.
SERVE_FLEET_SCHEMA: dict = {
    "buckets": list,
    "max_batch": int,
    "grid": dict,
    "swap": dict,
    "shed": dict,
    "quant_gate": (dict, type(None)),
}
# Per-arm keys of detail.serve_fleet.grid.*. `served_quant` records whether
# the arm ACTUALLY served the quantized program (the grid's int8 fleets
# install under a relaxed measurement floor; a false here on an int8 arm
# means even that floor refused and the numbers are the bf16 fallback).
SERVE_FLEET_ARM_SCHEMA: dict = {
    "replicas": int,
    "quant": str,
    "served_quant": bool,
    "requests": int,
    "completed": int,
    "throughput_rps": (int, float, type(None)),
    "p50_ms": (int, float, type(None)),
    "p95_ms": (int, float, type(None)),
}
# Typed keys of detail.elastic_fleet (round 22): the SLO-driven autoscaler
# + shadow-delivery contract — the 3-arm diurnal A/B (static-max holds the
# profile by burning replicas, static-min sheds at the peak, the autoscaled
# arm holds p95 with zero sheds and zero drops at STRICTLY lower
# replica-seconds than static-max), the autoscaler's full action audit,
# and the shadow-replica verdicts (one promote, one rollback, each with
# the deciding iou/psi/latency deltas).
ELASTIC_FLEET_SCHEMA: dict = {
    "profile": str,
    "rate_rps": (int, float),
    "requests": int,
    "slo_p95_ms": (int, float),
    "queue_bound": int,
    "arms": dict,
    "autoscaler": dict,
    "autoscaled_cheaper_than_static_max": bool,
    "autoscaled_held_slo": bool,
    "static_min_shed": bool,
    "shadow": dict,
}
# Per-arm keys of detail.elastic_fleet.arms.*. `replica_seconds` is the
# cost integral: live-replicas x wall for the autoscaled arm (the
# controller's meter), replicas x wall for the static arms. `replicas_*`
# come from load_gen's --metrics-url sampler polling the live
# serve_fleet_replicas gauge — `replicas_varied` True on the autoscaled
# arm is the wire-level proof the fleet actually resized mid-profile.
ELASTIC_ARM_SCHEMA: dict = {
    "replicas_band": list,
    "completed": int,
    "shed": int,
    "dropped": int,
    "p95_ms": (int, float, type(None)),
    "wall_s": (int, float),
    "replica_seconds": (int, float),
    "replicas_min": (int, type(None)),
    "replicas_max": (int, type(None)),
    "replicas_varied": bool,
}
# Required keys of detail.elastic_fleet.shadow: the progressive-delivery
# pins. Each record is a ShadowController verdict — iou vs the production
# payload's canary, drift PSI on the shared probe batch, the shadow-lane
# latency factor, and the reasons that decided it.
ELASTIC_SHADOW_SCHEMA: dict = {
    "promote": dict,
    "rollback": dict,
    "promoted": bool,
    "rolled_back": bool,
}
# Typed keys of detail.video_serving (round 19): the frame-coherent video
# contract — the stateless-vs-cached-session A/B over a seeded
# >=90%-overlap sequence, the per-frame byte-identity audit spanning a
# live mid-sequence hot swap, the effective-throughput model
# (img/s-equiv ~= stateless / changed-tile-fraction), the serve_stream_*
# exposition check, and the StreamPredict gRPC smoke.
VIDEO_SERVING_SCHEMA: dict = {
    "frame": dict,
    "stateless": dict,
    "session": dict,
    "effective_speedup": (int, float, type(None)),
    "effective_img_per_s": (int, float, type(None)),
    "speedup_target_met": bool,
    "identity": dict,
    "swap": dict,
    "metrics_in_exposition": bool,
    "grpc_smoke": (dict, type(None)),
}
# Typed keys of detail.lowp_kernels (round 20): the kernel-plane A/B — the
# r17 reference plane (dequantize-then-matmul in XLA) vs the fused-int8
# Pallas plane (dequant fused into the matmul's K loop; the Pallas
# INTERPRETER off-TPU) vs fp8 where the backend has the dtypes, on the
# round-5 interleaved two-length template. Off-TPU the artifact's value is
# the parity + gate columns (twin correctness); the timing columns become
# a perf claim only on a real TPU (ROADMAP TPU measurement item 10).
LOWP_KERNELS_SCHEMA: dict = {
    "img": int,
    "interpret_mode": bool,
    "fp8_supported": bool,
    "flops_per_forward_canonical": (int, float),
    "impls": dict,
    "speedup_vs_reference": dict,
}
# Per-variant keys of detail.lowp_kernels.impls.*. `parity_max_abs_diff`
# is vs the reference plane's probabilities on the same probe batch (0.0
# for the reference arm by construction); `gate` is the r17 two-phase
# install gate's full verdict for THIS plane's program.
LOWP_IMPL_SCHEMA: dict = {
    "round_s_short": (int, float),
    "round_s_long": (int, float),
    "per_step_ms": (int, float, type(None)),
    "mfu": (int, float, type(None)),
    "parity_max_abs_diff": (int, float),
    "gate": dict,
}
# Per-point keys of detail.reference_scale.* and the per-arm dicts of
# detail.segmented_pipeline.*: the staging/overlap decomposition contract.
REF_POINT_SCHEMA: dict = {
    "round_ms": (int, float),
    "round_plus_restage_ms": (int, float, type(None)),
    "staging_hidden_frac": (int, float, type(None)),
}


def validate_detail(detail: dict) -> list:
    """Schema-contract violations in an emitted ``detail`` payload (empty =
    clean). Pure checks — shared by the bench itself and the tier-1 guard
    test so the contract cannot drift from the code that writes it."""
    bad = []
    for key, typ in DETAIL_SCHEMA.items():
        if key in detail and not isinstance(detail[key], typ):
            bad.append(f"detail[{key!r}] is {type(detail[key]).__name__}, wants {typ}")
    for name, point in (detail.get("reference_scale") or {}).items():
        for key, typs in REF_POINT_SCHEMA.items():
            if key in point and not isinstance(point[key], typs):
                bad.append(f"reference_scale[{name!r}][{key!r}]: {type(point[key]).__name__}")
    for name, ab in (detail.get("segmented_pipeline") or {}).items():
        for arm in ("monolithic", "segmented"):
            for key, typs in REF_POINT_SCHEMA.items():
                val = (ab.get(arm) or {}).get(key)
                if val is not None and not isinstance(val, typs):
                    bad.append(f"segmented_pipeline[{name!r}][{arm}][{key!r}]")
    for name, ab in (detail.get("resident_pool") or {}).items():
        for arm in ("streamed", "resident"):
            for key, typs in REF_POINT_SCHEMA.items():
                val = (ab.get(arm) or {}).get(key)
                if val is not None and not isinstance(val, typs):
                    bad.append(f"resident_pool[{name!r}][{arm}][{key!r}]")
    serving = detail.get("serving")
    if isinstance(serving, dict) and "error" not in serving:
        for key, typs in SERVING_SCHEMA.items():
            if key not in serving:
                bad.append(f"serving[{key!r}] missing")
            elif not isinstance(serving[key], typs):
                bad.append(f"serving[{key!r}]: {type(serving[key]).__name__}")
    fleet = detail.get("serve_fleet")
    if isinstance(fleet, dict) and "error" not in fleet:
        for key, typs in SERVE_FLEET_SCHEMA.items():
            if key not in fleet:
                bad.append(f"serve_fleet[{key!r}] missing")
            elif not isinstance(fleet[key], typs):
                bad.append(f"serve_fleet[{key!r}]: {type(fleet[key]).__name__}")
        grid = fleet.get("grid")
        for name, point in (grid if isinstance(grid, dict) else {}).items():
            if not isinstance(point, dict):
                # Report, never TypeError — the r12 wire-map contract.
                bad.append(f"serve_fleet.grid[{name!r}]: {type(point).__name__}")
                continue
            for key, typs in SERVE_FLEET_ARM_SCHEMA.items():
                if key not in point:
                    bad.append(f"serve_fleet.grid[{name!r}][{key!r}] missing")
                elif not isinstance(point[key], typs):
                    bad.append(
                        f"serve_fleet.grid[{name!r}][{key!r}]: "
                        f"{type(point[key]).__name__}"
                    )
    elastic = detail.get("elastic_fleet")
    if isinstance(elastic, dict) and "error" not in elastic:
        for key, typs in ELASTIC_FLEET_SCHEMA.items():
            if key not in elastic:
                bad.append(f"elastic_fleet[{key!r}] missing")
            elif not isinstance(elastic[key], typs):
                bad.append(f"elastic_fleet[{key!r}]: {type(elastic[key]).__name__}")
        arms = elastic.get("arms")
        if isinstance(arms, dict) and not arms:
            bad.append("elastic_fleet['arms'] is empty")
        for name, point in (arms if isinstance(arms, dict) else {}).items():
            if not isinstance(point, dict):
                # Report, never TypeError — the r12 wire-map contract.
                bad.append(f"elastic_fleet.arms[{name!r}]: {type(point).__name__}")
                continue
            for key, typs in ELASTIC_ARM_SCHEMA.items():
                if key not in point:
                    bad.append(f"elastic_fleet.arms[{name!r}][{key!r}] missing")
                elif not isinstance(point[key], typs):
                    bad.append(
                        f"elastic_fleet.arms[{name!r}][{key!r}]: "
                        f"{type(point[key]).__name__}"
                    )
        shadow = elastic.get("shadow")
        if isinstance(shadow, dict):
            for key, typs in ELASTIC_SHADOW_SCHEMA.items():
                if key not in shadow:
                    bad.append(f"elastic_fleet.shadow[{key!r}] missing")
                elif not isinstance(shadow[key], typs):
                    bad.append(
                        f"elastic_fleet.shadow[{key!r}]: "
                        f"{type(shadow[key]).__name__}"
                    )
    comp = detail.get("update_compression")
    if isinstance(comp, dict) and "error" not in comp:
        for key, typs in COMPRESSION_SCHEMA.items():
            if key not in comp:
                bad.append(f"update_compression[{key!r}] missing")
            elif not isinstance(comp[key], typs):
                bad.append(f"update_compression[{key!r}]: {type(comp[key]).__name__}")
        wire = comp.get("wire")
        for name, point in (wire if isinstance(wire, dict) else {}).items():
            if not isinstance(point, dict):
                # Same contract as the wire map itself: a malformed artifact
                # is REPORTED, never a TypeError aborting validation.
                bad.append(
                    f"update_compression.wire[{name!r}]: {type(point).__name__}"
                )
                continue
            for key, typs in COMPRESSION_WIRE_SCHEMA.items():
                if key not in point:
                    bad.append(f"update_compression.wire[{name!r}][{key!r}] missing")
                elif not isinstance(point[key], typs):
                    bad.append(
                        f"update_compression.wire[{name!r}][{key!r}]: "
                        f"{type(point[key]).__name__}"
                    )
    asyncf = detail.get("async_federation")
    if isinstance(asyncf, dict) and "error" not in asyncf:
        for key, typs in ASYNC_FEDERATION_SCHEMA.items():
            if key not in asyncf:
                bad.append(f"async_federation[{key!r}] missing")
            elif not isinstance(asyncf[key], typs):
                bad.append(
                    f"async_federation[{key!r}]: {type(asyncf[key]).__name__}"
                )
        storm = asyncf.get("storm")
        for arm in ("sync", "buffered"):
            point = (storm if isinstance(storm, dict) else {}).get(arm)
            if not isinstance(point, dict):
                bad.append(
                    f"async_federation.storm[{arm!r}]: "
                    f"{type(point).__name__}"
                )
                continue
            for key, typs in ASYNC_STORM_ARM_SCHEMA.items():
                if key not in point:
                    bad.append(
                        f"async_federation.storm[{arm!r}][{key!r}] missing"
                    )
                elif not isinstance(point[key], typs):
                    bad.append(
                        f"async_federation.storm[{arm!r}][{key!r}]: "
                        f"{type(point[key]).__name__}"
                    )
    obsy = detail.get("observability")
    if isinstance(obsy, dict) and "error" not in obsy:
        for key, typs in OBSERVABILITY_SCHEMA.items():
            if key not in obsy:
                bad.append(f"observability[{key!r}] missing")
            elif not isinstance(obsy[key], typs):
                bad.append(f"observability[{key!r}]: {type(obsy[key]).__name__}")
        audit = obsy.get("audit")
        if isinstance(audit, dict):
            for key, typs in OBSERVABILITY_AUDIT_SCHEMA.items():
                if key not in audit:
                    bad.append(f"observability.audit[{key!r}] missing")
                elif not isinstance(audit[key], typs):
                    bad.append(
                        f"observability.audit[{key!r}]: "
                        f"{type(audit[key]).__name__}"
                    )
        scrape_block = obsy.get("scrape")
        if isinstance(scrape_block, dict):
            planes = scrape_block.get("planes_covered")
            if not isinstance(planes, dict):
                bad.append(
                    f"observability.scrape['planes_covered']: "
                    f"{type(planes).__name__}"
                )
        for key, typs in OBSERVABILITY_R16_SCHEMA.items():
            if key not in obsy:
                continue  # additive from round 16; r15 artifacts predate it
            if not isinstance(obsy[key], typs):
                bad.append(f"observability[{key!r}]: {type(obsy[key]).__name__}")
                continue
            sub_schema = (
                OBSERVABILITY_TRACING_SCHEMA
                if key == "tracing"
                else OBSERVABILITY_WATCHDOG_SCHEMA
            )
            for sub, styps in sub_schema.items():
                if sub not in obsy[key]:
                    bad.append(f"observability.{key}[{sub!r}] missing")
                elif not isinstance(obsy[key][sub], styps):
                    bad.append(
                        f"observability.{key}[{sub!r}]: "
                        f"{type(obsy[key][sub]).__name__}"
                    )
    health = detail.get("federation_health")
    if isinstance(health, dict) and "error" not in health:
        for key, typs in FEDERATION_HEALTH_SCHEMA.items():
            if key not in health:
                bad.append(f"federation_health[{key!r}] missing")
            elif not isinstance(health[key], typs):
                bad.append(
                    f"federation_health[{key!r}]: {type(health[key]).__name__}"
                )
        for block_key, sub_schema in (
            ("ledger", FEDERATION_HEALTH_LEDGER_SCHEMA),
            ("canary", FEDERATION_HEALTH_CANARY_SCHEMA),
            ("watchdog", FEDERATION_HEALTH_WATCHDOG_SCHEMA),
        ):
            block = health.get(block_key)
            if not isinstance(block, dict):
                continue
            for key, typs in sub_schema.items():
                if key not in block:
                    bad.append(
                        f"federation_health.{block_key}[{key!r}] missing"
                    )
                elif not isinstance(block[key], typs):
                    bad.append(
                        f"federation_health.{block_key}[{key!r}]: "
                        f"{type(block[key]).__name__}"
                    )
    robust = detail.get("robust_aggregation")
    if isinstance(robust, dict) and "error" not in robust:
        for key, typs in ROBUST_AGGREGATION_SCHEMA.items():
            if key not in robust:
                bad.append(f"robust_aggregation[{key!r}] missing")
            elif not isinstance(robust[key], typs):
                bad.append(
                    f"robust_aggregation[{key!r}]: "
                    f"{type(robust[key]).__name__}"
                )
        arms = robust.get("arms")
        if isinstance(arms, dict):
            for arm_name in sorted(arms):
                arm = arms[arm_name]
                if not isinstance(arm, dict):
                    # Report, never TypeError: a non-dict arm is its own
                    # violation, not a crash inside the validator.
                    bad.append(
                        f"robust_aggregation.arms[{arm_name!r}]: "
                        f"{type(arm).__name__}"
                    )
                    continue
                for key, typs in ROBUST_AGGREGATION_ARM_SCHEMA.items():
                    if key not in arm:
                        bad.append(
                            f"robust_aggregation.arms[{arm_name!r}]"
                            f"[{key!r}] missing"
                        )
                    elif not isinstance(arm[key], typs):
                        bad.append(
                            f"robust_aggregation.arms[{arm_name!r}]"
                            f"[{key!r}]: {type(arm[key]).__name__}"
                        )
        hp = robust.get("health_report")
        if isinstance(hp, dict):
            for key, typs in ROBUST_AGGREGATION_HEALTH_SCHEMA.items():
                if key not in hp:
                    bad.append(
                        f"robust_aggregation.health_report[{key!r}] missing"
                    )
                elif not isinstance(hp[key], typs):
                    bad.append(
                        f"robust_aggregation.health_report[{key!r}]: "
                        f"{type(hp[key]).__name__}"
                    )
    privacy = detail.get("privacy")
    if isinstance(privacy, dict) and "error" not in privacy:
        for key, typs in PRIVACY_SCHEMA.items():
            if key not in privacy:
                bad.append(f"privacy[{key!r}] missing")
            elif not isinstance(privacy[key], typs):
                bad.append(f"privacy[{key!r}]: {type(privacy[key]).__name__}")
        dp_arms = privacy.get("dp_utility")
        if isinstance(dp_arms, dict):
            if not dp_arms:
                bad.append("privacy['dp_utility'] is empty")
            for arm_name in sorted(dp_arms):
                arm = dp_arms[arm_name]
                if not isinstance(arm, dict):
                    bad.append(
                        f"privacy.dp_utility[{arm_name!r}]: "
                        f"{type(arm).__name__}"
                    )
                    continue
                for key, typs in PRIVACY_DP_ARM_SCHEMA.items():
                    if key not in arm:
                        bad.append(
                            f"privacy.dp_utility[{arm_name!r}]"
                            f"[{key!r}] missing"
                        )
                    elif not isinstance(arm[key], typs):
                        bad.append(
                            f"privacy.dp_utility[{arm_name!r}]"
                            f"[{key!r}]: {type(arm[key]).__name__}"
                        )
        overhead = privacy.get("secagg_overhead")
        if isinstance(overhead, dict):
            for key, typs in PRIVACY_SECAGG_OVERHEAD_SCHEMA.items():
                if key not in overhead:
                    bad.append(f"privacy.secagg_overhead[{key!r}] missing")
                elif not isinstance(overhead[key], typs):
                    bad.append(
                        f"privacy.secagg_overhead[{key!r}]: "
                        f"{type(overhead[key]).__name__}"
                    )
        drill = privacy.get("secagg_drill")
        if isinstance(drill, dict):
            for key, typs in PRIVACY_DRILL_SCHEMA.items():
                if key not in drill:
                    bad.append(f"privacy.secagg_drill[{key!r}] missing")
                elif not isinstance(drill[key], typs):
                    bad.append(
                        f"privacy.secagg_drill[{key!r}]: "
                        f"{type(drill[key]).__name__}"
                    )
    cohort = detail.get("cohort_scale")
    if isinstance(cohort, dict) and "error" not in cohort:
        for key, typs in COHORT_SCALE_SCHEMA.items():
            if key not in cohort:
                bad.append(f"cohort_scale[{key!r}] missing")
            elif not isinstance(cohort[key], typs):
                bad.append(f"cohort_scale[{key!r}]: {type(cohort[key]).__name__}")
        groups = cohort.get("groups")
        for name, point in (groups if isinstance(groups, dict) else {}).items():
            if not isinstance(point, dict):
                bad.append(f"cohort_scale.groups[{name!r}]: {type(point).__name__}")
                continue
            for key, typs in COHORT_GROUP_SCHEMA.items():
                if key not in point:
                    bad.append(f"cohort_scale.groups[{name!r}][{key!r}] missing")
                elif not isinstance(point[key], typs):
                    bad.append(
                        f"cohort_scale.groups[{name!r}][{key!r}]: "
                        f"{type(point[key]).__name__}"
                    )
    video = detail.get("video_serving")
    if isinstance(video, dict) and "error" not in video:
        for key, typs in VIDEO_SERVING_SCHEMA.items():
            if key not in video:
                bad.append(f"video_serving[{key!r}] missing")
            elif not isinstance(video[key], typs):
                bad.append(f"video_serving[{key!r}]: {type(video[key]).__name__}")
    lowp = detail.get("lowp_kernels")
    if isinstance(lowp, dict) and "error" not in lowp:
        for key, typs in LOWP_KERNELS_SCHEMA.items():
            if key not in lowp:
                bad.append(f"lowp_kernels[{key!r}] missing")
            elif not isinstance(lowp[key], typs):
                bad.append(f"lowp_kernels[{key!r}]: {type(lowp[key]).__name__}")
        impls = lowp.get("impls")
        if isinstance(impls, dict) and not impls:
            bad.append("lowp_kernels['impls'] is empty")
        for name, point in (impls if isinstance(impls, dict) else {}).items():
            if not isinstance(point, dict):
                # Report, never TypeError — the r12 wire-map contract.
                bad.append(f"lowp_kernels.impls[{name!r}]: {type(point).__name__}")
                continue
            for key, typs in LOWP_IMPL_SCHEMA.items():
                if key not in point:
                    bad.append(f"lowp_kernels.impls[{name!r}][{key!r}] missing")
                elif not isinstance(point[key], typs):
                    bad.append(
                        f"lowp_kernels.impls[{name!r}][{key!r}]: "
                        f"{type(point[key]).__name__}"
                    )
        if isinstance(impls, dict) and len(impls) >= 2:
            speed = lowp.get("speedup_vs_reference")
            if isinstance(speed, dict):
                for name, val in speed.items():
                    if not isinstance(val, (int, float)):
                        bad.append(
                            f"lowp_kernels.speedup_vs_reference[{name!r}]: "
                            f"{type(val).__name__}"
                        )
    return bad

# Default sized in round 4 from section costs on an access path that no
# longer exists (pre-round captures, removed in PR 21, in git history); not
# re-measured on today's machine. The reference-scale points run right after
# the sweep, so an overrun degrades the TAIL sections — host plane, batch
# curve, 256 sweep — not the headline. The budget belongs to the benchmark
# issue that replaces this file (ROADMAP Speed 1 / D1).
BUDGET_S = float(os.environ.get("FEDCRACK_BENCH_BUDGET_S", "780"))
_START = time.monotonic()

# XLA compile cost assumed for a program this bench has never run on this
# host (no persistent-cache entry); not measured on today's machine. Cost
# estimates for OPTIONAL sections must assume cold —
# round 4's first budget cut assumed warm and blew a wall-clock timeout
# inside the 256 sweep instead of skipping it.
COMPILE_EST_S = 60.0

# Mid-round kill→restart recovery drill (tools/chaos_drill): host-only,
# tiny weights, seconds — times the durable-statefile crash-recovery path
# (round 8). "0" opts out.
CHAOS = os.environ.get("FEDCRACK_BENCH_CHAOS", "1") == "1"

# Compressed update transport A/B (round 12, detail.update_compression):
# real wire bytes + encode/decode timings for the three codecs against one
# reference-scale round delta (host-side, seconds), and the mesh twins'
# crack-IoU trajectory vs the NullCodec oracle over
# FEDCRACK_BENCH_COMPRESSION_ROUNDS rounds of a small federation. "0" opts
# out.
COMPRESSION = os.environ.get("FEDCRACK_BENCH_COMPRESSION", "1") == "1"
COMPRESSION_ROUNDS = int(os.environ.get("FEDCRACK_BENCH_COMPRESSION_ROUNDS", "3"))

# Cohort-scale section (round 13, detail.cohort_scale): the group-count
# sweep over the time-multiplexed cohort round (wall ~linear in
# ceil(C/G) group dispatches, trajectory bitwise equal across splits), and
# the 1,024-simulated-client round through the 2-level aggregation tree
# with root-memory/byte accounting plus a flat-root A/B. "0" opts out.
COHORT = os.environ.get("FEDCRACK_BENCH_COHORT", "1") == "1"
COHORT_TREE_CLIENTS = int(os.environ.get("FEDCRACK_BENCH_COHORT_CLIENTS", "1024"))
COHORT_TREE_FANOUT = int(os.environ.get("FEDCRACK_BENCH_COHORT_FANOUT", "32"))

# Async-federation section (round 14, detail.async_federation): the chaos
# straggler-storm sync-vs-buffered A/B (real gRPC, seeded heavy-tail
# delays, equal wall), the bit-exact sync-degeneration pin (buffer_k=N,
# alpha=0 == sync FedAvg, sha-compared), the buffered mid-buffer
# kill→restart drill, and a deterministic equal-wall trajectory
# simulation. "0" opts out.
ASYNC = os.environ.get("FEDCRACK_BENCH_ASYNC", "1") == "1"

# Observability section (round 15, detail.observability): the concurrent
# mini-soak — buffered federation + edge shard + serve/hot-swap + driver
# leg under a rolling chaos schedule, self-scraped through a live /metrics
# endpoint, ending in the invariant audit. "0" opts out;
# FEDCRACK_BENCH_SOAK_S sizes the traffic wall.
OBSERVABILITY = os.environ.get("FEDCRACK_BENCH_OBSERVABILITY", "1") == "1"
SOAK_S = float(os.environ.get("FEDCRACK_BENCH_SOAK_S", "8"))
ASYNC_SEED = int(os.environ.get("FEDCRACK_BENCH_ASYNC_SEED", "0"))

# Federation-health section (round 18, detail.federation_health): the
# SCALED_UPDATE chaos drill — a sanitation-passing scaled update that
# FedAvg accepts, the per-client ledger's robust-z anomaly flag, the
# canary IoU cliff on the poisoned install, and the health SLO watchdog's
# breach → flight dump → exit-3 verdict. Host + tiny engine, seconds.
# "0" opts out.
HEALTH = os.environ.get("FEDCRACK_BENCH_HEALTH", "1") == "1"

# Robust-aggregation section (round 21, detail.robust_aggregation): the
# SCALED_UPDATE scenario as a 4-arm A/B over real gRPC (fedavg /
# trimmed_mean / krum / fedavg+quarantine — the only delta being
# FedConfig.aggregation), the per-arm canary IoU on one shared tiny
# engine, a 7-client colluding-minority variant, and the health-report
# join over the quarantine arm's ledger. Host + tiny engine, seconds.
# "0" opts out.
ROBUST = os.environ.get("FEDCRACK_BENCH_ROBUST", "1") == "1"

# Privacy section (round 23, detail.privacy): the DP-SGD utility/epsilon
# trade on the mesh twin (off vs FEDCRACK_BENCH_PRIVACY_SIGMAS noise arms,
# identical data/seeds), the secagg fixed-point masking overhead vs the
# plaintext wire with an EXACT unmask pin, and the real-gRPC
# dropped-masker drill. Tiny model; wall is the per-arm mesh compiles.
# "0" opts out.
PRIVACY = os.environ.get("FEDCRACK_BENCH_PRIVACY", "1") == "1"
PRIVACY_ROUNDS = int(os.environ.get("FEDCRACK_BENCH_PRIVACY_ROUNDS", "2"))
PRIVACY_SIGMAS = tuple(
    float(s)
    for s in os.environ.get(
        "FEDCRACK_BENCH_PRIVACY_SIGMAS", "0.5,1.1"
    ).split(",")
    if s.strip()
)

# Low-precision kernel A/B (round 20, detail.lowp_kernels): the quantized
# predict program per kernel plane — reference (the r17 dequantize-then-
# matmul XLA program), fused_int8 (the Pallas dequant-fused plane; the
# interpreter off-TPU), fp8 where the backend has the dtypes — interleaved
# on the r5 two-length template, plus per-plane numerics parity and the
# install gate's verdict. Tiny engine off-TPU, seconds. "0" opts out.
LOWP = os.environ.get("FEDCRACK_BENCH_LOWP", "1") == "1"
LOWP_IMG = int(os.environ.get("FEDCRACK_BENCH_LOWP_IMG", "64"))
LOWP_CALLS = int(os.environ.get("FEDCRACK_BENCH_LOWP_CALLS", "2"))

# Serving-plane SLO section (round 10, detail.serving): boots the full
# serve stack in-process (engine + micro-batcher + hot-swap manager + gRPC
# front door), drives it with tools/load_gen over >= 2 buckets, installs a
# live hot-swap at ~1/3 completions, and reports throughput / latency
# percentiles / swap pause. "0" opts out.
SERVING = os.environ.get("FEDCRACK_BENCH_SERVING", "1") == "1"
SERVE_SIZES = tuple(
    int(s)
    for s in os.environ.get("FEDCRACK_BENCH_SERVE_SIZES", "128,256").split(",")
    if s.strip()
)
SERVE_REQUESTS = int(os.environ.get("FEDCRACK_BENCH_SERVE_REQUESTS", "128"))
SERVE_MAX_BATCH = int(os.environ.get("FEDCRACK_BENCH_SERVE_MAX_BATCH", "8"))
SERVE_CONCURRENCY = int(os.environ.get("FEDCRACK_BENCH_SERVE_CONCURRENCY", "8"))

# Serve-fleet section (round 17, detail.serve_fleet): the replicas x
# {bf16,int8} in-process router grid (throughput + p50/p95 per arm), a
# fleet-wide two-phase swap with torn-version accounting, the gRPC-front-
# door shed run under a load_gen ramp profile against a tight queue bound,
# and the int8 install gate's probe-IoU verdict. "0" opts out.
SERVE_FLEET = os.environ.get("FEDCRACK_BENCH_SERVE_FLEET", "1") == "1"
FLEET_REPLICAS = tuple(
    int(s)
    for s in os.environ.get("FEDCRACK_BENCH_FLEET_REPLICAS", "1,2").split(",")
    if s.strip()
)
FLEET_REQUESTS = int(os.environ.get("FEDCRACK_BENCH_FLEET_REQUESTS", "64"))
FLEET_SHED_RATE = float(os.environ.get("FEDCRACK_BENCH_FLEET_SHED_RATE", "40"))

# Elastic-fleet section (round 22, detail.elastic_fleet): the 3-arm diurnal
# A/B — static-max vs static-min vs autoscaled — through the real gRPC
# front door with load_gen's diurnal profile and its --metrics-url replica
# sampler, plus the shadow-replica progressive-delivery pins (one candidate
# auto-promoted, one deliberately-degraded candidate auto-rolled-back).
# The model is deliberately tiny and every dispatch chaos-throttled: the
# section certifies the CONTROL LOOP (scale before shed, drain without
# drops, strictly fewer replica-seconds than static-max), not model
# throughput. "0" opts out.
ELASTIC = os.environ.get("FEDCRACK_BENCH_ELASTIC", "1") == "1"
ELASTIC_REQUESTS = int(os.environ.get("FEDCRACK_BENCH_ELASTIC_REQUESTS", "120"))
ELASTIC_RATE = float(os.environ.get("FEDCRACK_BENCH_ELASTIC_RATE", "24"))

# Video-serving section (round 19, detail.video_serving): the frame-coherent
# session A/B — stateless predict_tiled vs the per-stream tile-cached
# session over one seeded correlated sequence (>=90% frame-to-frame
# overlap), per-frame byte-identity audit across a live mid-sequence hot
# swap, the serve_stream_* registry exposition, and a StreamPredict gRPC
# smoke via load_gen --profile video. Tiny weights: the section certifies
# cache semantics and the effective-throughput model, not model quality.
# "0" opts out. The default motion fraction (0.04 -> 8 changed rows at 192)
# keeps the moving band inside ~2 of the 7 tile rows, so the steady-state
# changed-tile fraction stays well under 1/3 and the >=3x effective-speedup
# target is geometric, not timing-dependent.
VIDEO = os.environ.get("FEDCRACK_BENCH_VIDEO", "1") == "1"
VIDEO_FRAMES = int(os.environ.get("FEDCRACK_BENCH_VIDEO_FRAMES", "20"))
VIDEO_FRAME_SIZE = int(os.environ.get("FEDCRACK_BENCH_VIDEO_FRAME_SIZE", "192"))
VIDEO_MOTION_FRACTION = float(
    os.environ.get("FEDCRACK_BENCH_VIDEO_MOTION_FRACTION", "0.04")
)

# Longer-round multiplier for the dispatch-correction fit; the two-point
# slope needs the rounds to differ, so 2 is the floor.
FIT_FACTOR = max(2, int(os.environ.get("FEDCRACK_BENCH_FIT_FACTOR", "4")))

# Model-graph layout variants for the interleaved layout A/B (round 6):
# "reference", "s2d" (bit-exact width-folded space-to-depth stem),
# "s2d_full" (fully collapsed stride-1 stem, ~1 ulp), "respack" (channel-
# packed encoder residual projections, bit-exact); combine with "+"
# (e.g. "s2d+respack"). The first variant is the ratio denominator and
# should stay "reference".
LAYOUTS = tuple(
    s.strip()
    for s in os.environ.get(
        "FEDCRACK_BENCH_LAYOUTS", "reference,s2d,s2d_full,respack,s2d+respack"
    ).split(",")
    if s.strip()
)

CLIENTS_AX, BATCH_AX = "clients", "batch"


def _elapsed() -> float:
    return time.monotonic() - _START


def _remaining() -> float:
    return BUDGET_S - _elapsed()


# ---- partial-output machinery ------------------------------------------------
# The payload is rebuilt after every completed section; _emit prints it exactly
# once — at normal completion, or from the SIGTERM/SIGINT handler if the
# driver's own timeout fires first (rc will be 124 then, but the JSON line
# still carries every section that finished).
_OUT: dict = {"emitted": False, "payload": None}

# Where _emit writes the FULL payload as a file (best-effort; "" disables).
# The monolithic stdout payload line can run to hundreds of KB, and
# tail-capturing drivers truncate it (BENCH_r05.json shows "parsed": null
# for exactly that reason) — so the final stdout line is a COMPACT summary
# (headline metrics + this artifact path) that always survives, with the
# full payload printed on the line before it AND written here.
BENCH_OUT = os.environ.get("FEDCRACK_BENCH_OUT", "/tmp/fedcrack_bench_payload.json")


def compact_summary(payload: dict, artifact_path: str | None = None) -> dict:
    """The guaranteed-parseable final stdout line: headline metrics plus a
    pointer to the full-payload artifact, NO detail tree. Stays well under
    any sane line-capture limit regardless of how many sections ran —
    tier-1-tested (tests/test_bench.py) so it cannot regrow a payload."""
    detail = payload.get("detail") or {}
    out = {
        "compact": True,
        "metric": payload.get("metric"),
        "value": payload.get("value"),
        "unit": payload.get("unit"),
        "vs_baseline": payload.get("vs_baseline"),
        "sections": sorted(k for k in detail if k in DETAIL_SCHEMA and k != "skipped"),
        "skipped_n": len(detail.get("skipped") or []),
        "artifact": artifact_path,
    }
    if payload.get("interrupted"):
        out["interrupted"] = payload["interrupted"]
    if payload.get("schema_violations"):
        out["schema_violations_n"] = len(payload["schema_violations"])
    return out


def _set_payload(metric, value, vs_baseline, detail) -> None:
    _OUT["payload"] = {
        "metric": metric,
        "value": value,
        "unit": "ms",
        "vs_baseline": vs_baseline,
        "detail": detail,
    }


def _emit() -> None:
    if not _OUT["emitted"] and _OUT["payload"] is not None:
        _OUT["emitted"] = True
        try:
            # Self-check against the declared artifact schema at write time:
            # a violating payload still emits (a flagged artifact beats a
            # dead run) but carries the violations where consumers and the
            # committed-artifact guard test will surface them.
            bad = validate_detail(_OUT["payload"].get("detail") or {})
            if bad:
                _OUT["payload"]["schema_violations"] = bad
        except Exception:
            pass  # the schema self-check must never kill the artifact
        artifact_path = None
        try:
            if BENCH_OUT:
                with open(BENCH_OUT, "w") as f:
                    json.dump(_OUT["payload"], f)
                artifact_path = BENCH_OUT
        except Exception:
            artifact_path = None  # a read-only fs must never kill the emit
        print(json.dumps(_OUT["payload"]), flush=True)
        # FINAL stdout line: the compact summary — the one line a
        # tail-capturing driver is guaranteed to get whole.
        try:
            print(json.dumps(compact_summary(_OUT["payload"], artifact_path)), flush=True)
        except Exception:
            pass


def _install_signal_net() -> None:
    def handler(signum, frame):
        # Mark the artifact as interrupted (a run killed mid-section must be
        # distinguishable from one where later sections simply never ran) and
        # exit 128+signum so the rc says so too.
        if _OUT["payload"] is not None:
            try:
                name = signal.Signals(signum).name
            except ValueError:
                name = str(signum)
            _OUT["payload"]["interrupted"] = name
        _emit()
        os._exit(128 + signum)

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, handler)
        except (ValueError, OSError):
            pass  # non-main thread / exotic platform: budget checks still cover us


# Install at import time, not in main(): a TERM that lands while jax is still
# initializing the backend would otherwise take the process down with the
# default disposition and zero output.
_install_signal_net()


# ---- transfer/synthesis rate tracking (feeds the cost estimates) -------------
_XFER = {"bytes": 0.0, "s": 0.0}
_SYNTH = {"bytes": 0.0, "s": 0.0}


def _est_stage_s(nbytes: float) -> float:
    bw = _XFER["bytes"] / _XFER["s"] if _XFER["s"] > 0 else 25e6
    return nbytes / max(bw, 1e6)


def _est_synth_s(nbytes: float) -> float:
    rate = _SYNTH["bytes"] / _SYNTH["s"] if _SYNTH["s"] > 0 else 60e6
    return nbytes / max(rate, 1e6)


def _synth(n: int, img: int, seed: int):
    from fedcrack_tpu.data.synthetic import synth_crack_batch

    t0 = time.perf_counter()
    out = synth_crack_batch(n, img_size=img, seed=seed)
    _SYNTH["s"] += time.perf_counter() - t0
    _SYNTH["bytes"] += out[0].nbytes + out[1].nbytes
    return out


def _stage_timed(images, masks, mesh):
    """stage_round_data with the transfer rate recorded for estimates."""
    from fedcrack_tpu.parallel import stage_round_data

    t0 = time.perf_counter()
    si, sm = stage_round_data(images, masks, mesh)
    dt = time.perf_counter() - t0
    _XFER["s"] += dt
    _XFER["bytes"] += images.nbytes + masks.nbytes
    return si, sm, dt


def _stage_timed_chunks(images, masks, mesh, n_chunks: int):
    """Chunked staging with the transfer rate recorded: one device_put +
    barrier per step-range chunk (``data.pipeline.split_epoch_slab``), so no
    single transfer exceeds 1/n_chunks of the epoch slab — the grain the
    segmented round consumes, and the bounded-transfer form of the 1.6 GB
    256 px epoch."""
    from fedcrack_tpu.data.pipeline import split_epoch_slab
    from fedcrack_tpu.parallel import stage_round_data

    t0 = time.perf_counter()
    ic, mc = split_epoch_slab(images, masks, n_chunks)
    pairs = [stage_round_data(i, m, mesh) for i, m in zip(ic, mc)]
    dt = time.perf_counter() - t0
    _XFER["s"] += dt
    _XFER["bytes"] += images.nbytes + masks.nbytes
    return tuple(p[0] for p in pairs), tuple(p[1] for p in pairs), dt


def _fits(est_s: float, reserve_s: float = 15.0) -> bool:
    """Does a section with this cost estimate fit the remaining budget?
    1.2x slack for estimate error plus a flat reserve for the final JSON."""
    return _remaining() > est_s * 1.2 + reserve_s


def _skip(skips: list, section: str, est_s: float, reason: str) -> None:
    skips.append(
        {
            "section": section,
            "est_s": round(est_s, 1),
            "remaining_s": round(_remaining(), 1),
            "reason": reason,
        }
    )


def _median_time(fn, reps: int = REPS) -> float:
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _make_round_runner(round_fn, variables, si, sm, active, n_samples):
    """Chained, readback-synced round at pre-staged data.

    Rounds are CHAINED (each consumes the previous round's output) and synced
    via a host readback of the round metrics: repeating one identical call
    would time nothing a federation does (every real round consumes the
    previous round's weights). The loss depends on every step, so its
    readback is a full-program barrier.
    """
    state = {"v": variables}

    def run():
        new_vars, metrics = round_fn(state["v"], si, sm, active, n_samples)
        state["v"] = new_vars
        float(np.asarray(metrics["loss"])[0])
        return new_vars

    return run


def _tile_steps(x, factor: int, mesh):
    """Cycle a staged [C, steps, B, ...] array to factor x steps ON DEVICE —
    value-identical to stack_client_data's host-side cycling for whole
    multiples, without shipping the duplicated bytes from the host."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(CLIENTS_AX, None, BATCH_AX))
    out = jax.jit(
        lambda a: jnp.concatenate([a] * factor, axis=1), out_shardings=sharding
    )(x)
    jax.block_until_ready(out)
    return out


def _sweep_size(
    img: int, mesh, n_clients: int, device, peak, sweep: dict, checkpoint=None
):
    """Both dtypes at one crop size; returns the per-client float32 sample
    arrays (the host plane reuses them), the f32 initial state, and the
    staged short-scan device arrays (the batch curve regroups them on device
    instead of re-shipping bytes). ``checkpoint`` (if given) is called after
    each completed point so a mid-sweep TERM still ships the points that
    finished."""
    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.obs.flops import mfu, train_step_flops
    from fedcrack_tpu.parallel import build_federated_round, stack_client_data
    from fedcrack_tpu.train.local import create_train_state

    per_client = [
        _synth(STEPS * BATCH, img, SEED + i) for i in range(n_clients)
    ]
    images, masks = stack_client_data(per_client, STEPS, BATCH)
    # One staged data set serves both dtypes (values are dtype-independent);
    # the long-scan arrays are tiled on device from the short ones.
    si, sm, _ = _stage_timed(images, masks, mesh)
    si_long = _tile_steps(si, FIT_FACTOR, mesh)
    sm_long = _tile_steps(sm, FIT_FACTOR, mesh)
    active = np.ones(n_clients, np.float32)

    f32_state0 = None
    for dtype in ("float32", "bfloat16"):
        config = ModelConfig(img_size=img, compute_dtype=dtype)
        state0 = create_train_state(jax.random.key(SEED), config)
        if dtype == "float32":
            f32_state0 = state0
        round_fn = build_federated_round(
            mesh, config, learning_rate=1e-3, local_epochs=1
        )

        def timed(steps, data_i, data_m):
            n_samp = np.full(n_clients, float(steps * BATCH), np.float32)
            run = _make_round_runner(
                round_fn, state0.variables, data_i, data_m, active, n_samp
            )
            # Warm twice: first call consumes the host pytree, second
            # compiles the committed-device-input signature the timed
            # chained reps use.
            run()
            run()
            return _median_time(run)

        short_s = timed(STEPS, si, sm)
        long_s = timed(FIT_FACTOR * STEPS, si_long, sm_long)
        slope_s = (long_s - short_s) / ((FIT_FACTOR - 1) * STEPS)
        # A non-positive slope means timing noise swamped the fit: report
        # the point as unmeasurable (None) rather than publishing a garbage
        # per-step time / absurd MFU as if it were real.
        fit_ok = slope_s > 0.0
        step_s = slope_s if fit_ok else None
        flops = train_step_flops(config, BATCH)
        sweep[f"{dtype}_{img}"] = {
            "dtype": dtype,
            "img_size": img,
            # raw (unrounded) seconds: every derived ratio reads these,
            # so display rounding never leaks into the arithmetic
            "round_s_raw": short_s,
            "per_step_s_raw": step_s,
            "round_ms": round(short_s * 1e3, 2),
            "per_step_ms": round(step_s * 1e3, 3) if fit_ok else None,
            "naive_per_step_ms": round(short_s / STEPS * 1e3, 3),
            "dispatch_intercept_ms": (
                round(max(0.0, short_s - STEPS * step_s) * 1e3, 2)
                if fit_ok
                else None
            ),
            "flops_per_step": flops,
            "mfu": (
                round(mfu(step_s, flops, device), 4)
                if fit_ok and peak is not None
                else None
            ),
        }
        if checkpoint is not None:
            checkpoint()
    return per_client, f32_state0, (si, sm)


def _step_s(point) -> float:
    """Slope-based per-step seconds (raw), falling back to naive when the
    fit failed (the fallback overstates compute, so derived ratios degrade
    conservatively rather than crashing)."""
    if point["per_step_s_raw"] is not None:
        return point["per_step_s_raw"]
    return point["round_s_raw"] / STEPS


def _measure_host_plane(n_clients, variables, per_client, state0, reps=REPS):
    """The reference architecture, decomposed. Returns (total_s, parts).
    ``reps`` shrinks the median sample when the remaining budget is tight
    (a 1-rep host round beats a skipped host plane; the artifact records
    the rep count used)."""
    from fedcrack_tpu.fed.algorithms import fedavg
    from fedcrack_tpu.fed.serialization import tree_from_bytes, tree_to_bytes
    from fedcrack_tpu.train.local import train_step

    mu0 = np.float32(0.0)
    host_vars = {"v": variables}

    def host_round():
        blob = tree_to_bytes(host_vars["v"])  # server -> client broadcast
        uploads = []
        for c in range(n_clients):
            received = tree_from_bytes(blob, template=variables)
            st = state0.replace_variables(received)
            st = st.replace(opt_state=st.tx.init(st.params))
            images, masks = per_client[c]
            for s in range(STEPS):
                batch = (
                    images[s * BATCH : (s + 1) * BATCH],
                    masks[s * BATCH : (s + 1) * BATCH],
                )
                st, _ = train_step(st, batch, received["params"], mu0)
            jax.block_until_ready(st.params)
            uploads.append(tree_to_bytes(st.variables))  # client -> server
        trees = [tree_from_bytes(b, template=variables) for b in uploads]
        avg = fedavg(trees, weights=[float(STEPS * BATCH)] * n_clients)
        jax.block_until_ready(avg)
        host_vars["v"] = jax.device_get(avg)
        return avg

    host_round()  # warm-up: compiles train_step at this shape
    total_s = _median_time(host_round, reps=reps)

    # Serialization cost, measured on the same pytree: per round the host
    # plane serializes 1 broadcast + C uploads and parses 2C blobs
    # (client receive + server receive).
    blob = tree_to_bytes(variables)
    to_s = _median_time(lambda: tree_to_bytes(variables))
    from_s = _median_time(lambda: tree_from_bytes(blob, template=variables))
    ser_s = to_s * (1 + n_clients) + from_s * (2 * n_clients)

    trees = [tree_from_bytes(blob, template=variables) for _ in range(n_clients)]
    fedavg_s = _median_time(
        lambda: jax.block_until_ready(fedavg(trees, weights=[1.0] * n_clients))
    )
    return total_s, {
        "serialization_ms": ser_s * 1e3,
        "host_fedavg_ms": fedavg_s * 1e3,
        # raw per-operation costs, so reconstructions at OTHER client counts
        # (the 1-client reference-scale round) can rebuild serialization for
        # their own shape instead of inheriting this n_clients' total
        "to_bytes_s_raw": to_s,
        "from_bytes_s_raw": from_s,
        "fedavg_s_raw": fedavg_s,
    }


def _batch_curve(
    img: int, mesh, n_clients, device, peak, si, sm, curve: dict, checkpoint=None
):
    """bf16 per-step time + MFU at batch {32, 64} (batch 16 is the sweep's
    flagship point). Substantiates BASELINE.md's width-bound-ceiling claim:
    if the model's 32-256-lane widths are the bottleneck, larger batches
    occupy more MXU rows at the same lane width and MFU should rise.

    Data is the flagship sweep's staged float32 arrays regrouped ON DEVICE
    ([C, S, B, ...] -> [C, S/f, f*B, ...]) — same bytes, same total samples
    per round, zero extra host transfer. Batch 16 remains the parity
    headline (the reference's batch, client_fit_model.py:55-56); this curve
    is a non-parity appendix."""
    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.obs.flops import mfu, train_step_flops
    from fedcrack_tpu.parallel import build_federated_round
    from fedcrack_tpu.train.local import create_train_state

    from jax.sharding import NamedSharding, PartitionSpec as P

    config = ModelConfig(img_size=img, compute_dtype="bfloat16")
    state0 = create_train_state(jax.random.key(SEED), config)
    round_fn = build_federated_round(mesh, config, learning_rate=1e-3, local_epochs=1)
    active = np.ones(n_clients, np.float32)
    sharding = NamedSharding(mesh, P(CLIENTS_AX, None, BATCH_AX))

    for b in (32, 64):
        factor = b // BATCH
        if factor < 1 or b % BATCH:
            continue  # smoke-test batch overrides can make this degenerate
        steps_b = STEPS // factor
        if steps_b < 2 or steps_b * factor != STEPS:
            continue  # regroup must preserve element count (steps override)

        def regroup(a):
            out = jax.jit(
                lambda t: t.reshape(t.shape[0], steps_b, b, *t.shape[3:]),
                out_shardings=sharding,
            )(a)
            jax.block_until_ready(out)
            return out

        bi, bm = regroup(si), regroup(sm)
        bi_long = _tile_steps(bi, FIT_FACTOR, mesh)
        bm_long = _tile_steps(bm, FIT_FACTOR, mesh)
        n_samp = np.full(n_clients, float(steps_b * b), np.float32)

        def timed(data_i, data_m):
            run = _make_round_runner(
                round_fn, state0.variables, data_i, data_m, active, n_samp
            )
            run()
            run()
            return _median_time(run)

        short_s = timed(bi, bm)
        long_s = timed(bi_long, bm_long)
        slope_s = (long_s - short_s) / ((FIT_FACTOR - 1) * steps_b)
        fit_ok = slope_s > 0.0
        flops = train_step_flops(config, b)
        curve[f"bfloat16_{img}_b{b}"] = {
            "dtype": "bfloat16",
            "img_size": img,
            "batch": b,
            "steps": steps_b,
            "round_s_raw": short_s,
            "per_step_s_raw": slope_s if fit_ok else None,
            "round_ms": round(short_s * 1e3, 2),
            "per_step_ms": round(slope_s * 1e3, 3) if fit_ok else None,
            "per_sample_ms": round(slope_s / b * 1e3, 4) if fit_ok else None,
            "flops_per_step": flops,
            "mfu": (
                round(mfu(slope_s, flops, device), 4)
                if fit_ok and peak is not None
                else None
            ),
        }
        del bi, bm, bi_long, bm_long
        if checkpoint is not None:
            checkpoint()


def _layout_config(img: int, dtype: str, variant: str):
    """ModelConfig for a layout-A/B variant token (see ``LAYOUTS``)."""
    from fedcrack_tpu.configs import ModelConfig

    kw: dict = {}
    for tok in variant.split("+"):
        if tok == "reference":
            pass
        elif tok in ("s2d", "s2d_full"):
            kw["stem_layout"] = tok
        elif tok == "respack":
            kw["res_layout"] = "packed"
        else:
            raise ValueError(f"unknown layout variant token {tok!r}")
    return ModelConfig(img_size=img, compute_dtype=dtype, **kw)


def _layout_ab(
    img: int,
    mesh,
    n_clients: int,
    device,
    peak,
    si,
    sm,
    out: dict,
    *,
    dtype: str = "bfloat16",
    round_s_hint: float,
    skips: list,
    checkpoint=None,
):
    """Interleaved A/B of the model-graph layout transforms at one crop size.

    The transforms (ModelConfig.stem_layout / res_layout) are exact
    re-expressions of the same math (models/resunet.py), so the ONLY honest
    question is wall-clock — measured with the same discipline as the
    round-5 Pallas-BCE A/B: every variant's round program is built in one
    process over the SAME staged reference-layout data (the transforms pack
    on device — what a flag flip costs in production), timed at two scan
    lengths with the variants' reps INTERLEAVED (A,B,C,A,B,C,...) so slow
    drift hits all variants equally, slope = per-step time. MFU is charged
    on CANONICAL (reference-topology) FLOPs for every variant — the
    zero-extended kernels' structural-zero MACs are not achievement
    (obs/flops.py) — so the MFU column moves only when wall-clock does.

    Variants are added value-first and budget-gated INDIVIDUALLY: when the
    remaining budget cannot fund the next variant, it is recorded under
    ``skipped`` and the section publishes what it measured (a 2-variant A/B
    beats a skipped section). Artifact schema matches tools/ab_pallas_bce:
    per-variant dicts under ``impls``, derived ratios as sibling keys.
    """
    from fedcrack_tpu.obs.flops import mfu, train_step_flops
    from fedcrack_tpu.parallel import build_federated_round
    from fedcrack_tpu.train.local import create_train_state

    variant_est = (2 + REPS) * (1 + FIT_FACTOR) * max(round_s_hint, 1e-3) + 2 * COMPILE_EST_S
    if not _fits(variant_est * 2):
        # Not even a 2-variant comparison fits — record one skip and spend
        # nothing (not even the long-scan tiling below).
        _skip(
            skips,
            f"layout_ab_{dtype}_{img}",
            variant_est * 2,
            "estimate exceeds remaining budget",
        )
        return

    si_long = _tile_steps(si, FIT_FACTOR, mesh)
    sm_long = _tile_steps(sm, FIT_FACTOR, mesh)
    active = np.ones(n_clients, np.float32)
    n_samp = np.full(n_clients, float(STEPS * BATCH), np.float32)
    n_samp_long = np.full(n_clients, float(FIT_FACTOR * STEPS * BATCH), np.float32)
    # One initial state serves every variant: parameter trees are
    # layout-invariant (the transforms derive kernels in-forward).
    state0 = create_train_state(jax.random.key(SEED), _layout_config(img, dtype, "reference"))

    # Per-variant build + warm, value-first, individually budget-gated. The
    # FIRST variant is priced cold (COMPILE_EST_S); every later variant is priced off the first one's MEASURED
    # build+warm cost — on a warm persistent cache that is seconds, so a
    # second driver run funds the full variant set where the cold estimate
    # alone would starve it (same self-correcting-estimate pattern as
    # _est_stage_s/_est_synth_s).
    runners: dict[str, tuple] = {}
    measured_variant_s = None
    for variant in LAYOUTS:
        est = variant_est if measured_variant_s is None else measured_variant_s
        if not _fits(est * (1 if runners else 2)):
            # The first gate prices TWO variants: a single measured variant
            # has no comparison and would waste its budget.
            _skip(
                skips,
                f"layout_ab_{dtype}_{img}_{variant}",
                est,
                "estimate exceeds remaining budget",
            )
            continue
        t0v = time.monotonic()
        config = _layout_config(img, dtype, variant)
        round_fn = build_federated_round(mesh, config, learning_rate=1e-3, local_epochs=1)
        short = _make_round_runner(round_fn, state0.variables, si, sm, active, n_samp)
        long = _make_round_runner(
            round_fn, state0.variables, si_long, sm_long, active, n_samp_long
        )
        for r in (short, long):
            r()  # compile (host-pytree signature)
            r()  # committed-device-input signature the timed reps use
        runners[variant] = (short, long)
        # build+warm just executed 2 short + 2 long rounds (+ any compile);
        # the interleaved phase adds REPS x (short + long) on top.
        build_warm_s = time.monotonic() - t0v
        measured_variant_s = build_warm_s * (1.0 + REPS / 2.0)

    if len(runners) < 2:
        for variant in runners:
            _skip(
                skips,
                f"layout_ab_{dtype}_{img}",
                variant_est,
                "fewer than 2 variants funded; no comparison possible",
            )
        return

    # Interleaved timed reps: one short pass over all variants, then one
    # long pass, per rep — drift lands on every variant equally.
    shorts: dict[str, list] = {v: [] for v in runners}
    longs: dict[str, list] = {v: [] for v in runners}
    for _ in range(REPS):
        for v, (short, _long) in runners.items():
            shorts[v].append(_median_time(short, 1))
        for v, (_short, long) in runners.items():
            longs[v].append(_median_time(long, 1))

    flops = train_step_flops(_layout_config(img, dtype, "reference"), BATCH)
    impls = {}
    for v in runners:
        short_s = float(np.median(shorts[v]))
        long_s = float(np.median(longs[v]))
        slope = (long_s - short_s) / ((FIT_FACTOR - 1) * STEPS)
        fit_ok = slope > 0.0
        util = mfu(slope, flops, device) if fit_ok and peak is not None else None
        impls[v] = {
            "round_s_short": short_s,
            "round_s_long": long_s,
            "per_step_ms": round(slope * 1e3, 4) if fit_ok else None,
            "mfu": None if util is None else round(util, 4),
        }
    point = {
        "impls": impls,
        "flops_per_step_canonical": flops,
        "note": (
            "MFU charged on canonical (reference-layout) FLOPs for every "
            "variant; staged data is the shared reference-layout arrays "
            "(transforms pack on device — the production flag-flip cost)"
        ),
    }
    ref = impls.get("reference", {})
    if ref.get("per_step_ms"):
        point["speedup_vs_reference"] = {
            v: round(ref["per_step_ms"] / p["per_step_ms"], 4)
            for v, p in impls.items()
            if v != "reference" and p["per_step_ms"]
        }
    out[f"{dtype}_{img}"] = point
    del si_long, sm_long
    if checkpoint is not None:
        checkpoint()


def _bench_lowp_kernels(device, skips: list) -> dict | None:
    """Low-precision kernel-plane A/B (round 20, detail.lowp_kernels).

    One quantized model, one predict program per kernel plane: the r17
    reference (dequantize the int8 codes, then matmul in XLA), the
    round-20 fused-int8 Pallas plane (dequant fused into the matmul's K
    loop — the Pallas INTERPRETER off-TPU: numerics-true, wall-clock-
    meaningless there), and the fp8 plane where the backend has fp8
    dtypes. Discipline is the round-5 Pallas-BCE A/B: every variant's
    engine is built over the SAME weights, timed at two call counts with
    the variants' reps INTERLEAVED so drift hits all arms equally, slope =
    per-forward time; MFU is charged on canonical reference-topology FLOPs
    (obs/flops.py — bit-width changes bytes per MAC, not MACs).

    Each variant additionally records its numerics parity vs the reference
    plane's probabilities and the r17 two-phase install gate's verdict for
    ITS program — off-TPU those columns ARE the artifact's value (twin
    correctness, measured not assumed); the timing columns only become a
    perf claim on a real TPU (ROADMAP TPU measurement item 10). Variants
    are budget-gated individually; fp8 absence on this backend is recorded
    as ``fp8_supported: false``, not a skip (ambient truth, not a budget
    decision). A gate refusal is an honest artifact, not a failure.
    """
    import dataclasses

    from fedcrack_tpu import jaxcompat
    from fedcrack_tpu.configs import ModelConfig, ServeConfig
    from fedcrack_tpu.obs.flops import mfu, resunet_forward_flops
    from fedcrack_tpu.models.resunet import init_variables
    from fedcrack_tpu.serve import quant as quant_mod
    from fedcrack_tpu.serve.engine import InferenceEngine

    on_tpu = getattr(device, "platform", "") == "tpu"
    img = LOWP_IMG
    if on_tpu:
        model_config = ModelConfig(img_size=img, compute_dtype="bfloat16")
    else:
        # The interpreter executes kernel bodies in Python — the full-width
        # model would burn minutes proving nothing this one doesn't.
        model_config = ModelConfig(
            img_size=img,
            stem_features=8,
            encoder_features=(16, 32),
            decoder_features=(32, 16),
        )
    base_cfg = ServeConfig(
        bucket_sizes=(img,),
        max_batch=4,
        max_delay_ms=5.0,
        tile_overlap=0,
        quant="int8",
    )
    variables = init_variables(jax.random.key(SEED), model_config)
    batch = quant_mod.probe_images(img, 4, SEED)
    fp8_ok = bool(jaxcompat.fp8_supported())
    variants = ["reference", "fused_int8"] + (["fp8"] if fp8_ok else [])

    k_short = max(1, LOWP_CALLS)
    k_long = FIT_FACTOR * k_short

    # Per-variant build + gate + warm, reference first (it is the parity
    # oracle AND the speedup denominator — without it the section has no
    # comparison, so the first budget gate prices TWO variants). Later
    # variants are priced off the first one's measured cost (the
    # self-correcting-estimate pattern of _layout_ab).
    runners: dict[str, tuple] = {}
    impls: dict[str, dict] = {}
    probs_ref = None
    variant_est = COMPILE_EST_S + 10.0
    measured_variant_s = None
    for variant in variants:
        est = variant_est if measured_variant_s is None else measured_variant_s
        if not _fits(est * (1 if runners else 2)):
            _skip(
                skips,
                f"lowp_kernels_{variant}",
                est,
                "estimate exceeds remaining budget",
            )
            continue
        t0v = time.monotonic()
        cfg_v = dataclasses.replace(base_cfg, kernel_plane=variant)
        engine = InferenceEngine(model_config, cfg_v)
        ref_payload = engine.prepare(variables)
        q_payload = engine.prepare_quantized(
            quant_mod.quantize_for_plane(variables, engine.effective_kernel_plane)
        )
        gate = quant_mod.quant_gate(engine, ref_payload, q_payload)

        def run_calls(n, _engine=engine, _q=q_payload):
            for _ in range(n):
                _engine.predict_bucket(_q, batch)

        probs = engine.predict_bucket(q_payload, batch)  # warm + parity sample
        t0c = time.perf_counter()
        run_calls(1)  # second warm call — the committed-signature path
        per_call_hint = time.perf_counter() - t0c
        if variant == "reference":
            probs_ref = probs
        parity = (
            0.0
            if variant == "reference"
            else float(
                np.max(
                    np.abs(
                        np.asarray(probs, np.float64)
                        - np.asarray(probs_ref, np.float64)
                    )
                )
            )
        )
        impls[variant] = {
            "parity_max_abs_diff": parity,
            "gate": gate.to_json(),
            "effective_kernel_plane": engine.effective_kernel_plane,
        }
        runners[variant] = run_calls
        build_warm_s = time.monotonic() - t0v
        measured_variant_s = (
            build_warm_s + REPS * (k_short + k_long) * per_call_hint
        )

    if len(runners) < 2:
        for variant in runners:
            _skip(
                skips,
                "lowp_kernels",
                variant_est,
                "fewer than 2 variants funded; no comparison possible",
            )
        return None

    # Interleaved timed reps: one short pass over all variants, then one
    # long pass, per rep — drift lands on every variant equally.
    shorts: dict[str, list] = {v: [] for v in runners}
    longs: dict[str, list] = {v: [] for v in runners}
    for _ in range(REPS):
        for v, run_calls in runners.items():
            shorts[v].append(_median_time(lambda r=run_calls: r(k_short), 1))
        for v, run_calls in runners.items():
            longs[v].append(_median_time(lambda r=run_calls: r(k_long), 1))

    flops = resunet_forward_flops(model_config, int(batch.shape[0]))
    for v in runners:
        short_s = float(np.median(shorts[v]))
        long_s = float(np.median(longs[v]))
        slope = (long_s - short_s) / (k_long - k_short)
        fit_ok = slope > 0.0
        util = mfu(slope, flops, device) if fit_ok else None
        impls[v].update(
            round_s_short=short_s,
            round_s_long=long_s,
            per_step_ms=round(slope * 1e3, 4) if fit_ok else None,
            mfu=None if util is None else round(util, 4),
        )
    ref = impls.get("reference", {})
    speedup = {}
    if ref.get("per_step_ms"):
        speedup = {
            v: round(ref["per_step_ms"] / p["per_step_ms"], 4)
            for v, p in impls.items()
            if v != "reference" and p.get("per_step_ms")
        }
    return {
        "img": img,
        "interpret_mode": not on_tpu,
        "fp8_supported": fp8_ok,
        "calls_short": k_short,
        "calls_long": k_long,
        "flops_per_forward_canonical": flops,
        "impls": impls,
        "speedup_vs_reference": speedup,
        "note": (
            "MFU charged on canonical reference-topology FLOPs for every "
            "plane; off-TPU the fused arms run the Pallas interpreter — "
            "parity + gate columns are the claim there, timing is not"
        ),
    }


def _measure_input_pipeline(img: int) -> dict | None:
    """The reference's synchronous per-step input cost, measured on this host.

    The reference decodes its batch INSIDE the training loop: 16 x
    cv2.imread + cvtColor(BGR2RGB) + resize for images and 16 x imread +
    resize + binarize for masks, in ``__getitem__``, before EVERY step of
    every epoch (client_fit_model.py:30-43; keras Sequence with no
    prefetch workers wired, SURVEY.md §3.3). The host-plane reconstruction
    used to charge the reference ZERO for this (VERDICT round-4 weak #4);
    this section measures it so the co-located ratio can include it as a
    separate, labeled term.

    Measured variants: the reference's verbatim cv2 sequence (when cv2 is
    importable — the reference hard-requires it) and this framework's own
    ``data.pipeline.load_example`` decode (cv2 or PIL+native, whichever
    backend this host has). Source resolutions 227 and 448 px bracket
    public crack-segmentation datasets (SDNET2018-style 256-class patches
    to khanhha-style 448 tiles); the CHARGED term is the cheapest measured
    variant at the smallest source — a conservative lower bound.
    """
    import tempfile

    try:
        import cv2
    except Exception:
        cv2 = None

    out: dict = {"batch": BATCH, "target_px": img, "variants": {}}
    with tempfile.TemporaryDirectory() as td:
        for src in (227, 448):
            imgs_f, masks_f = _synth(BATCH, src, SEED)
            u8 = np.clip(imgs_f * 255.0, 0, 255).astype(np.uint8)
            m8 = (masks_f[..., 0] > 0.5).astype(np.uint8) * 255
            img_paths, mask_paths = [], []
            for i in range(BATCH):
                ip = os.path.join(td, f"img_{src}_{i}.jpg")
                mp = os.path.join(td, f"mask_{src}_{i}.png")
                if cv2 is not None:
                    cv2.imwrite(ip, cv2.cvtColor(u8[i], cv2.COLOR_RGB2BGR))
                    cv2.imwrite(mp, m8[i])
                else:
                    from PIL import Image

                    Image.fromarray(u8[i]).save(ip, quality=95)
                    Image.fromarray(m8[i]).save(mp)
                img_paths.append(ip)
                mask_paths.append(mp)

            variants = {}
            if cv2 is not None:

                def ref_step():
                    np.array(
                        [
                            cv2.resize(
                                cv2.cvtColor(cv2.imread(p, -1), cv2.COLOR_BGR2RGB),
                                (img, img),
                            )
                            for p in img_paths
                        ]
                    ) / 255
                    np.expand_dims(
                        np.array(
                            [
                                (cv2.resize(cv2.imread(p, -1), (img, img)) > 0).astype(
                                    np.uint8
                                )
                                for p in mask_paths
                            ]
                        ),
                        -1,
                    )

                ref_step()
                variants["reference_cv2"] = _median_time(ref_step, reps=5)

            from fedcrack_tpu.data.pipeline import load_example

            def our_step():
                for ip, mp in zip(img_paths, mask_paths):
                    load_example(ip, mp, img_size=img, transport_dtype="uint8")

            our_step()
            variants["framework_load_sample"] = _median_time(our_step, reps=5)
            out["variants"][f"src{src}"] = {
                k: {
                    "batch_ms": round(v * 1e3, 2),
                    "per_image_ms": round(v / BATCH * 1e3, 3),
                }
                for k, v in variants.items()
            }

    candidates = [
        v * 1e-3
        for by_src in out["variants"].values()
        for v in [x["batch_ms"] for x in by_src.values()]
    ]
    if not candidates:
        return None
    out["charged_per_step_s_raw"] = min(candidates)
    out["charged_per_step_ms"] = round(out["charged_per_step_s_raw"] * 1e3, 2)
    out["note"] = (
        "charged term = cheapest measured variant (conservative bound for "
        "the reference's per-step input cost); the mesh plane decodes each "
        "image once into a uint8 pool and restages it overlapped "
        "(parallel.driver), so its per-step input cost is ~0"
    )
    return out


def _ref_host_arrays(img: int):
    """One epoch of uint8 transport data in the round layout, PLUS the
    deduplicated unique pool it was cycled from (the resident-pool A/B
    gathers from that pool by the same cycling plan, so both arms train on
    byte-identical batches). 512 distinct syntheses cycled to the full
    epoch: timing is value-independent, and 6k unique syntheses would
    dominate host time for no fidelity gain — but the STAGED volume is the
    epoch's real data volume (unique data would ship the same bytes)."""
    from fedcrack_tpu.data.pipeline import to_uint8_transport
    from fedcrack_tpu.parallel import stack_client_data

    n_unique = min(512, REF_STEPS * BATCH)
    imgs_f, msks_f = _synth(n_unique, img, SEED)
    imgs_u8, msks_u8 = to_uint8_transport(imgs_f, msks_f)
    # stack_client_data cycles the unique pool to the full epoch length.
    images, masks = stack_client_data([(imgs_u8, msks_u8)], REF_STEPS, BATCH)
    return images, masks, (imgs_u8, msks_u8)


def _bench_reference_scale(
    img: int,
    dtype: str,
    device,
    mesh,
    *,
    full: bool = True,
    reuse: dict | None = None,
    segments: int = 0,
):
    """One-program federated round at the reference's true workload:
    REF_EPOCHS local epochs over REF_STEPS batches of BATCH, single client,
    uint8 transport staging.

    Decomposition reported:
    - ``staging_ms``: host->device transfer of one epoch's uint8 data,
      synced via a transfer barrier;
    - ``round_ms``: the chained round program on pre-staged data — at
      ~REF_EPOCHS*REF_STEPS steps the fixed dispatch cost is <2% of the
      round, so the naive per-step division is finally honest;
    - ``round_plus_restage_ms``: rounds driven through
      ``parallel.driver.run_mesh_federation`` with per-round restaging
      overlapped against the in-flight round (double buffering) — the
      production overlap pattern; ``staging_hidden_frac`` is how much of
      the staging cost the overlap hides.

    ``full=False`` measures only the round time and inherits staging/overlap
    numbers from ``reuse`` (the flagship point): the staged uint8 bytes are
    dtype-independent, so re-measuring transfers for the f32 ratio point
    would spend budget re-learning the same number.

    ``segments > 0`` runs the round through the epoch-segmented execution
    (``build_federated_round_segments``, bit-identical weights): each
    compiled program is REF_STEPS*REF_EPOCHS/segments steps — the chunked
    form that compiles at 256 px where the 3,880-step monolith fails
    (VERDICT r5 #6) — and ``run_mesh_federation`` streams the restage one
    chunk per in-flight segment.

    Returns ``(point_dict, reuse_dict)``; point_dict is None if the budget
    ran out after warmup (the partial JSON then omits this point).
    """
    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.obs.flops import mfu, train_step_flops
    from fedcrack_tpu.parallel import (
        build_federated_round,
        build_federated_round_segments,
        run_mesh_federation,
    )
    from fedcrack_tpu.train.local import create_train_state

    config = ModelConfig(img_size=img, compute_dtype=dtype)
    state0 = create_train_state(jax.random.key(SEED), config)
    if segments:
        round_fn = build_federated_round_segments(
            mesh, config, learning_rate=1e-3, local_epochs=REF_EPOCHS,
            segments=segments,
        )
    else:
        round_fn = build_federated_round(
            mesh, config, learning_rate=1e-3, local_epochs=REF_EPOCHS
        )
    if reuse is None:
        images, masks, pool_u8 = _ref_host_arrays(img)
        if segments:
            si, sm, init_stage_s = _stage_timed_chunks(images, masks, mesh, segments)
        else:
            si, sm, init_stage_s = _stage_timed(images, masks, mesh)
        reuse = {
            "images": images,
            "masks": masks,
            "pool": pool_u8,
            "si": si,
            "sm": sm,
            "stage_s": init_stage_s,
            "overlap": None,
        }
    images, masks = reuse["images"], reuse["masks"]
    si, sm = reuse["si"], reuse["sm"]

    active = np.ones(1, np.float32)
    n_samp = np.full(1, float(REF_STEPS * BATCH), np.float32)
    run = _make_round_runner(round_fn, state0.variables, si, sm, active, n_samp)

    # Warmup + settle: two warm rounds (compile / host-pytree consumption +
    # committed signature) + a 2 s drain; warm-round wall-clocks are recorded
    # so a contaminated measurement is visible in the artifact rather than
    # silent.
    warm_walls = []
    for _ in range(2):
        t0 = time.perf_counter()
        run()
        warm_walls.append(round(time.perf_counter() - t0, 3))
    time.sleep(2.0)

    reps = max(1, min(REPS, 3))
    round_est = warm_walls[-1] * reps
    if _remaining() < round_est + 10.0:
        return None, reuse  # budget died mid-point; emit without this entry
    round_s = _median_time(run, reps=reps)

    total_steps = REF_EPOCHS * REF_STEPS
    step_s = round_s / total_steps
    flops = train_step_flops(config, BATCH)
    util = mfu(step_s, flops, device)
    point = {
        "img_size": img,
        "dtype": dtype,
        "epochs": REF_EPOCHS,
        "steps_per_epoch": REF_STEPS,
        "batch": BATCH,
        "total_steps": total_steps,
        "segments": segments,
        "staging_bytes": int(images.nbytes + masks.nbytes),
        "warm_round_walls_s": warm_walls,
        "round_s_raw": round_s,
        "round_ms": round(round_s * 1e3, 2),
        "per_step_ms": round(step_s * 1e3, 3),
        "mfu": None if util is None else round(util, 4),
    }

    if full:
        if segments:
            stage_s = _median_time(
                lambda: _stage_timed_chunks(images, masks, mesh, segments), reps=2
            )
        else:
            stage_s = _median_time(lambda: _stage_timed(images, masks, mesh), reps=2)
        time.sleep(2.0)  # drain staging traffic before the overlap phase
        # Double-buffered multi-round federation through the PACKAGE driver:
        # data_fn re-returns the epoch arrays, so every round restages while
        # the previous round computes — per-round wall is max(round, staging)
        # plus the unhidden residue.
        overlap_rounds = reps + 1
        timeline = None
        if _remaining() > (overlap_rounds * max(stage_s, round_s)) * 1.2 + 10.0:
            _, records = run_mesh_federation(
                round_fn,
                state0.variables,
                lambda r: (images, masks, active, n_samp),
                overlap_rounds,
                mesh,
            )
            walls = [r.wall_clock_s for r in records[:-1]]  # last round: no restage
            overlap_s = float(np.median(walls[1:] if len(walls) > 2 else walls))
            if segments and len(records) > 1:
                # Segmented path: the driver's per-segment host timeline
                # (dispatch + the next-round chunk transfer that rode under
                # each segment) from a post-compile overlapped round.
                timeline = list(records[1].segments)
        else:
            overlap_s = None
        reuse = dict(reuse, stage_s=stage_s, overlap=overlap_s)
        hidden = (
            (stage_s + round_s - overlap_s) / stage_s
            if (overlap_s is not None and stage_s > 0)
            else None
        )
        point.update(
            {
                "round_plus_restage_ms": (
                    None if overlap_s is None else round(overlap_s * 1e3, 2)
                ),
                "staging_hidden_frac": (
                    None if hidden is None else round(max(0.0, min(1.0, hidden)), 3)
                ),
            }
        )
        if timeline is not None:
            point["segment_timeline"] = timeline
    else:
        # Staging cost is dtype-independent (same uint8 bytes) and inherited;
        # the overlap decomposition is NOT re-derived here — it would mix the
        # flagship's overlapped wall with this dtype's round time.
        stage_s = reuse["stage_s"]
        point["staging_shared_with_flagship"] = True
    point.update(
        {
            "staging_s_raw": stage_s,
            "staging_ms": round(stage_s * 1e3, 2),
        }
    )
    return point, reuse


def _bench_segmented_pipeline(
    img: int,
    dtype: str,
    device,
    mesh,
    reuse: dict,
    mono_point: dict,
    *,
    with_overlap: bool = True,
):
    """Monolithic vs epoch-segmented round execution at reference scale
    (round 7's deliverable): the same REF_EPOCHS x REF_STEPS trajectory run
    as K= SEGMENTS device-resident-carry programs with chunk-grain streamed
    restaging, against the monolithic one-program round already measured in
    ``reference_scale``. The weights are bit-identical by construction
    (test-pinned), so the ONLY honest question is the pipeline: dispatch
    overhead of K programs vs 1, and how much of the restage hides under
    compute at segment grain vs round grain (``staging_hidden_frac``).

    Reuses the monolithic point's staged buffers and host arrays (same
    uint8 bytes); ``with_overlap=False`` measures only the compute round
    (the f32 arm mirrors the monolithic f32 point's asymmetry). Returns
    None when the budget dies mid-measurement.
    """
    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.parallel import (
        build_federated_round_segments,
        run_mesh_federation,
    )
    from fedcrack_tpu.train.local import create_train_state

    k = SEGMENTS if SEGMENTS > 0 and REF_EPOCHS % SEGMENTS == 0 else REF_EPOCHS
    config = ModelConfig(img_size=img, compute_dtype=dtype)
    state0 = create_train_state(jax.random.key(SEED), config)
    seg_round = build_federated_round_segments(
        mesh, config, learning_rate=1e-3, local_epochs=REF_EPOCHS, segments=k
    )
    images, masks = reuse["images"], reuse["masks"]
    si, sm = reuse["si"], reuse["sm"]
    active = np.ones(1, np.float32)
    n_samp = np.full(1, float(REF_STEPS * BATCH), np.float32)
    run = _make_round_runner(seg_round, state0.variables, si, sm, active, n_samp)

    warm_walls = []
    for _ in range(2):
        t0 = time.perf_counter()
        run()
        warm_walls.append(round(time.perf_counter() - t0, 3))
    time.sleep(2.0)
    reps = max(1, min(REPS, 3))
    if _remaining() < warm_walls[-1] * reps + 10.0:
        return None
    seg_round_s = _median_time(run, reps=reps)

    stage_s = reuse.get("stage_s")
    overlap_s = None
    timeline = None
    if with_overlap and stage_s:
        overlap_rounds = reps + 1
        if _remaining() > (overlap_rounds * max(stage_s, seg_round_s)) * 1.2 + 10.0:
            _, records = run_mesh_federation(
                seg_round,
                state0.variables,
                lambda r: (images, masks, active, n_samp),
                overlap_rounds,
                mesh,
            )
            walls = [r.wall_clock_s for r in records[:-1]]
            overlap_s = float(np.median(walls[1:] if len(walls) > 2 else walls))
            if len(records) > 1:
                timeline = list(records[1].segments)

    hidden = (
        (stage_s + seg_round_s - overlap_s) / stage_s
        if (overlap_s is not None and stage_s)
        else None
    )
    segmented = {
        "round_ms": round(seg_round_s * 1e3, 2),
        "per_step_ms": round(seg_round_s / (REF_EPOCHS * REF_STEPS) * 1e3, 3),
        "warm_round_walls_s": warm_walls,
        "round_plus_restage_ms": (
            None if overlap_s is None else round(overlap_s * 1e3, 2)
        ),
        "staging_hidden_frac": (
            None if hidden is None else round(max(0.0, min(1.0, hidden)), 3)
        ),
    }
    if timeline is not None:
        segmented["segment_timeline"] = timeline
    out = {
        "segments": k,
        "segment_epochs": REF_EPOCHS // k,
        "img_size": img,
        "dtype": dtype,
        "monolithic": {
            "round_ms": mono_point["round_ms"],
            "round_plus_restage_ms": mono_point.get("round_plus_restage_ms"),
            "staging_hidden_frac": mono_point.get("staging_hidden_frac"),
        },
        "segmented": segmented,
        "round_speedup_mono_over_seg": round(
            mono_point["round_s_raw"] / seg_round_s, 4
        ),
        "note": (
            "same trajectory bit-for-bit (SegmentedRound exactness contract); "
            "the comparison is pure pipeline — K-program dispatch overhead vs "
            "chunk-grain staged-transfer streaming"
        ),
    }
    mono_wall = mono_point.get("round_plus_restage_ms")
    seg_wall = segmented["round_plus_restage_ms"]
    if mono_wall and seg_wall:
        out["round_plus_restage_speedup"] = round(mono_wall / seg_wall, 4)
    return out


def _bench_resident_pool(img: int, dtype: str, device, mesh, reuse: dict, mono_point: dict):
    """Streamed vs device-resident data plane at reference scale (round 9).

    The streamed arm (the monolithic point already measured in
    ``reference_scale``) re-stages the full uint8 epoch slab every round;
    the resident arm stages the deduplicated sample pool ONCE
    (``data.pipeline.SamplePool``) and per round ships only the
    ``[1, epochs, steps, batch]`` int32 gather plan — the round program
    assembles batches on device by ``jnp.take``. Both arms train on
    byte-identical batches (the gather plan cycles the same unique pool the
    streamed slab was assembled from; trajectory equality is test-pinned in
    tests/test_resident.py), so the ONLY honest question is the pipeline:
    per-round wall with the staging term collapsed from the slab's seconds
    to the plan's kilobytes — the roofline dropping from
    max(compute, staging) to the compute term (BASELINE.md "Resident data
    plane"). The overlapped arm runs through the production driver
    (``run_mesh_federation(data_placement="resident")``), whose
    ``RoundRecord``s also pin the per-round driver-staged bytes
    (indices only after round 0).

    Returns None when the budget dies mid-measurement.
    """
    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.data.pipeline import SamplePool
    from fedcrack_tpu.parallel import (
        build_federated_round,
        run_mesh_federation,
        stage_round_indices,
    )
    from fedcrack_tpu.train.local import create_train_state

    pool_u8 = reuse.get("pool")
    if pool_u8 is None:
        return None
    pool = SamplePool(pool_u8[0][None], pool_u8[1][None])
    n_unique = pool.n_samples
    config = ModelConfig(img_size=img, compute_dtype=dtype)
    state0 = create_train_state(jax.random.key(SEED), config)
    round_fn = build_federated_round(
        mesh, config, learning_rate=1e-3, local_epochs=REF_EPOCHS,
        data_placement="resident",
    )
    # Gather plan reproducing the streamed arm's cycled slab byte for byte:
    # stack_client_data cycles via np.resize(arange(n_unique)), tiled over
    # the epochs axis exactly like the slab is reused per local epoch.
    plan = np.resize(np.arange(n_unique, dtype=np.int32), REF_STEPS * BATCH)
    idx = np.ascontiguousarray(
        np.broadcast_to(
            plan.reshape(1, 1, REF_STEPS, BATCH),
            (1, REF_EPOCHS, REF_STEPS, BATCH),
        ).astype(np.int32)
    )

    t0 = time.perf_counter()
    pool_dev = pool.stage(mesh)
    pool_stage_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    idx_dev = stage_round_indices(idx, mesh)
    idx_stage_s = time.perf_counter() - t0

    active = np.ones(1, np.float32)
    n_samp = np.full(1, float(REF_STEPS * BATCH), np.float32)
    run = _make_round_runner(round_fn, state0.variables, pool_dev, idx_dev, active, n_samp)
    warm_walls = []
    for _ in range(2):
        t0 = time.perf_counter()
        run()
        warm_walls.append(round(time.perf_counter() - t0, 3))
    time.sleep(2.0)
    reps = max(1, min(REPS, 3))
    if _remaining() < warm_walls[-1] * reps + 10.0:
        return None
    res_round_s = _median_time(run, reps=reps)

    # Overlapped rounds through the production driver: per-round wall with
    # only the next plan staging under the in-flight round, and the honest
    # staged-bytes accounting straight off the RoundRecords.
    overlap_s = None
    driver_staged = None
    max_live = None
    overlap_rounds = reps + 1
    if _remaining() > overlap_rounds * res_round_s * 1.2 + 10.0:
        _, records = run_mesh_federation(
            round_fn,
            state0.variables,
            lambda r: (idx, active, n_samp),
            overlap_rounds,
            mesh,
            data_placement="resident",
            sample_pool=pool,
        )
        walls = [r.wall_clock_s for r in records[:-1]]
        overlap_s = float(np.median(walls[1:] if len(walls) > 2 else walls))
        driver_staged = [int(r.staged_bytes) for r in records]
        max_live = max(int(r.max_live_staged_bytes) for r in records)

    slab_bytes = int(reuse["images"].nbytes + reuse["masks"].nbytes)
    slab_stage_s = reuse.get("stage_s")
    hidden = (
        (idx_stage_s + res_round_s - overlap_s) / idx_stage_s
        if (overlap_s is not None and idx_stage_s > 0)
        else None
    )
    out = {
        "img_size": img,
        "dtype": dtype,
        "epochs": REF_EPOCHS,
        "steps_per_epoch": REF_STEPS,
        "pool_unique_samples": n_unique,
        "pool_bytes": pool.nbytes,
        "pool_stage_ms": round(pool_stage_s * 1e3, 2),
        "slab_bytes": slab_bytes,
        "idx_bytes_per_round": int(idx.nbytes),
        "staged_bytes_ratio": round(idx.nbytes / slab_bytes, 8),
        "driver_staged_bytes_per_round": driver_staged,
        "max_live_staged_bytes": max_live,
        "streamed": {
            "round_ms": mono_point["round_ms"],
            "round_plus_restage_ms": mono_point.get("round_plus_restage_ms"),
            "staging_hidden_frac": mono_point.get("staging_hidden_frac"),
            "staging_ms": mono_point.get("staging_ms"),
        },
        "resident": {
            "round_ms": round(res_round_s * 1e3, 2),
            "warm_round_walls_s": warm_walls,
            "round_plus_restage_ms": (
                None if overlap_s is None else round(overlap_s * 1e3, 2)
            ),
            "staging_hidden_frac": (
                None if hidden is None else round(max(0.0, min(1.0, hidden)), 3)
            ),
            "staging_ms": round(idx_stage_s * 1e3, 3),
        },
        "roofline": {
            "streamed_floor_s": round(
                max(mono_point["round_s_raw"], slab_stage_s or 0.0), 3
            ),
            "resident_floor_s": round(res_round_s, 3),
            "note": (
                "streamed wall >= max(compute, slab staging); resident wall "
                ">= compute — the index upload is kilobytes, so the staging "
                "roofline term vanishes (pool charged once)"
            ),
        },
        "note": (
            "identical data both arms: the resident gather plan cycles the "
            "same deduplicated pool the streamed slab was assembled from, so "
            "every batch is byte-identical; pool staged once (pool_stage_ms), "
            "indices per round (idx_bytes_per_round)"
        ),
    }
    streamed_wall = mono_point.get("round_plus_restage_ms")
    resident_wall = out["resident"]["round_plus_restage_ms"]
    if streamed_wall and resident_wall:
        out["round_plus_restage_speedup"] = round(streamed_wall / resident_wall, 4)
    return out


def _bench_serving(device) -> dict:
    """Serving-plane SLO measurement (round 10, detail.serving).

    The full production stack in one process: ``InferenceEngine`` (one
    compiled program per bucket), ``MicroBatcher`` (dynamic micro-batching),
    ``ModelVersionManager`` (hot swap), the gRPC ``ServePlane/Predict``
    front door, and ``tools/load_gen`` driving it closed-loop over every
    bucket size. At ~1/3 completions a new model version is installed
    through the manager (the request-boundary barrier) — ``swap`` records
    the load cost and the served-plane pause, and ``versions_observed``
    proves the swap was live mid-run. Weights are seed-initialized: serving
    throughput/latency are weight-independent, and the swap semantics are
    what the section certifies (bit-identity is test-pinned in
    tests/test_serve.py, not re-proven here).
    """
    import dataclasses

    from fedcrack_tpu.configs import ModelConfig, ServeConfig
    from fedcrack_tpu.models.resunet import init_variables
    from fedcrack_tpu.serve import (
        InferenceEngine,
        MicroBatcher,
        ModelVersionManager,
        ServeServer,
        ServeServerThread,
        ServeService,
    )
    from fedcrack_tpu.tools.load_gen import run_load

    dtype = "bfloat16" if getattr(device, "platform", "") == "tpu" else "float32"
    serve_config = ServeConfig(
        bucket_sizes=tuple(sorted(SERVE_SIZES)),
        max_batch=SERVE_MAX_BATCH,
        max_delay_ms=5.0,
        tile_overlap=min(16, min(SERVE_SIZES) - 16) if min(SERVE_SIZES) > 16 else 0,
        compute_dtype=dtype,
        port=0,
    )
    model_config = ModelConfig(img_size=max(SERVE_SIZES), compute_dtype=dtype)
    var_v0 = init_variables(jax.random.key(SEED), model_config)
    var_v1 = init_variables(jax.random.key(SEED + 1), model_config)

    t0 = time.perf_counter()
    engine = InferenceEngine(model_config, serve_config)
    manager = ModelVersionManager(engine, var_v0, initial_version=0)
    engine.warmup(manager.snapshot()[1])
    warmup_s = time.perf_counter() - t0

    batcher = MicroBatcher(engine, manager)
    server = ServeServer(ServeService(engine, batcher, manager), port=0)
    swap_at = max(1, SERVE_REQUESTS // 3)
    state = {"fired": False, "n": 0}

    def on_complete():
        state["n"] += 1
        if not state["fired"] and state["n"] >= swap_at:
            state["fired"] = True
            # Direct install (pre-decoded weights): the statefile/checkpoint
            # READ path is unit-tested; paying a multi-second msgpack decode
            # under the load's GIL here would only blur the swap timing.
            manager.install(1, var_v1)

    try:
        with ServeServerThread(server) as thread:
            summary = run_load(
                f"127.0.0.1:{thread.port}",
                mode="closed",
                n_requests=SERVE_REQUESTS,
                concurrency=SERVE_CONCURRENCY,
                sizes=serve_config.bucket_sizes,
                seed=SEED,
                on_complete=on_complete,
            )
    finally:
        batcher.close()
        manager.stop()

    stats = batcher.stats()
    swap = None
    if manager.last_swap is not None:
        gaps = stats.get("swap_gaps_ms") or []
        swap = {
            **manager.last_swap,
            "gap_ms": gaps[0] if gaps else None,
            "triggered_after_n": swap_at,
        }
    # Throughput in images/s == requests/s here (one image per request);
    # recomputed over the serving phase only via the load_gen wall.
    return {
        "dtype": dtype,
        "buckets": list(serve_config.bucket_sizes),
        "max_batch": serve_config.max_batch,
        "max_delay_ms": serve_config.max_delay_ms,
        "concurrency": SERVE_CONCURRENCY,
        "warmup_s": round(warmup_s, 3),
        "requests": {
            "total": summary["n_requests"],
            "completed": summary["completed"],
            "rejected": summary["rejected"],
            "per_size": summary["per_size"],
            "versions_observed": summary["versions_observed"],
        },
        "dropped": summary["dropped"],
        "throughput_rps": summary["throughput_rps"],
        "wall_s": summary["wall_s"],
        "latency_ms": summary["latency_ms"],
        "server_latency_ms": summary["server_latency_ms"],
        "batcher": {
            k: stats[k]
            for k in (
                "batches",
                "batch_retries",
                "deadline_missed",
                "per_bucket",
                "versions_served",
            )
        },
        "swap": swap,
        "note": (
            "closed-loop gRPC load over every bucket; one live hot-swap "
            "installed mid-run at the request-boundary barrier — "
            "versions_observed spanning two versions with dropped == 0 is "
            "the serve-while-training claim"
        ),
    }


def _bench_video_serving(device) -> dict:
    """Frame-coherent video serving (round 19, detail.video_serving).

    One seeded correlated sequence (a moving full-width noise band over a
    static base frame, ``VIDEO_MOTION_FRACTION`` of the rows per step —
    >=90% frame-to-frame overlap) served two ways on the SAME engine:

    - **stateless**: ``engine.predict_tiled`` per frame — every tile
      recomputed, the r10 contract and the byte-identity oracle;
    - **session**: a ``StreamSession`` behind ``StreamSessionManager`` —
      only tiles whose bytes changed run on device, keyed on
      (model_version, content hash).

    Mid-sequence a new model version installs through the SAME
    ``ModelVersionManager`` the still path uses — the swap frame must be a
    full re-run on the new version (old-version entries are unreachable by
    key and purged), and its bytes must match stateless-under-v1. The
    audit compares EVERY frame byte-for-byte against the per-version
    stateless oracle, so ``identity.ok`` is the cached==stateless claim
    measured, not assumed.

    ``effective_speedup`` is tile accounting over steady-state frames
    (frame 0 is by construction a cold full re-run):
    tiles_total / tiles_computed ~= 1 / changed-tile-fraction — the
    BASELINE.md effective-throughput model. It is seeded-deterministic;
    the measured walls corroborate it but carry CPU timing noise.
    """
    from fedcrack_tpu.configs import ModelConfig, ServeConfig
    from fedcrack_tpu.models.resunet import init_variables
    from fedcrack_tpu.obs.registry import MetricsRegistry
    from fedcrack_tpu.serve import (
        InferenceEngine,
        MicroBatcher,
        ModelVersionManager,
        ServeServer,
        ServeServerThread,
        ServeService,
    )
    from fedcrack_tpu.serve.stream import StreamSessionManager
    from fedcrack_tpu.tools.load_gen import make_frame_sequence, run_load

    dtype = "bfloat16" if getattr(device, "platform", "") == "tpu" else "float32"
    size = VIDEO_FRAME_SIZE
    serve_config = ServeConfig(
        bucket_sizes=(16, 32),
        max_batch=8,
        max_delay_ms=5.0,
        tile_overlap=4,
        compute_dtype=dtype,
        port=0,
    )
    model_config = ModelConfig(
        img_size=max(serve_config.bucket_sizes),
        stem_features=4,
        encoder_features=(8,),
        decoder_features=(8, 4),
        compute_dtype=dtype,
    )
    var_v0 = init_variables(jax.random.key(SEED), model_config)
    var_v1 = init_variables(jax.random.key(SEED + 1), model_config)

    t0 = time.perf_counter()
    engine = InferenceEngine(model_config, serve_config)
    manager = ModelVersionManager(engine, var_v0, initial_version=0)
    engine.warmup(manager.snapshot()[1])
    warmup_s = time.perf_counter() - t0

    n_frames = max(4, VIDEO_FRAMES)
    frames = make_frame_sequence(n_frames, size, VIDEO_MOTION_FRACTION, seed=SEED)
    band = int(round(VIDEO_MOTION_FRACTION * size))

    # ---- stateless arm: the oracle AND the timing baseline ----
    v0 = manager.snapshot()[1]
    t0 = time.perf_counter()
    stateless_probs = [engine.predict_tiled(v0, f) for f in frames]
    stateless_wall = time.perf_counter() - t0
    stateless_bytes = [np.asarray(p).tobytes() for p in stateless_probs]

    # ---- session arm through the manager (metrics in a private registry,
    # so the exposition check sees exactly this run's counters) ----
    registry = MetricsRegistry()
    smgr = StreamSessionManager(engine, manager, registry=registry)
    session = smgr.open("bench", height=size, width=size)
    swap_at = max(1, (2 * n_frames) // 3)
    results = []
    t0 = time.perf_counter()
    for i, frame in enumerate(frames):
        if i == swap_at:
            # Direct install (pre-decoded weights), same rationale as the
            # r10 serving section: the decode path is unit-tested and would
            # only blur the timing.
            manager.install(1, var_v1)
        result = session.process_frame(frame)
        smgr.record(result)
        results.append(result)
    session_wall = time.perf_counter() - t0

    # ---- byte-identity audit (untimed): every frame vs the stateless
    # oracle under the version the session actually pinned ----
    v1 = manager.snapshot()[1]
    mismatches = 0
    swap_info: dict = {}
    for i, (frame, result) in enumerate(zip(frames, results)):
        if result.model_version == 0:
            ref = stateless_bytes[i]
        else:
            ref = np.asarray(engine.predict_tiled(v1, frame)).tobytes()
        identical = np.asarray(result.probs).tobytes() == ref
        if not identical:
            mismatches += 1
        if i == swap_at:
            swap_info = {
                "frame": i,
                "model_version": result.model_version,
                "full_rerun_on_swap": result.tiles_computed == result.tiles_total,
                "stale_entries_purged": result.evicted,
                "identity_after_swap": bool(identical),
            }

    tiles_total = sum(r.tiles_total for r in results)
    tiles_computed = sum(r.tiles_computed for r in results)
    cache_hits = sum(r.cache_hits for r in results)
    steady = results[1:]
    st_total = sum(r.tiles_total for r in steady)
    st_computed = sum(r.tiles_computed for r in steady)
    effective_speedup = (st_total / st_computed) if st_computed else None
    stateless_ips = n_frames / stateless_wall if stateless_wall > 0 else None
    session_ips = n_frames / session_wall if session_wall > 0 else None
    effective_ips = (
        round(stateless_ips * effective_speedup, 3)
        if stateless_ips and effective_speedup
        else None
    )

    expo = registry.exposition()
    wanted = (
        "serve_stream_sessions_total",
        "serve_stream_frames_total",
        "serve_stream_cache_hits_total",
        "serve_stream_cache_misses_total",
        "serve_stream_full_rerun_total",
        "serve_stream_frame_seconds",
        "serve_stream_cache_hit_ratio",
        "serve_stream_effective_speedup_ratio",
    )
    metrics_ok = all(name in expo for name in wanted)
    smgr.close("bench")

    # ---- gRPC smoke: the full StreamPredict front door under
    # load_gen --profile video (mixed still + video traffic) ----
    grpc_smoke = None
    batcher = MicroBatcher(engine, manager)
    front_smgr = StreamSessionManager(engine, manager)
    server = ServeServer(
        ServeService(engine, batcher, manager, stream_manager=front_smgr),
        port=0,
    )
    try:
        with ServeServerThread(server) as thread:
            summary = run_load(
                f"127.0.0.1:{thread.port}",
                profile="video",
                n_requests=4,
                concurrency=2,
                sizes=(max(serve_config.bucket_sizes),),
                seed=SEED,
                streams=1,
                frames_per_stream=6,
                motion_fraction=VIDEO_MOTION_FRACTION,
                video_size=2 * max(serve_config.bucket_sizes),
                audit_every=2,
            )
        video = summary["video"]
        grpc_smoke = {
            "frames_completed": video["frames_completed"],
            "frames_dropped": video["dropped"],
            "stills_completed": summary["completed"],
            "stills_dropped": summary["dropped"],
            "hit_ratio": video["hit_ratio"],
            "effective_speedup": video["effective_speedup"],
            "audit": video["audit"],
        }
    except Exception as e:  # the smoke must not void the in-process A/B
        grpc_smoke = {"error": repr(e)}
    finally:
        batcher.close()
        manager.stop()

    return {
        "dtype": dtype,
        "warmup_s": round(warmup_s, 3),
        "frame": {
            "size": size,
            "frames": n_frames,
            "motion_fraction": VIDEO_MOTION_FRACTION,
            "motion_rows": band,
            "overlap_fraction": round(1.0 - band / size, 4),
            "tile": max(serve_config.bucket_sizes),
            "tile_overlap": serve_config.tile_overlap,
            "tiles_per_frame": results[0].tiles_total,
        },
        "stateless": {
            "wall_s": round(stateless_wall, 3),
            "img_per_s": round(stateless_ips, 3) if stateless_ips else None,
        },
        "session": {
            "wall_s": round(session_wall, 3),
            "img_per_s": round(session_ips, 3) if session_ips else None,
            "wall_speedup": (
                round(stateless_wall / session_wall, 3) if session_wall > 0 else None
            ),
            "tiles_total": tiles_total,
            "tiles_computed": tiles_computed,
            "cache_hits": cache_hits,
            "hit_ratio": round(cache_hits / tiles_total, 4) if tiles_total else 0.0,
            "steady_state": {
                "frames": len(steady),
                "tiles_total": st_total,
                "tiles_computed": st_computed,
            },
        },
        "effective_speedup": (
            round(effective_speedup, 3) if effective_speedup else None
        ),
        "effective_img_per_s": effective_ips,
        "speedup_target_met": bool(
            effective_speedup is not None and effective_speedup >= 3.0
        ),
        "identity": {
            "frames_checked": len(results),
            "mismatches": mismatches,
            "ok": mismatches == 0,
        },
        "swap": swap_info,
        "metrics_in_exposition": metrics_ok,
        "grpc_smoke": grpc_smoke,
        "note": (
            "cached-session bytes == stateless predict_tiled bytes on every "
            "frame, across a live mid-sequence hot swap; effective_speedup "
            "is steady-state tiles_total/tiles_computed — the "
            "1/(changed-tile-fraction) throughput model, seeded and "
            "timing-independent"
        ),
    }


def _bench_serve_fleet(device) -> dict:
    """Serve-fleet scale-out + quantized predict (round 17,
    detail.serve_fleet).

    Four measurements over one model:

    - **grid**: replicas x {bf16,int8} closed-loop throughput and p50/p95
      through the in-process router (the gRPC overhead is the r10 serving
      section's number; this grid isolates the replica/quant levers).
    - **swap**: a fleet-wide two-phase install under concurrent load —
      commit pause (the fleet lock hold) and the torn-version count over
      post-commit requests (the zero-torn claim, measured not assumed).
    - **shed**: the full gRPC front door + load_gen ramp profile against a
      tight queue bound — shed counts by reason and per-phase client
      latency (admission control proven by overload, not by unit test).
    - **quant_gate**: the int8 install gate's probe-IoU verdict (a refusal
      is an honest artifact, not a failure: the fleet serves bf16 then).
    """
    import dataclasses
    import threading

    from fedcrack_tpu.configs import ModelConfig, ServeConfig
    from fedcrack_tpu.models.resunet import init_variables
    from fedcrack_tpu.obs.metrics import StreamingPercentiles
    from fedcrack_tpu.serve import (
        InferenceEngine,
        ServeFleet,
        ServeServer,
        ServeServerThread,
        ServeService,
    )
    from fedcrack_tpu.tools.load_gen import make_images, run_load

    dtype = "bfloat16" if getattr(device, "platform", "") == "tpu" else "float32"
    buckets = tuple(sorted(SERVE_SIZES))
    base_cfg = ServeConfig(
        bucket_sizes=buckets,
        max_batch=SERVE_MAX_BATCH,
        max_delay_ms=5.0,
        tile_overlap=min(16, min(buckets) - 16) if min(buckets) > 16 else 0,
        compute_dtype=dtype,
        port=0,
    )
    model_config = ModelConfig(img_size=max(buckets), compute_dtype=dtype)
    var_v0 = init_variables(jax.random.key(SEED), model_config)
    var_v1 = init_variables(jax.random.key(SEED + 1), model_config)
    images = make_images(FLEET_REQUESTS, buckets, SEED)

    def drive(fleet, imgs, concurrency=SERVE_CONCURRENCY):
        """Closed-loop router load: C threads, one request in flight each."""
        from queue import Empty, Queue

        jobs: Queue = Queue()
        for img in imgs:
            jobs.put(img)
        versions: list[int] = []
        vlock = threading.Lock()

        def worker():
            while True:
                try:
                    img = jobs.get_nowait()
                except Empty:
                    return
                res = fleet.submit(img).result(timeout=300)
                with vlock:
                    versions.append(res.model_version)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=worker) for _ in range(concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0, versions

    engines: dict[str, InferenceEngine] = {}
    grid: dict[str, dict] = {}
    for quant, arm in (("none", "bf16"), ("int8", "int8")):
        cfg_q = dataclasses.replace(base_cfg, quant=quant)
        engines[quant] = InferenceEngine(model_config, cfg_q)
        if quant == "int8":
            # The grid measures the int8 PROGRAM's throughput, so its
            # fleets install under a relaxed MEASUREMENT floor; the
            # production-floor verdict is the separate quant_gate record
            # below (a refusal there is an honest artifact, but it must
            # not silently turn the int8 arms into bf16 re-measurements).
            cfg_q = dataclasses.replace(cfg_q, quant_iou_floor=0.5)
        for n in FLEET_REPLICAS:
            fleet = ServeFleet(
                model_config,
                dataclasses.replace(cfg_q, replicas=n),
                var_v0,
                shared_engine=engines[quant],
            )
            try:
                from fedcrack_tpu.serve.quant import QuantizedVariables

                served_quant = isinstance(
                    fleet.manager.snapshot_for(0)[1], QuantizedVariables
                )
                wall, versions = drive(fleet, images)
                pooled = StreamingPercentiles(8192)
                for r in fleet.replicas:
                    pooled.merge(r.batcher.latency)
            finally:
                fleet.close()
            grid[f"r{n}_{arm}"] = {
                "replicas": n,
                "quant": arm,
                "served_quant": served_quant,
                "requests": len(images),
                "completed": len(versions),
                "wall_s": round(wall, 3),
                "throughput_rps": round(len(versions) / wall, 3) if wall else None,
                "p50_ms": pooled.percentile(50.0),
                "p95_ms": pooled.percentile(95.0),
            }

    # The production-floor gate verdict (ServeConfig defaults): what an
    # operator's install would do with THESE weights on THIS host.
    from fedcrack_tpu.serve.quant import quant_gate as run_quant_gate
    from fedcrack_tpu.serve.quant import quantize_variables

    eng_q = engines["int8"]
    quant_gate = run_quant_gate(
        eng_q,
        eng_q.prepare(var_v0),
        eng_q.prepare_quantized(quantize_variables(var_v0)),
    ).to_json()

    # ---- fleet-wide two-phase swap under load (max replicas, int8 cfg:
    # the swap re-runs the gate, so a refused quantization swaps bf16) ----
    n_max = max(FLEET_REPLICAS)
    swap_fleet = ServeFleet(
        model_config,
        dataclasses.replace(base_cfg, quant="int8", replicas=n_max),
        var_v0,
        shared_engine=engines["int8"],
    )
    try:
        half = images[: max(1, len(images) // 2)]
        _, pre_versions = drive(swap_fleet, half)
        swap_fleet.install(1, var_v1)
        _, post_versions = drive(swap_fleet, half)
        torn = sum(1 for v in post_versions if v != 1)
        swap = {
            "replicas": n_max,
            "pause_ms": (swap_fleet.manager.last_swap or {}).get("pause_ms"),
            "prepare_ms": (swap_fleet.manager.last_swap or {}).get("load_ms"),
            "pre_commit_versions": sorted(set(pre_versions)),
            "post_commit_versions": sorted(set(post_versions)),
            "torn_versions": torn,
            "zero_torn": torn == 0,
        }
    finally:
        swap_fleet.close()

    # ---- admission control: gRPC front door + ramp arrival profile vs a
    # tight queue bound — the 2x phase MUST shed, the artifact shows where ----
    shed_cfg = dataclasses.replace(
        base_cfg, quant="none", replicas=n_max, queue_bound=4
    )
    shed_fleet = ServeFleet(
        model_config, shed_cfg, var_v0, shared_engine=engines["none"]
    )
    server = ServeServer(
        ServeService(shed_fleet.engine, shed_fleet.router, shed_fleet.manager),
        port=0,
    )
    try:
        with ServeServerThread(server) as thread:
            shed_summary = run_load(
                f"127.0.0.1:{thread.port}",
                mode="open",
                profile="ramp",
                n_requests=max(32, FLEET_REQUESTS),
                rate_rps=FLEET_SHED_RATE,
                concurrency=SERVE_CONCURRENCY,
                sizes=(min(buckets),),
                seed=SEED,
            )
    finally:
        shed_fleet.close()
    shed = {
        "profile": "ramp",
        "rate_rps": FLEET_SHED_RATE,
        "queue_bound": shed_cfg.queue_bound,
        "total": shed_summary["shed"],
        "by_reason": shed_fleet.router.shed_counts(),
        "completed": shed_summary["completed"],
        "dropped": shed_summary["dropped"],
        "per_phase": shed_summary["per_phase"],
    }

    return {
        "dtype": dtype,
        "buckets": list(buckets),
        "max_batch": base_cfg.max_batch,
        "concurrency": SERVE_CONCURRENCY,
        "grid": grid,
        "swap": swap,
        "shed": shed,
        "quant_gate": quant_gate,
        "note": (
            "in-process router grid isolates the replica/quant levers "
            "(gRPC overhead is detail.serving's number); int8 grid arms "
            "install under a relaxed measurement floor so they measure the "
            "quantized PROGRAM (served_quant says what actually ran) while "
            "quant_gate is the production-floor verdict; zero_torn is "
            "measured over post-commit requests; CPU-smoke ratios are "
            "machinery validation — decisive img/s queue behind the "
            "ROADMAP TPU session"
        ),
    }


def _bench_elastic_fleet(device) -> dict:
    """Elastic serve fleet (round 22, detail.elastic_fleet).

    Two halves over one deliberately tiny model (dispatches chaos-throttled
    to 80 ms so capacity is REPLICA-bound, not model-bound — the section
    certifies the control loop, never CPU throughput):

    - **diurnal A/B**: the same seeded compressed-day arrival profile
      (night/morning/peak/evening at 0.2x/1x/1.8x/0.8x of the base rate)
      through the real gRPC front door three times — static-max (burns
      ``max`` replicas all day), static-min (one replica: the 1.8x peak
      MUST overrun its queue bound and shed), and autoscaled (starts at
      min, the FleetAutoscaler grows/shrinks the fleet live from the
      registry's own exposition). load_gen's ``--metrics-url`` sampler
      polls ``serve_fleet_replicas`` through each run — the autoscaled
      arm's ``replicas_varied`` is wire-level proof the fleet resized.
      The claims: autoscaled holds p95 with shed == 0 and dropped == 0 at
      STRICTLY lower replica-seconds than static-max; static-min sheds.
    - **shadow delivery**: a ShadowController stages one candidate that
      matches production (mirrored live traffic, canary IoU 1.0, zero
      drift → auto-PROMOTE installs it) and one deliberately degraded
      candidate (zeroed weights → IoU cliff + PSI blowout → auto-ROLLBACK,
      never installed, clients never see a shadow answer). Both full
      verdict records land in the artifact.
    """
    import dataclasses
    import threading

    from fedcrack_tpu.configs import ModelConfig, ServeConfig
    from fedcrack_tpu.models.resunet import init_variables
    from fedcrack_tpu.obs.promexp import MetricsExporter
    from fedcrack_tpu.obs.registry import REGISTRY
    from fedcrack_tpu.serve import (
        FleetAutoscaler,
        InferenceEngine,
        ServeFleet,
        ServeServer,
        ServeServerThread,
        ServeService,
        ShadowController,
    )
    from fedcrack_tpu.tools.load_gen import make_images, run_load

    model_config = ModelConfig(
        img_size=16,
        stem_features=4,
        encoder_features=(8,),
        decoder_features=(8, 4),
    )
    slo_ms = 1500.0
    base_cfg = ServeConfig(
        bucket_sizes=(16,),
        max_batch=2,
        max_delay_ms=5.0,
        tile_overlap=4,
        # 16 open-loop client streams bound in-flight requests at 16, so a
        # bound of 10 is reachable by a one-replica backlog at the 1.8x
        # peak (static-min MUST shed) while the autoscaler's queue trigger
        # (2 x live <= 6) fires well inside it (autoscaled must NOT).
        queue_bound=10,
        slo_p95_ms=slo_ms,
        port=0,
    )
    v0 = init_variables(jax.random.key(SEED), model_config)
    engine = InferenceEngine(model_config, base_cfg)

    class _SlowBatches:
        """Stretch every dispatch so a replica's service rate is the
        throttle (max_batch/0.08s ~ 25 rps), making the 1.8x peak a real
        capacity cliff a tiny CPU model would otherwise never feel."""

        def on_batch(self, bucket, batch_index, attempt):
            time.sleep(0.08)

    exporter = MetricsExporter(REGISTRY)
    metrics_url = f"http://127.0.0.1:{exporter.start()}/metrics"
    arms: dict[str, dict] = {}
    auto_audit: dict = {}

    def run_arm(name: str, *, replicas: int, min_r: int = 0, max_r: int = 0):
        cfg = dataclasses.replace(
            base_cfg,
            replicas=replicas,
            min_replicas=min_r,
            max_replicas=max_r,
            scale_interval_s=0.05,
            scale_cooldown_s=0.15,
            scale_up_queue_depth=2,
            scale_down_idle_evals=6,
        )
        fleet = ServeFleet(
            model_config, cfg, v0, shared_engine=engine, chaos=_SlowBatches()
        )
        server = ServeServer(
            ServeService(fleet.engine, fleet.router, fleet.manager), port=0
        )
        autoscaler = None
        try:
            if min_r > 0:
                autoscaler = FleetAutoscaler(fleet)
                autoscaler.start()
            with ServeServerThread(server) as thread:
                summary = run_load(
                    f"127.0.0.1:{thread.port}",
                    mode="open",
                    profile="diurnal",
                    n_requests=ELASTIC_REQUESTS,
                    rate_rps=ELASTIC_RATE,
                    concurrency=16,
                    sizes=(16,),
                    seed=SEED,
                    metrics_url=metrics_url,
                    metrics_interval_s=0.25,
                )
            replica_seconds = (
                autoscaler.replica_seconds()
                if autoscaler is not None
                else replicas * summary["wall_s"]
            )
        finally:
            if autoscaler is not None:
                autoscaler.stop()
            fleet.close()
        fleet_block = summary.get("fleet") or {}
        fleet_block.pop("track", None)  # per-sample detail; keep artifact lean
        arms[name] = {
            "replicas_band": [min_r or replicas, max_r or replicas],
            "completed": summary["completed"],
            "shed": summary["shed"],
            "dropped": summary["dropped"],
            "p95_ms": (summary["latency_ms"] or {}).get("p95"),
            "wall_s": summary["wall_s"],
            "replica_seconds": round(replica_seconds, 3),
            "replicas_min": fleet_block.get("replicas_min"),
            "replicas_max": fleet_block.get("replicas_max"),
            "replicas_varied": bool(fleet_block.get("replicas_varied")),
            "per_phase": summary["per_phase"],
            "shed_by_reason": fleet.router.shed_counts(),
        }
        if autoscaler is not None:
            auto_audit.update(autoscaler.audit())

    run_arm("static_max", replicas=3)
    run_arm("static_min", replicas=1)
    run_arm("autoscaled", replicas=1, min_r=1, max_r=3)
    exporter.stop()

    # ---- shadow-replica progressive delivery: one promote, one rollback,
    # under live mirrored traffic (no throttle — the mirror needs samples,
    # not backlog) ----
    shadow_cfg = dataclasses.replace(
        base_cfg, replicas=1, shadow_fraction=1.0, shadow_min_samples=8
    )
    sfleet = ServeFleet(model_config, shadow_cfg, v0, shared_engine=engine)
    ctrl = ShadowController(sfleet)
    pump_imgs = make_images(8, (16,), SEED)
    stop_pump = threading.Event()

    def pump():
        i = 0
        while not stop_pump.is_set():
            try:
                sfleet.submit(pump_imgs[i % len(pump_imgs)]).result(timeout=30)
            except Exception:
                pass
            i += 1

    pump_thread = threading.Thread(target=pump, daemon=True)
    pump_thread.start()
    try:
        # A candidate indistinguishable from production (a re-publish):
        # IoU pins at 1.0, PSI at 0 — the promote path.
        promote_rec = ctrl.stage(1, v0, wait_s=15.0)
        # A deliberately degraded candidate: zeroed weights crater the
        # canary IoU and blow out the drift PSI — the rollback path.
        v_bad = jax.tree_util.tree_map(lambda x: x * 0, v0)
        rollback_rec = ctrl.stage(2, v_bad, wait_s=15.0)
    finally:
        stop_pump.set()
        pump_thread.join(timeout=10)
        sfleet.close()
    # Verdict records carry model outputs' floats; round-trip through JSON
    # (numpy scalars -> floats) so the artifact writer never trips.
    promote_rec = json.loads(json.dumps(promote_rec, default=float))
    rollback_rec = json.loads(json.dumps(rollback_rec, default=float))
    shadow = {
        "promote": promote_rec,
        "rollback": rollback_rec,
        "promoted": promote_rec.get("verdict") == "promote"
        and bool(promote_rec.get("installed")),
        "rolled_back": rollback_rec.get("verdict") == "rollback"
        and not rollback_rec.get("installed"),
    }

    auto = arms["autoscaled"]
    return {
        "profile": "diurnal",
        "rate_rps": ELASTIC_RATE,
        "requests": ELASTIC_REQUESTS,
        "slo_p95_ms": slo_ms,
        "queue_bound": base_cfg.queue_bound,
        "arms": arms,
        "autoscaler": auto_audit,
        "autoscaled_cheaper_than_static_max": (
            auto["replica_seconds"] < arms["static_max"]["replica_seconds"]
        ),
        "autoscaled_held_slo": (
            auto["shed"] == 0
            and auto["dropped"] == 0
            and auto["p95_ms"] is not None
            and auto["p95_ms"] <= slo_ms
        ),
        "static_min_shed": arms["static_min"]["shed"] > 0,
        "shadow": shadow,
        "note": (
            "dispatches chaos-throttled to 80 ms so capacity is replica-"
            "bound: the section certifies the control loop (scale before "
            "shed, drain without drops, fewer replica-seconds than "
            "static-max) on a CPU smoke; absolute rps is not a claim"
        ),
    }


def _bench_update_compression(rounds: int = COMPRESSION_ROUNDS) -> dict:
    """Compressed update transport A/B (round 12, fedcrack_tpu/compress).

    Two halves, both cheap enough for a CPU smoke run:

    - **wire** — one REFERENCE-SCALE round delta (the real ModelConfig, a
      synthetic per-leaf-scaled N(0, 1e-3·std) perturbation standing in for
      an Adam round delta) pushed through every codec on the host: measured
      frame bytes on the wire, bytes ratio vs the dense msgpack blob,
      median encode/decode wall. NullCodec is asserted BYTE-IDENTICAL to
      the dense path (null_identical) — the escape-hatch contract.
    - **trajectory** — the mesh plane's on-device encode∘decode twins
      (build_federated_round(update_codec=...)) over ``rounds`` rounds of a
      small 2-client federation: per-round crack-IoU for each codec, max
      absolute IoU delta vs the NullCodec oracle, and the driver's
      RoundRecord.bytes_per_round counter per codec. The null twin is
      additionally pinned bit-identical to a no-codec build (the tier-1
      test re-pins this; here it is recorded in the artifact).
    """
    from fedcrack_tpu.compress import decode_update, get_codec
    from fedcrack_tpu.compress.codecs import DEFAULT_TOPK_FRACTION
    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.data.synthetic import synth_crack_batch
    from fedcrack_tpu.fed.serialization import tree_from_bytes, tree_to_bytes
    from fedcrack_tpu.parallel import (
        build_federated_round,
        make_mesh,
        run_mesh_federation,
        stack_client_data,
    )
    from fedcrack_tpu.train.local import create_train_state

    # ---- wire half: real bytes at reference scale ----
    ref = ModelConfig()
    ref_vars = jax.device_get(create_train_state(jax.random.key(SEED), ref).variables)
    base_tree = {"params": ref_vars["params"], "batch_stats": ref_vars["batch_stats"]}
    base_blob = tree_to_bytes(base_tree)
    rng = np.random.default_rng(SEED)
    upd_tree = jax.tree_util.tree_map(
        lambda x: (
            np.asarray(x, np.float32)
            + (
                1e-3
                * max(1e-6, float(np.std(np.asarray(x, np.float32))))
                * rng.standard_normal(np.shape(x))
            ).astype(np.float32)
        ),
        base_tree,
    )
    upd_blob = tree_to_bytes(upd_tree)
    wire: dict = {}
    reps = max(1, min(REPS, 3))
    for name in ("null", "int8", "topk_delta"):
        codec = get_codec(name)
        enc_times, frame = [], b""
        for _ in range(reps):
            codec.reset()
            t0 = time.perf_counter()
            frame = codec.encode_update(upd_blob, base_blob, round=1, base_version=0)
            enc_times.append(time.perf_counter() - t0)
        dec_times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            if name == "null":
                tree_from_bytes(frame, template=base_tree)
            else:
                decode_update(
                    frame, template=base_tree, base=base_tree, expected_base_version=0
                )
            dec_times.append(time.perf_counter() - t0)
        wire[name] = {
            "bytes_per_round": len(frame),
            "ratio_vs_null": (
                None if name == "null" else round(len(upd_blob) / len(frame), 2)
            ),
            "encode_ms": round(1e3 * float(np.median(enc_times)), 3),
            "decode_ms": round(1e3 * float(np.median(dec_times)), 3),
        }
        if name == "null":
            wire[name]["null_identical"] = frame == upd_blob

    # ---- trajectory half: mesh twins vs the NullCodec oracle ----
    n_clients = 2 if len(jax.devices()) >= 2 else 1
    mesh = make_mesh(n_clients, 1)
    tiny = ModelConfig(
        img_size=16, stem_features=4, encoder_features=(8,), decoder_features=(8, 4)
    )
    steps, batch = 2, 4
    per_client = [
        synth_crack_batch(steps * batch, img_size=16, seed=i)
        for i in range(n_clients)
    ]
    images, masks = stack_client_data(per_client, steps, batch)
    active = np.ones(n_clients, np.float32)
    ns = np.full(n_clients, float(steps * batch), np.float32)
    state0 = create_train_state(jax.random.key(SEED), tiny)
    data_fn = lambda r: (images, masks, active, ns) if r == 0 else None

    trajectory: dict = {}
    null_iou: list[float] | None = None
    for name in ("null", "int8", "topk_delta"):
        rf = build_federated_round(
            mesh,
            tiny,
            learning_rate=1e-3,
            local_epochs=1,
            update_codec=name,
            topk_fraction=DEFAULT_TOPK_FRACTION,
        )
        _, recs = run_mesh_federation(rf, state0.variables, data_fn, rounds, mesh)
        iou = [round(float(np.mean(r.metrics["iou"])), 6) for r in recs]
        if name == "null":
            null_iou = iou
        trajectory[name] = {
            "iou": iou,
            "bytes_per_round": int(recs[-1].bytes_per_round),
            "max_abs_iou_delta_vs_null": (
                None
                if null_iou is None or name == "null"
                else round(max(abs(a - b) for a, b in zip(iou, null_iou)), 6)
            ),
        }

    return {
        "dense_update_bytes": len(upd_blob),
        "rounds": rounds,
        "wire": wire,
        "trajectory": trajectory,
        "ref_model_leaves": len(jax.tree_util.tree_leaves(base_tree)),
        "ref_model_params": int(
            sum(np.asarray(l).size for l in jax.tree_util.tree_leaves(base_tree))
        ),
        "topk_fraction": DEFAULT_TOPK_FRACTION,
        "note": (
            "wire half is REAL bytes at reference scale (synthetic "
            "1e-3-relative round delta; measured frames, zlib'd) — the "
            ">=10x claim; trajectory half is the mesh twins' IoU vs the "
            "NullCodec oracle on a small federation (tolerance pinned at "
            "0.15 absolute by tests/test_compress.py)"
        ),
    }


def _bench_cohort_scale() -> dict:
    """Cohort-scale A/B (round 13). Three pieces, all CPU-smoke cheap:

    - **groups** — one 8-client cohort round executed time-multiplexed as
      groups ∈ {1, 2, 4} over progressively narrower meshes (tiny model):
      per-round wall vs group-dispatch count (the ~linear-in-ceil(C/G)
      scaling claim) and the final-weights sha256 per split — all splits
      must agree BITWISE (the ordered-fold contract, also test-pinned).
    - **tree** — a ``COHORT_TREE_CLIENTS``-simulated-client round through
      the 2-level aggregation tree (tiny 4x4 weight blobs — the protocol
      and memory shape are what is under test, not the model): root/edge
      peak resident update blobs, wire bytes at the root vs the flat
      equivalent, wall, and a double-run bit-reproducibility check from
      the cohort seed.
    - **flat** — the same cohort through a flat root (every leaf enrolls
      directly): peak resident blobs == cohort size, the O(N) shape the
      tree removes.
    """
    import hashlib

    from fedcrack_tpu.configs import FedConfig, ModelConfig
    from fedcrack_tpu.data.synthetic import synth_crack_batch
    from fedcrack_tpu.fed import rounds as R
    from fedcrack_tpu.fed.algorithms import sample_cohort
    from fedcrack_tpu.fed.serialization import tree_from_bytes, tree_to_bytes
    from fedcrack_tpu.fed.tree import run_tree_federation
    from fedcrack_tpu.parallel import (
        build_federated_cohort_round,
        make_mesh,
        run_cohort_federation,
        stack_client_data,
    )
    from fedcrack_tpu.train.local import create_train_state

    out: dict = {}

    # ---- group-count sweep: time-multiplexed mesh execution ----
    tiny = ModelConfig(
        img_size=16, stem_features=4, encoder_features=(8,), decoder_features=(8, 4)
    )
    steps, batch, cohort_c = 2, 4, min(8, max(2, jax.device_count()))
    per_client = [
        synth_crack_batch(steps * batch, img_size=16, seed=i)
        for i in range(cohort_c)
    ]
    images, masks = stack_client_data(per_client, steps, batch)
    active = np.ones(cohort_c, np.float32)
    ns = np.full(cohort_c, float(steps * batch), np.float32)
    variables = create_train_state(jax.random.key(SEED), tiny).variables
    groups_out: dict = {}
    shas = set()
    for n_groups in (1, 2, 4):
        if cohort_c % n_groups:
            continue
        g = cohort_c // n_groups
        mesh = make_mesh(g, 1)
        cr = build_federated_cohort_round(
            mesh, tiny, learning_rate=1e-3, local_epochs=1, segments=1
        )
        data_fn = lambda r: (images, masks, active, ns)
        # One compile round, one measured round.
        out_vars, recs = run_cohort_federation(cr, variables, data_fn, 2, mesh)
        sha = hashlib.sha256(
            tree_to_bytes(jax.device_get(out_vars))
        ).hexdigest()
        shas.add(sha)
        groups_out[str(n_groups)] = {
            "group_size": g,
            "group_dispatches": n_groups,
            "round_wall_s": round(recs[-1].wall_clock_s, 4),
            "compile_round_wall_s": round(recs[0].wall_clock_s, 4),
            "staged_bytes": recs[-1].staged_bytes,
            "max_live_staged_bytes": recs[-1].max_live_staged_bytes,
            "weights_sha256": sha,
        }
    out["groups"] = groups_out
    out["groups_bitwise_equal"] = len(shas) == 1
    out["cohort_size_mesh"] = cohort_c

    # ---- 1,024-simulated-client tree round + flat A/B ----
    def _vars(v):
        return {"params": {"w": np.full((4, 4), v, np.float32)}}

    def make_update(idx, r, base_blob, base_version):
        rng = np.random.default_rng([7, idx, r])
        base = tree_from_bytes(base_blob)
        tree = {
            "params": {
                "w": np.asarray(base["params"]["w"], np.float32)
                + rng.standard_normal((4, 4)).astype(np.float32) * 0.01
            }
        }
        return tree_to_bytes(tree), int(rng.integers(1, 50))

    n_tree = COHORT_TREE_CLIENTS
    fan_out = COHORT_TREE_FANOUT
    t0 = time.perf_counter()
    res = run_tree_federation(
        _vars(0.0),
        make_update,
        n_clients=4 * n_tree,
        cohort_size=n_tree,
        n_rounds=2,
        n_edges=fan_out,
        cohort_seed=SEED,
    )
    tree_wall = time.perf_counter() - t0
    res2 = run_tree_federation(
        _vars(0.0),
        make_update,
        n_clients=4 * n_tree,
        cohort_size=n_tree,
        n_rounds=2,
        n_edges=fan_out,
        cohort_seed=SEED,
    )
    out["tree"] = {
        "n_clients": res.n_clients,
        "cohort_size": res.cohort_size,
        "fan_out": res.n_edges,
        "rounds": res.rounds,
        "root_peak_blobs": res.root_peak_blobs,
        "edge_peak_blobs": res.edge_peak_blobs,
        "max_leaf_fan_in": res.max_leaf_fan_in,
        "root_peak_within_fan_in": res.root_peak_blobs <= res.n_edges,
        "bytes_at_root": res.bytes_at_root,
        "bytes_flat_equiv": res.bytes_flat_equiv,
        "leaf_updates": res.leaf_updates,
        "wall_s": round(tree_wall, 3),
        "bit_reproducible": res.global_sha256 == res2.global_sha256,
        "global_sha256": res.global_sha256,
    }

    cfg = FedConfig(
        max_rounds=1,
        cohort_size=n_tree,
        registration_window_s=3600.0,
        sanitize_updates=True,
    )
    state = R.initial_state(cfg, _vars(0.0))
    cohort = sample_cohort(4 * n_tree, n_tree, 0, SEED)
    now = 0.0
    t0 = time.perf_counter()
    for i in cohort:
        now += 1e-4
        state, _ = R.transition(state, R.Ready(cname=f"client-{int(i)}", now=now))
    base_blob = state.broadcast_blob
    flat_peak = 0
    flat_bytes = 0
    for i in cohort:
        blob, n = make_update(int(i), 0, base_blob, state.model_version)
        flat_bytes += len(blob)
        now += 1e-4
        state, rep = R.transition(
            state,
            R.TrainDone(
                cname=f"client-{int(i)}", round=1, blob=blob, num_samples=n, now=now
            ),
        )
        flat_peak = max(
            flat_peak,
            len(state.received) if rep.status != R.RESP_ARY and rep.status != R.FIN
            else n_tree,
        )
    out["flat"] = {
        "n_clients": n_tree,
        "root_peak_blobs": flat_peak,
        "bytes_at_root": flat_bytes,
        "wall_s": round(time.perf_counter() - t0, 3),
    }
    out["note"] = (
        "groups: wall ~linear in group dispatches with BITWISE-equal "
        "weights across splits (ordered-fold contract); tree: root peak "
        "resident update blobs <= fan-out where the flat root holds the "
        "whole cohort — the O(fan-in) memory claim; CPU smoke (protocol + "
        "memory shape), the v5e-8 round-wall point is ROADMAP measurement "
        "item 6"
    )
    return out


def _async_sync_equivalence() -> dict:
    """The buffered mode's escape hatch, pinned in the artifact: with
    ``buffer_k = cohort_size`` and ``staleness_alpha = 0`` the buffered
    flush IS sync FedAvg — sha-identical global bytes over the same
    updates — and a permuted arrival order flushes to the same bytes (the
    sorted-fold discipline). Transition-driven, host-only, milliseconds."""
    import hashlib

    from fedcrack_tpu.configs import FedConfig
    from fedcrack_tpu.fed import rounds as R
    from fedcrack_tpu.fed.serialization import tree_to_bytes

    def _vars(v):
        return {"params": {"w": np.full((8, 8), v, np.float32)}}

    values = {"a": 1.0, "b": 3.0, "c": 6.0}
    samples = {"a": 10, "b": 30, "c": 20}

    def drive(mode: str, order: tuple) -> tuple[str, int]:
        kw = (
            dict(mode="buffered", buffer_k=3, staleness_alpha=0.0, max_staleness=4)
            if mode == "buffered"
            else {}
        )
        cfg = FedConfig(
            max_rounds=3, cohort_size=3, registration_window_s=3600.0, **kw
        )
        st = R.initial_state(cfg, _vars(0.0))
        now = 0.0
        for c in ("a", "b", "c"):
            now += 1e-3
            st, _ = R.transition(st, R.Ready(cname=c, now=now))
        for rnd in range(1, 4):
            for c in order:
                now += 1e-3
                st, _ = R.transition(st, R.PullWeights(cname=c, now=now))
            for c in order:
                now += 1e-3
                st, _ = R.transition(
                    st,
                    R.TrainDone(
                        cname=c,
                        round=rnd,
                        blob=tree_to_bytes(_vars(values[c] + rnd)),
                        num_samples=samples[c],
                        now=now,
                    ),
                )
        return hashlib.sha256(st.global_blob).hexdigest(), int(st.model_version)

    sync_sha, _ = drive("sync", ("a", "b", "c"))
    buf_sha, buf_v = drive("buffered", ("a", "b", "c"))
    perm_sha, _ = drive("buffered", ("c", "a", "b"))
    return {
        "sync_sha": sync_sha,
        "buffered_sha": buf_sha,
        "bit_identical": sync_sha == buf_sha,
        "arrival_order_independent": buf_sha == perm_sha,
        "global_versions": buf_v,
    }


def _async_trajectory_sim(
    seed: int = ASYNC_SEED,
    n_clients: int = 8,
    buffer_k: int = 2,
    alpha: float = 0.5,
    rounds: int = 6,
    lr: float = 0.1,
) -> dict:
    """Equal-wall trajectory quality, sync vs buffered, under the SAME
    seeded storm schedule — a deterministic event-clock simulation (no
    sleeps) of a toy quadratic (each client pulls the global toward its
    own target; the optimum is the target mean). The sync arm runs
    ``rounds`` barrier rounds (wall = sum of per-round max delays); the
    buffered arm replays the same per-(client, iteration) delays up to
    that wall. This is the CPU PROXY for 'trajectory quality at equal
    wall' — the real-model crack-IoU point is TPU measurement item 7."""
    import heapq
    import random as _random

    from fedcrack_tpu.chaos.plan import STRAGGLER_DELAY, FaultPlan
    from fedcrack_tpu.fed.buffered import staleness_weight

    names = [f"c{i}" for i in range(n_clients)]
    n_iter = rounds * 8
    plan = FaultPlan.storm(
        seed,
        clients=names,
        n_iterations=n_iter,
        tail_alpha=1.1,
        scale_s=0.03,
        cap_s=0.8,
    )
    delays = {
        (f.client, f.round): f.delay_s
        for f in plan.pending
        if f.kind == STRAGGLER_DELAY
    }
    rng = _random.Random(seed)
    targets = {n: rng.uniform(0.5, 1.5) for n in names}
    opt = sum(targets[n] for n in names) / n_clients

    def local(w: float, n: str) -> float:
        return w + lr * (targets[n] - w)

    # Sync arm: each round's wall is the cohort MAX delay.
    w, t = 0.0, 0.0
    for r in range(1, rounds + 1):
        t += max(delays[(n, r)] for n in names)
        w = sum(local(w, n) for n in names) / n_clients
    sync_wall, sync_loss = t, (w - opt) ** 2

    # Buffered arm to the same wall: clients loop, the server flushes the
    # staleness-weighted buffer at K (the fed/buffered.py semantics, on
    # the toy model).
    w, version = 0.0, 0
    buf: list = []
    heap: list = []
    for n in names:
        heapq.heappush(heap, (delays[(n, 1)], n, 1, w, version))
    while heap and heap[0][0] <= sync_wall:
        t_fin, n, it, base_w, base_v = heapq.heappop(heap)
        u = local(base_w, n)
        wt = staleness_weight(version - base_v, alpha)
        buf.append((u, wt))
        if len(buf) >= buffer_k:
            # The fed/buffered.py flush: weighted buffer mean, anchored on
            # the current global by the mean staleness weight.
            tot = sum(x for _, x in buf)
            mean = sum(u * x for u, x in buf) / tot
            mix = tot / len(buf)
            w = (1.0 - mix) * w + mix * mean
            version += 1
            buf = []
        nxt = it + 1
        d = delays[(n, (nxt - 1) % n_iter + 1)]
        heapq.heappush(heap, (t_fin + d, n, nxt, w, version))
    buffered_loss = (w - opt) ** 2
    return {
        "equal_wall_s": round(sync_wall, 4),
        "sync_final_loss": round(sync_loss, 8),
        "buffered_final_loss": round(buffered_loss, 8),
        "sync_versions": rounds,
        "buffered_versions": int(version),
        "buffered_at_least_as_close": buffered_loss <= sync_loss,
    }


def _bench_async_federation() -> dict:
    """detail.async_federation (round 14): storm A/B + sync-degeneration
    pin + mid-buffer recovery + equal-wall trajectory sim."""
    from fedcrack_tpu.tools.chaos_drill import (
        run_buffered_kill_drill,
        run_straggler_storm_drill,
    )

    return {
        "storm": run_straggler_storm_drill(seed=ASYNC_SEED),
        "sync_equivalence": _async_sync_equivalence(),
        "recovery": run_buffered_kill_drill(),
        "trajectory": _async_trajectory_sim(),
    }


def _bench_observability() -> dict:
    """detail.observability (round 15): the concurrent mini-soak + its
    end-of-soak invariant audit, self-scraped over a real /metrics HTTP
    endpoint."""
    from fedcrack_tpu.tools.soak import run_soak

    return run_soak(duration_s=SOAK_S, seed=0)


def _bench_federation_health() -> dict:
    """detail.federation_health (round 18): the SCALED_UPDATE end-to-end
    drill — sanitation accepts, ledger flags, canary IoU regresses,
    watchdog breaches with a flight dump."""
    from fedcrack_tpu.tools.chaos_drill import run_scaled_update_drill

    return run_scaled_update_drill()


def _bench_robust_aggregation() -> dict:
    """detail.robust_aggregation (round 21): the 4-arm robust-combine A/B
    over real gRPC — FedAvg drags and cliffs the canary; trimmed-mean,
    Krum, and the ledger-coupled quarantine hold it — plus the
    colluding-minority variant and the health-report exclusion join."""
    from fedcrack_tpu.tools.chaos_drill import run_robust_aggregation_drill

    return run_robust_aggregation_drill()


def _bench_privacy() -> dict:
    """detail.privacy (round 23): what the privacy plane COSTS.

    1. DP utility A/B: the mesh DP-SGD twin at the off arm plus each
       ``PRIVACY_SIGMAS`` noise multiplier — identical tiny model, data
       and seeds, the noise multiplier the only delta — reporting val
       IoU/loss, the final-weight drift off the noiseless trajectory, and
       the accountant's closed-form eps(delta) per arm.
    2. Secagg overhead: host-math masking microbench on a real-sized
       update tree — fixed-point encode + pairwise pads per client timed
       against the plaintext serialize, wire-size ratio, and the unmasked
       weighted mean pinned EXACT against the plaintext fixed-point sum.
    3. The real-gRPC dropped-masker drill (tools/chaos_drill): quorum
       close, seed recovery, bit-for-bit survivor average, zero torn
       rounds.
    """
    import jax

    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.data.synthetic import synth_crack_batch
    from fedcrack_tpu.fed.serialization import tree_to_bytes
    from fedcrack_tpu.parallel import make_mesh, run_mesh_federation
    from fedcrack_tpu.parallel.fedavg_mesh import (
        build_federated_round,
        stack_client_data,
    )
    from fedcrack_tpu.privacy import secagg as S
    from fedcrack_tpu.privacy.accountant import compute_epsilon
    from fedcrack_tpu.tools.chaos_drill import run_secagg_dropout_drill
    from fedcrack_tpu.train.local import create_train_state, evaluate

    t0 = time.monotonic()
    tiny = ModelConfig(
        img_size=16, stem_features=4, encoder_features=(8,),
        decoder_features=(8, 4),
    )
    steps, batch = 2, 2
    mesh1 = make_mesh(1, 1)
    state0 = create_train_state(jax.random.key(0), tiny)
    init = state0.variables

    def data_fn(r: int):
        images, masks = stack_client_data(
            [synth_crack_batch(steps * batch, img_size=16, seed=r)],
            steps,
            batch,
        )
        return (
            images,
            masks,
            np.ones(1, np.float32),
            np.full(1, float(steps * batch), np.float32),
        )

    val_images, val_masks = synth_crack_batch(8, img_size=16, seed=977)

    def run_arm(sigma: float):
        rf = build_federated_round(
            mesh1, tiny, learning_rate=1e-3, local_epochs=1,
            dp_clip_norm=1.0 if sigma > 0.0 else 0.0,
            dp_noise_multiplier=sigma, dp_seed=42,
        )
        v, _ = run_mesh_federation(rf, init, data_fn, PRIVACY_ROUNDS, mesh1)
        metrics = evaluate(
            state0.replace_variables(v), [(val_images, val_masks)]
        )
        return v, metrics

    v_off, m_off = run_arm(0.0)
    leaves_off = [np.asarray(x) for x in jax.tree_util.tree_leaves(v_off)]

    def drift(v) -> float:
        return float(
            np.sqrt(
                sum(
                    float(np.sum((np.asarray(a) - b) ** 2))
                    for a, b in zip(jax.tree_util.tree_leaves(v), leaves_off)
                )
            )
        )

    # One noise step per mesh round (local_epochs=1): eps after the run is
    # the accountant's closed form at steps=PRIVACY_ROUNDS, the default
    # FedConfig q/delta (0.01 / 1e-5) — the same numbers the server's
    # history entries carry for this schedule.
    dp_utility: dict = {
        "off": {
            "noise_multiplier": 0.0,
            "clip_norm": 0.0,
            "epsilon": None,
            "val_iou": round(float(m_off["iou"]), 6),
            "val_loss": round(float(m_off["loss"]), 6),
            "weight_drift_vs_off": 0.0,
        }
    }
    for sigma in PRIVACY_SIGMAS:
        v_arm, m_arm = run_arm(float(sigma))
        dp_utility[f"sigma_{sigma:g}"] = {
            "noise_multiplier": float(sigma),
            "clip_norm": 1.0,
            "epsilon": round(
                compute_epsilon(0.01, float(sigma), PRIVACY_ROUNDS, 1e-5), 6
            ),
            "val_iou": round(float(m_arm["iou"]), 6),
            "val_loss": round(float(m_arm["loss"]), 6),
            "weight_drift_vs_off": round(drift(v_arm), 6),
        }

    # ---- secagg masking overhead, host math on a real-sized tree ----
    bits = S.DEFAULT_BITS
    rng = np.random.Generator(np.random.Philox(key=7))
    big_tree = {
        "params": {
            f"layer_{i}": rng.standard_normal(16384).astype(np.float32)
            for i in range(4)
        }
    }
    cohort = {name: S.client_seed(name) for name in ("a", "b", "c")}
    roster = S.round_roster(cohort, 1)
    plaintext_bytes = len(tree_to_bytes(big_tree))
    t_mask = time.perf_counter()
    masked = {
        name: S.mask_update(
            big_tree, cname=name, n_samples=10, roster=roster, bits=bits
        )
        for name in cohort
    }
    mask_ms = (time.perf_counter() - t_mask) / len(cohort) * 1e3
    masked_bytes = max(len(b) for b in masked.values())
    t_unmask = time.perf_counter()
    uploads = {name: S.decode_masked(masked[name]) for name in masked}
    total, total_samples, _dropped = S.unmask_sum(uploads, roster, bits)
    mean = S.unmasked_mean(total, total_samples, big_tree, bits)
    unmask_ms = (time.perf_counter() - t_unmask) * 1e3
    expected = S.fixed_point_decode(
        S.weighted_fixed_sum([big_tree] * 3, [10, 10, 10], bits),
        30, bits, big_tree,
    )
    exact = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree_util.tree_leaves(mean),
            jax.tree_util.tree_leaves(expected),
        )
    )
    overhead = {
        "n_params": int(
            sum(np.asarray(x).size for x in jax.tree_util.tree_leaves(big_tree))
        ),
        "cohort": len(cohort),
        "bits": int(bits),
        "plaintext_bytes": plaintext_bytes,
        "masked_bytes": int(masked_bytes),
        "wire_ratio": round(masked_bytes / plaintext_bytes, 4),
        "mask_ms": round(mask_ms, 3),
        "unmask_ms": round(unmask_ms, 3),
        "exact_vs_plaintext": bool(exact),
    }

    return {
        "rounds": PRIVACY_ROUNDS,
        "dp_utility": dp_utility,
        "secagg_overhead": overhead,
        "secagg_drill": run_secagg_dropout_drill(),
        "bench_s": round(time.monotonic() - t0, 2),
    }


def main() -> None:
    if os.environ.get("FEDCRACK_BENCH_FORCE_CPU"):
        from fedcrack_tpu.jaxcompat import ensure_cpu_devices

        ensure_cpu_devices()
    # Persistent XLA compilation cache: the sweep + ref-scale programs are
    # O(10) distinct compilations; on a warm cache they cost ~0 instead of
    # minutes of the budget.
    from fedcrack_tpu.jaxcompat import enable_compilation_cache

    enable_compilation_cache()
    from fedcrack_tpu.obs.flops import device_peak_flops
    from fedcrack_tpu.parallel import make_mesh

    n_clients = max(1, jax.device_count())
    device = jax.devices()[0]
    peak = device_peak_flops(device)
    mesh = make_mesh(n_clients, 1)
    # The reference-scale sections are single-client by definition (the
    # reference's workload is one client's round): they need a 1-device mesh
    # regardless of how many chips the sweep uses.
    ref_mesh = make_mesh(1, 1)
    skips: list = []
    section_s: dict = {}
    # Whatever happens past this point — a later section raising, not just a
    # signal — the sections that DID finish go out as the one JSON line.
    try:
        _run_sections(
            mesh, ref_mesh, n_clients, device, peak, skips, section_s
        )
    finally:
        _emit()


def _run_sections(mesh, ref_mesh, n_clients, device, peak, skips, section_s) -> None:

    def _budget_detail():
        return {
            "budget_s": BUDGET_S,
            "elapsed_s": round(_elapsed(), 1),
            "sections_s": {k: round(v, 1) for k, v in section_s.items()},
        }

    # ---- mandatory: sweep at the flagship size (every ratio needs it) ----
    t0 = time.monotonic()
    sweep: dict = {}

    # Bootstrap + per-point payloads: a TERM landing mid-sweep (even one
    # deferred through a native XLA compile until the call returns) still
    # ships every point that finished, instead of round 3's empty artifact.
    def _sweep_checkpoint():
        done = [p for p in sweep.values() if p.get("round_ms")]
        _set_payload(
            f"INCOMPLETE sweep ({len(done)} point(s) finished before "
            f"interruption): one-program FedAvg round wall-clock, "
            f"{n_clients} client(s), b{BATCH}, {STEPS} steps",
            done[-1]["round_ms"] if done else None,
            None,
            {"sweep": sweep, "skipped": skips, "budget": _budget_detail()},
        )

    _sweep_checkpoint()
    flagship_per_client, f32_state0, (flag_si, flag_sm) = _sweep_size(
        SIZES[0], mesh, n_clients, device, peak, sweep, checkpoint=_sweep_checkpoint
    )
    section_s[f"sweep_{SIZES[0]}"] = time.monotonic() - t0

    f32_key = f"float32_{SIZES[0]}"
    bf16_key = f"bfloat16_{SIZES[0]}"
    mesh_f32_s = sweep[f32_key]["round_s_raw"]
    mesh_bf16_s = sweep[bf16_key]["round_s_raw"]
    mesh_f32_compute_s = STEPS * _step_s(sweep[f32_key])
    mesh_bf16_compute_s = STEPS * _step_s(sweep[bf16_key])

    detail = {
        "sweep": sweep,
        "bf16_speedup_over_f32": (
            round(mesh_f32_compute_s / mesh_bf16_compute_s, 3)
            if sweep[f32_key]["per_step_ms"] is not None
            and sweep[bf16_key]["per_step_ms"] is not None
            else None
        ),
        "device_kind": getattr(device, "device_kind", "unknown"),
        "peak_tflops_bf16": None if peak is None else peak / 1e12,
        "n_clients": n_clients,
        "steps": STEPS,
        "batch": BATCH,
        "skipped": skips,
        "budget": _budget_detail(),
    }
    metric_sweep = (
        f"flagship one-program FedAvg round wall-clock "
        f"({n_clients} client(s), {SIZES[0]}x{SIZES[0]}, bf16 compute, "
        f"b{BATCH}, {STEPS} steps); vs_baseline = host/gRPC-style plane "
        f"over mesh plane at equal float32 dtype, dispatch-inclusive "
        f"(see detail for compute-only ratio, MFU sweep, decomposition)"
    )
    # Safety-net payload before the host plane exists (vs_baseline unknowable).
    _set_payload(metric_sweep, sweep[bf16_key]["round_ms"], None, detail)

    # ---- reference-scale points, budget-gated — the HEADLINE, so they run
    # immediately after the flagship sweep (the host plane used to run first
    # and starved these out of the driver's budget two rounds running) ----
    run_ref = REF_SCALE == "1" or (
        REF_SCALE == "auto" and getattr(device, "platform", "") == "tpu"
    )
    reference_scale: dict = {}
    segmented_pipeline: dict = {}
    resident_pool: dict = {}
    reuse = None
    total_steps = REF_EPOCHS * REF_STEPS
    if run_ref:
        img = SIZES[0]
        data_bytes = REF_STEPS * BATCH * (img * img * 4)  # uint8 imgs+masks
        synth_bytes = min(512, REF_STEPS * BATCH) * img * img * 16  # f32 synth
        reps = max(1, min(REPS, 3))
        round_est = _step_s(sweep[bf16_key]) * total_steps
        stage_est = _est_stage_s(data_bytes)
        # Warm rounds are priced at ~3x the settled round time (an estimate
        # from pre-round captures, not re-measured), hence 2 warms cost ~6
        # round-equivalents; one fresh program compile on top.
        flag_est = (
            _est_synth_s(synth_bytes)
            + 3 * stage_est
            + (6 + reps) * round_est
            + (reps + 1) * max(stage_est, round_est)
            + COMPILE_EST_S
            + 8.0
        )
        if _fits(flag_est):
            t0 = time.monotonic()
            point, reuse = _bench_reference_scale(
                img, "bfloat16", device, ref_mesh, full=True
            )
            section_s["ref_bf16"] = time.monotonic() - t0
            if point is not None:
                reference_scale[f"bfloat16_{img}"] = point
            else:
                _skip(skips, f"ref_scale_bfloat16_{img}", flag_est, "budget ran out mid-point")
        else:
            _skip(skips, f"ref_scale_bfloat16_{img}", flag_est, "estimate exceeds remaining budget")

        f32_round_est = _step_s(sweep[f32_key]) * total_steps
        f32_est = (6 + reps) * f32_round_est + COMPILE_EST_S + 4.0
        if reuse is not None and _fits(f32_est):
            t0 = time.monotonic()
            point, reuse = _bench_reference_scale(
                img, "float32", device, ref_mesh, full=False, reuse=reuse
            )
            section_s["ref_f32"] = time.monotonic() - t0
            if point is not None:
                reference_scale[f"float32_{img}"] = point
            else:
                _skip(skips, f"ref_scale_float32_{img}", f32_est, "budget ran out mid-point")
        else:
            _skip(
                skips,
                f"ref_scale_float32_{img}",
                f32_est,
                "estimate exceeds remaining budget"
                if reuse is not None
                else "flagship point skipped, no staged data to reuse",
            )
        # ---- segmented-pipeline A/B (round 7): the SAME reference-scale
        # round as K epoch-segment programs with chunk-grain streamed
        # restaging, vs the monolithic points above — reuses their staged
        # buffers, so it must run before the epoch is dropped ----
        for sp_dtype, with_ov in (("bfloat16", True), ("float32", False)):
            mono_point = reference_scale.get(f"{sp_dtype}_{img}")
            if mono_point is None or reuse is None:
                _skip(
                    skips,
                    f"segmented_pipeline_{sp_dtype}_{img}",
                    0.0,
                    "monolithic reference-scale point missing; no baseline",
                )
                continue
            mono_round_s = mono_point["round_s_raw"]
            stage_est = reuse.get("stage_s") or _est_stage_s(data_bytes)
            sp_est = (
                (2 + reps) * mono_round_s
                + (reps + 1) * max(stage_est, mono_round_s) * (1 if with_ov else 0)
                + COMPILE_EST_S
                + 4.0
            )
            if not _fits(sp_est):
                _skip(
                    skips,
                    f"segmented_pipeline_{sp_dtype}_{img}",
                    sp_est,
                    "estimate exceeds remaining budget",
                )
                continue
            t0 = time.monotonic()
            sp_point = _bench_segmented_pipeline(
                img, sp_dtype, device, ref_mesh, reuse, mono_point,
                with_overlap=with_ov,
            )
            section_s[f"segmented_pipeline_{sp_dtype}"] = time.monotonic() - t0
            if sp_point is not None:
                segmented_pipeline[f"{sp_dtype}_{img}"] = sp_point
            else:
                _skip(
                    skips,
                    f"segmented_pipeline_{sp_dtype}_{img}",
                    sp_est,
                    "budget ran out mid-point",
                )

        # ---- resident-pool A/B (round 9): streamed vs device-resident
        # data plane at reference scale — the roofline-collapse deliverable.
        # Reuses the monolithic point's host arrays + dedup pool, so it must
        # run before the epoch is dropped ----
        mono_bf16 = reference_scale.get(f"bfloat16_{img}")
        if mono_bf16 is None or reuse is None:
            _skip(
                skips,
                f"resident_pool_bfloat16_{img}",
                0.0,
                "monolithic reference-scale point missing; no baseline",
            )
        else:
            mono_round_s = mono_bf16["round_s_raw"]
            rp_est = (2 + reps) * mono_round_s + (reps + 1) * mono_round_s + COMPILE_EST_S + 8.0
            if not _fits(rp_est):
                _skip(
                    skips,
                    f"resident_pool_bfloat16_{img}",
                    rp_est,
                    "estimate exceeds remaining budget",
                )
            else:
                t0 = time.monotonic()
                rp_point = _bench_resident_pool(
                    img, "bfloat16", device, ref_mesh, reuse, mono_bf16
                )
                section_s["resident_pool_bfloat16"] = time.monotonic() - t0
                if rp_point is not None:
                    resident_pool[f"bfloat16_{img}"] = rp_point
                else:
                    _skip(
                        skips,
                        f"resident_pool_bfloat16_{img}",
                        rp_est,
                        "budget ran out mid-point",
                    )

        # The ref-128 epoch (~400 MB host + device) is dead weight for the
        # remaining sections — drop it before the 256px staging below.
        reuse = None

    ref_bf16 = reference_scale.get(f"bfloat16_{SIZES[0]}")
    ref_f32 = reference_scale.get(f"float32_{SIZES[0]}")
    metric_headline = metric_sweep
    value = sweep[bf16_key]["round_ms"]
    vs_baseline = None
    mesh_ref_f32_s = None
    if reference_scale:
        detail["reference_scale"] = reference_scale
        if segmented_pipeline:
            detail["segmented_pipeline"] = segmented_pipeline
        if resident_pool:
            detail["resident_pool"] = resident_pool
        # Ratio denominator: the measured f32 ref round when it ran; else the
        # slope-reconstructed f32 round (conservative — slope excludes the
        # one-dispatch cost the measured round would include).
        denom_note = "measured f32 reference-scale round"
        if ref_f32 is not None:
            mesh_ref_f32_s = ref_f32["round_s_raw"]
        else:
            mesh_ref_f32_s = _step_s(sweep[f32_key]) * total_steps
            denom_note = "slope-reconstructed f32 round (f32 ref point skipped)"
        if ref_bf16 is not None:
            # The metric/value pair switches to reference scale ONLY when the
            # bf16 reference-scale point actually landed (round-4 advisor
            # finding: an aborted bf16 point must not leave a reference-scale
            # metric string over a sweep-scale value).
            value = ref_bf16["round_ms"]
            metric_headline = (
                f"reference-scale one-program FedAvg round wall-clock "
                f"(1 client, {SIZES[0]}x{SIZES[0]}, bf16 compute, b{BATCH}, "
                f"{REF_EPOCHS} epochs x {REF_STEPS} steps = {total_steps} steps, "
                f"uint8 staging); vs_baseline = reconstructed host/gRPC-style "
                f"plane over {denom_note} at equal float32 dtype, "
                f"dispatch-inclusive (detail.vs_baseline_ref_compute_only is the "
                f"dispatch-free floor; detail.reference_scale has the "
                f"staging/compute/overlap decomposition)"
            )
        else:
            metric_headline = metric_sweep + (
                " [bf16 reference-scale point missing: value stays "
                "sweep-scale; vs_baseline is the reference-scale f32 ratio "
                "when reference_scale is non-empty; detail.reference_scale "
                "holds what landed]"
            )
        detail["budget"] = _budget_detail()
        _set_payload(metric_headline, value, vs_baseline, detail)

    # ---- serving plane (round 10): the full serve stack (compiled buckets,
    # micro-batcher, hot-swap manager, gRPC front door) under closed-loop
    # load with one live hot-swap — THIS round's deliverable, so it runs
    # right after the reference-scale headline ----
    if SERVING:
        serve_est = (
            2 * COMPILE_EST_S
            + SERVE_REQUESTS * 0.3
            + _est_synth_s(
                sum(
                    s * s * 16 * (SERVE_REQUESTS // max(1, len(SERVE_SIZES)) + 1)
                    for s in SERVE_SIZES
                )
            )
            + 15.0
        )
        if _fits(serve_est):
            t0 = time.monotonic()
            try:
                detail["serving"] = _bench_serving(device)
            except Exception as e:  # the serving extra must never kill the artifact
                detail["serving"] = {"error": repr(e)}
            section_s["serving"] = time.monotonic() - t0
            detail["budget"] = _budget_detail()
            _set_payload(metric_headline, value, vs_baseline, detail)
        else:
            _skip(skips, "serving", serve_est, "estimate exceeds remaining budget")

    # ---- serve fleet (round 17): replicas x quant grid through the
    # in-process router, the fleet-wide two-phase swap, and the ramp-profile
    # shed run — this round's deliverable, right after the r10 serving
    # section (they share warm programs when both run) ----
    if SERVE_FLEET:
        fleet_est = (
            3 * COMPILE_EST_S  # ref + int8 + (cache-warm) swap/shed builds
            + len(FLEET_REPLICAS) * 2 * FLEET_REQUESTS * 0.15
            + 30.0
        )
        if _fits(fleet_est):
            t0 = time.monotonic()
            try:
                detail["serve_fleet"] = _bench_serve_fleet(device)
            except Exception as e:  # never kills the artifact
                detail["serve_fleet"] = {"error": repr(e)}
            section_s["serve_fleet"] = time.monotonic() - t0
            detail["budget"] = _budget_detail()
            _set_payload(metric_headline, value, vs_baseline, detail)
        else:
            _skip(
                skips, "serve_fleet", fleet_est, "estimate exceeds remaining budget"
            )

    # ---- elastic fleet (round 22): the 3-arm diurnal A/B (static-max vs
    # static-min vs autoscaled) through the gRPC front door plus the
    # shadow-replica promote/rollback pins. The model is tiny (host-scale
    # compile); wall is dominated by the seeded diurnal schedule itself —
    # ~2*requests/rate per arm — plus the two shadow stagings ----
    if ELASTIC:
        elastic_est = (
            COMPILE_EST_S
            + 3 * 2.2 * ELASTIC_REQUESTS / max(1.0, ELASTIC_RATE)
            + 40.0
        )
        if _fits(elastic_est):
            t0 = time.monotonic()
            try:
                detail["elastic_fleet"] = _bench_elastic_fleet(device)
            except Exception as e:  # never kills the artifact
                detail["elastic_fleet"] = {"error": repr(e)}
            section_s["elastic_fleet"] = time.monotonic() - t0
            detail["budget"] = _budget_detail()
            _set_payload(metric_headline, value, vs_baseline, detail)
        else:
            _skip(
                skips, "elastic_fleet", elastic_est,
                "estimate exceeds remaining budget",
            )

    # ---- video serving (round 19): the frame-coherent session plane —
    # stateless-vs-cached-session A/B over one seeded >=90%-overlap
    # sequence, per-frame byte-identity across a live mid-sequence hot
    # swap, serve_stream_* exposition, and the StreamPredict gRPC smoke.
    # Tiny weights + two small bucket programs: host-scale seconds ----
    if VIDEO:
        video_est = 2 * COMPILE_EST_S + VIDEO_FRAMES * 0.5 + 20.0
        if _fits(video_est):
            t0 = time.monotonic()
            try:
                detail["video_serving"] = _bench_video_serving(device)
            except Exception as e:  # never kills the artifact
                detail["video_serving"] = {"error": repr(e)}
            section_s["video_serving"] = time.monotonic() - t0
            detail["budget"] = _budget_detail()
            _set_payload(metric_headline, value, vs_baseline, detail)
        else:
            _skip(
                skips, "video_serving", video_est, "estimate exceeds remaining budget"
            )

    # ---- low-precision kernels (round 20): the kernel-plane A/B —
    # reference vs fused-int8 (interpreter off-TPU) vs fp8-where-supported
    # quantized predict on the r5 interleaved template, with per-plane
    # parity + install-gate verdicts. Tiny engine: host-scale seconds
    # off-TPU; the function budget-gates its variants individually ----
    if LOWP:
        t0 = time.monotonic()
        try:
            lowp_point = _bench_lowp_kernels(device, skips)
            if lowp_point is not None:
                detail["lowp_kernels"] = lowp_point
        except Exception as e:  # never kills the artifact
            detail["lowp_kernels"] = {"error": repr(e)}
        section_s["lowp_kernels"] = time.monotonic() - t0
        detail["budget"] = _budget_detail()
        _set_payload(metric_headline, value, vs_baseline, detail)

    # ---- layout A/B (round 6): the VERDICT r5 top ask — space-to-depth /
    # channel-packing graph transforms vs the reference layout, interleaved,
    # at the flagship size in the headline dtypes. Runs right after the
    # reference-scale headline (it is this round's deliverable) and before
    # the host plane; per-variant budget gating degrades it gracefully ----
    layout_ab: dict = {}

    def _layout_checkpoint():
        detail["layout_ab"] = layout_ab
        detail["budget"] = _budget_detail()
        _set_payload(metric_headline, value, vs_baseline, detail)

    t0 = time.monotonic()
    for ab_dtype in ("bfloat16", "float32"):
        _layout_ab(
            SIZES[0],
            mesh,
            n_clients,
            device,
            peak,
            flag_si,
            flag_sm,
            layout_ab,
            dtype=ab_dtype,
            round_s_hint=sweep[f"{ab_dtype}_{SIZES[0]}"]["round_s_raw"],
            skips=skips,
            checkpoint=_layout_checkpoint,
        )
    if layout_ab:
        section_s[f"layout_ab_{SIZES[0]}"] = time.monotonic() - t0
        _layout_checkpoint()

    # ---- host plane (reference architecture) — AFTER the headline sections
    # (it once starved them); degrades to a 1-rep median, then to a recorded skip ----
    host_parts = None
    host_total_s = None
    host_round_est = n_clients * STEPS * (_step_s(sweep[f32_key]) + 0.12) + 2.0
    for host_reps in (REPS, 1):
        host_est = COMPILE_EST_S + (1 + host_reps) * host_round_est + 5.0
        if _fits(host_est):
            t0 = time.monotonic()
            host_total_s, host_parts = _measure_host_plane(
                n_clients,
                f32_state0.variables,
                flagship_per_client,
                f32_state0,
                reps=host_reps,
            )
            section_s["host_plane"] = time.monotonic() - t0
            break
    else:
        _skip(skips, "host_plane", host_est, "estimate exceeds remaining budget")
        if (
            "reconstructed host/gRPC-style" in metric_headline
            or "reference-scale f32 ratio" in metric_headline
        ):
            # The metric text promises a host-plane ratio that now cannot be
            # computed — annotate rather than mislabel (the same
            # labeling-honesty class as the round-4 metric/value fix). BOTH
            # promising variants are matched (ADVICE r5 #2): the full
            # ref-scale string and the bf16-point-missing string, whose
            # "vs_baseline is the reference-scale f32 ratio" clause would
            # otherwise keep promising a ratio that stays None.
            metric_headline += (
                " [host plane budget-skipped: vs_baseline unavailable this run]"
            )
            _set_payload(metric_headline, value, vs_baseline, detail)

    host_ref_s = None
    host_ref_compute_s = None
    if host_parts is not None:
        # Compute-only reconstruction of a host round: the same SGD step costs
        # what the mesh plane's scan charges per step (identical XLA program);
        # everything above that is the host architecture's own overhead.
        compute_s = n_clients * STEPS * _step_s(sweep[f32_key])
        ser_s = host_parts["serialization_ms"] / 1e3
        agg_s = host_parts["host_fedavg_ms"] / 1e3
        dispatch_s = max(0.0, host_total_s - compute_s - ser_s - agg_s)
        compute_only_s = compute_s + ser_s + agg_s

        detail["host_plane"] = {
            "dtype": "float32",
            "img_size": SIZES[0],
            "round_ms": round(host_total_s * 1e3, 2),
            "reps": host_reps,
            "per_step_compute_ms": round(_step_s(sweep[f32_key]) * 1e3, 3),
            "serialization_ms": round(host_parts["serialization_ms"], 2),
            "host_fedavg_ms": round(host_parts["host_fedavg_ms"], 2),
            "dispatch_overhead_ms": round(dispatch_s * 1e3, 2),
            "note": (
                "dispatch_overhead is per-step Python dispatch + host<->device "
                "transfer round-trips; it is NOT a compute advantage"
            ),
        }
        # Same-architecture-work ratio, dispatch excluded on BOTH sides: host
        # round rebuilt from its compute + serialization + aggregation parts,
        # over the mesh round's slope-based (dispatch-free) time.
        detail["vs_baseline_compute_only"] = round(
            compute_only_s / mesh_f32_compute_s, 3
        )
        # Measured end-to-end ratio against the bf16 flagship.
        detail["vs_baseline_vs_flagship"] = round(host_total_s / mesh_bf16_s, 3)

        if reference_scale:
            # Host plane restated AT THE REFERENCE'S SCALE: reconstructed from
            # measured components — per-step compute slope, per-step dispatch
            # overhead from the measured STEPS-step host round, serialization,
            # host FedAvg — because driving 3,880 Python-dispatched steps
            # per rep is minutes per measurement for no added information.
            per_step_overhead_s = dispatch_s / max(1, n_clients * STEPS)
            # 1-client serialization shape: 1 broadcast + 1 upload serialized,
            # 1 client parse + 1 server parse (NOT this run's n_clients total).
            ser_ref_s = (
                2 * host_parts["to_bytes_s_raw"]
                + 2 * host_parts["from_bytes_s_raw"]
            )
            agg_ref_s = host_parts["fedavg_s_raw"]
            host_ref_s = (
                total_steps * (_step_s(sweep[f32_key]) + per_step_overhead_s)
                + ser_ref_s
                + agg_ref_s
            )
            host_ref_compute_s = (
                total_steps * _step_s(sweep[f32_key]) + ser_ref_s + agg_ref_s
            )
            detail["host_ref_reconstructed_s"] = round(host_ref_s, 3)
            detail["vs_baseline_ref_compute_only"] = round(
                host_ref_compute_s / mesh_ref_f32_s, 3
            )
            vs_baseline = round(host_ref_s / mesh_ref_f32_s, 3)
        else:
            vs_baseline = round(host_total_s / mesh_f32_s, 3)
        detail["budget"] = _budget_detail()
        _set_payload(metric_headline, value, vs_baseline, detail)

    # ---- input pipeline: the reference's synchronous per-step decode cost
    # (host-CPU-only, cheap — no device traffic) — closes the
    # decode-exclusive-reconstruction caveat (round-4 weak #4) ----
    input_pipeline = None
    if _fits(20.0):
        t0 = time.monotonic()
        try:
            input_pipeline = _measure_input_pipeline(SIZES[0])
        except Exception as e:  # a host-only extra must never kill the artifact
            input_pipeline = {"error": repr(e)}
        section_s["input_pipeline"] = time.monotonic() - t0
    else:
        _skip(skips, "input_pipeline", 20.0, "estimate exceeds remaining budget")
    if input_pipeline is not None:
        detail["input_pipeline"] = input_pipeline
        dec = input_pipeline.get("charged_per_step_s_raw")
        if dec is not None and host_ref_s is not None:
            # Decode-inclusive reconstruction: the reference pays BATCH
            # synchronous image+mask decodes before every step (inside fit);
            # the mesh plane's input cost is already inside its measured
            # round (uint8 pool staged + overlapped by parallel.driver).
            detail["host_ref_with_input_s"] = round(
                host_ref_s + total_steps * dec, 3
            )
            detail["vs_baseline_ref_with_input"] = round(
                (host_ref_s + total_steps * dec) / mesh_ref_f32_s, 3
            )
            detail["vs_baseline_ref_compute_plus_input"] = round(
                (host_ref_compute_s + total_steps * dec) / mesh_ref_f32_s, 3
            )
        detail["budget"] = _budget_detail()
        _set_payload(metric_headline, value, vs_baseline, detail)

    # ---- chaos recovery: the mid-round server kill→restart drill (host-only
    # control plane, tiny weights, seconds — times the round-8 durable-
    # statefile crash-recovery path; semantics are pinned by the tier-1
    # chaos suite, this section contributes the TIMING artifact) ----
    if CHAOS:
        if _fits(15.0):
            t0 = time.monotonic()
            try:
                from fedcrack_tpu.tools.chaos_drill import run_kill_restart_drill

                detail["chaos_recovery"] = run_kill_restart_drill()
            except Exception as e:  # a host-only extra must never kill the artifact
                detail["chaos_recovery"] = {"error": repr(e)}
            section_s["chaos_recovery"] = time.monotonic() - t0
            detail["budget"] = _budget_detail()
            _set_payload(metric_headline, value, vs_baseline, detail)
        else:
            _skip(skips, "chaos_recovery", 15.0, "estimate exceeds remaining budget")

    # ---- compressed update transport A/B (round 12): wire bytes + codec
    # timings at reference scale (host, seconds) and the mesh twins'
    # IoU-trajectory delta vs the NullCodec oracle (three tiny-model round
    # programs; COMPILE-dominated, so the estimate assumes cold) ----
    if COMPRESSION:
        comp_est = 3 * 20.0 + 10.0
        if _fits(comp_est):
            t0 = time.monotonic()
            try:
                detail["update_compression"] = _bench_update_compression()
            except Exception as e:  # a host-side extra must never kill the artifact
                detail["update_compression"] = {"error": repr(e)}
            section_s["update_compression"] = time.monotonic() - t0
            detail["budget"] = _budget_detail()
            _set_payload(metric_headline, value, vs_baseline, detail)
        else:
            _skip(
                skips,
                "update_compression",
                comp_est,
                "estimate exceeds remaining budget",
            )

    # ---- cohort scale (round 13): the group-count sweep over the time-
    # multiplexed cohort round (three grouped builds of the tiny model —
    # compile-dominated, assume cold) plus the 1,024-simulated-client
    # tree round and its flat A/B (host-only, tiny blobs, seconds) ----
    if COHORT:
        cohort_est = 3 * 30.0 + 20.0
        if _fits(cohort_est):
            t0 = time.monotonic()
            try:
                detail["cohort_scale"] = _bench_cohort_scale()
            except Exception as e:  # a host-side extra must never kill the artifact
                detail["cohort_scale"] = {"error": repr(e)}
            section_s["cohort_scale"] = time.monotonic() - t0
            detail["budget"] = _budget_detail()
            _set_payload(metric_headline, value, vs_baseline, detail)
        else:
            _skip(
                skips,
                "cohort_scale",
                cohort_est,
                "estimate exceeds remaining budget",
            )

    # ---- async federation (round 14): the straggler-storm sync-vs-
    # buffered A/B over a real gRPC control plane (seeded delays, equal
    # wall — seconds of real sleeps), the bit-exact sync-degeneration pin,
    # the mid-buffer kill→restart drill, and the equal-wall trajectory
    # simulation (host-only, deterministic) ----
    if ASYNC:
        if _fits(20.0):
            t0 = time.monotonic()
            try:
                detail["async_federation"] = _bench_async_federation()
            except Exception as e:  # a host-only extra must never kill the artifact
                detail["async_federation"] = {"error": repr(e)}
            section_s["async_federation"] = time.monotonic() - t0
            detail["budget"] = _budget_detail()
            _set_payload(metric_headline, value, vs_baseline, detail)
        else:
            _skip(
                skips, "async_federation", 20.0, "estimate exceeds remaining budget"
            )

    # ---- observability (round 15): the concurrent mini-soak — buffered
    # federation + edge shard + live hot-swapping serve plane + driver leg
    # under a rolling chaos schedule (storm delays, corrupt frames, a
    # mid-soak server kill→restart), watched through its own /metrics
    # endpoint and closed with the invariant audit ----
    if OBSERVABILITY:
        obsy_est = SOAK_S + 25.0  # + tiny-engine compile & teardown
        if _fits(obsy_est):
            t0 = time.monotonic()
            try:
                detail["observability"] = _bench_observability()
            except Exception as e:  # an in-process extra must never kill the artifact
                detail["observability"] = {"error": repr(e)}
            section_s["observability"] = time.monotonic() - t0
            detail["budget"] = _budget_detail()
            _set_payload(metric_headline, value, vs_baseline, detail)
        else:
            _skip(
                skips, "observability", obsy_est, "estimate exceeds remaining budget"
            )

    # ---- federation health (round 18): the SCALED_UPDATE drill — the
    # sanitation gate accepts a scaled-but-finite update, the per-client
    # ledger's robust-z score flags it, the canary IoU cliffs on the
    # poisoned install, and the health watchdog turns the pair of signals
    # into a breach + flight dump + exit-3 verdict ----
    if HEALTH:
        health_est = 30.0  # one 1-round federation + tiny-engine compile
        if _fits(health_est):
            t0 = time.monotonic()
            try:
                detail["federation_health"] = _bench_federation_health()
            except Exception as e:  # a host-only extra must never kill the artifact
                detail["federation_health"] = {"error": repr(e)}
            section_s["federation_health"] = time.monotonic() - t0
            detail["budget"] = _budget_detail()
            _set_payload(metric_headline, value, vs_baseline, detail)
        else:
            _skip(
                skips,
                "federation_health",
                health_est,
                "estimate exceeds remaining budget",
            )

    # ---- robust aggregation (round 21): the same SCALED_UPDATE poison as
    # a 4-arm A/B — FedAvg drags the global ~x300 and cliffs the canary;
    # trimmed-mean / Krum / the ledger-coupled quarantine hold IoU and cut
    # the drag by >= 10x; the colluding-minority variant and the
    # health-report join ride along ----
    if ROBUST:
        robust_est = 20.0  # nine tiny 1-round federations + one engine
        if _fits(robust_est):
            t0 = time.monotonic()
            try:
                detail["robust_aggregation"] = _bench_robust_aggregation()
            except Exception as e:  # a host-only extra must never kill the artifact
                detail["robust_aggregation"] = {"error": repr(e)}
            section_s["robust_aggregation"] = time.monotonic() - t0
            detail["budget"] = _budget_detail()
            _set_payload(metric_headline, value, vs_baseline, detail)
        else:
            _skip(
                skips,
                "robust_aggregation",
                robust_est,
                "estimate exceeds remaining budget",
            )

    # ---- privacy (round 23): the DP utility/epsilon A/B on the mesh
    # twin (one compile per noise arm — that IS the wall), the secagg
    # masking-overhead microbench with its exact unmask pin, and the
    # real-gRPC dropped-masker drill ----
    if PRIVACY:
        privacy_est = (1 + len(PRIVACY_SIGMAS)) * COMPILE_EST_S + 15.0
        if _fits(privacy_est):
            t0 = time.monotonic()
            try:
                detail["privacy"] = _bench_privacy()
            except Exception as e:  # a host-only extra must never kill the artifact
                detail["privacy"] = {"error": repr(e)}
            section_s["privacy"] = time.monotonic() - t0
            detail["budget"] = _budget_detail()
            _set_payload(metric_headline, value, vs_baseline, detail)
        else:
            _skip(
                skips,
                "privacy",
                privacy_est,
                "estimate exceeds remaining budget",
            )

    # ---- batch-scaling curve (bf16 flagship at batch 32/64; non-parity
    # appendix substantiating the width-bound-ceiling claim) ----
    curve: dict = {}
    bf16_round_s = sweep[bf16_key]["round_s_raw"]
    curve_est = (
        2 * (2 + REPS) * (1 + FIT_FACTOR) * bf16_round_s + 4 * COMPILE_EST_S + 5.0
    )
    if _fits(curve_est):

        def _curve_checkpoint():
            detail["batch_curve"] = curve
            detail["budget"] = _budget_detail()
            _set_payload(metric_headline, value, vs_baseline, detail)

        t0 = time.monotonic()
        _batch_curve(
            SIZES[0],
            mesh,
            n_clients,
            device,
            peak,
            flag_si,
            flag_sm,
            curve,
            checkpoint=_curve_checkpoint,
        )
        section_s["batch_curve"] = time.monotonic() - t0
        _curve_checkpoint()
    else:
        _skip(skips, "batch_curve", curve_est, "estimate exceeds remaining budget")
    # The staged flagship arrays are dead weight for the remaining sections.
    del flag_si, flag_sm

    # ---- secondary sweep sizes (MFU completeness; least load-bearing) ----
    for img in SIZES[1:]:
        sz_bytes = STEPS * BATCH * n_clients * img * img * 16
        # Per dtype: (2 warm + REPS) rounds at BOTH scan lengths (short +
        # FIT_FACTOR x long); per-step cost scales ~quadratically with crop.
        # 4 fresh programs (2 dtypes x 2 scan lengths) assumed UNCACHED.
        step_scaled = _step_s(sweep[f32_key]) * (img / SIZES[0]) ** 2
        est = (
            _est_synth_s(sz_bytes)
            + _est_stage_s(sz_bytes)
            + 2 * (2 + REPS) * (1 + FIT_FACTOR) * STEPS * step_scaled
            + 4 * COMPILE_EST_S
            + 5.0
        )
        if not _fits(est):
            _skip(skips, f"sweep_{img}", est, "estimate exceeds remaining budget")
            continue
        t0 = time.monotonic()
        _, _, (sz_si, sz_sm) = _sweep_size(img, mesh, n_clients, device, peak, sweep)
        section_s[f"sweep_{img}"] = time.monotonic() - t0
        detail["budget"] = _budget_detail()
        _set_payload(metric_headline, value, vs_baseline, detail)
        # Layout A/B at the secondary size (the 256 px point of the round-6
        # deliverable), reusing this sweep's staged arrays — bf16 only (the
        # MFU headline dtype); per-variant gating trims it under pressure.
        t0 = time.monotonic()
        _layout_ab(
            img,
            mesh,
            n_clients,
            device,
            peak,
            sz_si,
            sz_sm,
            layout_ab,
            dtype="bfloat16",
            round_s_hint=sweep[f"bfloat16_{img}"]["round_s_raw"],
            skips=skips,
            checkpoint=_layout_checkpoint,
        )
        if f"bfloat16_{img}" in layout_ab:
            section_s[f"layout_ab_{img}"] = time.monotonic() - t0
            _layout_checkpoint()
        del sz_si, sz_sm

    # ---- opt-in: the ~10 min bf16/256 reference-scale point ----
    if run_ref and REF_256 and len(SIZES) > 1:
        img = SIZES[1]
        data_bytes = REF_STEPS * BATCH * (img * img * 4)
        round_256_est = _step_s(sweep[bf16_key]) * total_steps * (img / SIZES[0]) ** 2
        est = (
            _est_synth_s(min(512, REF_STEPS * BATCH) * img * img * 16)
            + 3 * _est_stage_s(data_bytes)
            + (6 + REPS) * round_256_est
            + (REPS + 1) * max(_est_stage_s(data_bytes), round_256_est)
            + COMPILE_EST_S
            + 8.0
        )
        if _fits(est):
            t0 = time.monotonic()
            try:
                # Round 7: measured via epoch-chunked execution — K programs
                # of REF_STEPS steps each, staged as K chunk transfers:
                # each 388-step segment is the same size class as the
                # 128 px programs, where the monolith is one 3,880-step
                # program fed by a 1.6 GB single transfer.
                point, _ = _bench_reference_scale(
                    img, "bfloat16", device, ref_mesh, full=True,
                    segments=(
                        SEGMENTS
                        if SEGMENTS > 0 and REF_EPOCHS % SEGMENTS == 0
                        else REF_EPOCHS
                    ),
                )
            except Exception as e:
                # Even the chunked form can fail; record
                # the failure as a skip — every earlier section's data is
                # already in the payload.
                point = None
                _skip(skips, f"ref_scale_bfloat16_{img}", est, f"failed: {e!r:.180}")
            section_s[f"ref_bf16_{img}"] = time.monotonic() - t0
            if point is not None:
                detail.setdefault("reference_scale", {})[f"bfloat16_{img}"] = point
            elif not any(s["section"] == f"ref_scale_bfloat16_{img}" for s in skips):
                _skip(skips, f"ref_scale_bfloat16_{img}", est, "budget ran out mid-point")
        else:
            _skip(skips, f"ref_scale_bfloat16_{img}", est, "estimate exceeds remaining budget")

    detail["budget"] = _budget_detail()


if __name__ == "__main__":
    main()
