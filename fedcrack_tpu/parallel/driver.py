"""Multi-round mesh federation driver with double-buffered staging.

The one-program round (``parallel.fedavg_mesh``) consumes per-client data
already resident on the chips; what turns it into a *federation* is this
loop: stage round r's data, dispatch the round program (asynchronously),
and — while the device computes — synthesize/shuffle and stage round r+1's
buffers, so host→device transfer rides under device time instead of adding
to it. The reference's input pipeline is the opposite architecture: a
synchronous per-batch cv2 decode in the middle of the hot loop
(reference: client_fit_model.py:30-43 inside fit, SURVEY.md §3.3) — the
first-order bottleneck SURVEY.md §7 told us to replace.

Two round execution modes (round 7):

- **Monolithic** (``round_fn`` from ``build_federated_round``): the whole
  round is one program and staging double-buffers at ROUND grain — one
  ``device_put`` of the full epoch slab per round.
- **Segmented** (a ``SegmentedRound`` from
  ``build_federated_round_segments``): the round runs as K segment
  programs with a device-resident donated carry, and the next round's
  slab streams CHUNK BY CHUNK between segment dispatches
  (``segment_overlap=True``), so a single monolithic transfer never sits
  on the bus and the previous round's chunks are released as soon as the
  round barrier passes — peak staged-data HBM is bounded by ~2 epoch
  slabs (test-pinned via ``RoundRecord.max_live_staged_bytes``). Both
  modes produce bit-identical weights (staging is data-independent and
  the segmented program is byte-exact vs the monolithic scan).

The benchmark's cells (``benchmark/lib/``), ``chip_smoke.py``,
``tools/profile_step`` and ``tools/refscale_federation`` all drive rounds
through it, and the overlap's correctness (same weights as sequential
staging) is test-pinned (tests/test_driver.py).

Mid-federation checkpoint/resume (round 7): pass a
``ckpt.manager.FedCheckpointer`` as ``checkpointer`` and the driver saves
the global variables at every round boundary; a restarted session restores
the checkpoint, passes the restored variables plus ``start_round`` and
continues the same trajectory (bit-identical on the deterministic path —
the data_fn is called with absolute round indices either way).

Preemption tolerance (round 8): ``max_round_retries > 0`` arms a bounded
per-round retry loop — an attempt that raises (device/host loss) or emits
non-finite weights/metrics is rolled back to the round boundary (durable
checkpoint when available, else an in-memory snapshot) and replayed,
bit-identically. The chaos suite drives it through
``fault_injector`` (``chaos.inject.MeshChaos``); both knobs are zero-cost
when off.

Resident data plane (round 9): both staging modes above re-ship the SAME
samples every round in a new shuffle order — the wire carries a
permutation of bytes already in HBM, and the staging term of the
max(compute, staging) roofline is pure waste. ``data_placement="resident"``
stages a deduplicated ``data.pipeline.SamplePool`` ONCE (sharded
``P('clients')``) and per round uploads only the ``[C, epochs, steps, B]``
int32 gather plan (kilobytes); the round program assembles each batch on
device by ``jnp.take`` — byte-identical to the streamed round over the
host-assembled slab (test-pinned). Accounting stays honest: the pool is
charged to the first round's record, every later round's ``staged_bytes``
is indices only, and ``max_live_staged_bytes`` includes the resident pool.
An HBM guard (:func:`resident_pool_fits`) falls the federation back to the
streamed/segment-chunked path — slabs host-assembled from the same pool +
plan, same trajectory — when the pool doesn't fit; a chaos/preemption
replay re-stages pool and plan bit-identically from the retained host twin.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import resource
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedcrack_tpu.data.pipeline import SamplePool, split_epoch_slab
from fedcrack_tpu.obs import spans as tracing
from fedcrack_tpu.obs.registry import REGISTRY
from fedcrack_tpu.obs.sentries import device_memory_stats, fullest_device_memory
from fedcrack_tpu.parallel.fedavg_mesh import (
    CohortRound,
    SegmentedRound,
    pad_cohort_axis,
)

CLIENTS, BATCH = "clients", "batch"


def _observe_round_record(record: "RoundRecord", sentry: Any = None) -> None:
    """Project one RoundRecord into the metric registry (the mesh/driver
    plane of the r15 catalog). Purely additive: the record stays the
    artifact of truth, the registry is the live view a scrape sees
    mid-session. The round's spans (``driver.round`` and its phases) are
    opened around the work itself, in ``run_mesh_federation``."""
    REGISTRY.counter(
        "driver_rounds_total", "mesh federated rounds driven to their barrier"
    ).inc()
    REGISTRY.histogram(
        "driver_round_seconds",
        "host wall clock of one driven round (dispatch to barrier)",
        buckets=(0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0),
    ).observe(record.wall_clock_s)
    REGISTRY.counter(
        "driver_staged_bytes_total",
        "host->device bytes newly staged for driven rounds",
    ).inc(max(0, record.staged_bytes))
    REGISTRY.gauge(
        "driver_live_staged_bytes",
        "peak concurrently-staged driver bytes in the latest round",
    ).set(record.max_live_staged_bytes)
    if record.bytes_per_round:
        REGISTRY.counter(
            "driver_wire_bytes_total",
            "modeled update wire bytes for driven rounds (codec-priced)",
        ).inc(record.bytes_per_round)
    if sentry is not None:
        REGISTRY.gauge(
            "driver_recompiles_total",
            "RecompileSentry deltas since its mark over the driver's "
            "watched round programs (steady-state contract: 0)",
        ).set(sum(sentry.deltas().values()))

@dataclasses.dataclass
class RoundRecord:
    """One round's timing + metrics, host-side.

    BOUNDARY-TERM NOTE (round 7): ``staging_s`` is the host-BLOCKING
    staging time paid for THIS round's data, in both modes. Round
    ``start_round``'s record carries the initial (never-overlapped)
    staging; a sequential-mode round carries the post-barrier staging of
    its own data (measured during the previous round's slot); an
    overlapped round carries 0.0 BY CONSTRUCTION, because its staging rode
    under the previous round's compute: that 0.0 is not a reading of what
    staging costs. The cost is ``host_s["stage"]`` of the round it ran
    under (the record before). Before round 7 the initial staging was
    charged to NO record and sequential records carried the NEXT round's
    staging — session totals (``sum(wall_clock_s + data_fn_s +
    staging_s)``) silently understated by one staging period.

    COMPARABILITY NOTE (round 5+): in sequential mode
    (``overlap_staging=False``) the ``data_fn(r+1)`` host shuffle is ALSO
    deferred past the round barrier (previously only staging was serialized
    while the shuffle rode under the in-flight round). Sequential session
    totals therefore now include the unoverlapped shuffle and are NOT
    comparable to pre-round-5 sequential runs; per-round ``wall_clock_s``
    is the intended pure round time either way. Overlap-mode records are
    unaffected.
    """

    round_idx: int
    metrics: dict[str, np.ndarray]  # per-client leaves from the round program
    # dispatch -> metrics readback. In overlap mode the NEXT round's data_fn
    # and staging ride under the in-flight round, so their host time is
    # EMBEDDED in this wall — summing wall_clock_s + data_fn_s across records
    # double-counts data_fn. Sum wall_clock_s alone for session time (plus
    # the first record's staging_s — the initial transfer precedes the first
    # dispatch in both modes). In sequential mode (overlap_staging=False)
    # data_fn/staging run after the round barrier, so wall_clock_s is a pure
    # round time and the session total picks up shuffle + staging from the
    # records (see the class docstring).
    wall_clock_s: float
    data_fn_s: float  # host time data_fn spent producing THIS round's data
    staging_s: float  # host-blocking staging paid for THIS round's data
    staged_bytes: int  # bytes newly staged for THIS round (0 = buffers reused)
    overlapped: bool  # next round's staging rode under this round's compute
    # Segmented path only: per-segment host timeline — dispatch time of each
    # segment program plus the next-round chunk transfer that rode under it
    # ({"segment", "dispatch_s", "staging_s", "staged_bytes"} per entry).
    segments: tuple = ()
    # Peak bytes of driver-staged round data live on the mesh at any point
    # during this round (current slab + however much of the next had landed).
    max_live_staged_bytes: int = 0
    # Preemption-tolerance path only (max_round_retries > 0): how many
    # failed attempts this round absorbed before the recorded (successful)
    # one, and what each failure was ("InjectedDeviceFailure: ...",
    # "non-finite round output", ...). 0/() on the default path.
    retries: int = 0
    faults: tuple = ()
    # Which data plane executed this round: "streamed" (per-round epoch-slab
    # staging) or "resident" (device-resident pool, index-only uploads —
    # staged_bytes is then the gather plan's bytes after the first round,
    # which also carries the one-time pool transfer). A federation asked to
    # run resident but bounced by the HBM guard records "streamed".
    data_placement: str = "streamed"
    # Compressed-transport counter (round 12): what this round's client
    # uploads would cost on the wire under the round program's update
    # codec — active clients x the round_fn's priced wire_bytes_per_client
    # (compress.codecs.encoded_bytes_model; the mesh plane moves no real
    # wire bytes, so this is the analytic twin of the gRPC plane's
    # history["bytes_received"]). None for round programs without the
    # counter (spatial rounds, externally built callables).
    bytes_per_round: int | None = None
    # Monolithic rounds only ({} on the segmented and cohort paths, which
    # keep ``segments``): where the host's time went, read from the same
    # ``perf_counter`` pairs that bound the ``driver.<key>`` spans.
    # ``dispatch`` (the round program's call), ``feed`` (``data_fn(r+1)``),
    # ``stage`` (staging of round r+1's data) and ``barrier`` (the metrics
    # read-back) lie inside ``wall_clock_s`` and sum to it; ``feed`` and
    # ``stage`` are 0.0 in sequential mode, where that work runs after the
    # barrier. ``handoff`` is the gap BEFORE this round's dispatch, back to
    # the previous round's barrier (record, registry, ``on_round``,
    # checkpoint, slab release; in sequential mode the feed and staging
    # too): the device idles through all of it. 0.0 for the first round.
    host_s: dict = dataclasses.field(default_factory=dict)
    # The next three are monolithic rounds' too ({} on the segmented and
    # cohort paths), always on, and change nothing the device runs.
    # ``stage``: the staging that ran under this round (what
    # ``host_s["stage"]`` times), split where the runtime takes over
    # (:func:`stage_round_data`): ``put_s`` (the host inside the
    # ``device_put`` calls, the ``driver.stage.put`` span), ``land_s`` (one
    # stamp a mesh device in mesh order: seconds from the staging's start by
    # which that device's shards had landed, the ends of the
    # ``driver.stage.land`` spans) and ``bytes`` (put on each device). {}
    # where no slab was staged under the round: the last round, a reused
    # slab, sequential mode (that staging lies in the next round's
    # ``handoff``), the resident plane (a gather plan of kilobytes).
    stage: dict = dataclasses.field(default_factory=dict)
    # The chip's memory as the program reads it once the barrier has passed
    # (inside ``driver.handoff``: the device has nothing to run):
    # ``obs.sentries.MEMORY_KEYS`` of the mesh device that holds most,
    # ``peak_bytes_in_use + bytes_reserved``. {} where the backend reports
    # none (CPU).
    device_memory: dict = dataclasses.field(default_factory=dict)
    # What the PROCESS did from this round's dispatch to its barrier
    # (``getrusage(RUSAGE_SELF)`` at both ends): ``cpu_s`` (user + system
    # seconds of ALL its threads: the runtime's and the feed's beside the
    # driver's own, so it may pass ``wall_clock_s``), ``nivcsw`` (involuntary
    # context switches: the process was pre-empted) and ``majflt`` (major
    # faults: it was paging). A round that ran long with all three at their
    # usual level sat quiet: the excess is the device's or the machine's.
    proc: dict = dataclasses.field(default_factory=dict)


HOST_PHASES = ("dispatch", "feed", "stage", "barrier")


@contextlib.contextmanager
def _host_phase(host_s: dict | None, key: str, span):
    """One phase of a round's host work, one measurement into two sinks:
    ``span`` (a ``tracing.span("driver.<key>", ...)`` not yet entered: JSONL
    recorder and, under a profiler session, the ``/host:CPU`` plane) and
    ``host_s[key]``, which is always counted. Yields the span's handle
    (``None`` without a recorder), for the phase's own children.
    ``host_s=None`` (segmented rounds, which have their own timeline) makes
    it a no-op."""
    if host_s is None:
        yield None
        return
    t = time.perf_counter()
    try:
        with span as handle:
            yield handle
    finally:
        host_s[key] += time.perf_counter() - t


def _under(trace: str, handle) -> dict:
    """The ``trace`` / ``parent`` keywords of a span opened under ``handle``
    (``None`` without a recorder)."""
    return {"trace": trace, "parent": None if handle is None else handle.span_id}


PROC_KEYS = ("cpu_s", "nivcsw", "majflt")


def _rusage() -> tuple[float, int, int]:
    """:data:`PROC_KEYS` of this process so far, all threads: CPU seconds
    (user + system), involuntary context switches, major faults."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_utime + ru.ru_stime, ru.ru_nivcsw, ru.ru_majflt


class NonFiniteRound(RuntimeError):
    """A round produced NaN/Inf weights or metrics (detected only when
    ``max_round_retries > 0`` — the detection costs one device reduction +
    scalar readback per round, so the default path never pays it)."""


def _tree_finite(tree: Any) -> bool:
    """One fused device-side finiteness reduction over every float leaf,
    a single scalar readback on the host."""
    ok = jnp.asarray(True)
    for leaf in jax.tree_util.tree_leaves(tree):
        a = jnp.asarray(leaf)
        if jnp.issubdtype(a.dtype, jnp.floating):
            ok = jnp.logical_and(ok, jnp.isfinite(a).all())
    return bool(ok)


def stage_round_data(
    images: np.ndarray,
    masks: np.ndarray,
    mesh: Mesh,
    image_spec: P | None = None,
    *,
    under: dict | None = None,
    split: dict | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Put one round's ``[C, steps, B, ...]`` arrays on the mesh and barrier
    until the bytes have landed.

    The staging is timed where the runtime takes over, as two kinds of span
    (``under``: their ``trace`` / ``parent`` keywords, the caller's
    ``driver.stage``) and, where ``split`` is given, into it
    (``RoundRecord.stage``). ``driver.stage.put`` is the host inside the two
    ``jax.device_put`` calls until they return: slicing the arrays by the
    sharding, host copies, the enqueue. ``driver.stage.land``, one a mesh
    device in mesh order (attribute ``device``: its id), waits on that
    device's shards of both arrays; ``land_s[d]`` is the clock at that wait's
    end, from the staging's start. The waits are made in device order, so a
    stamp is an upper bound for every device but the first: stamps evenly
    spaced say the transfers ran one after another, equal stamps say only
    that all had landed by then. Where the seconds lie in ``put_s`` they lie
    inside the one ``device_put`` call: putting a device at a time is the
    caller's change to make, not a reading this one can take.

    Staging shapes are layout-agnostic: under a transformed model layout
    (``ModelConfig.stem_layout``) ``images`` may be pre-packed to
    ``[C, steps, B, H/2, W/2, 4*ch]`` (``data.pipeline.space_to_depth_images``
    — identical byte count, so transfer estimates and ``staged_bytes``
    accounting are unchanged); the default ``P(clients, None, batch)`` spec
    shards the same leading axes either way. Masks always stage
    full-resolution. Segment-grain staging calls this once per step-range
    chunk (``data.pipeline.split_epoch_slab``) — the layout is closed under
    step-axis slicing."""
    sharding = NamedSharding(mesh, image_spec if image_spec is not None else P(CLIENTS, None, BATCH))
    under = under or {}
    t0 = time.perf_counter()
    with tracing.span("driver.stage.put", **under):
        si = jax.device_put(images, sharding)
        sm = jax.device_put(masks, sharding)
    put_s = time.perf_counter() - t0
    shards: dict = {}
    for a in (si, sm):
        for s in a.addressable_shards:
            shards.setdefault(s.device, []).append(s.data)
    land_s, nbytes = [], []
    for d in mesh.devices.flat:
        with tracing.span("driver.stage.land", device=d.id, **under):
            jax.block_until_ready(shards.get(d, []))
        land_s.append(time.perf_counter() - t0)
        nbytes.append(sum(int(s.nbytes) for s in shards.get(d, [])))
    if split is not None:
        split.update(put_s=put_s, land_s=land_s, bytes=nbytes)
    return si, sm


def stage_round_indices(
    idx: np.ndarray, mesh: Mesh, seg: SegmentedRound | None = None
):
    """Put one round's ``[C, epochs, steps, B]`` int32 gather plan on the
    mesh (clients sharded, per-step batch split over the ``batch`` axis —
    the same per-shard batch the streamed slab spec delivers) and barrier.

    For a segmented round the plan is staged as one ``[C, segment_epochs,
    steps, B]`` array per segment (each ``seg.segment`` call consumes its
    own slice); monolithic rounds get the single full array. Either way the
    payload is kilobytes — the entire point of the resident plane."""
    idx = np.ascontiguousarray(np.asarray(idx, np.int32))
    sharding = NamedSharding(mesh, P(CLIENTS, None, None, BATCH))
    if seg is None:
        return jax.block_until_ready(jax.device_put(idx, sharding))
    se = seg.segment_epochs
    parts = tuple(
        jax.device_put(np.ascontiguousarray(idx[:, k * se : (k + 1) * se]), sharding)
        for k in range(seg.n_segments)
    )
    return jax.block_until_ready(parts)


def _stage_next_round(
    nxt, mesh: Mesh, spec: P, resident: bool, seg: SegmentedRound | None, n_chunks: int,
    *, under: dict | None = None, split: dict | None = None,
):
    """Stage what ``data_fn(r + 1)`` returned, whole (no streaming between
    segment dispatches: that is ``_run_segmented_round``'s). Returns
    ``(buffers, (active, n_samples), staged bytes, host gather plan)``; the
    plan is ``None`` on the streamed plane. ``under`` and ``split`` are
    :func:`stage_round_data`'s, for a monolithic round's one slab."""
    if resident:
        nidx, na, nn = nxt
        host_idx = np.ascontiguousarray(np.asarray(nidx, np.int32))
        buffers = stage_round_indices(host_idx, mesh, seg)
        return buffers, (na, nn), int(host_idx.nbytes), host_idx
    ni, nm, na, nn = nxt
    nbytes = int(ni.nbytes + nm.nbytes)
    if seg is None:
        return stage_round_data(ni, nm, mesh, spec, under=under, split=split), (na, nn), nbytes, None
    nic, nmc = split_epoch_slab(ni, nm, n_chunks)
    pairs = [stage_round_data(ci, cm, mesh, spec) for ci, cm in zip(nic, nmc)]
    return ([p[0] for p in pairs], [p[1] for p in pairs]), (na, nn), nbytes, None


def resident_pool_fits(
    pool_nbytes: int,
    mesh: Mesh,
    *,
    limit_bytes: int | None = None,
    safety: float = 0.8,
) -> tuple[bool, dict]:
    """HBM guard for the resident data plane: does this pool's per-device
    share fit alongside the model/carry working set?

    The limit comes from, in order: the explicit ``limit_bytes`` argument,
    ``FEDCRACK_RESIDENT_HBM_LIMIT_BYTES`` (operator override), or the
    backend's reported per-device ``bytes_limit`` (TPU). When none is
    discoverable (CPU backends report nothing useful) the guard PASSES —
    the fallback exists for devices that can say no, not to veto hosts that
    can't say anything. ``safety`` reserves headroom for weights, optimizer
    carry and activations (the guard is deliberately coarse: a wrong "fits"
    surfaces as an allocator error on the first stage, a wrong "doesn't"
    only costs the streamed path's staging).

    Returns ``(fits, info)`` where ``info`` records the decision inputs for
    artifacts/logs."""
    limit = limit_bytes
    if limit is None:
        env = os.environ.get("FEDCRACK_RESIDENT_HBM_LIMIT_BYTES", "")
        if env:
            limit = int(env)
    if limit is None:
        limit = device_memory_stats([next(iter(mesh.devices.flat))])[0].get("bytes_limit")
    n_clients = int(mesh.shape[CLIENTS]) if CLIENTS in mesh.shape else 1
    per_device = -(-int(pool_nbytes) // max(1, n_clients))  # ceil
    info = {
        "pool_bytes": int(pool_nbytes),
        "per_device_bytes": per_device,
        "limit_bytes": None if limit is None else int(limit),
        "safety": safety,
    }
    if limit is None:
        info["reason"] = "no per-device memory limit discoverable; assuming fit"
        return True, info
    fits = per_device <= safety * limit
    info["reason"] = (
        "fits"
        if fits
        else f"per-device pool share {per_device} B exceeds "
        f"{safety:.0%} of the {int(limit)} B device limit"
    )
    return fits, info


def _assembling_data_fn(pool: SamplePool, data_fn: Callable) -> Callable:
    """HBM-guard fallback bridge: wrap a resident-contract ``data_fn``
    (returning ``(idx, active, n_samples)``) into the streamed contract by
    host-assembling each round's epoch slab from the pool's host twin —
    ``pool[idx]`` on host is the same data movement the device gather
    performs, so the fallback trajectory is byte-identical."""

    def wrapped(r):
        out = data_fn(r)
        if out is None:
            return None
        idx, active, n_samples = out
        images, masks = pool.assemble_round_slab(np.asarray(idx))
        return images, masks, active, n_samples

    return wrapped


def _delete_staged(chunks: Sequence[jax.Array]) -> None:
    """Release driver-owned staged buffers NOW (not at GC): the segmented
    path's 2-epoch-slab HBM bound depends on the previous round's chunks
    dying at the round barrier, not whenever the collector runs."""
    for a in chunks:
        try:
            a.delete()
        except Exception:
            pass  # already deleted / backend without explicit delete


def _save_round_checkpoint(checkpointer, round_idx, variables, record, history):
    """Persist the round boundary through ``ckpt.manager.FedCheckpointer``.
    The device_get is a deliberate barrier — checkpoint cost is NOT
    overlapped with compute (it runs between rounds, like on_round)."""
    from fedcrack_tpu.ckpt.manager import FedCheckpoint

    history.append(
        {
            "round": round_idx + 1,
            "wall_clock_s": round(record.wall_clock_s, 3),
            "loss_mean": float(np.mean(record.metrics["loss"])),
        }
    )
    checkpointer.save(
        FedCheckpoint(
            current_round=round_idx + 1,
            model_version=round_idx + 1,
            variables=jax.device_get(variables),
            history=tuple(history),
        )
    )


def _run_segmented_round(
    seg: SegmentedRound,
    variables: Any,
    si: tuple,
    sm: tuple,
    active,
    n_samples,
    *,
    data_fn,
    round_idx: int,
    n_rounds: int,
    overlap_staging: bool,
    n_chunks: int,
    mesh: Mesh,
    spec: P,
    acct: dict,
    pipelined: dict | None = None,
):
    """One segmented round: K segment dispatches with the NEXT round's slab
    streaming chunk-by-chunk between them, then the finalize program.

    Mirrors ``SegmentedRound.__call__``'s host loop plus the driver-only
    concerns — next-round staging, the per-segment host timeline, and the
    live-staged-bytes accounting (``acct`` is the driver's mutable
    ``{"live": bytes, "round_max": bytes}``). Returns ``(variables,
    metrics, out)`` where ``out`` carries the timeline, the (possibly
    host-viewed) cohort arrays, and the staged next-round state.

    ``pipelined`` (round 14, ``round_overlap``): segment 0 was already
    dispatched by the PREVIOUS round's tail (its carry/raw and the
    validated cohort arrive here); the loop resumes at segment 1 and the
    next-round data trigger fires on the first EXECUTED segment instead of
    literal ``k == 0`` (with ``n_segments == 1`` it fires after the loop).
    """
    out: dict = {
        "next_buffers": None,
        "next_cohort": None,
        "next_bytes": 0,
        "next_data_s": 0.0,
    }
    timeline: list[dict] = []
    if pipelined is None:
        active, n_samples = seg.check_inputs(si, active, n_samples)
        carry = seg.init(variables)
        raws = []
        start_k = 0
    else:
        active, n_samples = pipelined["active"], pipelined["n_samples"]
        carry, raws = pipelined["carry"], [pipelined["raw"]]
        timeline.append(pipelined["entry"])
        start_k = 1
    pending: list = []
    nxt = None
    did_data = False

    def _pull_next_data():
        nonlocal nxt, pending, did_data
        did_data = True
        tdd = time.perf_counter()
        nxt = data_fn(round_idx + 1)
        out["next_data_s"] = time.perf_counter() - tdd
        if nxt is not None:
            ni, nm, na, nn = nxt
            out["next_cohort"] = (na, nn)
            out["next_bytes"] = int(ni.nbytes + nm.nbytes)
            nic, nmc = split_epoch_slab(ni, nm, n_chunks)
            pending = list(zip(nic, nmc))
            out["next_buffers"] = ([], [])

    for k in range(start_k, seg.n_segments):
        td = time.perf_counter()
        carry, raw = seg.segment(carry, variables, si, sm)
        raws.append(raw)
        entry = {
            "segment": k,
            "dispatch_s": round(time.perf_counter() - td, 4),
        }
        if overlap_staging and round_idx + 1 < n_rounds:
            if not did_data:
                _pull_next_data()
            if pending:
                # One chunk transfer rides under each in-flight segment
                # (all of them at k=0 in round-grain mode).
                take = len(pending) if n_chunks == 1 else 1
                tss = time.perf_counter()
                nb = 0
                for ci, cm in pending[:take]:
                    s_i, s_m = stage_round_data(ci, cm, mesh, spec)
                    out["next_buffers"][0].append(s_i)
                    out["next_buffers"][1].append(s_m)
                    nb += int(ci.nbytes + cm.nbytes)
                del pending[:take]
                acct["live"] += nb
                acct["round_max"] = max(acct["round_max"], acct["live"])
                entry["staging_s"] = round(time.perf_counter() - tss, 4)
                entry["staged_bytes"] = nb
        timeline.append(entry)
    # A fully pipelined single-segment round never entered the loop: the
    # next round's data still has to be produced + staged (under the
    # in-flight segment 0 + finalize).
    if overlap_staging and round_idx + 1 < n_rounds and not did_data:
        _pull_next_data()
    # Chunks the segment loop didn't reach (n_chunks was clamped below
    # n_segments, or data_fn ran long): still overlapped with the in-flight
    # tail segments + finalize.
    while pending:
        ci, cm = pending.pop(0)
        tss = time.perf_counter()
        s_i, s_m = stage_round_data(ci, cm, mesh, spec)
        out["next_buffers"][0].append(s_i)
        out["next_buffers"][1].append(s_m)
        acct["live"] += int(ci.nbytes + cm.nbytes)
        acct["round_max"] = max(acct["round_max"], acct["live"])
        timeline.append(
            {
                "segment": "drain",
                "staging_s": round(time.perf_counter() - tss, 4),
                "staged_bytes": int(ci.nbytes + cm.nbytes),
            }
        )
    variables, metrics = seg.finalize(
        carry, variables, active, n_samples, seg.join_raws(raws)
    )
    out["timeline"] = timeline
    out["active"], out["n_samples"] = active, n_samples
    return variables, metrics, out


def _run_segmented_round_resident(
    seg: SegmentedRound,
    variables: Any,
    pool_dev: tuple,
    idx_parts: tuple,
    host_idx: np.ndarray,
    active,
    n_samples,
    *,
    data_fn,
    round_idx: int,
    n_rounds: int,
    overlap_staging: bool,
    mesh: Mesh,
    acct: dict,
    pipelined: dict | None = None,
):
    """One segmented round on the resident plane: K segment dispatches over
    the shared device pool, each gathering by its own plan slice. The next
    round's plan (kilobytes) stages after the first dispatch — there is no
    slab to stream chunk-by-chunk, which is the point. ``pipelined`` as in
    :func:`_run_segmented_round` (segment 0 pre-dispatched by the previous
    round's tail under ``round_overlap``)."""
    out: dict = {
        "next_buffers": None,
        "next_cohort": None,
        "next_bytes": 0,
        "next_data_s": 0.0,
        "next_host_idx": None,
    }
    timeline: list[dict] = []
    if pipelined is None:
        active, n_samples = seg.check_inputs(
            pool_dev, active, n_samples, idx=host_idx
        )
        carry = seg.init(variables)
        raws = []
        start_k = 0
    else:
        active, n_samples = pipelined["active"], pipelined["n_samples"]
        carry, raws = pipelined["carry"], [pipelined["raw"]]
        timeline.append(pipelined["entry"])
        start_k = 1
    did_data = False

    def _pull_next_plan(entry=None):
        nonlocal did_data
        did_data = True
        tdd = time.perf_counter()
        nxt = data_fn(round_idx + 1)
        out["next_data_s"] = time.perf_counter() - tdd
        if nxt is not None:
            nidx, na, nn = nxt
            nidx = np.ascontiguousarray(np.asarray(nidx, np.int32))
            out["next_cohort"] = (na, nn)
            out["next_host_idx"] = nidx
            out["next_bytes"] = int(nidx.nbytes)
            tss = time.perf_counter()
            out["next_buffers"] = stage_round_indices(nidx, mesh, seg)
            acct["live"] += out["next_bytes"]
            acct["round_max"] = max(acct["round_max"], acct["live"])
            if entry is not None:
                entry["staging_s"] = round(time.perf_counter() - tss, 4)
                entry["staged_bytes"] = out["next_bytes"]

    for k in range(start_k, seg.n_segments):
        td = time.perf_counter()
        carry, raw = seg.segment(carry, variables, pool_dev, idx_parts[k])
        raws.append(raw)
        entry = {
            "segment": k,
            "dispatch_s": round(time.perf_counter() - td, 4),
        }
        if overlap_staging and round_idx + 1 < n_rounds and not did_data:
            _pull_next_plan(entry)
        timeline.append(entry)
    if overlap_staging and round_idx + 1 < n_rounds and not did_data:
        _pull_next_plan()
    variables, metrics = seg.finalize(
        carry, variables, active, n_samples, seg.join_raws(raws)
    )
    out["timeline"] = timeline
    out["active"], out["n_samples"] = active, n_samples
    return variables, metrics, out


def _dispatch_pipelined_segment(
    seg: SegmentedRound,
    out_vars: Any,
    resident: bool,
    *,
    si,
    sm,
    active,
    n_samples,
    host_idx_cur,
    segout,
    next_buffers,
    next_cohort,
):
    """Round-overlap (round 14): dispatch the NEXT round's init + segment-0
    programs against the in-flight current round's output, before the host
    blocks on the current round's metrics. Data dependencies (the new
    variables) order the device; the host merely enqueues earlier — same
    expression tree, bit-identical trajectory. When the next round reuses
    this round's buffers (``data_fn`` returned None) the dispatch runs over
    the current staged data and cohort."""
    td = time.perf_counter()
    if resident:
        if next_buffers is not None:
            idx_parts = next_buffers
            na, nn = next_cohort
            host_idx = segout["next_host_idx"]
        else:
            idx_parts = sm
            na, nn = active, n_samples
            host_idx = host_idx_cur
        pa, pn = seg.check_inputs(si, na, nn, idx=host_idx)
        carry = seg.init(out_vars)
        carry, raw = seg.segment(carry, out_vars, si, idx_parts[0])
    else:
        if next_buffers is not None:
            nsi, nsm = tuple(next_buffers[0]), tuple(next_buffers[1])
            na, nn = next_cohort
        else:
            nsi, nsm = si, sm
            na, nn = active, n_samples
        pa, pn = seg.check_inputs(nsi, na, nn)
        carry = seg.init(out_vars)
        carry, raw = seg.segment(carry, out_vars, nsi, nsm)
    return {
        "carry": carry,
        "raw": raw,
        "active": pa,
        "n_samples": pn,
        "entry": {
            "segment": 0,
            "dispatch_s": round(time.perf_counter() - td, 4),
            "pipelined": True,
        },
    }


def run_mesh_federation(
    round_fn: Callable,
    variables: Any,
    data_fn: Callable[[int], Any],
    n_rounds: int,
    mesh: Mesh,
    *,
    image_spec: P | None = None,
    overlap_staging: bool = True,
    segment_overlap: bool = True,
    round_overlap: bool = False,
    data_placement: str = "streamed",
    sample_pool: SamplePool | None = None,
    streamed_round_fn: Callable | None = None,
    resident_limit_bytes: int | None = None,
    on_round: Callable[[RoundRecord, Any], None] | None = None,
    checkpointer: Any | None = None,
    start_round: int = 0,
    history: Sequence[dict] = (),
    max_round_retries: int = 0,
    fault_injector: Callable[[int, int], Any] | None = None,
    recompile_sentry: Any | None = None,
) -> tuple[Any, list[RoundRecord]]:
    """Drive federated rounds ``start_round .. n_rounds-1`` through
    ``round_fn``.

    - ``round_fn``: a round program from ``build_federated_round`` /
      ``build_spatial_federated_round`` (signature
      ``(variables, images, masks, active, n_samples) -> (variables,
      metrics)``), or a :class:`~fedcrack_tpu.parallel.fedavg_mesh.
      SegmentedRound` from ``build_federated_round_segments`` — the driver
      then runs the segment loop itself so staging can stream between
      segment dispatches.
    - ``data_fn(r)``: host data for round ``r`` as ``(images, masks,
      active, n_samples)`` numpy arrays, or ``None`` to reuse round
      ``r-1``'s staged buffers and cohort (a client whose local dataset
      doesn't change between rounds should not re-ship it).
      ``data_fn(start_round)`` must return data. With ``overlap_staging``
      on, ``data_fn(r+1)`` is called while round ``r`` runs on device, so
      per-round synthesis/shuffle cost also hides under compute; with it
      off, it is called after round ``r``'s barrier, so sequential timing
      charges it separately.
    - ``overlap_staging``: stage round r+1 while round r's program runs
      (double buffering). ``False`` serializes staging after the round
      barrier — the two orders produce bit-identical weights (staging is
      data-independent), which the driver's tests pin.
    - ``segment_overlap`` (segmented rounds only): ``True`` streams the
      next round's slab as one step-range chunk per segment dispatch
      (epoch-grain double buffering — no monolithic transfer ever sits on
      the bus); ``False`` keeps round-grain staging (the full next slab
      transfers after the first segment dispatch). Ignored for monolithic
      ``round_fn``s.
    - ``round_overlap`` (round 14, segmented rounds only): overlap round
      N+1's FIRST SEGMENT dispatch with round N's aggregation tail — after
      round N's finalize program is dispatched (asynchronously), round
      N+1's init + segment-0 programs are dispatched against its output
      BEFORE the host blocks on round N's metrics readback, so the
      readback + record bookkeeping + ``on_round`` host work hide under
      device compute instead of serializing the rounds at the host. Pure
      host scheduling: the device-side expression tree is unchanged, so
      the trajectory is BIT-identical to ``round_overlap=False``
      (test-pinned). Requires a ``SegmentedRound`` (the r7 segment
      boundaries are the interleave points), ``overlap_staging=True`` (the
      next round's data must be staged before its segment can dispatch),
      and ``max_round_retries == 0`` (a pipelined segment dispatched
      against a round that later fails its finiteness check would need
      unwinding). The pipelined segment's dispatch time is recorded in the
      CONSUMING round's timeline (``"pipelined": True``) but rode under
      the previous round's wall.
    - ``data_placement``: ``"streamed"`` (default — the contracts above) or
      ``"resident"``: ``round_fn`` must be built with
      ``data_placement="resident"``, ``sample_pool`` must be the
      :class:`~fedcrack_tpu.data.pipeline.SamplePool` the plan indexes
      into, and ``data_fn(r)`` returns ``(idx, active, n_samples)`` where
      ``idx`` is the round's ``[C, epochs, steps, B]`` int32 gather plan
      (``SamplePool.round_indices``), or ``None`` to reuse round ``r-1``'s
      plan. The driver stages the pool ONCE (charged to the first executed
      round's record), uploads only the plan per round (same
      overlap/sequential semantics as slab staging), and keeps the pool
      resident across rounds — per-round ``staged_bytes`` collapses from
      the epoch slab to the plan's kilobytes. On a retry
      (``max_round_retries``) pool AND plan are re-staged bit-identically
      from the retained host twin before the replay.
    - ``streamed_round_fn`` + ``resident_limit_bytes``: the HBM-guard
      fallback. When :func:`resident_pool_fits` (against
      ``resident_limit_bytes``, the ``FEDCRACK_RESIDENT_HBM_LIMIT_BYTES``
      env override, or the backend's reported per-device limit) says the
      pool does NOT fit, the federation runs ``streamed_round_fn`` (a
      streamed-contract round over the same mesh/model) with epoch slabs
      host-assembled from the pool + plan — byte-identical trajectory,
      records tagged ``data_placement="streamed"``. With no fallback round
      provided, an unfittable pool raises instead of guessing.
    - ``on_round(record, variables)``: per-round hook (metrics sinks,
      held-out eval). ``variables`` is the round's output pytree, still on
      device; the hook runs between rounds, so its cost is NOT overlapped
      with device compute.
    - ``checkpointer``: optional ``ckpt.manager.FedCheckpointer``; the
      driver saves the global variables + history at EVERY round boundary
      (after ``on_round``). To resume a killed session, restore the
      checkpoint, pass the restored variables, ``start_round =
      ckpt.current_round`` and ``history = ckpt.history`` — with a
      deterministic ``data_fn`` the continued trajectory is identical to
      the uninterrupted run (test-pinned).
    - ``start_round``: absolute index of the first round to run (checkpoint
      resume); ``data_fn`` and ``RoundRecord.round_idx`` use absolute
      indices throughout.
    - ``max_round_retries``: preemption tolerance (0 disables, the default
      — no snapshotting, no finiteness checks, no overhead). With N > 0,
      each round absorbs up to N failed attempts: an attempt that raises
      (device/host loss) or produces non-finite weights/metrics is rolled
      back — weights restored from this round's boundary (the
      ``checkpointer``'s latest step when present, else an in-memory host
      snapshot taken at round start) — and replayed with the same
      ``data_fn(r)`` data, so the recovered trajectory is bit-identical to
      an unfaulted run (test-pinned). Attempt N+1's failure re-raises: a
      clean abort, never a hang. Per-round cost when enabled: one host
      ``device_get`` of the weights + one fused device-side finiteness
      reduction. NOTE: bit-identical replay requires ``data_fn`` to be a
      pure function of the round index — a data_fn advancing a shared RNG
      per CALL (rather than seeding from ``r``) yields a different shuffle
      on the replayed attempt (still a valid federation, not the pinned
      identical trajectory).
    - ``fault_injector``: chaos hook (``chaos.inject.MeshChaos``), called
      as ``injector(round_idx, attempt)`` before each attempt; it may raise
      (simulated preemption) or return an output-poisoning transform.
      Production runs leave it None.

    Returns the final global ``variables`` (on device) and one
    :class:`RoundRecord` per executed round. The first round's wall-clock
    includes XLA compilation; report post-compile medians from
    ``records[1:]``.

    Single-process staging only: ``stage_round_data`` device_puts host
    arrays this process can address in full. A multi-host job stages each
    process's client shards with ``jax.make_array_from_process_local_data``
    (see ``parallel.multihost`` and tests/test_multihost.py) and should
    drive its own round loop around ``round_fn``.
    """
    if n_rounds <= 0:
        raise ValueError(f"n_rounds must be positive, got {n_rounds}")
    if not 0 <= start_round < n_rounds:
        raise ValueError(
            f"start_round={start_round} outside [0, n_rounds={n_rounds})"
        )
    if max_round_retries < 0:
        raise ValueError(
            f"max_round_retries must be >= 0, got {max_round_retries}"
        )
    if data_placement not in ("streamed", "resident"):
        raise ValueError(
            f"data_placement must be 'streamed' or 'resident', got {data_placement!r}"
        )
    resident = data_placement == "resident"
    if resident:
        if sample_pool is None:
            raise ValueError("data_placement='resident' needs a sample_pool")
        if getattr(round_fn, "data_placement", "streamed") != "resident":
            raise ValueError(
                "data_placement='resident' needs a round_fn built with "
                "data_placement='resident' (the gather-assembly data contract)"
            )
        fits, guard = resident_pool_fits(
            sample_pool.nbytes, mesh, limit_bytes=resident_limit_bytes
        )
        if not fits:
            if streamed_round_fn is None:
                raise RuntimeError(
                    f"resident sample pool does not fit HBM ({guard['reason']}) "
                    "and no streamed_round_fn fallback was provided"
                )
            if getattr(streamed_round_fn, "data_placement", "streamed") != "streamed":
                raise ValueError("streamed_round_fn must be a streamed-contract round")
            # Same pool, same plan, same trajectory — just host-assembled
            # slabs shipped the old way.
            round_fn = streamed_round_fn
            data_fn = _assembling_data_fn(sample_pool, data_fn)
            resident = False
    elif getattr(round_fn, "data_placement", "streamed") != "streamed":
        raise ValueError(
            "round_fn was built with data_placement='resident' but the driver "
            "was asked to run streamed — pass data_placement='resident' plus "
            "the sample_pool (mismatched contracts would feed slabs to a "
            "gather program)"
        )
    spec = image_spec if image_spec is not None else P(CLIENTS, None, BATCH)
    seg = round_fn if isinstance(round_fn, SegmentedRound) else None
    if round_overlap:
        if seg is None:
            raise ValueError(
                "round_overlap=True requires a SegmentedRound — the r7 "
                "segment boundaries are the interleave points (an HBM-guard "
                "fallback to a monolithic streamed_round_fn cannot pipeline)"
            )
        if not overlap_staging:
            raise ValueError(
                "round_overlap=True requires overlap_staging=True: the next "
                "round's data must be staged before its first segment can "
                "dispatch early"
            )
        if max_round_retries > 0:
            raise ValueError(
                "round_overlap does not compose with max_round_retries: a "
                "pipelined segment dispatched against a round that later "
                "fails its finiteness check would need unwinding — run "
                "preemption tolerance without round-overlap"
            )
    hist = list(history)
    # Every round returns the global model replicated over the mesh. Start
    # from that placement too: a host (or single-device) pytree is a second
    # input signature, and round 2 would compile the whole round program
    # again for it (placement only — the values are untouched).
    variables = jax.device_put(variables, NamedSharding(mesh, P()))

    t0 = time.perf_counter()
    first = data_fn(start_round)
    data_s = time.perf_counter() - t0
    if first is None:
        raise ValueError(
            f"data_fn({start_round}) returned None: the first round has no data"
        )
    n_chunks = 1
    base_bytes = 0  # non-rotating driver-staged bytes (the resident pool)
    host_idx_cur = None
    ts = time.perf_counter()
    if resident:
        idx0, active, n_samples = first
        host_idx_cur = np.ascontiguousarray(np.asarray(idx0, np.int32))
        # The pool stages ONCE; it never rotates with the rounds.
        si = sample_pool.stage(mesh)
        sm = stage_round_indices(host_idx_cur, mesh, seg)
        base_bytes = sample_pool.nbytes
        staged_bytes = base_bytes + int(host_idx_cur.nbytes)
        cur_bytes = int(host_idx_cur.nbytes)
    else:
        images, masks, active, n_samples = first
        if seg is not None:
            n_chunks = seg.n_segments if segment_overlap else 1
            ic, mc = split_epoch_slab(images, masks, n_chunks)
            staged_pairs = [stage_round_data(i, m, mesh, spec) for i, m in zip(ic, mc)]
            si = tuple(p[0] for p in staged_pairs)
            sm = tuple(p[1] for p in staged_pairs)
        else:
            si, sm = stage_round_data(images, masks, mesh, spec)
        staged_bytes = int(images.nbytes + masks.nbytes)
        cur_bytes = staged_bytes
    # Charged to the first executed round's record (boundary-term fix,
    # round 7): the initial transfer is host-blocking in both modes.
    pending_staging_s = time.perf_counter() - ts
    acct = {"live": base_bytes + cur_bytes, "round_max": base_bytes + cur_bytes}

    records: list[RoundRecord] = []
    # round_overlap: the NEXT round's pre-dispatched segment-0 state
    # (carry/raw/validated cohort + its timeline entry), produced at the
    # previous round's tail and consumed by the next runner call.
    pipelined_state: dict | None = None
    # The gap between a round's barrier and the next round's dispatch is one
    # ``driver.handoff`` span (record, registry, on_round, checkpoint, slab
    # release; in sequential mode the next round's feed and staging too). It
    # opens at the tail of one iteration and closes at the head of the next,
    # so it lives on an ExitStack: an exception from ``on_round`` still
    # closes it.
    handoff_t = None
    with contextlib.ExitStack() as handoff:
        for r in range(start_round, n_rounds):
            # Preemption tolerance: snapshot the round's input weights so a
            # failed attempt (device loss, non-finite output) can replay THIS
            # round from identical state. Host device_get round-trips float32
            # exactly, so the replayed trajectory is bit-identical (test-pinned).
            snapshot = jax.device_get(variables) if max_round_retries > 0 else None
            # Codec-twin cross-round state rides the same contract (r12 review
            # fix): the round program commits its error-feedback pytree / int8
            # seed counter when the async dispatch returns — before a
            # non-finite output surfaces at the host fetch — so a retry must
            # roll it back too, or the topk twin banks mass from the discarded
            # attempt. Pointer-level snapshot (immutable jax arrays + an int).
            codec_snapshot = (
                round_fn.codec_state()
                if max_round_retries > 0 and hasattr(round_fn, "codec_state")
                else None
            )
            attempt = 0
            round_faults: list[str] = []
            handoff_s = 0.0
            while True:
                acct["round_max"] = acct["live"]
                next_buffers = None
                next_cohort = None
                next_bytes = 0
                next_data_s = 0.0
                next_staging_s = 0.0
                next_host_idx = None
                timeline: list[dict] = []
                # Host seconds of this attempt by phase (RoundRecord.host_s);
                # monolithic rounds only, the segmented runners keep their
                # per-segment timeline.
                host_s = dict.fromkeys(HOST_PHASES, 0.0) if seg is None else None
                stage_split: dict = {}
                if handoff_t is not None:
                    handoff.close()
                    handoff_s = time.perf_counter() - handoff_t
                    handoff_t = None

                ru0 = _rusage() if seg is None else None
                t0 = time.perf_counter()
                try:
                    with tracing.span(
                        "driver.round",
                        trace=f"round-{r}",
                        attempt=attempt,
                        data_placement="resident" if resident else "streamed",
                    ) as round_span:
                        under = _under(f"round-{r}", round_span)
                        post = None
                        if fault_injector is not None:
                            # Chaos hook (chaos.inject.MeshChaos): may raise (device
                            # failure) or return an output poison; one attribute
                            # check when absent.
                            post = fault_injector(r, attempt)
                        if seg is None:
                            with _host_phase(
                                host_s, "dispatch", tracing.span("driver.dispatch", **under)
                            ):
                                out_vars, metrics = round_fn(
                                    variables, si, sm, active, n_samples
                                )
                                if post is not None:
                                    out_vars, metrics = post(out_vars, metrics)

                            if overlap_staging and r + 1 < n_rounds:
                                # The round program is in flight; data_fn's host work
                                # and the staging transfers ride under it (the
                                # barrier inside stage_round_data only waits for the
                                # *transfer*, not the round), which is why this
                                # round's wall embeds them — see RoundRecord.
                                with _host_phase(
                                    host_s, "feed", tracing.span("driver.feed", **under)
                                ):
                                    nxt = data_fn(r + 1)
                                next_data_s = host_s["feed"]
                                if nxt is not None:
                                    with _host_phase(
                                        host_s, "stage", tracing.span("driver.stage", **under)
                                    ) as stage_span:
                                        (
                                            next_buffers,
                                            next_cohort,
                                            next_bytes,
                                            next_host_idx,
                                        ) = _stage_next_round(
                                            nxt, mesh, spec, resident, None, 1,
                                            under=_under(f"round-{r}", stage_span),
                                            split=stage_split,
                                        )
                                        acct["live"] += next_bytes
                                        acct["round_max"] = max(
                                            acct["round_max"], acct["live"]
                                        )
                        elif resident:
                            out_vars, metrics, segout = _run_segmented_round_resident(
                                seg,
                                variables,
                                si,
                                sm,
                                host_idx_cur,
                                active,
                                n_samples,
                                data_fn=data_fn,
                                round_idx=r,
                                n_rounds=n_rounds,
                                overlap_staging=overlap_staging,
                                mesh=mesh,
                                acct=acct,
                                pipelined=pipelined_state,
                            )
                            if post is not None:
                                out_vars, metrics = post(out_vars, metrics)
                            timeline = segout["timeline"]
                            next_buffers = segout["next_buffers"]
                            next_cohort = segout["next_cohort"]
                            next_bytes = segout["next_bytes"]
                            next_data_s = segout["next_data_s"]
                            next_host_idx = segout["next_host_idx"]
                            active, n_samples = segout["active"], segout["n_samples"]
                        else:
                            out_vars, metrics, segout = _run_segmented_round(
                                seg,
                                variables,
                                si,
                                sm,
                                active,
                                n_samples,
                                data_fn=data_fn,
                                round_idx=r,
                                n_rounds=n_rounds,
                                overlap_staging=overlap_staging,
                                n_chunks=n_chunks,
                                mesh=mesh,
                                spec=spec,
                                acct=acct,
                                pipelined=pipelined_state,
                            )
                            if post is not None:
                                out_vars, metrics = post(out_vars, metrics)
                            timeline = segout["timeline"]
                            next_buffers = segout["next_buffers"]
                            next_cohort = segout["next_cohort"]
                            next_bytes = segout["next_bytes"]
                            next_data_s = segout["next_data_s"]
                            active, n_samples = segout["active"], segout["n_samples"]

                        if max_round_retries > 0:
                            # A device reduction read back on the host: it
                            # waits for the round, so it counts as barrier.
                            with _host_phase(
                                host_s, "barrier", tracing.span("driver.barrier", **under)
                            ):
                                finite = _tree_finite(metrics) and _tree_finite(out_vars)
                            if not finite:
                                raise NonFiniteRound(
                                    f"round {r} produced non-finite weights/metrics"
                                )
                        pipelined_state = None
                        if round_overlap and r + 1 < n_rounds:
                            # Dispatch round r+1's init + segment 0 against this
                            # round's (still in-flight) output BEFORE blocking on
                            # its metrics — round N's aggregation-tail readback now
                            # rides under round N+1's first segment. Device
                            # ordering is by data dependency, so the math is
                            # bit-identical to the unpipelined schedule.
                            pipelined_state = _dispatch_pipelined_segment(
                                seg,
                                out_vars,
                                resident,
                                si=si,
                                sm=sm,
                                active=active,
                                n_samples=n_samples,
                                host_idx_cur=host_idx_cur,
                                segout=segout if seg is not None else None,
                                next_buffers=next_buffers,
                                next_cohort=next_cohort,
                            )
                        # Round barrier: metrics depend on every step of every client.
                        with _host_phase(
                            host_s, "barrier", tracing.span("driver.barrier", **under)
                        ):
                            metrics_host = jax.tree_util.tree_map(np.asarray, metrics)
                        variables = out_vars
                        wall = time.perf_counter() - t0
                        proc = (
                            {}
                            if ru0 is None
                            else {k: b - a for k, a, b in zip(PROC_KEYS, ru0, _rusage())}
                        )
                        if round_span is not None:
                            round_span.set(
                                wall_s=round(wall, 6),
                                staging_s=round(pending_staging_s, 6),
                                staged_bytes=int(staged_bytes),
                            )
                        break
                except Exception as e:
                    if attempt >= max_round_retries:
                        raise
                    round_faults.append(f"{type(e).__name__}: {e}")
                    attempt += 1
                    # Drop whatever of the NEXT round landed during the failed
                    # attempt; the retry re-produces it (deterministic data_fn).
                    if next_buffers is not None:
                        if resident:
                            flat = (
                                next_buffers
                                if isinstance(next_buffers, tuple)
                                else (next_buffers,)
                            )
                        elif seg is not None:
                            flat = tuple(next_buffers[0]) + tuple(next_buffers[1])
                        else:
                            flat = next_buffers
                        _delete_staged(flat)
                    acct["live"] = base_bytes + cur_bytes
                    if resident:
                        # A real preemption may have taken the resident pool
                        # down with the device: drop the placement and re-stage
                        # pool AND plan from the retained host twin — bit
                        # identical (test-pinned), charged to this round's
                        # staging term.
                        rs = time.perf_counter()
                        _delete_staged(
                            tuple(si)
                            + (tuple(sm) if isinstance(sm, tuple) else (sm,))
                        )
                        si = sample_pool.stage(mesh)
                        sm = stage_round_indices(host_idx_cur, mesh, seg)
                        pending_staging_s += time.perf_counter() - rs
                    # Restore the round's input weights: prefer the durable
                    # checkpoint (it IS this round's boundary when present —
                    # a real preemption may have taken the in-memory snapshot
                    # down with the host), else the host snapshot.
                    restored = None
                    if checkpointer is not None:
                        try:
                            ck = checkpointer.restore(template=snapshot)
                            if ck is not None and ck.current_round == r:
                                restored = ck.variables
                        except Exception:
                            restored = None
                    variables = restored if restored is not None else snapshot
                    if codec_snapshot is not None:
                        round_fn.set_codec_state(codec_snapshot)

            # From here to the next dispatch the device has nothing to run.
            handoff_t = time.perf_counter()
            handoff_span = handoff.enter_context(
                tracing.span("driver.handoff", trace=f"round-{r}")
            )
            device_memory = fullest_device_memory(mesh.devices.flat) if seg is None else {}
            if not overlap_staging and r + 1 < n_rounds:
                # Sequential mode: produce AND stage the next round's data after
                # the barrier, so the recorded wall is a pure round time and the
                # shuffle cost is paid (and accounted) outside it. The staging
                # time is charged to the NEXT round's record (the round that
                # consumes the data — see the RoundRecord boundary-term note);
                # both sit inside this handoff, so their spans are its children
                # and they are in no record's ``host_s`` but the next one's
                # ``handoff``.
                seq = {"feed": 0.0, "stage": 0.0}
                under = _under(f"round-{r}", handoff_span)
                with _host_phase(seq, "feed", tracing.span("driver.feed", **under)):
                    nxt = data_fn(r + 1)
                next_data_s = seq["feed"]
                if nxt is not None:
                    with _host_phase(
                        seq, "stage", tracing.span("driver.stage", **under)
                    ) as stage_span:
                        (
                            next_buffers,
                            next_cohort,
                            next_bytes,
                            next_host_idx,
                        ) = _stage_next_round(
                            nxt, mesh, spec, resident, seg, n_chunks,
                            under=_under(f"round-{r}", stage_span),
                        )
                    next_staging_s = seq["stage"]
                    acct["live"] += next_bytes
                    acct["round_max"] = max(acct["round_max"], acct["live"])

            wpc = getattr(round_fn, "wire_bytes_per_client", None)
            bytes_per_round = None
            if wpc:
                try:
                    n_active = int(np.sum(np.asarray(active, np.float32) > 0.0))
                except Exception:
                    # Cross-process sharded cohort mask: this process cannot
                    # fetch it — charge the full client axis.
                    n_active = int(mesh.shape[CLIENTS]) if CLIENTS in mesh.shape else 1
                bytes_per_round = int(wpc) * n_active
            record = RoundRecord(
                round_idx=r,
                metrics=metrics_host,
                wall_clock_s=wall,
                data_fn_s=data_s,
                staging_s=pending_staging_s,
                staged_bytes=staged_bytes,
                overlapped=overlap_staging and next_buffers is not None,
                segments=tuple(timeline),
                max_live_staged_bytes=acct["round_max"],
                retries=attempt,
                faults=tuple(round_faults),
                data_placement="resident" if resident else "streamed",
                bytes_per_round=bytes_per_round,
                host_s={} if host_s is None else dict(host_s, handoff=handoff_s),
                stage=stage_split,
                device_memory=device_memory,
                proc=proc,
            )
            records.append(record)
            _observe_round_record(record, sentry=recompile_sentry)
            if on_round is not None:
                on_round(record, variables)
            if checkpointer is not None:
                _save_round_checkpoint(checkpointer, r, variables, record, hist)

            data_s = next_data_s
            pending_staging_s = next_staging_s
            if next_buffers is not None:
                # The round barrier above guarantees every consumer of the old
                # buffers has run; release them NOW so peak staged HBM stays at
                # ~2 epoch slabs instead of growing until GC. On the resident
                # plane only the gather plan rotates — the pool stays put.
                if resident:
                    _delete_staged(tuple(sm) if isinstance(sm, tuple) else (sm,))
                    sm = next_buffers
                    host_idx_cur = next_host_idx
                elif seg is not None:
                    _delete_staged(tuple(si) + tuple(sm))
                    si = tuple(next_buffers[0])
                    sm = tuple(next_buffers[1])
                else:
                    _delete_staged((si, sm))
                    si, sm = next_buffers
                acct["live"] -= cur_bytes
                cur_bytes = next_bytes
                active, n_samples = next_cohort
                staged_bytes = next_bytes
            else:
                staged_bytes = 0

    return variables, records


def _stage_group_slab(images, masks, mesh, spec):
    """Stage one GROUP's ``[G, steps, B, ...]`` slab pair and barrier."""
    return stage_round_data(
        np.ascontiguousarray(images), np.ascontiguousarray(masks), mesh, spec
    )


def _stage_group_resident(pool_i, pool_m, idx, mesh):
    """Stage one group's resident pool slice (sharded ``P('clients')``)
    plus its full-round gather plan, barriered."""
    sharding = NamedSharding(mesh, P(CLIENTS))
    si = jax.device_put(np.ascontiguousarray(pool_i), sharding)
    sm = jax.device_put(np.ascontiguousarray(pool_m), sharding)
    sx = jax.device_put(
        np.ascontiguousarray(idx), NamedSharding(mesh, P(CLIENTS, None, None, BATCH))
    )
    jax.block_until_ready((si, sm, sx))
    return (si, sm), sx


def _prep_cohort_round(
    cohort_round: CohortRound,
    r: int,
    data,
    sample_pool: SamplePool | None,
    resident: bool,
) -> dict:
    """Validate + pad one round's cohort data into the staging-ready form
    (shared by the inline and the round-overlap pipelined paths)."""
    if data is None:
        raise ValueError(f"data_fn({r}) returned None: a cohort round never reuses")
    g = cohort_round.group_size
    prep: dict = {}
    if resident:
        idx, active, n_samples = data
        idx = np.ascontiguousarray(np.asarray(idx, np.int32))
        c = idx.shape[0]
        if sample_pool.n_clients != c:
            raise ValueError(
                f"sample_pool carries {sample_pool.n_clients} clients, "
                f"round {r}'s plan {c} — the pool's client axis must "
                "align with the cohort"
            )
        prep["idx"] = idx
    else:
        images, masks, active, n_samples = data
        images = np.asarray(images)
        masks = np.asarray(masks)
        c = images.shape[0]
        cohort_round.seg.validate_data(images)
        prep["images"], prep["masks"] = images, masks
    active = np.asarray(active, np.float32)
    n_samples = np.asarray(n_samples, np.float32)
    if active.shape[0] != c:
        raise ValueError(
            f"cohort data carries {c} clients, mask {active.shape[0]}"
        )
    if float(np.sum(active * n_samples)) <= 0.0:
        raise ValueError(
            "non-positive total FedAvg weight: every cohort client dropped"
        )
    n_groups = cohort_round.n_groups(c)
    c_pad = n_groups * g
    prep["active"] = pad_cohort_axis(active, c_pad)
    prep["n_samples"] = pad_cohort_axis(n_samples, c_pad)
    prep["c"], prep["n_groups"] = c, n_groups
    return prep


def _stage_cohort_group(
    prep: dict,
    gi: int,
    g: int,
    mesh: Mesh,
    spec: P,
    sample_pool: SamplePool | None,
    resident: bool,
):
    """Stage ONE group's slab (or resident pool slice + plan), padding only
    the last group's slice for ragged cohorts."""
    c = prep["c"]
    lo, hi = gi * g, (gi + 1) * g

    def slice_pad(arr):
        # Pad ONLY the last group's slice (ragged cohorts): padding the
        # whole cohort array up front would copy the entire pool/slab
        # host-side every round — GBs of memcpy for one short group.
        part = arr[lo:min(hi, c)]
        return part if part.shape[0] == hi - lo else pad_cohort_axis(part, hi - lo)

    ts = time.perf_counter()
    if resident:
        pi = slice_pad(sample_pool.images)
        pm = slice_pad(sample_pool.masks)
        ix = slice_pad(prep["idx"])
        bufs = _stage_group_resident(pi, pm, ix, mesh)
        nbytes = int(pi.nbytes + pm.nbytes + ix.nbytes)
    else:
        gi_imgs = slice_pad(prep["images"])
        gi_msks = slice_pad(prep["masks"])
        bufs = _stage_group_slab(gi_imgs, gi_msks, mesh, spec)
        nbytes = int(gi_imgs.nbytes + gi_msks.nbytes)
    return bufs, nbytes, time.perf_counter() - ts


def run_cohort_federation(
    cohort_round: CohortRound,
    variables: Any,
    data_fn: Callable[[int], Any],
    n_rounds: int,
    mesh: Mesh,
    *,
    sample_pool: SamplePool | None = None,
    image_spec: P | None = None,
    round_overlap: bool = False,
    on_round: Callable[[RoundRecord, Any], None] | None = None,
    recompile_sentry: Any | None = None,
) -> tuple[Any, list[RoundRecord]]:
    """Drive a time-multiplexed cohort federation (round 13): each round's
    C-client cohort executes as ``ceil(C / G)`` sequential group dispatches
    over the G-wide mesh, with PER-GROUP staging — group g+1's slab (or
    resident pool slice + plan) stages while group g's programs run, and
    group g's buffers are released at its barrier, so peak driver-staged
    HBM is ~2 group slices regardless of C.

    - ``cohort_round``: a :class:`~fedcrack_tpu.parallel.fedavg_mesh.
      CohortRound` from ``build_federated_cohort_round``.
    - ``data_fn(r)``: the round's cohort — streamed: ``(images [C, steps,
      B, ...], masks, active [C], n_samples [C])`` numpy arrays; resident
      (``sample_pool`` set): ``(idx [C, epochs, steps, B], active,
      n_samples)`` where ``idx`` indexes the COHORT-wide ``sample_pool``
      (the pool's host twin is sliced and staged per group — the r9
      resident plane at group grain). Cohort sampling composes here: a
      ``data_fn`` built on :func:`fedcrack_tpu.fed.algorithms.
      sample_cohort` makes the whole multi-round trajectory reproducible
      from one seed. Unlike ``run_mesh_federation`` there is no
      ``None``-reuse contract — every round supplies its cohort (cohorts
      change per round; that is the point).
    - ``on_round(record, variables)``: per-round hook, as in
      :func:`run_mesh_federation`.

    ``round_overlap`` (round 14): overlap round N+1's cohort production,
    first-group staging AND first-group dispatch with round N's
    aggregation tail — after round N's ``finish`` program is dispatched
    (asynchronously), round N+1's data_fn/staging/group-0 programs run
    against its output BEFORE the host blocks on round N's metrics
    readback. Pure host scheduling over the same data-dependency graph, so
    the trajectory is BIT-identical to the unoverlapped schedule
    (test-pinned). The pipelined group's dispatch/staging host time is
    recorded in the CONSUMING round's timeline (``"pipelined": True``) but
    rode under the previous round's wall.

    Returns the final global ``variables`` and one :class:`RoundRecord`
    per round; ``record.segments`` carries the per-GROUP host timeline
    (``{"group", "dispatch_s", "staging_s", "staged_bytes"}``) — round
    wall scales ~linearly in the number of group dispatches.
    """
    if n_rounds <= 0:
        raise ValueError(f"n_rounds must be positive, got {n_rounds}")
    resident = sample_pool is not None
    if resident and cohort_round.data_placement != "resident":
        raise ValueError(
            "sample_pool given but cohort_round was built streamed — build "
            "it with data_placement='resident' for the pool/plan contract"
        )
    if not resident and cohort_round.data_placement == "resident":
        raise ValueError(
            "cohort_round is resident but no sample_pool was given"
        )
    spec = image_spec if image_spec is not None else P(CLIENTS, None, BATCH)
    g = cohort_round.group_size
    records: list[RoundRecord] = []
    # Same placement as every round's output (see run_mesh_federation): no
    # second input signature, no second compile of the group programs.
    variables = jax.device_put(variables, NamedSharding(mesh, P()))
    # round_overlap: round r+1's prepped data + staged group 0 + its
    # dispatched (sums, raw) carry, produced at round r's tail.
    pipeline: dict | None = None

    for r in range(n_rounds):
        if pipeline is None:
            td = time.perf_counter()
            data = data_fn(r)
            data_s = time.perf_counter() - td
            prep = _prep_cohort_round(cohort_round, r, data, sample_pool, resident)
            t0 = time.perf_counter()
            cur, cur_bytes, stage_s = _stage_cohort_group(
                prep, 0, g, mesh, spec, sample_pool, resident
            )
            sums = cohort_round.zeros(variables)
            pre_raw = None
            pre_entry = None
        else:
            prep = pipeline["prep"]
            data_s = pipeline["data_s"]
            t0 = pipeline["t0"]
            cur, cur_bytes, stage_s = pipeline["staged"]
            sums = pipeline["sums"]
            pre_raw = pipeline["raw"]
            pre_entry = pipeline["entry"]
            pipeline = None
        # One enclosing span a round; the per-group host timeline is
        # ``RoundRecord.segments``, not spans. Under ``round_overlap`` group 0
        # was dispatched at the previous round's tail, before this span opens
        # (its timeline entry says ``pipelined``).
        with tracing.span(
            "driver.round",
            trace=f"round-{r}",
            data_placement="resident" if resident else "streamed",
        ) as round_span:
            active, n_samples = prep["active"], prep["n_samples"]
            n_groups = prep["n_groups"]
            raw_lasts = []
            timeline: list[dict] = []
            staged_total = 0
            staging_total = 0.0
            live = cur_bytes
            round_max = live
            for gi in range(n_groups):
                lo = gi * g
                if gi == 0 and pre_raw is not None:
                    # Group 0 was dispatched by the previous round's tail
                    # (round_overlap): its fold already sits in `sums`.
                    raw = pre_raw
                    entry = pre_entry
                else:
                    tdp = time.perf_counter()
                    if resident:
                        (pool_dev, idx_dev) = cur
                        sums, raw = cohort_round.run_group(
                            sums, variables, pool_dev, idx_dev,
                            active[lo : lo + g], n_samples[lo : lo + g],
                        )
                    else:
                        si, sm = cur
                        sums, raw = cohort_round.run_group(
                            sums, variables, si, sm,
                            active[lo : lo + g], n_samples[lo : lo + g],
                        )
                    entry = {
                        "group": gi,
                        "dispatch_s": round(time.perf_counter() - tdp, 4),
                        "staging_s": round(stage_s, 4),
                        "staged_bytes": cur_bytes,
                    }
                staged_total += cur_bytes
                staging_total += stage_s
                nxt = None
                if gi + 1 < n_groups:
                    # Next group's transfer rides under this group's compute
                    # (the dispatches above are async; only the staging
                    # barrier blocks the host).
                    nxt, nxt_bytes, stage_s = _stage_cohort_group(
                        prep, gi + 1, g, mesh, spec, sample_pool, resident
                    )
                    live += nxt_bytes
                    round_max = max(round_max, live)
                # Group barrier: raw_last depends on every step of every
                # client in the group, so fetching it proves the staged
                # buffers are consumed and safe to release.
                raw = jax.tree_util.tree_map(np.asarray, raw)
                raw_lasts.append(raw)
                if resident:
                    _delete_staged(tuple(cur[0]) + (cur[1],))
                else:
                    _delete_staged(cur)
                live -= cur_bytes
                timeline.append(entry)
                if nxt is not None:
                    cur, cur_bytes = nxt, nxt_bytes
            out_vars, metrics = cohort_round.finish(
                sums, variables, raw_lasts, active, prep["c"]
            )
            if round_overlap and r + 1 < n_rounds:
                # Round r's finish is dispatched but not yet read back: produce
                # round r+1's cohort, stage its first group and dispatch its
                # first group program NOW, so all that host work (and the
                # metrics readback below) hides under device compute. Data
                # dependencies (out_vars) keep the device order — and thus the
                # trajectory — bit-identical.
                td = time.perf_counter()
                data2 = data_fn(r + 1)
                data2_s = time.perf_counter() - td
                prep2 = _prep_cohort_round(
                    cohort_round, r + 1, data2, sample_pool, resident
                )
                t0n = time.perf_counter()
                cur2, cur2_bytes, stage2_s = _stage_cohort_group(
                    prep2, 0, g, mesh, spec, sample_pool, resident
                )
                sums2 = cohort_round.zeros(out_vars)
                tdp = time.perf_counter()
                if resident:
                    (pool2, idx2) = cur2
                    sums2, raw2 = cohort_round.run_group(
                        sums2, out_vars, pool2, idx2,
                        prep2["active"][:g], prep2["n_samples"][:g],
                    )
                else:
                    si2, sm2 = cur2
                    sums2, raw2 = cohort_round.run_group(
                        sums2, out_vars, si2, sm2,
                        prep2["active"][:g], prep2["n_samples"][:g],
                    )
                pipeline = {
                    "prep": prep2,
                    "data_s": data2_s,
                    "t0": t0n,
                    "staged": (cur2, cur2_bytes, stage2_s),
                    "sums": sums2,
                    "raw": raw2,
                    "entry": {
                        "group": 0,
                        "dispatch_s": round(time.perf_counter() - tdp, 4),
                        "staging_s": round(stage2_s, 4),
                        "staged_bytes": cur2_bytes,
                        "pipelined": True,
                    },
                }
            # Round barrier (the aggregation-tail readback round_overlap hides
            # the pipelined work under).
            metrics_host = jax.tree_util.tree_map(np.asarray, metrics)
            variables = out_vars
            wall = time.perf_counter() - t0
            if round_span is not None:
                round_span.set(wall_s=round(wall, 6))
        record = RoundRecord(
            round_idx=r,
            metrics=metrics_host,
            wall_clock_s=wall,
            data_fn_s=data_s,
            staging_s=staging_total,
            staged_bytes=staged_total,
            overlapped=n_groups > 1 or pre_raw is not None,
            segments=tuple(timeline),
            max_live_staged_bytes=round_max,
            data_placement="resident" if resident else "streamed",
        )
        records.append(record)
        _observe_round_record(record, sentry=recompile_sentry)
        if on_round is not None:
            on_round(record, variables)
    return variables, records


def shuffled_epoch_data(
    pool_images: np.ndarray,
    pool_masks: np.ndarray,
    steps: int,
    batch_size: int,
    rng: np.random.Generator,
) -> tuple[np.ndarray, np.ndarray]:
    """One client's reshuffled epoch in the round layout ``[1, steps, B, ...]``.

    A fresh permutation of the client's fixed sample pool per round — the
    reference reshuffles between fits the same way (keras Sequence +
    ``fit`` per round, client_fit_model.py:164-166). Returning new arrays
    per round is what makes per-round restaging (and thus the double
    buffer) load-bearing rather than decorative.
    """
    n = pool_images.shape[0]
    need = steps * batch_size
    if n < need:
        raise ValueError(f"pool has {n} samples, round needs {need}")
    idx = rng.permutation(n)[:need]
    images = np.ascontiguousarray(
        pool_images[idx].reshape(1, steps, batch_size, *pool_images.shape[1:])
    )
    masks = np.ascontiguousarray(
        pool_masks[idx].reshape(1, steps, batch_size, *pool_masks.shape[1:])
    )
    return images, masks
