"""The first concurrent mini-soak — every plane at once, watched live.

``python -m fedcrack_tpu.tools.soak --duration 10 --out soak.json``

Rounds 6–14 drilled every subsystem in isolation (17 chaos scenarios, kill
drills, storm A/Bs); this harness is the ROADMAP's continuous-operation
item shrunk to a bounded wall: a **buffered federation** (FedBuff root,
real FedClients looping pull→train→push through the r12 compressed
transport), an **edge-tier shard** (buffered EdgeAggregator + raw relay
feeding the same root), a **serve plane** (compiled bucket engine,
micro-batcher, hot-swap manager polling the federation's LIVE statefile —
the models being served are the models being trained), and a **driver
leg** (a small ``run_mesh_federation`` session), all running CONCURRENTLY
under a rolling chaos schedule:

- a seeded straggler storm (``FaultPlan.storm``) delaying every client's
  pushes with heavy-tail draws,
- periodic CORRUPT_COMPRESSED_FRAME / STALE_REPLAY poisons (rejected
  loudly; the poisoned client dies and is restarted, like a pod),
- one mid-soak server **kill → restart on the same port** over the durable
  statefile, with clients riding the restart on their retry budgets.

The soak watches itself through the round-15 telemetry plane: it exports
the process registry on an ephemeral ``/metrics`` port, SCRAPES ITS OWN
ENDPOINT mid-run and at the end (valid Prometheus text format covering all
five instrumented planes — fed, serve, driver, edge, transport-client),
records correlated trace spans to JSONL, and finishes with the invariant
audit the ROADMAP names:

- **zero torn versions** — per-batch served model versions are
  monotonically non-decreasing and every served version was actually
  published (initial weights or a recorded hot-swap);
- **EF mass conserved** — a top-k error-feedback twin runs alongside the
  chaos and checks, per encode, that the codec's accumulator equals the
  conservation-implied remainder (kept + residual == delta + prior
  residual), then drains on a quiet tail;
- **statefile restores bit-identical** — the final durable statefile
  round-trips through load → save to byte-identical bytes (canonical
  snapshot idempotence, under whatever arrival order the chaos produced);
- **watermarks steady** — RSS + device-memory leak sentries marked after
  warmup must stay inside their slack.

tests/test_telemetry.py runs a short version of :func:`run_soak` tier-1 and
the 60-second version slow-marked.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

from fedcrack_tpu.configs import FedConfig, ModelConfig, ServeConfig

JOIN_S = 30.0


class _SoakStop(Exception):
    """Raised inside a client train_fn when the wall expires — unwinds the
    session thread without waiting on the server."""


def _perturb_tree(tree, rng: np.random.Generator, scale: float = 1e-3):
    """A cheap deterministic 'local fit': base + seeded noise per leaf.
    Real training would need a compiled program per client; the soak is
    about the PROTOCOL planes, so the update only has to be a plausible
    finite delta."""
    import jax

    return jax.tree_util.tree_map(
        lambda leaf: np.asarray(leaf)
        + rng.normal(0.0, scale, np.shape(leaf)).astype(np.asarray(leaf).dtype)
        if np.issubdtype(np.asarray(leaf).dtype, np.floating)
        else np.asarray(leaf),
        tree,
    )


def _ef_conservation_leg(template, stop: threading.Event, out: dict, seed: int) -> None:
    """The error-feedback mass audit: drive a TopKDeltaCodec twin with
    seeded deltas WHILE the soak's real traffic contends for the GIL, and
    verify after every encode that the codec's residual mass equals the
    conservation-implied remainder — |delta + prior_residual| split
    exactly into |transmitted| + |residual|. Then feed zero deltas and
    require the accumulator to drain monotonically ('nothing lost, only
    delayed' converges)."""
    from fedcrack_tpu.compress.codecs import TopKDeltaCodec
    from fedcrack_tpu.compress.frames import decode_update
    from fedcrack_tpu.fed.serialization import tree_from_bytes, tree_to_bytes

    import jax

    rng = np.random.default_rng(seed + 777)
    codec = TopKDeltaCodec(fraction=0.25)
    base_tree = tree_from_bytes(tree_to_bytes(template), template=template)
    base_blob = tree_to_bytes(base_tree)
    violations = 0
    checks = 0
    mirror = None  # our independent residual mirror

    def leaves(t):
        return [np.asarray(x, np.float64) for x in jax.tree_util.tree_leaves(t)]

    base_leaves = leaves(base_tree)
    while not stop.is_set() and checks < 200:
        trained = _perturb_tree(base_tree, rng, scale=1e-2)
        delta = [t - b for t, b in zip(leaves(trained), base_leaves)]
        if mirror is None:
            mirror = [np.zeros_like(d) for d in delta]
        eff = [d + m for d, m in zip(delta, mirror)]
        frame = codec.encode_update(
            tree_to_bytes(trained), base_blob, round=checks + 1, base_version=0
        )
        decoded, _ = decode_update(frame, template, base_tree)
        kept = [t - b for t, b in zip(leaves(decoded), base_leaves)]
        mirror = [e - k for e, k in zip(eff, kept)]
        implied = float(sum(np.abs(m).sum() for m in mirror))
        got = float(codec.residual_mass())
        checks += 1
        if not np.isclose(got, implied, rtol=1e-5, atol=1e-7):
            violations += 1
        time.sleep(0.02)
    # Quiet tail: zero deltas must drain the accumulator toward zero.
    drain = [codec.residual_mass()]
    for i in range(12):
        codec.encode_update(base_blob, base_blob, round=1000 + i, base_version=0)
        drain.append(codec.residual_mass())
    out["checks"] = checks
    out["violations"] = violations
    out["drain_start_mass"] = round(drain[0], 9)
    out["drain_end_mass"] = round(drain[-1], 9)
    out["drained"] = drain[-1] <= drain[0] * 0.05 + 1e-12


def run_soak(
    duration_s: float = 8.0,
    seed: int = 0,
    workdir: str | None = None,
    n_clients: int = 3,
    buffer_k: int = 2,
    staleness_alpha: float = 0.5,
    max_staleness: int = 8,
    update_codec: str = "topk_delta",
    topk_fraction: float = 0.25,
    kill_restart: bool = True,
    rss_slack_bytes: int = 256 * 1024 * 1024,
    slo_rules: str | None = None,
) -> dict:
    """Run the concurrent mini-soak for ``duration_s`` of traffic wall
    (warmup/compile excluded) and return the audit artifact.

    Round 16 adds the watchdog + flight-recorder + tracing layer: SLO
    rules (``slo_rules`` = a configs/slo_*.json path, default the built-in
    set) are machine-evaluated DURING the run, a breach dumps the flight
    ring and fails the audit, and the span JSONL is stitched into
    end-to-end update-lifecycle chains (client → root → serve under one
    trace id) embedded as the artifact's ``tracing`` arm."""
    import jax

    from fedcrack_tpu.chaos.plan import (
        CORRUPT_COMPRESSED_FRAME,
        STALE_REPLAY,
        Fault,
        FaultPlan,
    )
    from fedcrack_tpu.chaos.inject import ClientChaos
    from fedcrack_tpu.ckpt import load_state_file, save_state_file
    from fedcrack_tpu.fed import rounds as R
    from fedcrack_tpu.fed.serialization import tree_from_bytes, tree_to_bytes
    from fedcrack_tpu.fed.tree import EdgeAggregator
    from fedcrack_tpu.models.resunet import init_variables
    from fedcrack_tpu.obs import spans as tracing
    from fedcrack_tpu.obs.metrics import MetricsLogger, read_metrics
    from fedcrack_tpu.obs.promexp import MetricsExporter, scrape
    from fedcrack_tpu.obs.registry import REGISTRY
    from fedcrack_tpu.obs.sentries import LeakSentry
    from fedcrack_tpu.parallel import make_mesh, run_mesh_federation
    from fedcrack_tpu.serve.batcher import MicroBatcher
    from fedcrack_tpu.serve.engine import InferenceEngine, watch_recompiles
    from fedcrack_tpu.serve.hot_swap import ModelVersionManager
    from fedcrack_tpu.transport.client import FedClient
    from fedcrack_tpu.transport.edge import raw_caller
    from fedcrack_tpu.transport.service import FedServer, ServerThread

    from fedcrack_tpu.health import ledger as health_ledger
    from fedcrack_tpu.health.canary import CanaryEvaluator
    from fedcrack_tpu.health.drift import (
        DriftMonitor,
        export_drift_metrics,
        write_drift_json,
    )
    from fedcrack_tpu.obs import flight
    from fedcrack_tpu.obs.watchdog import Watchdog, load_rules

    ctx = tempfile.TemporaryDirectory(prefix="soak_") if workdir is None else None
    base_dir = ctx.name if ctx is not None else workdir
    os.makedirs(base_dir, exist_ok=True)
    state_path = os.path.join(base_dir, "server_state.msgpack")
    spans_path = os.path.join(base_dir, "spans.jsonl")
    serve_metrics_path = os.path.join(base_dir, "serve_metrics.jsonl")
    metrics_dump_path = os.path.join(base_dir, "metrics.prom")
    flight_path = os.path.join(base_dir, "flight.json")
    stitched_path = os.path.join(base_dir, "trace_stitched.json")
    # Size-bounded span sink: an hours-long soak rotates instead of
    # appending one unbounded JSONL (the stitcher reads the whole set).
    tracing.install(spans_path, max_bytes=64 * 1024 * 1024, keep=3)
    flight.install(path=flight_path)
    watchdog = Watchdog(load_rules(slo_rules) if slo_rules else None)

    model_config = ModelConfig(
        img_size=32, stem_features=4, encoder_features=(8,),
        decoder_features=(8, 4),
    )
    template = init_variables(jax.random.key(seed), model_config)
    names = [f"c{i}" for i in range(n_clients)]
    edge_id = "edge-0"
    cfg = FedConfig(
        max_rounds=100_000,  # the soak is wall-bounded, never round-bounded
        cohort_size=n_clients + 1,  # + the edge shard
        mode="buffered",
        buffer_k=buffer_k,
        staleness_alpha=staleness_alpha,
        max_staleness=max_staleness,
        registration_window_s=10.0,
        round_deadline_s=2.0,  # partial-flush liveness backstop
        port=0,
        state_path=state_path,
        update_codec=update_codec,
        topk_fraction=topk_fraction,
    )

    # ---- serve plane (compiled BEFORE the traffic wall starts) ----
    serve_config = ServeConfig(
        bucket_sizes=(16,), max_batch=4, max_delay_ms=5.0, tile_overlap=4
    )
    engine = InferenceEngine(model_config, serve_config)
    serve_metrics = MetricsLogger(serve_metrics_path)
    # Round 18 health plane: canary IoU per installed version (evaluated
    # from the manager's poll thread AFTER each pointer flip — never on
    # the serving path) + serve-side drift vs a frozen install-time
    # reference profile (observed from the load loop's consumer thread).
    canary = CanaryEvaluator(engine, metrics=serve_metrics)
    manager = ModelVersionManager(
        engine,
        template,
        initial_version=0,
        state_path=state_path,
        poll_s=0.15,
        template=template,
        metrics=None,
        canary=canary,
    )
    engine.warmup(manager.snapshot()[1])
    recompile_sentry = watch_recompiles(engine)
    # The canary reference and the frozen drift profile both pin to the
    # BOOT weights, after warmup (their probe batches reuse the compiled
    # bucket programs; recompiles_since_warmup must stay 0 through them).
    canary.evaluate(0, manager.snapshot()[1])
    drift_monitor = DriftMonitor(
        reference=DriftMonitor.capture_reference(engine, manager.snapshot()[1])
    )
    batcher = MicroBatcher(engine, manager, metrics=serve_metrics)
    manager.start()  # hot-swap poller: the federation's statefile IS the feed

    # ---- leak sentries: steady state begins after warmup/compiles ----
    leak_sentry = LeakSentry(rss_slack_bytes=rss_slack_bytes)
    leak_sentry.mark()

    # ---- the /metrics endpoint the soak scrapes ITSELF through ----
    exporter = MetricsExporter(REGISTRY)
    exporter.start()
    # Pre-traffic baseline: the process registry is shared (a test session
    # runs other drills in the same process earlier), so every number the
    # artifact reports from a scrape must be a DELTA over this snapshot.
    from fedcrack_tpu.obs.promexp import sample_value as _sample_value

    pre_scrape = scrape(exporter.url)
    pre_accepted = _sample_value(
        pre_scrape, "fed_updates_total", {"result": "accepted"}
    ) or 0.0

    # ---- rolling chaos schedule (seeded) ----
    plan = FaultPlan.storm(
        seed,
        clients=names,
        n_iterations=200,
        tail_alpha=1.1,
        scale_s=0.02,
        cap_s=0.5,
    )
    storm_fired = plan.take("straggler_storm", round=1) is not None
    for r in range(3, 200, 9):
        plan.pending.append(
            Fault(kind=CORRUPT_COMPRESSED_FRAME, client=names[0], round=r)
        )
    for r in range(5, 200, 11):
        plan.pending.append(
            Fault(kind=STALE_REPLAY, client=names[-1], round=r)
        )

    stop = threading.Event()
    counters = {"client_restarts": 0, "client_errors": []}
    counters_lock = threading.Lock()

    def make_train_fn(cname: str, idx: int):
        it = {"n": 0}
        rng = np.random.default_rng((seed, idx))

        def train(weights_bytes: bytes, rnd: int):
            if stop.is_set():
                raise _SoakStop()
            it["n"] += 1
            tree = tree_from_bytes(weights_bytes, template=template)
            trained = _perturb_tree(tree, rng)
            return tree_to_bytes(trained), 8 + idx, {"loss": 1.0 / it["n"]}

        return train

    port_ref = {"port": None}

    def client_loop(cname: str, idx: int) -> None:
        """Run sessions until the wall; a poisoned/killed session is
        restarted with a fresh FedClient (operators restart pods)."""
        first = True
        while not stop.is_set():
            if not first:
                with counters_lock:
                    counters["client_restarts"] += 1
            first = False
            try:
                client = FedClient(
                    cfg,
                    make_train_fn(cname, idx),
                    cname=cname,
                    port=port_ref["port"],
                    max_retries=6,
                    call_timeout_s=10.0,
                    retry_budget_s=8.0,
                    chaos=ClientChaos(plan),
                )
                client.run_session()
            except _SoakStop:
                return
            except Exception as e:
                if stop.is_set():
                    return
                with counters_lock:
                    counters["client_errors"].append(f"{cname}: {e!r}")
                time.sleep(0.1)

    edge_stats = {"flushes": 0, "accepted": 0, "resyncs": 0, "errors": []}

    def edge_loop() -> None:
        """The edge-tier shard: two synthetic leaves fold into a buffered
        EdgeAggregator whose partials relay up to the SAME root."""
        from fedcrack_tpu.transport import transport_pb2 as pb
        from fedcrack_tpu.transport.codec import decode_scalar_map, encode_scalar_map

        edge = EdgeAggregator(
            edge_id,
            template,
            mode="buffered",
            buffer_k=2,
            staleness_alpha=staleness_alpha,
            max_staleness=max_staleness,
            state_path=os.path.join(base_dir, "edge_state.msgpack"),
        )
        rng = np.random.default_rng((seed, 99))
        channel = call = None
        enrolled = False
        leaf_it = 0
        while not stop.is_set():
            try:
                if call is None:
                    channel, call = raw_caller(port_ref["port"])
                if not enrolled:
                    msg = pb.ClientMessage(cname=edge_id)
                    msg.ready.SetInParent()
                    if call(msg).status != R.SW:
                        time.sleep(0.1)
                        continue
                    enrolled = True
                msg = pb.ClientMessage(cname=edge_id)
                msg.pull.SetInParent()
                rep = call(msg)
                pcfg = decode_scalar_map(rep.config)
                version = int(pcfg.get("model_version", 0))
                rnd = int(pcfg.get("current_round", 1))
                if version != edge.base_version:
                    if edge.base_version < 0:
                        edge.begin_round(rnd, rep.weights, version, ["l0", "l1"])
                    else:
                        edge.advance_base(rnd, rep.weights, version)
                base_tree = tree_from_bytes(edge.base_blob, template=template)
                for leaf in ("l0", "l1"):
                    leaf_it += 1
                    leaf_ctx = tracing.TraceContext(
                        tracing.version_trace(edge.base_version),
                        f"train:{leaf}:n{leaf_it}",
                    )
                    with tracing.span(
                        "client.train",
                        trace=leaf_ctx.trace,
                        cname=leaf,
                        ctx=leaf_ctx.to_wire(),
                    ):
                        blob = tree_to_bytes(_perturb_tree(base_tree, rng))
                    ok, _why = edge.offer_buffered(
                        leaf, blob, 4 + leaf_it % 3, edge.base_version,
                        trace_ctx=leaf_ctx.to_wire(),
                    )
                    edge_stats["accepted"] += bool(ok)
                if edge.buffer_ready():
                    partial, total, info = edge.flush_partial()
                    msg = pb.ClientMessage(cname=edge_id)
                    msg.done.round = rnd
                    msg.done.weights = partial
                    msg.done.sample_count = total
                    # The edge flush's wire context rides the hop up like
                    # any client push's — the root re-parents it onto the
                    # flush that folds this partial.
                    encode_scalar_map(
                        msg.done.metrics, {"__trace": info["trace_ctx"]}
                    )
                    prep = call(msg)
                    edge_stats["flushes"] += 1
                    if prep.status == R.NOT_WAIT:
                        edge_stats["resyncs"] += 1
                time.sleep(0.05)
            except Exception as e:
                # Server restart mid-soak: drop the channel, re-dial the
                # (same) port. A dead channel is the EXPECTED fault here.
                if stop.is_set():
                    return
                edge_stats["errors"].append(repr(e))
                if channel is not None:
                    channel.close()
                channel = call = None
                time.sleep(0.2)

    load_stats = {"submitted": 0, "completed": 0, "failed": 0}
    versions_seen: set[int] = set()

    def load_loop() -> None:
        """Closed-loop serve traffic: small bursts of bucket-shaped
        requests; every future is awaited (zero-drop accounting)."""
        rng = np.random.default_rng((seed, 7))
        while not stop.is_set():
            futures = []
            for _ in range(4):
                img = rng.integers(0, 256, (16, 16, 3), dtype=np.uint8)
                futures.append((img, batcher.submit(img, deadline_ms=250.0)))
                load_stats["submitted"] += 1
            for img, f in futures:
                try:
                    res = f.result(timeout=10.0)
                    load_stats["completed"] += 1
                    versions_seen.add(res.model_version)
                    # Drift profiling happens HERE — after the future
                    # resolved, on this consumer thread, never inside the
                    # batcher (the hot path pays nothing for it).
                    drift_monitor.observe(img, res.probs)
                except Exception:
                    load_stats["failed"] += 1
            time.sleep(0.01)

    driver_stats: dict = {}

    def driver_leg() -> None:
        """A small concurrent run_mesh_federation session — the mesh/driver
        plane's counters and spans land in the same registry the scrape
        reads. The round program is a host-side stub: the DRIVER machinery
        (staging, records, telemetry) is what this leg exercises, not XLA."""
        try:
            mesh = make_mesh(1, 1)

            def round_fn(variables, images, masks, active, n_samples):
                return variables, {"loss": np.zeros((1,), np.float32)}

            def data_fn(r):
                images = np.zeros((1, 1, 1, 8, 8, 3), np.uint8)
                masks = np.zeros((1, 1, 1, 8, 8, 1), np.uint8)
                return (
                    images, masks,
                    np.ones(1, np.float32), np.ones(1, np.float32),
                )

            t0 = time.perf_counter()
            _, records = run_mesh_federation(
                round_fn, template, data_fn, 3, mesh,
                recompile_sentry=recompile_sentry,
            )
            driver_stats["rounds"] = len(records)
            driver_stats["wall_s"] = round(time.perf_counter() - t0, 4)
        except Exception as e:
            driver_stats["error"] = repr(e)

    ef_out: dict = {}

    # ---- boot the root and unleash ----
    server = FedServer(cfg, template, tick_period_s=0.05)
    st = ServerThread(server)
    st.__enter__()
    port_ref["port"] = st.port
    threads = [
        threading.Thread(target=client_loop, args=(n, i), name=f"soak-{n}")
        for i, n in enumerate(names)
    ]
    threads.append(threading.Thread(target=edge_loop, name="soak-edge"))
    threads.append(threading.Thread(target=load_loop, name="soak-load"))
    threads.append(threading.Thread(target=driver_leg, name="soak-driver"))
    threads.append(
        threading.Thread(
            target=_ef_conservation_leg,
            args=(template, stop, ef_out, seed),
            name="soak-ef",
        )
    )
    t_start = time.monotonic()
    deadline = t_start + duration_s
    for t in threads:
        t.start()

    mid_scrape_families = 0
    kill_event: dict = {"killed": False}
    st_current = st
    last_watchdog_eval = 0.0
    try:
        # Mid-soak: scrape our own endpoint while everything is in flight.
        while time.monotonic() < deadline:
            remaining = deadline - time.monotonic()
            if time.monotonic() - last_watchdog_eval >= 0.5:
                # The SLO watchdog rides the run: rules evaluated over the
                # live registry every ~0.5 s; a breach dumps the flight
                # ring immediately (Watchdog.enforce) — the audit verdict
                # lands below.
                last_watchdog_eval = time.monotonic()
                watchdog.enforce()
            if kill_restart and not kill_event["killed"] and (
                time.monotonic() - t_start >= duration_s * 0.45
            ):
                held_port = st_current.port
                t_kill = time.monotonic()
                st_current.kill()
                server2 = FedServer(
                    dataclasses.replace(cfg, port=held_port),
                    template,
                    tick_period_s=0.05,
                )
                restored_version = server2.state.model_version
                st_current = ServerThread(server2).__enter__()
                kill_event.update(
                    killed=True,
                    restart_s=round(time.monotonic() - t_kill, 4),
                    restored_version=restored_version,
                    restored_buffer=len(server2.state.buffer),
                )
                continue
            if mid_scrape_families == 0 and time.monotonic() - t_start > min(
                2.0, duration_s / 3
            ):
                mid_scrape_families = len(scrape(exporter.url))
                leak_sentry.sample()
                continue
            time.sleep(min(0.1, max(0.01, remaining)))
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=JOIN_S)
        hung = [t.name for t in threads if t.is_alive()]
        st_current.__exit__(None, None, None)
        manager.stop()
        batcher.close()
    traffic_wall_s = time.monotonic() - t_start

    # ---- final scrape + dump (the CI artifact) ----
    exposition = REGISTRY.exposition()
    with open(metrics_dump_path, "w", encoding="utf-8") as f:
        f.write(exposition)
    parsed = scrape(exporter.url)
    # One final watchdog pass over the REAL scrape (the same text a
    # dashboard would read), then the verdict.
    watchdog.enforce(parsed)
    watchdog_audit = watchdog.audit()
    exporter.stop()
    final_state = st_current.state
    tracing.uninstall()

    # ---- invariant audit ----
    plane_prefixes = ("fed_", "serve_", "driver_", "edge_", "client_")
    planes_covered = {
        p.rstrip("_"): any(name.startswith(p) for name in parsed)
        for p in plane_prefixes
    }
    # Torn versions: serve_batch records land in batch order (one bucket =
    # one worker); versions must be non-decreasing and every served
    # version actually published.
    batch_records = read_metrics(serve_metrics_path, kind="serve_batch")
    batch_versions = [int(rec["model_version"]) for rec in batch_records]
    torn = sum(
        1 for a, b in zip(batch_versions, batch_versions[1:]) if b < a
    )
    # A dead serve plane must not audit clean: the torn-version check is
    # vacuous over zero batches, so the audit requires traffic actually
    # served (and zero loud failures) before "zero torn" means anything.
    serve_healthy = (
        load_stats["completed"] > 0
        and load_stats["failed"] == 0
        and len(batch_versions) > 0
    )
    published = {0} | {s["to_version"] for s in manager.swaps}
    unpublished_served = sorted(set(batch_versions) - published)
    # Statefile: load -> save must reproduce the file byte-identically
    # (canonical snapshot; arrival order must not leak into the bytes).
    with open(state_path, "rb") as f:
        state_bytes = f.read()
    resaved = os.path.join(base_dir, "server_state.resaved.msgpack")
    save_state_file(resaved, load_state_file(state_path, cfg))
    with open(resaved, "rb") as f:
        resaved_bytes = f.read()
    statefile_ok = state_bytes == resaved_bytes
    leak = leak_sentry.summary()
    recompiles = sum(recompile_sentry.deltas().values())
    # ---- round 18 health plane: artifacts + audit arms ----
    ledger_path = os.path.join(base_dir, "ledger.jsonl")
    canary_path = os.path.join(base_dir, "canary.json")
    drift_path = os.path.join(base_dir, "drift.json")
    health_ledger.write_ledger_jsonl(final_state.ledger, ledger_path)
    canary_audit = canary.audit()
    with open(canary_path, "w", encoding="utf-8") as f:
        json.dump(
            {"history": canary.history, "audit": canary_audit},
            f, indent=1, sort_keys=True,
        )
        f.write("\n")
    drift_psis = drift_monitor.compare()
    export_drift_metrics(drift_psis)
    write_drift_json(
        drift_path,
        reference=drift_monitor.reference,
        current=drift_monitor.profile(),
        psis=drift_psis,
    )
    ledger_conservation = health_ledger.conservation(final_state.ledger)
    audit = {
        "torn_versions": int(torn),
        "unpublished_served_versions": unpublished_served,
        "zero_torn_versions": torn == 0 and not unpublished_served,
        "serve_healthy": serve_healthy,
        "ef": ef_out,
        "ef_mass_conserved": (
            ef_out.get("violations") == 0
            and bool(ef_out.get("drained"))
            and ef_out.get("checks", 0) > 0
        ),
        "statefile_restore_bit_identical": statefile_ok,
        "watermarks": leak,
        "watermarks_steady": bool(leak.get("steady")),
        "recompiles_since_warmup": int(recompiles),
        "hung_threads": hung,
        # Round 16: the machine-checked SLO verdict joins the audit — the
        # rule set replaces what used to be hand-coded per-harness checks.
        "watchdog_clean": bool(watchdog_audit["clean"]),
        # Round 18: every gate verdict the chaos produced must be in the
        # ledger exactly once (offers == accepted + rejected + resyncs,
        # surviving the mid-soak kill→restart via the statefile), and
        # every canary eval must be a finite unit-interval IoU.
        "ledger_conservation": ledger_conservation,
        "ledger_conserved": (
            ledger_conservation["clients"] > 0
            and not ledger_conservation["violations"]
        ),
        "canary_steady": (
            canary_audit["evals"] > 0 and bool(canary_audit["all_finite_unit"])
        ),
    }
    audit["clean"] = (
        audit["zero_torn_versions"]
        and audit["serve_healthy"]
        and audit["ef_mass_conserved"]
        and audit["statefile_restore_bit_identical"]
        and audit["watermarks_steady"]
        and recompiles == 0
        and not hung
        and audit["watchdog_clean"]
        and audit["ledger_conserved"]
        and audit["canary_steady"]
    )

    def _sample(name: str, labels: dict | None = None):
        from fedcrack_tpu.obs.promexp import sample_value

        return sample_value(parsed, name, labels)

    from fedcrack_tpu.obs.spans import read_spans, span_files
    from fedcrack_tpu.tools.trace_stitch import stitch_files, summarize

    # The census must cover the whole ROTATED set (this run arms 64 MiB
    # rotation): reading only the live file would silently undercount an
    # hours-long soak's early spans.
    span_records = [
        rec for path in span_files(spans_path) for rec in read_spans(path)
    ]
    span_names: dict[str, int] = {}
    for rec in span_records:
        span_names[rec["name"]] = span_names.get(rec["name"], 0) + 1

    # Stitch the span file into end-to-end update lifecycles: in this
    # one-process harness the planes share a JSONL, but the joins are the
    # SAME wire-context/version joins a multi-process deployment stitches
    # across per-process files. The full result lands next to the spans
    # for CI upload; the artifact embeds the summary.
    stitched = stitch_files([spans_path])
    with open(stitched_path, "w", encoding="utf-8") as f:
        json.dump(stitched, f, indent=1, sort_keys=True, default=str)
    tracing_summary = summarize(stitched)

    artifact = {
        "config": {
            "duration_s": duration_s,
            "seed": seed,
            "n_clients": n_clients,
            "buffer_k": buffer_k,
            "staleness_alpha": staleness_alpha,
            "max_staleness": max_staleness,
            "update_codec": update_codec,
            "kill_restart": kill_restart,
        },
        "traffic_wall_s": round(traffic_wall_s, 3),
        "storm_fired": storm_fired,
        "federation": {
            "global_versions": int(final_state.model_version),
            "flushes": len(final_state.history),
            "accepted_updates_scraped": (
                # delta over the pre-traffic baseline: absolutes would fold
                # in earlier same-process registry traffic (e.g. a storm
                # drill run before this one)
                (_sample("fed_updates_total", {"result": "accepted"}) or 0.0)
                - pre_accepted
            ),
            "client_restarts": counters["client_restarts"],
            "client_errors": counters["client_errors"][:8],
            "kill_restart": kill_event,
        },
        "edge": {k: v if k != "errors" else v[:4] for k, v in edge_stats.items()},
        "serve": {
            **load_stats,
            "versions_seen": sorted(versions_seen),
            "swaps": len(manager.swaps),
            "latency_ms": batcher.latency.summary(),
            "deadline_missed": batcher.stats()["deadline_missed"],
        },
        "driver": driver_stats,
        "scrape": {
            "families": len(parsed),
            "mid_soak_families": mid_scrape_families,
            "planes_covered": planes_covered,
            "all_planes_covered": all(planes_covered.values()),
            "exposition_bytes": len(exposition),
        },
        "spans": {"total": len(span_records), "by_name": dict(sorted(span_names.items()))},
        "tracing": tracing_summary,
        "watchdog": watchdog_audit,
        "health": {
            "ledger_clients": ledger_conservation["clients"],
            "flagged_clients": sorted(
                name
                for name, rec in final_state.ledger.items()
                if rec.get("flags", 0)
            ),
            "canary": canary_audit,
            "drift_psi": drift_psis,
        },
        "audit": audit,
        "paths": {
            "metrics_dump": metrics_dump_path,
            "spans": spans_path,
            "statefile": state_path,
            "flight": flight_path,
            "stitched_trace": stitched_path,
            "ledger": ledger_path,
            "canary": canary_path,
            "drift": drift_path,
        },
    }
    if not audit["clean"] and not any(
        d["reason"].startswith("watchdog") for d in (flight.current().dumps if flight.current() else [])
    ):
        # A failed audit ships its flight record even when no watchdog
        # rule breached (e.g. a torn version or a leak): the dump is the
        # red run's last-N-seconds history.
        flight.dump("soak audit failed")
    flight.uninstall()
    if ctx is not None:
        # Preserve nothing from a temp workdir (the artifact embeds the
        # numbers); named workdirs keep their dumps for CI upload.
        artifact["paths"] = {}
        ctx.cleanup()
    return artifact


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m fedcrack_tpu.tools.soak", description=__doc__
    )
    p.add_argument("--duration", type=float, default=10.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--clients", type=int, default=3)
    p.add_argument("--buffer-k", type=int, default=2)
    p.add_argument("--codec", default="topk_delta")
    p.add_argument("--no-kill", action="store_true",
                   help="skip the mid-soak server kill -> restart")
    p.add_argument("--slo-rules", default="",
                   help="SLO watchdog rule file (configs/slo_*.json); "
                   "empty = the built-in default set")
    p.add_argument("--workdir", default="",
                   help="keep dumps (metrics.prom, spans.jsonl, flight.json, "
                   "trace_stitched.json) here; empty = temp dir, dumps "
                   "discarded")
    p.add_argument("--out", default="", help="write the audit artifact JSON here")
    args = p.parse_args(argv)
    artifact = run_soak(
        duration_s=args.duration,
        seed=args.seed,
        n_clients=args.clients,
        buffer_k=args.buffer_k,
        update_codec=args.codec,
        kill_restart=not args.no_kill,
        workdir=args.workdir or None,
        slo_rules=args.slo_rules or None,
    )
    payload = json.dumps(artifact, indent=1, sort_keys=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(payload)
        print(f"wrote {args.out}")
        print(json.dumps(artifact["audit"], indent=1, sort_keys=True))
    else:
        print(payload)
    if artifact["watchdog"]["breaches"]:
        # The breach → flight-dump → exit-code contract (the dump already
        # landed the moment the first breaching evaluation ran).
        from fedcrack_tpu.obs.watchdog import BREACH_EXIT

        return BREACH_EXIT
    return 0 if artifact["audit"]["clean"] else 1


if __name__ == "__main__":
    sys.exit(main())
