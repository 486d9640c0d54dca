"""Kill→restart recovery drill: time the mid-round server crash path.

``python -m fedcrack_tpu.tools.chaos_drill --out drill.json``

The scripted scenario (deterministic, raw-RPC driven, tiny weights — no
JAX model, runs in seconds on any host):

1. boot a coordinator with a durable statefile (``FedConfig.state_path``),
2. enroll a 2-client cohort, deliver client A's round-1 update,
3. KILL the server with zero grace mid-round (client B still training),
4. boot a fresh coordinator over the same statefile,
5. deliver client B's update — the round must aggregate using A's update
   restored from disk, with the exact weighted average and an unbroken
   history prefix — then drive the remaining rounds to FIN.

Timings reported: ``restore_s`` (dead process → resumed state machine),
``kill_to_recover_s`` (kill instant → the interrupted round's aggregation),
and ``session_s`` (:func:`run_kill_restart_drill`). tests/test_chaos.py pins
the semantics (identical history prefix, exact average) so the timing
artifact can never go green on wrong recovery.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

from fedcrack_tpu.configs import FedConfig
from fedcrack_tpu.fed import rounds as R
from fedcrack_tpu.fed.serialization import tree_from_bytes, tree_to_bytes


def _vars(value: float):
    return {"params": {"w": np.full((4, 4), value, np.float32)}}


def _raw_caller(port: int):
    """One-message-per-call raw client (transport.edge.raw_caller — the
    same caller the edge tier's upstream relay is built on)."""
    from fedcrack_tpu.transport.edge import raw_caller

    return raw_caller(port)


def _ready(cname: str):
    from fedcrack_tpu.transport import transport_pb2 as pb

    msg = pb.ClientMessage(cname=cname)
    msg.ready.SetInParent()
    return msg


def _done(cname: str, rnd: int, value: float, ns: int):
    from fedcrack_tpu.transport import transport_pb2 as pb

    msg = pb.ClientMessage(cname=cname)
    msg.done.round = rnd
    msg.done.weights = tree_to_bytes(_vars(value))
    msg.done.sample_count = ns
    return msg


def _wait_for_statefile(path: str, config: FedConfig, pred, timeout_s: float = 10.0):
    """Poll the durable snapshot until ``pred(state)`` holds — the drill's
    kill must land AFTER the update it relies on has been made durable
    (a real kill races this too; the drill pins the recoverable side)."""
    from fedcrack_tpu.ckpt import load_state_file

    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        state = load_state_file(path, config)
        if state is not None and pred(state):
            return state
        time.sleep(0.01)
    raise TimeoutError(f"statefile {path} never satisfied the predicate")


def run_kill_restart_drill(rounds: int = 3, workdir: str | None = None) -> dict:
    """The scripted scenario; returns the timing/verification artifact."""
    from fedcrack_tpu.transport.service import FedServer, ServerThread

    ctx = (
        tempfile.TemporaryDirectory(prefix="chaos_drill_")
        if workdir is None
        else None
    )
    base = ctx.name if ctx is not None else workdir
    try:
        cfg = FedConfig(
            max_rounds=rounds,
            cohort_size=2,
            registration_window_s=5.0,
            round_deadline_s=60.0,  # backstop only; the drill never waits it out
            port=0,
            state_path=os.path.join(base, "server_state.msgpack"),
        )
        t_session = time.perf_counter()
        server1 = FedServer(cfg, _vars(0.0), tick_period_s=0.02)
        with ServerThread(server1) as st1:
            channel, call = _raw_caller(st1.port)
            assert call(_ready("a")).status == R.SW
            assert call(_ready("b")).status == R.SW
            assert call(_done("a", 1, 1.0, 10)).status == R.RESP_ACY
            channel.close()
            # The kill must strike after A's update is durable.
            _wait_for_statefile(
                cfg.state_path, cfg, lambda s: "a" in s.received
            )
            t_kill = time.perf_counter()
            st1.kill()

        server2 = FedServer(cfg, _vars(0.0), tick_period_s=0.02)
        resumed = server2.state
        t_restored = time.perf_counter()
        if not (
            resumed.phase == R.PHASE_RUNNING
            and resumed.current_round == 1
            and "a" in resumed.received
            and resumed.cohort == frozenset({"a", "b"})
        ):
            raise RuntimeError(
                f"restart did not resume the round: phase={resumed.phase} "
                f"round={resumed.current_round} received={sorted(resumed.received)}"
            )
        with ServerThread(server2) as st2:
            channel, call = _raw_caller(st2.port)
            rep = call(_done("b", 1, 3.0, 30))
            t_recovered = time.perf_counter()
            if rep.status != R.RESP_ARY:
                raise RuntimeError(f"recovery aggregation failed: {rep.status}")
            # Weighted average over BOTH updates — A's restored from disk:
            # (10*1 + 30*3) / 40 = 2.5.
            got = tree_from_bytes(rep.weights)["params"]["w"]
            avg_exact = bool(np.allclose(got, 2.5, atol=1e-6))
            for rnd in range(2, rounds + 1):
                call(_done("a", rnd, 1.0, 10))
                rep = call(_done("b", rnd, 3.0, 30))
            channel.close()
            state = st2.state
        history_rounds = [h["round"] for h in state.history]
        return {
            "rounds": rounds,
            "restore_s": round(t_restored - t_kill, 4),
            "kill_to_recover_s": round(t_recovered - t_kill, 4),
            "session_s": round(time.perf_counter() - t_session, 4),
            "resumed_mid_round": True,
            "received_preserved": True,
            "recovered_avg_exact": avg_exact,
            "finished": state.phase == R.PHASE_FINISHED,
            "history_rounds": history_rounds,
            "history_gapless": history_rounds
            == list(range(1, len(history_rounds) + 1)),
        }
    finally:
        if ctx is not None:
            ctx.cleanup()


def run_corrupt_frame_drill() -> dict:
    """CORRUPT_COMPRESSED_FRAME drill (round 12): a cohort uploading int8
    compressed frames where one client's frame takes a single bit-flip on
    the wire. The server must reject it on the frame CRC — BEFORE any
    reconstruction — log it to the round's ``rejected`` history map, and
    still close the round at quorum from the two clean frames. The
    aggregation result is checked EXACTLY against the weighted average of
    what decode_update reconstructs from the two clean frames (int8 encode
    is seeded, so frames and reconstructions are deterministic)."""
    from fedcrack_tpu.chaos.inject import _poison_weights
    from fedcrack_tpu.chaos.plan import CORRUPT_COMPRESSED_FRAME
    from fedcrack_tpu.compress import decode_update, get_codec, is_frame
    from fedcrack_tpu.transport import transport_pb2 as pb
    from fedcrack_tpu.transport.service import FedServer, ServerThread

    cfg = FedConfig(
        max_rounds=1,
        cohort_size=3,
        quorum_fraction=2.0 / 3.0,  # 2 of 3: the poisoned client must not stall it
        registration_window_s=5.0,
        round_deadline_s=60.0,
        update_codec="int8",
        port=0,
    )
    base_vars = _vars(0.0)
    server = FedServer(cfg, base_vars, tick_period_s=0.02)
    base_blob = server.state.broadcast_blob

    def framed(cname: str, value: float, ns: int, corrupt: bool) -> pb.ClientMessage:
        frame = get_codec("int8", client_tag=cname).encode_update(
            tree_to_bytes(_vars(value)), base_blob, round=1, base_version=0
        )
        assert is_frame(frame)
        if corrupt:
            frame = _poison_weights(frame, CORRUPT_COMPRESSED_FRAME)
        msg = pb.ClientMessage(cname=cname)
        msg.done.round = 1
        msg.done.weights = frame
        msg.done.sample_count = ns
        return msg

    t0 = time.perf_counter()
    with ServerThread(server) as st:
        channel, call = _raw_caller(st.port)
        for c in ("a", "b", "c"):
            assert call(_ready(c)).status == R.SW
        # The corrupt frame lands FIRST: rejection, not a stale-round resync.
        rej = call(framed("c", 9.0, 20, corrupt=True))
        rep_a = call(framed("a", 1.0, 10, corrupt=False))
        rep_b = call(framed("b", 3.0, 30, corrupt=False))
        t_quorum = time.perf_counter()
        channel.close()
        state = st.state
    got = tree_from_bytes(rep_b.weights)["params"]["w"]
    base_tree = tree_from_bytes(base_blob)
    dec = {}
    for cname, value in (("a", 1.0), ("b", 3.0)):
        frame = get_codec("int8", client_tag=cname).encode_update(
            tree_to_bytes(_vars(value)), base_blob, round=1, base_version=0
        )
        tree, _ = decode_update(
            frame, template=base_tree, base=base_tree, expected_base_version=0
        )
        dec[cname] = np.asarray(tree["params"]["w"], np.float32)
    want = (10 * dec["a"] + 30 * dec["b"]) / 40
    entry = state.history[0] if state.history else {}
    return {
        "corrupt_rejected": rej.status == R.REJECTED,
        "reject_reason_is_checksum": "checksum" in (
            entry.get("rejected", {}).get("c", "")
        ),
        "quorum_reached": rep_a.status == R.RESP_ACY
        and rep_b.status in (R.RESP_ARY, R.FIN),
        "clean_clients_aggregated": entry.get("clients") == ["a", "b"],
        "codecs": entry.get("codecs"),
        "wire_bytes_received": entry.get("bytes_received"),
        "decoded_bytes_received": entry.get("decoded_bytes_received"),
        "avg_matches_decoded_frames": bool(np.allclose(got, want, atol=1e-5)),
        "reject_to_quorum_s": round(t_quorum - t0, 4),
    }


def run_edge_crash_drill(workdir: str | None = None) -> dict:
    """EDGE_AGGREGATOR_CRASH drill (round 13): a 2-edge aggregation tree
    where one edge tier is KILLED mid-round — after 2 of its 3 leaves
    reported — and restarted from its statefile. The restarted edge must
    resume the SAME round with the already-received updates intact, accept
    the third leaf, close its K-of-N quorum, and push its partial to the
    root (a real gRPC FedServer) so the root round still closes — with the
    root average EXACTLY the sample-weighted mean over both edges'
    partials, and the recovered edge's partial EXACTLY the weighted mean
    of all three leaves (nothing lost to the crash). The scripted kill is
    scheduled and recorded through a chaos FaultPlan so the artifact
    proves the fault actually fired."""
    from fedcrack_tpu.chaos.plan import EDGE_AGGREGATOR_CRASH, Fault, FaultPlan
    from fedcrack_tpu.fed.tree import EdgeAggregator
    from fedcrack_tpu.transport.edge import EdgeRelay
    from fedcrack_tpu.transport.service import FedServer, ServerThread

    ctx = (
        tempfile.TemporaryDirectory(prefix="edge_crash_drill_")
        if workdir is None
        else None
    )
    base = ctx.name if ctx is not None else workdir
    try:
        cfg = FedConfig(
            max_rounds=1,
            cohort_size=2,  # the ROOT's cohort is the two edges
            registration_window_s=5.0,
            round_deadline_s=60.0,
            port=0,
        )
        plan = FaultPlan(
            [Fault(kind=EDGE_AGGREGATOR_CRASH, round=1, client="edge-0")]
        )
        t0 = time.perf_counter()
        root = FedServer(cfg, _vars(0.0), tick_period_s=0.02)
        template = root.state.template
        with ServerThread(root) as st:
            relay0 = EdgeRelay("edge-0", st.port)
            relay1 = EdgeRelay("edge-1", st.port)
            h0 = relay0.enroll()
            relay1.enroll()
            base_blob = relay0.pull()
            base_version = int(h0["model_version"])
            round_no = int(h0["current_round"])

            state_path = os.path.join(base, "edge-0.msgpack")
            edge0 = EdgeAggregator(
                "edge-0", template, quorum_fraction=1.0, state_path=state_path
            )
            edge0.begin_round(
                round_no, base_blob, base_version, ["a", "b", "c"]
            )
            assert edge0.offer("a", tree_to_bytes(_vars(1.0)), 10)[0]
            assert edge0.offer("b", tree_to_bytes(_vars(2.0)), 10)[0]
            # KILL edge-0 mid-round (leaf c still training): drop the
            # in-memory aggregator; durable state is whatever the atomic
            # writer had renamed.
            assert plan.take(EDGE_AGGREGATOR_CRASH, client="edge-0", round=round_no)
            t_kill = time.perf_counter()
            del edge0

            restored = EdgeAggregator.restore(
                state_path, template, quorum_fraction=1.0
            )
            t_restored = time.perf_counter()
            if restored is None or sorted(restored.received) != ["a", "b"]:
                raise RuntimeError("edge restart did not resume from its statefile")
            resumed_mid_round = (
                restored.round == round_no
                and restored.base_version == base_version
            )
            assert restored.offer("c", tree_to_bytes(_vars(6.0)), 20)[0]
            assert restored.quorum_met()
            partial0, total0 = restored.partial()
            status0, _, _ = relay0.push_partial(round_no, partial0, total0)

            edge1 = EdgeAggregator("edge-1", template, quorum_fraction=1.0)
            edge1.begin_round(round_no, base_blob, base_version, ["d"])
            assert edge1.offer("d", tree_to_bytes(_vars(8.0)), 40)[0]
            partial1, total1 = edge1.partial()
            status1, new_global, _ = relay1.push_partial(round_no, partial1, total1)
            t_recovered = time.perf_counter()
            relay0.close()
            relay1.close()
            state = st.state
        # edge-0's partial: (10*1 + 10*2 + 20*6) / 40 = 3.75 — A and B
        # restored from disk, C delivered post-restart.
        p0 = tree_from_bytes(partial0)["params"]["w"]
        # root: (40*3.75 + 40*8) / 80 = 5.875.
        got = tree_from_bytes(new_global)["params"]["w"]
        entry = state.history[0] if state.history else {}
        return {
            "fault_fired": [f.kind for f in plan.triggered] == [EDGE_AGGREGATOR_CRASH],
            "resumed_mid_round": bool(resumed_mid_round),
            "received_preserved": True,
            "edge_partial_exact": bool(np.allclose(p0, 3.75, atol=1e-6)),
            "root_round_closed": status0 == R.RESP_ACY
            and status1 in (R.RESP_ARY, R.FIN),
            "root_avg_exact": bool(np.allclose(got, 5.875, atol=1e-6)),
            "root_clients": entry.get("clients"),
            "root_cohort_size": entry.get("cohort_size"),
            "restore_s": round(t_restored - t_kill, 4),
            "kill_to_recover_s": round(t_recovered - t_kill, 4),
            "session_s": round(time.perf_counter() - t0, 4),
        }
    finally:
        if ctx is not None:
            ctx.cleanup()


def _poll(cname: str, model_version: int, rnd: int):
    from fedcrack_tpu.transport import transport_pb2 as pb

    msg = pb.ClientMessage(cname=cname)
    msg.poll.model_version = model_version
    msg.poll.round = rnd
    return msg


def _pull(cname: str):
    from fedcrack_tpu.transport import transport_pb2 as pb

    msg = pb.ClientMessage(cname=cname)
    msg.pull.SetInParent()
    return msg


def run_straggler_storm_drill(
    seed: int = 0,
    n_clients: int = 6,
    versions: int = 3,
    buffer_k: int = 2,
    staleness_alpha: float = 0.5,
) -> dict:
    """STRAGGLER_STORM drill (round 14): the sync-vs-buffered A/B under ONE
    seeded heavy-tail delay schedule (``FaultPlan.storm`` — both arms
    replay the identical per-(client, iteration) delays).

    - SYNC arm: the barrier round machine; every round's wall is the
      cohort's MAX delay (the failure mode the async plane exists for).
    - BUFFERED arm: FedBuff — the server flushes on the ``buffer_k``
      fastest arrivals, staleness-weighting the stragglers' late updates
      instead of waiting on them.

    Decision metrics (the ROADMAP async item's): sustained accepted
    updates/sec and global versions/min at EQUAL WALL — the sync arm runs
    ``versions`` barrier rounds, then the buffered arm runs for that same
    wall-clock window and we count what it ingested/flushed in it (a
    buffered server never idles waiting on a straggler, so equal-versions
    would cap its throughput at K x versions while the stragglers are
    still sleeping — "sustained" is a rate, measured over a window). The
    returned artifact carries both arms plus the strict comparison bools
    the acceptance gate reads.

    Round 15: each arm's counts come from SCRAPING the live metric
    registry over a real ``/metrics`` HTTP endpoint (before/after sample
    deltas of ``fed_updates_total{result="accepted"}`` and
    ``fed_global_versions_total``) — and each arm pins its scraped deltas
    against the protocol history (``scrape_matches_history``), so the A/B
    rates a dashboard would show and the rates this artifact reports are
    the SAME numbers by construction, not parallel bookkeeping."""
    import threading

    from fedcrack_tpu.chaos.plan import (
        STRAGGLER_DELAY,
        STRAGGLER_STORM,
        FaultPlan,
    )
    from fedcrack_tpu.fed.buffered import async_summary
    from fedcrack_tpu.obs.promexp import MetricsExporter, sample_value, scrape
    from fedcrack_tpu.obs.registry import REGISTRY
    from fedcrack_tpu.transport.codec import decode_scalar_map
    from fedcrack_tpu.transport.service import FedServer, ServerThread

    def fed_counters(url: str) -> dict:
        """One scrape, reduced to the two A/B series (absent -> 0: the
        registry only materializes a family at its first bump)."""
        parsed = scrape(url)
        return {
            "accepted": sample_value(
                parsed, "fed_updates_total", {"result": "accepted"}
            ) or 0.0,
            "versions": sample_value(parsed, "fed_global_versions_total") or 0.0,
        }

    names = [f"c{i}" for i in range(n_clients)]
    # One schedule, two arms: the delay dicts are read WITHOUT consuming
    # (plan.take is single-threaded-per-target; N drill threads share the
    # schedule), the storm MARKER is consumed so `triggered` proves the
    # storm actually fired.
    plan = FaultPlan.storm(
        seed,
        clients=names,
        n_iterations=versions * 4,
        # Heavy enough that the per-round MAX over the cohort (what the
        # sync barrier serializes on) dwarfs the K fastest draws (what a
        # buffered flush waits for) — the regime the async plane targets.
        tail_alpha=1.1,
        scale_s=0.03,
        cap_s=0.8,
    )
    assert plan.take(STRAGGLER_STORM, round=1) is not None
    delays = {
        (f.client, f.round): f.delay_s
        for f in plan.pending
        if f.kind == STRAGGLER_DELAY
    }

    def run_sync(url: str) -> dict:
        cfg = FedConfig(
            max_rounds=versions,
            cohort_size=n_clients,
            registration_window_s=5.0,
            round_deadline_s=60.0,
            port=0,
        )
        server = FedServer(cfg, _vars(0.0), tick_period_s=0.02)
        errors: list[str] = []

        def client(name: str):
            channel, call = _raw_caller(server_thread.port)
            try:
                assert call(_ready(name)).status == R.SW
                rnd, mv = 1, 0
                for it in range(1, versions + 1):
                    time.sleep(delays[(name, it)])
                    rep = call(_done(name, rnd, 1.0 + it, 10))
                    if rep.status == R.RESP_ACY:
                        # The barrier: poll until the round closes behind
                        # the slowest client.
                        while True:
                            time.sleep(0.01)
                            rep = call(_poll(name, mv, rnd))
                            if rep.status != R.WAIT:
                                break
                    if rep.status == R.FIN:
                        return
                    c = decode_scalar_map(rep.config)
                    rnd, mv = int(c["current_round"]), int(c["model_version"])
            except Exception as e:  # surfaced in the artifact, never silent
                errors.append(f"{name}: {e!r}")
            finally:
                channel.close()

        pre = fed_counters(url)
        t0 = time.perf_counter()
        with ServerThread(server) as server_thread:
            threads = [
                threading.Thread(target=client, args=(n,)) for n in names
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            wall = time.perf_counter() - t0
            state = server_thread.state
        post = fed_counters(url)
        # The arm's counts come from the SCRAPE (before/after deltas of the
        # live registry over HTTP); the protocol history is the cross-check.
        n_accepted = int(post["accepted"] - pre["accepted"])
        n_versions = int(post["versions"] - pre["versions"])
        return {
            "wall_s": round(wall, 4),
            "accepted_updates": n_accepted,
            "global_versions": n_versions,
            "updates_per_sec": round(n_accepted / wall, 3),
            "versions_per_min": round(n_versions / wall * 60.0, 3),
            "scrape_matches_history": (
                n_accepted == sum(len(h["clients"]) for h in state.history)
                and n_versions == int(state.model_version)
            ),
            "errors": errors,
        }

    def run_buffered(window_s: float, url: str) -> dict:
        cfg = FedConfig(
            # A horizon the window can never reach: the drill measures the
            # SUSTAINED rate over `window_s`, not time-to-N-versions.
            max_rounds=100_000,
            cohort_size=n_clients,
            mode="buffered",
            buffer_k=buffer_k,
            staleness_alpha=staleness_alpha,
            max_staleness=8,
            registration_window_s=5.0,
            round_deadline_s=60.0,
            port=0,
        )
        server = FedServer(cfg, _vars(0.0), tick_period_s=0.02)
        errors: list[str] = []
        stop = threading.Event()
        n_sched = versions * 4

        def client(name: str):
            channel, call = _raw_caller(server_thread.port)
            try:
                assert call(_ready(name)).status == R.SW
                it = 0
                while not stop.is_set():
                    it += 1
                    rep = call(_pull(name))
                    c = decode_scalar_map(rep.config)
                    # The same schedule, consumed cyclically past the sync
                    # arm's horizon (the window outlives `versions`
                    # iterations for fast clients — that is the point).
                    time.sleep(delays[(name, (it - 1) % n_sched + 1)])
                    if stop.is_set():
                        return
                    call(_done(name, int(c["current_round"]), 1.0 + it, 10))
            except Exception as e:
                errors.append(f"{name}: {e!r}")
            finally:
                channel.close()

        pre = fed_counters(url)
        t0 = time.perf_counter()
        with ServerThread(server) as server_thread:
            threads = [
                threading.Thread(target=client, args=(n,)) for n in names
            ]
            for t in threads:
                t.start()
            time.sleep(window_s)
            # Measure AT the window edge: in-flight sleeps past it must not
            # count (the rates divide by window_s). Scrape-sandwich the
            # state snapshot — two identical scrapes bracketing the read
            # prove no update landed mid-measurement, so the scraped deltas
            # and the history describe the SAME instant.
            for _ in range(200):
                post = fed_counters(url)
                state = server_thread.state
                if fed_counters(url) == post:
                    break
            stop.set()
            for t in threads:
                t.join(timeout=60)
        summary = async_summary(state.history)
        n_accepted = int(post["accepted"] - pre["accepted"])
        n_versions = int(post["versions"] - pre["versions"])
        return {
            "wall_s": round(window_s, 4),
            "accepted_updates": n_accepted,
            "global_versions": n_versions,
            "updates_per_sec": round(n_accepted / window_s, 3),
            "versions_per_min": round(n_versions / window_s * 60.0, 3),
            "scrape_matches_history": (
                n_accepted
                == int(summary["accepted_updates"]) + len(state.buffer)
                and n_versions == int(state.model_version)
            ),
            "staleness": summary["staleness"],
            "mean_buffer_fill": summary["mean_buffer_fill"],
            "errors": errors,
        }

    with MetricsExporter(REGISTRY) as exporter:
        sync = run_sync(exporter.url)
        buffered = run_buffered(sync["wall_s"], exporter.url)
    return {
        "rates_scraped_from_registry": True,
        "seed": seed,
        "n_clients": n_clients,
        "versions": versions,
        "buffer_k": buffer_k,
        "staleness_alpha": staleness_alpha,
        "storm_fired": [f.kind for f in plan.triggered] == [STRAGGLER_STORM],
        "sync": sync,
        "buffered": buffered,
        # The ROADMAP decision points, read by the acceptance gate: same
        # fault plan, strictly more sustained updates/sec AND global
        # versions/min in buffered mode.
        "buffered_gt_sync_updates_per_sec": (
            buffered["updates_per_sec"] > sync["updates_per_sec"]
        ),
        "buffered_gt_sync_versions_per_min": (
            buffered["versions_per_min"] > sync["versions_per_min"]
        ),
    }


def run_buffered_kill_drill(workdir: str | None = None) -> dict:
    """Buffered-mode mid-BUFFER server kill→restart drill (round 14): a
    3-client buffered federation (``buffer_k=3``), two of three updates
    accepted into the buffer, server KILLED with zero grace, restarted
    over the same statefile, third update delivered — the flush must land
    on the BIT-IDENTICAL next global version an unkilled twin produces
    (same buffer contents, same sorted fold, same bytes)."""
    from fedcrack_tpu.transport.service import FedServer, ServerThread

    ctx = (
        tempfile.TemporaryDirectory(prefix="buffered_kill_drill_")
        if workdir is None
        else None
    )
    base = ctx.name if ctx is not None else workdir
    try:
        def cfg_for(state_path: str) -> FedConfig:
            return FedConfig(
                max_rounds=1,
                cohort_size=3,
                mode="buffered",
                buffer_k=3,
                staleness_alpha=0.5,
                max_staleness=4,
                registration_window_s=5.0,
                round_deadline_s=60.0,
                port=0,
                state_path=state_path,
            )

        def drive(call):
            for c in ("a", "b", "c"):
                assert call(_ready(c)).status == R.SW
            for c in ("a", "b", "c"):
                call(_pull(c))

        # Twin 1: uninterrupted.
        cfg1 = cfg_for(os.path.join(base, "twin.msgpack"))
        server1 = FedServer(cfg1, _vars(0.0), tick_period_s=0.02)
        with ServerThread(server1) as st:
            channel, call = _raw_caller(st.port)
            drive(call)
            call(_done("a", 1, 1.0, 10))
            call(_done("b", 1, 3.0, 30))
            rep = call(_done("c", 1, 6.0, 20))
            channel.close()
            twin_status = rep.status
            twin_blob = bytes(rep.weights)
            twin_version = st.state.model_version

        # Twin 2: killed mid-buffer.
        cfg2 = cfg_for(os.path.join(base, "killed.msgpack"))
        server2 = FedServer(cfg2, _vars(0.0), tick_period_s=0.02)
        with ServerThread(server2) as st:
            channel, call = _raw_caller(st.port)
            drive(call)
            call(_done("a", 1, 1.0, 10))
            call(_done("b", 1, 3.0, 30))
            channel.close()
            # The kill must strike after both buffer entries AND c's pull
            # record are durable (c's framed/raw base is pinned to it).
            _wait_for_statefile(
                cfg2.state_path,
                cfg2,
                lambda s: len(s.buffer) == 2 and "c" in s.pulled,
            )
            t_kill = time.perf_counter()
            st.kill()

        server3 = FedServer(cfg2, _vars(0.0), tick_period_s=0.02)
        resumed = server3.state
        t_restored = time.perf_counter()
        resumed_mid_buffer = (
            len(resumed.buffer) == 2
            and sorted(e["cname"] for e in resumed.buffer) == ["a", "b"]
            and resumed.pulled.get("c") == 0
        )
        if not resumed_mid_buffer:
            raise RuntimeError(
                f"restart did not resume the buffer: "
                f"{[e['cname'] for e in resumed.buffer]} pulled={dict(resumed.pulled)}"
            )
        with ServerThread(server3) as st:
            channel, call = _raw_caller(st.port)
            rep = call(_done("c", 1, 6.0, 20))
            t_recovered = time.perf_counter()
            channel.close()
            killed_blob = bytes(rep.weights)
            killed_version = st.state.model_version
        return {
            "resumed_mid_buffer": True,
            "twin_flush_status": twin_status,
            "recovered_flush_status": rep.status,
            "global_version_identical": killed_version == twin_version,
            "global_blob_bit_identical": killed_blob == twin_blob,
            "restore_s": round(t_restored - t_kill, 4),
            "kill_to_recover_s": round(t_recovered - t_kill, 4),
        }
    finally:
        if ctx is not None:
            ctx.cleanup()


def run_replica_crash_drill() -> dict:
    """Serve-fleet replica-crash drill (round 17, SERVE_REPLICA_CRASH).

    A 2-replica fleet (tiny model, shared engine) under concurrent load:
    one replica is killed mid-load with requests still queued on it — the
    router drains that queue to the survivor WITH the original futures, so
    every accepted request answers (zero drops). Then the fleet-wide
    two-phase swap is driven on the surviving topology and must land: every
    post-commit request answers from the new version (zero torn versions on
    a degraded fleet). The kill is scheduled and consumed through a chaos
    FaultPlan so the artifact proves it fired."""
    import threading

    import jax

    from fedcrack_tpu.chaos.plan import SERVE_REPLICA_CRASH, Fault, FaultPlan
    from fedcrack_tpu.configs import ModelConfig, ServeConfig
    from fedcrack_tpu.models.resunet import init_variables
    from fedcrack_tpu.serve.fleet import ServeFleet

    model_config = ModelConfig(
        img_size=16, stem_features=4, encoder_features=(8,), decoder_features=(8, 4)
    )
    serve_config = ServeConfig(
        bucket_sizes=(16,),
        max_batch=4,
        max_delay_ms=30.0,
        tile_overlap=4,
        replicas=2,
    )
    v0 = init_variables(jax.random.key(0), model_config)
    v1 = init_variables(jax.random.key(1), model_config)
    plan = FaultPlan([Fault(kind=SERVE_REPLICA_CRASH, round=1)])

    class _SlowBatches:
        """Batcher chaos hook stretching every dispatch, so a queued
        BACKLOG provably exists on the victim at kill time (a tiny CPU
        model would otherwise drain its queue before the kill lands and
        the reroute path would go untested)."""

        def on_batch(self, bucket, batch_index, attempt):
            time.sleep(0.08)

    fleet = ServeFleet(
        model_config, serve_config, v0, initial_version=0, chaos=_SlowBatches()
    )
    rng = np.random.default_rng(0)
    img = rng.integers(0, 256, (16, 16, 3), dtype=np.uint8)
    t_start = time.perf_counter()
    try:
        # Phase 1: a burst wide enough that BOTH replicas hold queued work
        # (least-outstanding routing alternates them), submitted from
        # threads like real front-door traffic.
        n_burst = 24
        futures = []
        fut_lock = threading.Lock()

        def submit_some(n):
            for _ in range(n):
                f = fleet.submit(img)
                with fut_lock:
                    futures.append(f)

        threads = [
            threading.Thread(target=submit_some, args=(n_burst // 4,))
            for _ in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # Phase 2: the scheduled crash — consumed from the plan (the
        # artifact's proof it fired), executed by the router's kill path.
        fault = plan.take(SERVE_REPLICA_CRASH, round=1)
        assert fault is not None
        victim = 1
        t_kill = time.perf_counter()
        reroute = fleet.router.kill_replica(victim)
        # Phase 3: every accepted request answers (original futures).
        results = [f.result(timeout=60) for f in futures]
        answered = len(results)
        # Phase 4: the fleet swap still lands on the degraded fleet.
        installed = fleet.install(1, v1)
        post = [fleet.submit(img) for _ in range(4)]
        post_versions = sorted({f.result(timeout=60).model_version for f in post})
        stats = fleet.router.stats()
        return {
            "replicas": serve_config.replicas,
            "burst": n_burst,
            "fault_fired": fault.kind,
            "victim": victim,
            "rerouted": reroute["rerouted"],
            "reroute_failed": reroute["failed"],
            "answered": answered,
            "dropped": n_burst - answered,
            "zero_dropped": answered == n_burst,
            "live_after_kill": stats["live"],
            "swap_installed": installed,
            "post_swap_versions": post_versions,
            "swap_landed_untorn": installed and post_versions == [1],
            "swap_pause_ms": (fleet.manager.last_swap or {}).get("pause_ms"),
            "kill_to_drained_s": round(time.perf_counter() - t_kill, 3),
            "drill_s": round(time.perf_counter() - t_start, 3),
        }
    finally:
        fleet.close()


def run_elastic_fleet_drill() -> dict:
    """Elastic-fleet drill (round 22): REPLICA_CRASH_DURING_SCALE +
    SHADOW_REPLICA_CRASH.

    Part A — crash racing a scale-down: a 3-replica fleet under threaded
    load; the autoscaler's scale-down (drains the highest-index replica)
    races a concurrent crash of ANOTHER replica — two drains contend on
    one router, and the pin is that every accepted request still answers
    with its original future (zero drops), exactly the r17 discipline.

    Part B — dying shadow lane: while a candidate stages on the shadow
    mirror under live traffic, the shadow batcher is killed mid-staging.
    Pins: every production request answers (the shadow has no wire path to
    clients), zero sheds attributable to the shadow, and the verdict
    degrades to a LOUD rollback (a lane that answered nothing can never be
    promoted). Both faults are scheduled and consumed through a chaos
    FaultPlan so the artifact proves they fired."""
    import threading

    import jax

    from fedcrack_tpu.chaos.plan import (
        REPLICA_CRASH_DURING_SCALE,
        SHADOW_REPLICA_CRASH,
        Fault,
        FaultPlan,
    )
    from fedcrack_tpu.configs import ModelConfig, ServeConfig
    from fedcrack_tpu.models.resunet import init_variables
    from fedcrack_tpu.serve.autoscaler import FleetAutoscaler
    from fedcrack_tpu.serve.fleet import ServeFleet
    from fedcrack_tpu.serve.shadow import ShadowController

    model_config = ModelConfig(
        img_size=16, stem_features=4, encoder_features=(8,), decoder_features=(8, 4)
    )
    serve_config = ServeConfig(
        bucket_sizes=(16,),
        max_batch=4,
        max_delay_ms=30.0,
        tile_overlap=4,
        replicas=3,
        min_replicas=1,
        max_replicas=3,
        scale_cooldown_s=0.0,
        scale_down_idle_evals=1,
        shadow_fraction=0.5,
        shadow_min_samples=64,
    )
    v0 = init_variables(jax.random.key(0), model_config)
    v1 = init_variables(jax.random.key(1), model_config)
    plan = FaultPlan(
        [
            Fault(kind=REPLICA_CRASH_DURING_SCALE, round=1),
            Fault(kind=SHADOW_REPLICA_CRASH, round=0),
        ]
    )

    class _SlowBatches:
        """Stretch every dispatch so queued backlogs provably exist on the
        drained/crashed replicas at race time (see run_replica_crash_drill)."""

        def on_batch(self, bucket, batch_index, attempt):
            time.sleep(0.05)

    fleet = ServeFleet(
        model_config, serve_config, v0, initial_version=0, chaos=_SlowBatches()
    )
    auto = FleetAutoscaler(fleet)
    rng = np.random.default_rng(0)
    img = rng.integers(0, 256, (16, 16, 3), dtype=np.uint8)
    t_start = time.perf_counter()
    # A calm synthetic exposition: the autoscaler sees 3 idle replicas and
    # wants one drained — the drill controls WHEN, so the crash can race it.
    calm = {
        "serve_fleet_replicas": {
            "type": "gauge", "help": "", "samples": {(): 3.0}
        },
        "serve_rolling_p95_seconds": {
            "type": "gauge", "help": "", "samples": {(): 0.0}
        },
        "serve_router_queue_depth_total": {
            "type": "gauge", "help": "",
            "samples": {(("bucket", "16"),): 0.0},
        },
    }
    try:
        # ---- part A: crash vs scale-down race ----
        n_burst = 24
        futures = []
        fut_lock = threading.Lock()

        def submit_some(n):
            for _ in range(n):
                f = fleet.submit(img)
                with fut_lock:
                    futures.append(f)

        threads = [
            threading.Thread(target=submit_some, args=(n_burst // 4,))
            for _ in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        fault = plan.take(REPLICA_CRASH_DURING_SCALE, round=1)
        assert fault is not None
        crash_victim = fault.round  # replica index, like SERVE_REPLICA_CRASH
        t_race = time.perf_counter()
        barrier = threading.Barrier(2)

        def scale_down():
            barrier.wait()
            auto.evaluate(calm)  # calm + idle_evals=1 -> drains replica 2

        def crash():
            barrier.wait()
            fleet.router.kill_replica(crash_victim)

        racers = [
            threading.Thread(target=scale_down),
            threading.Thread(target=crash),
        ]
        for t in racers:
            t.start()
        for t in racers:
            t.join()
        results = [f.result(timeout=60) for f in futures]
        answered = len(results)
        live_after = len(fleet.router.live_replicas())
        scale_actions = [a["action"] for a in auto.actions]
        race_s = round(time.perf_counter() - t_race, 3)

        # ---- part B: dying shadow lane ----
        ctrl = ShadowController(fleet)
        stop_pump = threading.Event()
        prod_results: list = []
        prod_errors: list = []

        def pump():
            while not stop_pump.is_set():
                try:
                    prod_results.append(fleet.submit(img).result(timeout=30))
                except Exception as e:  # any shed/fail here breaks the pin
                    prod_errors.append(repr(e))

        pump_threads = [threading.Thread(target=pump) for _ in range(2)]
        for t in pump_threads:
            t.start()

        def kill_shadow():
            # Wait for the mirror to attach, then kill its lane — the
            # scheduled fault, consumed so the artifact proves it fired.
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                mirror = fleet.router._shadow
                if mirror is not None and mirror.completed() >= 1:
                    break
                time.sleep(0.01)
            fault_b = plan.take(SHADOW_REPLICA_CRASH, round=0)
            assert fault_b is not None
            mirror = fleet.router._shadow
            if mirror is not None:
                mirror._batcher.close()

        killer = threading.Thread(target=kill_shadow)
        killer.start()
        verdict = ctrl.stage(1, v1, wait_s=4.0)
        killer.join(timeout=15)
        stop_pump.set()
        for t in pump_threads:
            t.join(timeout=15)
        shed = sum(fleet.router.shed_counts().values())
        return {
            "burst": n_burst,
            "fault_fired": [f.kind for f in plan.triggered],
            "crash_victim": crash_victim,
            "answered": answered,
            "dropped": n_burst - answered,
            "zero_dropped": answered == n_burst,
            "live_after_race": live_after,
            "scale_actions": scale_actions,
            "shadow_verdict": verdict["verdict"],
            "shadow_reasons": verdict["reasons"],
            "shadow_completed": verdict["completed"],
            "shadow_failures": verdict["shadow_failures"],
            "production_answered_during_shadow": len(prod_results),
            "production_errors_during_shadow": prod_errors,
            "production_unperturbed": not prod_errors,
            "shed_total": shed,
            "rollback_not_promote": verdict["verdict"] == "rollback",
            "race_s": race_s,
            "drill_s": round(time.perf_counter() - t_start, 3),
        }
    finally:
        fleet.close()


def run_stream_reset_drill() -> dict:
    """SERVE_STREAM_RESET drill (round 19): a mid-stream session drop on
    the video serving plane.

    A video session (tiny engine, multi-tile frames) serves a seeded
    correlated frame sequence while a chaos plan schedules a
    ``SERVE_STREAM_RESET`` at a mid-sequence frame — ``StreamChaos``
    consumes it and wipes the per-stream tile cache BEFORE that frame is
    served. The pinned claims:

    - the reset stream falls back to a full-tile re-run on the reset frame
      (``tiles_computed == tiles_total``, zero cache hits);
    - ZERO wrong bytes: every frame, including the reset frame and the
      cache-warm frames around it, is byte-identical to stateless
      ``engine.predict_tiled`` under the same weights snapshot;
    - zero dropped accepted requests: every submitted frame answers.

    The fault is scheduled and consumed through the plan, so the artifact
    proves the reset actually fired instead of silently matching nothing.
    """
    import jax

    from fedcrack_tpu.chaos.inject import StreamChaos
    from fedcrack_tpu.chaos.plan import SERVE_STREAM_RESET, Fault, FaultPlan
    from fedcrack_tpu.configs import ModelConfig, ServeConfig
    from fedcrack_tpu.models.resunet import init_variables
    from fedcrack_tpu.obs.registry import MetricsRegistry
    from fedcrack_tpu.serve.engine import InferenceEngine
    from fedcrack_tpu.serve.stream import StreamSessionManager
    from fedcrack_tpu.tools.load_gen import make_frame_sequence

    model_config = ModelConfig(
        img_size=32, stem_features=4, encoder_features=(8,), decoder_features=(8, 4)
    )
    serve_config = ServeConfig(
        bucket_sizes=(16, 32), max_batch=4, max_delay_ms=10.0, tile_overlap=4
    )
    engine = InferenceEngine(model_config, serve_config)
    variables = engine.prepare(init_variables(jax.random.key(0), model_config))

    class _Static:
        def snapshot(self):
            return 0, variables

    n_frames, reset_at = 8, 4
    plan = FaultPlan([Fault(kind=SERVE_STREAM_RESET, round=reset_at)])
    manager = StreamSessionManager(
        engine,
        _Static(),
        chaos=StreamChaos(plan, manager=None),
        registry=MetricsRegistry(),
    )
    manager.chaos.manager = manager
    frames = make_frame_sequence(n_frames, 64, 0.1, seed=7)
    session = manager.open("drill", height=64, width=64)
    t_start = time.perf_counter()
    wrong_bytes = 0
    answered = 0
    reset_frame = None
    per_frame = []
    for fi, frame in enumerate(frames):
        result = session.process_frame(frame)
        manager.record(result)
        answered += 1
        ref = engine.predict_tiled(variables, frame)
        identical = result.probs.tobytes() == ref.tobytes()
        if not identical:
            wrong_bytes += 1
        if fi == reset_at:
            reset_frame = {
                "frame": fi,
                "full_rerun": result.full_rerun,
                "tiles_computed": result.tiles_computed,
                "tiles_total": result.tiles_total,
                "cache_hits": result.cache_hits,
            }
        per_frame.append(
            {
                "frame": fi,
                "hits": result.cache_hits,
                "computed": result.tiles_computed,
                "identical": identical,
            }
        )
    manager.close("drill")
    fired = [f.kind for f in plan.triggered]
    stats = manager.stats()
    return {
        "frames": n_frames,
        "reset_at": reset_at,
        "fault_fired": SERVE_STREAM_RESET in fired,
        "resets_recorded": session.totals["resets"],
        "answered": answered,
        "dropped": n_frames - answered,
        "zero_dropped": answered == n_frames,
        "wrong_bytes": wrong_bytes,
        "zero_wrong_bytes": wrong_bytes == 0,
        "reset_frame": reset_frame,
        "reset_was_full_rerun": bool(
            reset_frame
            and reset_frame["full_rerun"]
            and reset_frame["tiles_computed"] == reset_frame["tiles_total"]
        ),
        "per_frame": per_frame,
        "hit_ratio": stats["hit_ratio"],
        "effective_speedup": stats["effective_speedup"],
        "drill_s": round(time.perf_counter() - t_start, 3),
    }


def run_scaled_update_drill() -> dict:
    """SCALED_UPDATE drill (round 18, Blanchard et al.'s threat model): an
    adversarially AMPLIFIED update — the client's real trained weights
    scaled by a large finite factor, shape-correct and fully finite — is
    ACCEPTED by sanitation and averaged into the global. The drill pins the
    two-layer detection story the health plane exists for:

    Part 1 (ledger): a 3-client sync round where client c uploads its
    update poisoned by ``_poison_weights(..., SCALED_UPDATE)`` (scheduled
    and consumed through a chaos FaultPlan so the artifact proves it
    fired). The server ACCEPTS it — same status as the honest clients, c
    lands in the round's ``clients`` history — and FedAvg drags the global
    by orders of magnitude; but the flush-time robust z-score in
    ``state.ledger`` flags c past ANOMALY_ALERT while the honest clients
    stay well below.

    Part 2 (canary → watchdog): a tiny ResUNet serve stack evaluates the
    canary reference on the boot weights, then hot-swaps in the dragged
    global (the boot weights scaled by the same FedAvg drag factor part 1
    produced). The pinned-probe IoU cliffs, the armed
    ``configs/slo_health.json`` rules breach on BOTH signals (canary IoU
    floor + anomaly ceiling over part 1's exported ledger), the flight
    ring dumps, and the artifact records the ``BREACH_EXIT`` (3) contract.
    """
    import jax

    from fedcrack_tpu.chaos import inject
    from fedcrack_tpu.chaos.inject import _poison_weights
    from fedcrack_tpu.chaos.plan import SCALED_UPDATE, Fault, FaultPlan
    from fedcrack_tpu.configs import ModelConfig, ServeConfig
    from fedcrack_tpu.health import ledger as health_ledger
    from fedcrack_tpu.health.canary import CanaryEvaluator
    from fedcrack_tpu.models.resunet import init_variables
    from fedcrack_tpu.obs import flight
    from fedcrack_tpu.obs.registry import MetricsRegistry
    from fedcrack_tpu.obs.watchdog import BREACH_EXIT, Watchdog, load_rules
    from fedcrack_tpu.serve.engine import InferenceEngine, watch_recompiles
    from fedcrack_tpu.serve.hot_swap import ModelVersionManager
    from fedcrack_tpu.transport import transport_pb2 as pb
    from fedcrack_tpu.transport.service import FedServer, ServerThread

    # ---- part 1: sanitation accepts, the ledger flags ----
    plan = FaultPlan([Fault(kind=SCALED_UPDATE, round=1, client="c")])
    cfg = FedConfig(
        max_rounds=1,
        cohort_size=3,
        registration_window_s=5.0,
        round_deadline_s=60.0,
        port=0,
    )
    server = FedServer(cfg, _vars(0.0), tick_period_s=0.02)
    t0 = time.perf_counter()
    with ServerThread(server) as st:
        channel, call = _raw_caller(st.port)
        for c in ("a", "b", "c"):
            assert call(_ready(c)).status == R.SW
        # The poisoned upload lands FIRST: its accept status (RESP_ACY)
        # cannot be confused with a round-closing reply.
        fault = plan.take(SCALED_UPDATE, client="c", round=1)
        assert fault is not None
        poisoned = _poison_weights(tree_to_bytes(_vars(1.1)), SCALED_UPDATE)
        msg = pb.ClientMessage(cname="c")
        msg.done.round = 1
        msg.done.weights = poisoned
        msg.done.sample_count = 10
        rep_c = call(msg)
        rep_a = call(_done("a", 1, 1.0, 10))
        rep_b = call(_done("b", 1, 1.2, 10))
        channel.close()
        state = st.state
    entry = state.history[0] if state.history else {}
    # Equal sample counts: the dragged global is the plain mean
    # (1.0 + 1.2 + 1.1 * SCALE_FACTOR) / 3.
    got_avg = float(
        np.mean(tree_from_bytes(rep_b.weights)["params"]["w"])
    )
    drag = (1.0 + 1.2 + 1.1 * inject.SCALE_FACTOR) / 3.0
    scores = {
        name: state.ledger.get(name, {}).get("anomaly", 0.0)
        for name in ("a", "b", "c")
    }
    ledger_part = {
        "fault_fired": fault.kind,
        "poisoned_accepted": rep_c.status == R.RESP_ACY,
        "honest_accepted": rep_a.status == R.RESP_ACY
        and rep_b.status in (R.RESP_ARY, R.FIN),
        "poisoned_in_history_clients": entry.get("clients") == ["a", "b", "c"],
        "nothing_rejected": entry.get("rejected", {}) == {},
        "global_dragged_avg": round(got_avg, 4),
        "global_drag_matches_fedavg": bool(
            np.isclose(got_avg, drag, rtol=1e-5)
        ),
        "anomaly_scores": {k: round(v, 3) for k, v in scores.items()},
        "alert_threshold": health_ledger.ANOMALY_ALERT,
        "poisoned_flagged": scores["c"] >= health_ledger.ANOMALY_ALERT,
        "honest_below_alert": max(scores["a"], scores["b"])
        < health_ledger.ANOMALY_ALERT,
        "flagged_flushes": state.ledger.get("c", {}).get("flags", 0),
        "round_s": round(time.perf_counter() - t0, 4),
    }

    # ---- part 2: the dragged global cliffs the canary; watchdog fires ----
    model_config = ModelConfig(
        img_size=16, stem_features=4, encoder_features=(8,), decoder_features=(8, 4)
    )
    serve_config = ServeConfig(
        bucket_sizes=(16,), max_batch=4, max_delay_ms=30.0, tile_overlap=4
    )
    v0 = init_variables(jax.random.key(0), model_config)
    # The serving-side view of part 1's FedAvg: every float leaf dragged by
    # the same mean-of-(1, 1, SCALE_FACTOR) factor a x1000 client lands on
    # a 3-cohort — finite and shape-correct, so the swap path installs it.
    leaf_drag = (1.0 + 1.0 + inject.SCALE_FACTOR) / 3.0
    v_poisoned = jax.tree_util.tree_map(
        lambda a: a * np.asarray(leaf_drag, np.asarray(a).dtype)
        if np.asarray(a).dtype.kind == "f"
        else a,
        v0,
    )
    reg = MetricsRegistry()
    engine = InferenceEngine(model_config, serve_config)
    canary = CanaryEvaluator(engine, registry=reg)
    manager = ModelVersionManager(
        engine, v0, initial_version=0, canary=canary
    )
    engine.warmup(manager.snapshot()[1])
    sentry = watch_recompiles(engine, registry=reg)
    ref = canary.evaluate(0, manager.snapshot()[1])
    installed = manager.install(1, v_poisoned)
    assert installed and canary.last is not None
    post = canary.last
    recompiles = (
        sum(sentry.deltas().values())
        if type(sentry).supported(engine._fn)
        else -1
    )

    # The armed health rules over ONE registry holding both signals: part
    # 1's exported ledger anomaly gauges + the canary IoU time-series.
    health_ledger.export_anomaly_metrics(state.ledger, registry=reg)
    rules_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        os.pardir, os.pardir, "configs", "slo_health.json",
    )
    ring = flight.current()
    installed_ring = False
    if ring is None:  # direct invocation (tests); main() arms its own
        ring = flight.install(path=os.path.join(
            tempfile.gettempdir(), "scaled_update_drill.flight.json"
        ), hooks=False)
        installed_ring = True
    try:
        dumps_before = len(ring.dumps)
        watchdog = Watchdog(load_rules(rules_path), registry=reg)
        report = watchdog.enforce()
        audit = watchdog.audit()
        dumped = ring.dumps[dumps_before:]
    finally:
        if installed_ring:
            flight.uninstall()
    breached_rules = sorted({b["rule"] for b in report["breaches"]})
    return {
        "ledger": ledger_part,
        "canary": {
            "reference_iou": ref["iou"],
            "poisoned_iou": post["iou"],
            "iou_cliff": post["iou"] < 0.5 <= ref["iou"],
            "swap_still_installed": installed,
            "recompiles_since_warmup": recompiles,
        },
        "watchdog": {
            "rules": audit["rules"],
            "breached": breached_rules,
            "both_signals_breached": breached_rules
            == ["canary_iou_floor", "client_anomaly_ceiling"],
            "flight_dumped": bool(dumped),
            "flight_dump_reason": dumped[0]["reason"] if dumped else None,
            "breach_exit_code": BREACH_EXIT,
            "would_exit": BREACH_EXIT if audit["breaches"] else 0,
        },
    }


def run_robust_aggregation_drill() -> dict:
    """Robust-aggregation A/B drill (round 21): the r18 SCALED_UPDATE
    scenario re-run as FOUR arms over real gRPC — identical cohort
    (a=1.0, b=1.2, c's 1.1 update amplified x``SCALE_FACTOR`` through a
    consumed chaos FaultPlan), identical wire path, the ONLY delta being
    ``FedConfig.aggregation``/``quarantine_z``:

    - ``fedavg``       — the r18 baseline; the global drags by ~x300.
    - ``trimmed_mean`` — beta=0.34 trims one value per coordinate end;
      the x1000 coordinate is the trimmed tail, drag collapses to the
      honest spread.
    - ``krum``         — f=1; the poisoned vector's pairwise distance is
      astronomical, an HONEST update is selected verbatim.
    - ``fedavg_quarantine`` — null combine, ``quarantine_z=3.5``: the
      flush-time robust z-score (the r18 *detection*) now feeds the fold's
      exclusion gate (the r21 *response*). The poisoned client lands LAST
      so it triggers the flush — and gets the direct ``NOT_WAIT`` resync
      reply (the EF-rollback contract) instead of an ``RESP_ARY`` that
      would claim its update was averaged.

    Each arm's serve-side story rides one shared tiny-ResUNet engine: the
    canary reference is evaluated once on the boot weights, then every
    arm installs the boot weights scaled by THAT arm's combine applied to
    an honest/honest/x``SCALE_FACTOR`` cohort (the exact part-2 framing
    of the r18 drill). FedAvg cliffs the IoU; every robust arm holds it.

    A colluding-minority variant re-runs the fed plane with 7 clients —
    5 honest, 2 colluders shipping the IDENTICAL amplified update (the
    worst case for Krum's min-distance score; n=7 >= 2f+3 keeps the
    selection sound) — across fedavg / trimmed_mean / krum / multi_krum /
    quarantine, and the quarantine arm's ledger round-trips through
    ``tools/health_report`` to prove the exclusion is visible there too.
    """
    import jax

    from fedcrack_tpu.chaos import inject
    from fedcrack_tpu.chaos.inject import _poison_weights
    from fedcrack_tpu.chaos.plan import SCALED_UPDATE, Fault, FaultPlan
    from fedcrack_tpu.configs import ModelConfig, ServeConfig
    from fedcrack_tpu.fed import aggregation as A
    from fedcrack_tpu.health import ledger as health_ledger
    from fedcrack_tpu.health.canary import CanaryEvaluator
    from fedcrack_tpu.models.resunet import init_variables
    from fedcrack_tpu.obs.registry import MetricsRegistry
    from fedcrack_tpu.serve.engine import InferenceEngine
    from fedcrack_tpu.serve.hot_swap import ModelVersionManager
    from fedcrack_tpu.tools.health_report import build_report, validate_report
    from fedcrack_tpu.transport import transport_pb2 as pb
    from fedcrack_tpu.transport.service import FedServer, ServerThread

    t0 = time.perf_counter()

    def run_arm(clients, poisoned_names, poison_value, order, **agg_kwargs):
        """One real-gRPC round: ``clients`` is {name: (value, ns)};
        updates land in ``order`` (last one closes the barrier); every
        name in ``poisoned_names`` ships its update through
        ``_poison_weights(..., SCALED_UPDATE)``, scheduled and consumed
        via a FaultPlan so the artifact proves the faults fired."""
        plan = FaultPlan(
            [Fault(kind=SCALED_UPDATE, round=1, client=n)
             for n in sorted(poisoned_names)]
        )
        cfg = FedConfig(
            max_rounds=1,
            cohort_size=len(clients),
            registration_window_s=5.0,
            round_deadline_s=60.0,
            port=0,
            **agg_kwargs,
        )
        server = FedServer(cfg, _vars(0.0), tick_period_s=0.02)
        replies = {}
        with ServerThread(server) as st:
            channel, call = _raw_caller(st.port)
            for c in order:
                assert call(_ready(c)).status == R.SW
            for c in order:
                value, ns = clients[c]
                if c in poisoned_names:
                    fault = plan.take(SCALED_UPDATE, client=c, round=1)
                    assert fault is not None
                    msg = pb.ClientMessage(cname=c)
                    msg.done.round = 1
                    msg.done.weights = _poison_weights(
                        tree_to_bytes(_vars(value)), SCALED_UPDATE
                    )
                    msg.done.sample_count = ns
                else:
                    msg = _done(c, 1, value, ns)
                replies[c] = call(msg)
            channel.close()
            state = st.state
        closer = replies[order[-1]]
        # The round-closing reply carries the aggregated global UNLESS the
        # closer was quarantined (NOT_WAIT resync); read the broadcast then.
        blob = closer.weights if closer.weights else state.broadcast_blob
        got_avg = float(np.mean(tree_from_bytes(blob)["params"]["w"]))
        entry = state.history[0] if state.history else {}
        return {
            "state": state,
            "entry": entry,
            "replies": replies,
            "global_avg": got_avg,
        }

    # ---- part 1: the 4-arm A/B (3 clients, 1 poisoned) ----
    clients3 = {"a": (1.0, 10), "b": (1.2, 10), "c": (1.1, 10)}
    honest_mean = (1.0 * 10 + 1.2 * 10) / 20.0  # what a,b alone average to
    arm_specs = {
        # r18 ordering (poisoned first) for the combine arms; the
        # quarantine arm puts the poisoned client LAST so the NOT_WAIT
        # direct-reply resync contract is exercised on the wire.
        "fedavg": dict(order=("c", "a", "b")),
        "trimmed_mean": dict(
            order=("c", "a", "b"), aggregation="trimmed_mean",
            trim_fraction=0.34,
        ),
        "krum": dict(
            order=("c", "a", "b"), aggregation="krum", byzantine_f=1,
        ),
        "fedavg_quarantine": dict(
            order=("a", "b", "c"), quarantine_z=3.5,
        ),
    }
    arms = {}
    raw = {}
    for name, spec in arm_specs.items():
        spec = dict(spec)
        order = spec.pop("order")
        r = run_arm(clients3, {"c"}, 1.1, order, **spec)
        raw[name] = r
        drag = abs(r["global_avg"] - honest_mean)
        arms[name] = {
            "aggregation": spec.get("aggregation", "fedavg"),
            "quarantine_z": spec.get("quarantine_z", 0.0),
            "global_avg": round(r["global_avg"], 4),
            "drag": round(drag, 4),
            "quarantined": {
                k: round(v, 3)
                for k, v in r["entry"].get("quarantined", {}).items()
            },
        }
    fedavg_drag = abs(raw["fedavg"]["global_avg"] - honest_mean)
    for name in ("trimmed_mean", "krum", "fedavg_quarantine"):
        d = abs(raw[name]["global_avg"] - honest_mean)
        arms[name]["drag_reduction_vs_fedavg"] = round(
            fedavg_drag / max(d, 1e-9), 1
        )
    q = raw["fedavg_quarantine"]
    arms["fedavg_quarantine"].update({
        # The poisoned closer is excluded AND resynced: NOT_WAIT with the
        # clean global attached (fires the client-side topk EF rollback).
        "poisoned_reply": q["replies"]["c"].status,
        "poisoned_resynced_not_wait": q["replies"]["c"].status == R.NOT_WAIT,
        "clean_global_attached": bool(q["replies"]["c"].weights),
        "ledger_quarantined_count": q["state"].ledger.get("c", {}).get(
            "quarantined", 0
        ),
        "honest_not_quarantined": all(
            q["state"].ledger.get(n, {}).get("quarantined", 0) == 0
            for n in ("a", "b")
        ),
    })

    # ---- part 2: per-arm canary over ONE shared tiny serve stack ----
    # The serving-side view of each arm: the boot weights scaled by the
    # arm's combine applied to an honest/honest/xSCALE cohort — the exact
    # r18 part-2 framing ((1 + 1 + SCALE)/3 for FedAvg), now computed
    # THROUGH the real algebra per arm instead of hard-coded for FedAvg.
    def arm_factor(algebra):
        triples = [
            ("a", 10, {"w": np.float32([1.0])}),
            ("b", 10, {"w": np.float32([1.0])}),
            ("c", 10, {"w": np.float32([1.0 * inject.SCALE_FACTOR])}),
        ]
        return float(A.fold(algebra, triples)["w"][0])

    factors = {
        "fedavg": arm_factor(A.FedAvg()),
        "trimmed_mean": arm_factor(A.TrimmedMean(0.34)),
        "krum": arm_factor(A.Krum(1)),
        # Quarantine excludes c before the fold (part 1 proved that over
        # the wire); the serving factor is the honest mean: 1.0 exactly.
        "fedavg_quarantine": 1.0,
    }
    model_config = ModelConfig(
        img_size=16, stem_features=4, encoder_features=(8,),
        decoder_features=(8, 4),
    )
    serve_config = ServeConfig(
        bucket_sizes=(16,), max_batch=4, max_delay_ms=30.0, tile_overlap=4
    )
    v0 = init_variables(jax.random.key(0), model_config)
    reg = MetricsRegistry()
    engine = InferenceEngine(model_config, serve_config)
    canary = CanaryEvaluator(engine, registry=reg)
    manager = ModelVersionManager(engine, v0, initial_version=0, canary=canary)
    engine.warmup(manager.snapshot()[1])
    ref = canary.evaluate(0, manager.snapshot()[1])
    for version, name in enumerate(arms, start=1):
        factor = factors[name]
        v_arm = jax.tree_util.tree_map(
            lambda a: a * np.asarray(factor, np.asarray(a).dtype)
            if np.asarray(a).dtype.kind == "f"
            else a,
            v0,
        )
        installed = manager.install(version, v_arm)
        assert installed and canary.last is not None
        arms[name]["canary_iou"] = round(float(canary.last["iou"]), 6)
        arms[name]["serve_factor"] = round(factor, 4)

    # ---- part 3: colluding minority (7 clients, 2 identical colluders) ----
    honest7 = {
        "h1": (1.0, 10), "h2": (1.05, 10), "h3": (1.1, 10),
        "h4": (1.15, 10), "h5": (1.2, 10),
    }
    clients7 = dict(honest7, p1=(1.1, 10), p2=(1.1, 10))
    order7 = ("p1", "p2", "h1", "h2", "h3", "h4", "h5")
    honest_mean7 = sum(v for v, _ in honest7.values()) / len(honest7)
    colluding_specs = {
        "fedavg": {},
        # floor(0.3 * 7) = 2 trimmed per coordinate end: both colluders.
        "trimmed_mean": dict(aggregation="trimmed_mean", trim_fraction=0.3),
        "krum": dict(aggregation="krum", byzantine_f=2),
        "multi_krum": dict(aggregation="multi_krum", byzantine_f=2),
        "fedavg_quarantine": dict(quarantine_z=3.5),
    }
    colluding = {}
    q7_state = None
    for name, spec in colluding_specs.items():
        r = run_arm(clients7, {"p1", "p2"}, 1.1, order7, **spec)
        d = abs(r["global_avg"] - honest_mean7)
        colluding[name] = {
            "global_avg": round(r["global_avg"], 4),
            "drag": round(d, 4),
            "quarantined": sorted(r["entry"].get("quarantined", {})),
        }
        if name == "fedavg_quarantine":
            q7_state = r["state"]
    fedavg_drag7 = colluding["fedavg"]["drag"]
    colluders_beaten = {
        name: bool(colluding[name]["drag"] <= 0.25)
        for name in colluding if name != "fedavg"
    }

    # ---- part 4: the exclusion is visible in the joined health report ----
    with tempfile.TemporaryDirectory() as tmp:
        ledger_path = os.path.join(tmp, "ledger.jsonl")
        health_ledger.write_ledger_jsonl(q7_state.ledger, ledger_path)
        report = build_report(ledger_path)
        violations = validate_report(report)
    health_part = {
        "schema_violations": violations,
        "quarantines": report["summary"]["quarantines"],
        "quarantined_clients": report["summary"]["quarantined_clients"],
        "exclusion_visible": report["summary"]["quarantined_clients"]
        == ["p1", "p2"],
    }

    robust_arm_names = ("trimmed_mean", "krum", "fedavg_quarantine")
    return {
        "scale_factor": inject.SCALE_FACTOR,
        "honest_mean": honest_mean,
        "reference_iou": round(float(ref["iou"]), 6),
        "arms": arms,
        "fedavg_cliffed": arms["fedavg"]["canary_iou"] < 0.5,
        "robust_arms_hold": all(
            arms[n]["canary_iou"] >= 0.9 for n in robust_arm_names
        ),
        "drag_reduced_10x": all(
            arms[n]["drag_reduction_vs_fedavg"] >= 10.0
            for n in robust_arm_names
        ),
        "colluding": {
            "n_clients": len(clients7),
            "colluders": ["p1", "p2"],
            "honest_mean": honest_mean7,
            "fedavg_drag": fedavg_drag7,
            "arms": colluding,
            "colluders_beaten": colluders_beaten,
        },
        "health_report": health_part,
        "drill_s": round(time.perf_counter() - t0, 4),
    }


def run_secagg_dropout_drill() -> dict:
    """SECAGG_DROPOUT drill (round 23, privacy plane): a masker dies in the
    Bonawitz recovery window — AFTER its seed froze into the masking roster
    (survivors' uploads carry uncancelled pairwise masks against it) and
    BEFORE its own masked upload — over REAL gRPC. The round must still
    close at quorum via seed recovery, and the unmasked cohort average must
    equal the plaintext weighted fixed-point mean of the SURVIVORS
    bit-for-bit: modular integer cancellation, not float-tolerance.

    3 FedClient sessions, `c` injected with a chaos-plan SECAGG_DROPOUT
    (consumed through the plan so the artifact proves the drop fired).
    The survivors' trainers add known constants, so the expected average
    is closed-form; the pin runs in the fixed-point residue domain AND on
    the decoded float blob the survivors pulled as the new global.
    """
    import threading

    from fedcrack_tpu.chaos.inject import ClientChaos, InjectedCrash
    from fedcrack_tpu.chaos.plan import SECAGG_DROPOUT, Fault, FaultPlan
    from fedcrack_tpu.privacy.secagg import (
        fixed_point_decode,
        weighted_fixed_sum,
    )
    from fedcrack_tpu.transport.client import FedClient
    from fedcrack_tpu.transport.service import FedServer, ServerThread

    def fake_train(inc: float, ns: int):
        def train_fn(blob: bytes, rnd: int):
            tree = tree_from_bytes(blob)
            tree["params"]["w"] = tree["params"]["w"] + np.float32(inc)
            return tree_to_bytes(tree), ns, {"loss": float(rnd)}

        return train_fn

    cfg = FedConfig(
        max_rounds=1,
        cohort_size=3,
        registration_window_s=5.0,
        round_deadline_s=2.0,
        quorum_fraction=0.67,
        poll_period_s=0.05,
        secagg=True,
        port=0,
    )
    plan = FaultPlan([Fault(kind=SECAGG_DROPOUT, round=1, client="c")])
    server = FedServer(cfg, _vars(0.0), tick_period_s=0.05)
    t0 = time.perf_counter()
    errors: dict[str, BaseException] = {}
    results: dict[str, object] = {}

    def run(client: FedClient, name: str) -> None:
        try:
            results[name] = client.run_session()
        except InjectedCrash as e:
            errors[name] = e

    with ServerThread(server) as st:
        clients = {
            "a": FedClient(cfg, fake_train(1.0, 10), cname="a", port=st.port),
            "b": FedClient(cfg, fake_train(3.0, 30), cname="b", port=st.port),
            "c": FedClient(
                cfg,
                fake_train(5.0, 20),
                cname="c",
                port=st.port,
                chaos=ClientChaos(plan),
            ),
        }
        threads = [
            threading.Thread(target=run, args=(cl, n))
            for n, cl in clients.items()
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        state = st.state

    entry = state.history[0] if state.history else {}
    secagg_info = entry.get("secagg") or {}
    # The drill's pin: the unmasked fixed-point sum of the fold equals the
    # PLAINTEXT weighted sum of the survivors — recover it from the global
    # blob by re-encoding the closed-form expectation through the same
    # fixed-point path (bit-for-bit on the decoded float leaves).
    surv_updates = [_vars(1.0), _vars(3.0)]
    surv_ns = [10, 30]
    want = fixed_point_decode(
        weighted_fixed_sum(surv_updates, surv_ns, cfg.secagg_bits),
        sum(surv_ns),
        cfg.secagg_bits,
        _vars(0.0),
    )
    got = tree_from_bytes(state.global_blob)
    exact = bool(
        np.array_equal(got["params"]["w"], want["params"]["w"])
    )
    return {
        "fault_fired": [f.kind for f in plan.triggered] == [SECAGG_DROPOUT],
        "dropper_crashed": "c" in errors and "c" not in results,
        "survivors_completed": all(
            n in results and results[n].rounds_completed == 1
            for n in ("a", "b")
        ),
        "round_closed": state.phase == R.PHASE_FINISHED
        and len(state.history) == 1,
        "maskers": secagg_info.get("maskers"),
        "recovered": secagg_info.get("recovered"),
        "dropout_recovered": secagg_info.get("recovered") == ["c"],
        "exact_average_bit_for_bit": exact,
        "torn_rounds": int(state.failed_rounds),
        "drill_s": round(time.perf_counter() - t0, 4),
    }


def run_dp_replay_drill() -> dict:
    """DP replay drill (round 23): a mesh round with the DP-SGD twin on
    (clip + seeded Gaussian noise) is killed by an injected device failure
    and retried under ``max_round_retries`` — the retried trajectory must
    be BIT-IDENTICAL to an uninterrupted run. The noise key chain's round
    axis is the same replicated per-dispatch seed scalar the r12 codec
    threads, restored on replay via ``codec_state()``; this drill is the
    proof that a chaos-retried DP round never double-draws its noise."""
    import jax

    from fedcrack_tpu.chaos.inject import MeshChaos
    from fedcrack_tpu.chaos.plan import MESH_DEVICE_FAIL, Fault, FaultPlan
    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.data.synthetic import synth_crack_batch
    from fedcrack_tpu.parallel import make_mesh, run_mesh_federation
    from fedcrack_tpu.parallel.fedavg_mesh import (
        build_federated_round,
        stack_client_data,
    )
    from fedcrack_tpu.train.local import create_train_state

    tiny = ModelConfig(
        img_size=16, stem_features=4, encoder_features=(8,),
        decoder_features=(8, 4),
    )
    steps, batch = 2, 2
    mesh = make_mesh(1, 1)
    t0 = time.perf_counter()

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

    def build():
        return build_federated_round(
            mesh, tiny, learning_rate=1e-3, local_epochs=1,
            dp_clip_norm=1.0, dp_noise_multiplier=1.1, dp_seed=42,
        )

    init = create_train_state(jax.random.key(0), tiny).variables
    v_clean, _ = run_mesh_federation(build(), init, data_fn, 2, mesh)

    plan = FaultPlan([Fault(kind=MESH_DEVICE_FAIL, round=0)])
    v_chaos, records = run_mesh_federation(
        build(), init, data_fn, 2, mesh,
        max_round_retries=1, fault_injector=MeshChaos(plan),
    )
    identical = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree_util.tree_leaves(v_clean),
            jax.tree_util.tree_leaves(v_chaos),
        )
    )
    return {
        "fault_fired": not plan.pending and len(plan.triggered) == 1,
        "retries_round_0": int(records[0].retries),
        "replay_bit_identical": bool(identical),
        "rounds": len(records),
        "drill_s": round(time.perf_counter() - t0, 4),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--out", required=True)
    p.add_argument("--rounds", type=int, default=3)
    args = p.parse_args(argv)
    # Flight recorder (round 16): the drills feed the ring for free (fault
    # injections via FaultPlan.take, fed-plane transitions, spans); a drill
    # that dies ships its last-N-seconds history next to the traceback
    # instead of just final counters.
    from fedcrack_tpu.obs import flight

    flight_path = os.path.abspath(f"{args.out}.flight.json")
    flight.install(path=flight_path)
    try:
        artifact = {
            "generated_by": "fedcrack_tpu.tools.chaos_drill",
            "kill_restart": run_kill_restart_drill(rounds=args.rounds),
            "corrupt_frame": run_corrupt_frame_drill(),
            "edge_crash": run_edge_crash_drill(),
            "straggler_storm": run_straggler_storm_drill(),
            "buffered_kill": run_buffered_kill_drill(),
            "replica_crash": run_replica_crash_drill(),
            "elastic_fleet": run_elastic_fleet_drill(),
            "scaled_update": run_scaled_update_drill(),
            "robust_aggregation": run_robust_aggregation_drill(),
            "stream_reset": run_stream_reset_drill(),
            "secagg_dropout": run_secagg_dropout_drill(),
            "dp_replay": run_dp_replay_drill(),
        }
    except BaseException:
        flight.dump("chaos drill failed")
        print(f"drill failed; flight record at {flight_path}", file=sys.stderr)
        raise
    finally:
        flight.uninstall()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
    print(json.dumps(artifact["kill_restart"]), flush=True)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
