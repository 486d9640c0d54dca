"""FedBuff-style buffered-asynchronous aggregation (round 14).

Everything before this round is barrier-synchronous: the gRPC round machine
(:mod:`fedcrack_tpu.fed.rounds`), the mesh drivers, and the r13 cohort/tree
tiers all close a round only when K-of-N updates are in — so one straggler
stalls the whole federation, exactly the failure mode the reference's
single-stream FedAvg server inherits. FedBuff (Nguyen et al., 2022) removes
the barrier server-side: updates are accepted AS THEY ARRIVE, weighted by a
polynomial staleness decay (FedAsync, Xie et al., 2019), folded into a
K-sized buffer, and flushed to a new global version at K. Clients loop
pull→train→push continuously; a slow client's update lands late, stale and
down-weighted — never blocking.

This module is that server: the :class:`BufferedAggregator` state machine,
a pure alternative to the round barrier in ``fed/rounds.py`` operating on
the SAME immutable :class:`~fedcrack_tpu.fed.rounds.ServerState`
(``rounds.transition`` dispatches ``PullWeights``/``TrainDone`` here when
``FedConfig.mode == "buffered"``). Everything composes with the machinery
already in the tree:

- every accepted update passes the one shared acceptance gate
  (``rounds.decode_and_validate_update``), decoded against the base the
  client ACTUALLY pulled — the server tracks per-client pulled versions and
  retains a ``max_staleness``-bounded window of past broadcast blobs, so a
  stale framed delta reconstructs against the right base or is rejected;
- the flush is a SORTED fold (entries ordered by ``(cname, seq)``, the r13
  ordered-fold discipline): the flushed global is a pure function of the
  buffer CONTENTS, never of cross-client arrival order (fedlint ASYNC001
  pins this statically, tests pin it dynamically);
- buffer, per-client pulled versions and the retained base window persist
  in the r8 atomic statefile, so a server killed MID-BUFFER restarts with
  the already-accepted updates intact and flushes to the bit-identical
  next global version (drilled by ``tools/chaos_drill``);
- ``buffer_k = cohort_size`` with ``staleness_alpha = 0`` degenerates to
  sync FedAvg BIT-exactly: weight ``ns * (1+s)^0 == ns`` as the same float,
  the sorted fold is the same ``fedavg`` call over the same decoded trees,
  and the FedOpt server step is the shared ``rounds.apply_fedopt``.

Observability: each flush appends a history entry carrying
``updates_per_sec``, ``buffer_fill``, the per-update ``staleness`` list and
``global_version``; :func:`async_summary` reduces a history to staleness
percentiles through :class:`fedcrack_tpu.obs.metrics.StreamingPercentiles`
for the chaos drills and the soak.
"""

from __future__ import annotations

import jax

from fedcrack_tpu.fed import aggregation as _aggregation
from fedcrack_tpu.fed import rounds as R
from fedcrack_tpu.fed.serialization import tree_from_bytes, tree_to_bytes
from fedcrack_tpu.health import ledger as _health_ledger

MODE_SYNC = "sync"
MODE_BUFFERED = "buffered"


def staleness_weight(staleness: int, alpha: float) -> float:
    """The FedAsync polynomial decay ``(1 + staleness)^-alpha``.

    Closed form, exact at the edges (test-pinned): ``alpha == 0`` yields
    exactly ``1.0`` for EVERY staleness (Python float ``x ** -0.0 == 1.0``),
    which is what makes the sync-FedAvg degeneration bit-exact — the
    effective FedAvg weight ``ns * 1.0`` is the same float as ``ns``.
    """
    if staleness < 0:
        raise ValueError(f"staleness must be >= 0, got {staleness}")
    if alpha < 0.0:
        raise ValueError(f"staleness alpha must be >= 0, got {alpha}")
    return float((1.0 + float(staleness)) ** (-float(alpha)))


def _entry_sort_key(entry: dict) -> tuple:
    """The sorted-flush order: ``(cname, seq)``. ``seq`` is the entry's
    per-CLIENT arrival index within the current buffer — a client's own
    uploads are ordered by its own session (deterministic), so the key is
    independent of how uploads from DIFFERENT clients interleaved."""
    return (entry["cname"], entry["seq"])


# The 9-field wire row for one buffer entry — ONE codec for every place a
# buffer crosses a serialization boundary (the server statefile, the edge
# statefile), so a field added to the entry is added in exactly one
# encode/decode pair instead of drifting across positional copies.
def buffer_entry_to_wire(e: dict) -> list:
    return [
        e["cname"], int(e["seq"]), e["blob"], int(e["ns"]),
        int(e["staleness"]), float(e["weight"]), int(e["base_version"]),
        int(e["wire_len"]), e["codec"],
    ]


def buffer_entry_from_wire(row) -> dict:
    return {
        "cname": str(row[0]),
        "seq": int(row[1]),
        "blob": bytes(row[2]),
        "ns": int(row[3]),
        "staleness": int(row[4]),
        "weight": float(row[5]),
        "base_version": int(row[6]),
        "wire_len": int(row[7]),
        "codec": str(row[8]),
    }


def decode_buffer(buffer, template) -> tuple:
    """The decode half of the buffered fold: entries sorted by ``(cname,
    seq)``, decoded against ``template``. Returns ``(entries_sorted,
    counts, eff, trees)`` aligned lists — split from the combine (round
    21) so the root flush can ledger-score the decoded trees BEFORE
    folding and quarantine flagged entries out of the triples."""
    if not buffer:
        raise RuntimeError("fold of an empty buffer")
    entries = sorted(buffer, key=_entry_sort_key)
    trees = [tree_from_bytes(e["blob"], template=template) for e in entries]
    counts = [e["ns"] for e in entries]
    eff = [e["ns"] * e["weight"] for e in entries]
    return entries, counts, eff, trees


def fold_buffer(buffer, template) -> tuple:
    """THE staleness-weighted sorted fold, shared by the root flush and
    the edge tier's ``flush_partial`` (one fold, all tiers — the same
    discipline as ``decode_and_validate_update``): entries sorted by
    ``(cname, seq)``, decoded against ``template``, combined through the
    aggregation algebra's null instance (fed/aggregation.py) with
    effective weight ``ns * staleness_weight``. Returns ``(avg_tree,
    entries_sorted, counts, eff, trees)`` — ``eff`` and ``trees`` aligned
    with ``entries_sorted`` (the decoded trees, so the flush-time health
    scoring reuses this decode instead of paying a second one); the
    average is unweighted when every effective weight is zero (mirroring
    the sync barrier — ``eff[i] > 0`` iff ``ns[i] > 0``, the staleness
    decay being strictly positive)."""
    entries, counts, eff, trees = decode_buffer(buffer, template)
    triples = [
        (e["cname"], w, t) for e, w, t in zip(entries, eff, trees)
    ]
    avg = _aggregation.fold(_aggregation.FedAvg(), triples)
    return avg, entries, counts, eff, trees


# Decoded-base memo for the accept path: version -> (blob, tree). Every
# framed upload decodes its delta against a retained base; without the
# memo the single-writer transition pays a full-model decode PER PUSH on
# the continuous-loop hot path (the exact cost rounds._decoded_round_base
# exists to kill on the sync plane). Keyed by version AND the blob bytes
# (identity fast-path, equality fallback) so two servers sharing the
# process-wide memo at worst thrash and re-decode — correctness is
# carried by the key, never by which server wrote the entry. Pruned to
# the caller's retained window on every miss.
_BASE_TREE_MEMO: dict = {}


def _decoded_base(state: "R.ServerState", version: int, blob: bytes):
    hit = _BASE_TREE_MEMO.get(version)
    if hit is not None and (hit[0] is blob or hit[0] == blob):
        return hit[1]
    tree = tree_from_bytes(blob, template=state.template)
    _BASE_TREE_MEMO[version] = (blob, tree)
    for v in sorted(_BASE_TREE_MEMO):
        if v not in state.base_blobs:
            del _BASE_TREE_MEMO[v]
    return tree


class BufferedAggregator:
    """The buffered-mode event handlers, as pure transitions over
    :class:`~fedcrack_tpu.fed.rounds.ServerState` — same single-writer
    contract as ``rounds.transition`` (which is the only caller).

    State layout (all on ``ServerState``, all statefile-persisted):

    - ``pulled``: cname -> the model_version that client last pulled (the
      base its next update is trained on — framed deltas are pinned to it).
    - ``buffer``: the accepted-but-unflushed updates, each a dict of
      ``{cname, seq, blob (decoded full tree), ns, staleness, weight,
      base_version, wire_len, codec}``.
    - ``base_blobs``: version -> broadcast blob, retained for the last
      ``max_staleness`` versions so stale framed deltas can reconstruct.
    """

    # -- pull tracking --

    @staticmethod
    def record_pull(state: R.ServerState, cname: str) -> R.ServerState:
        """A client pulled the current global: remember which version it now
        holds — the base its next upload decodes against and the anchor of
        its staleness."""
        pulled = dict(state.pulled)
        pulled[cname] = state.model_version
        return state._replace(pulled=pulled)

    # -- the accept path --

    @staticmethod
    def offer(
        state: R.ServerState, event: R.TrainDone
    ) -> tuple[R.ServerState, R.Reply]:
        """One client upload, buffered-mode. Decodes against the base the
        client actually pulled, staleness-gates, staleness-weights, folds
        into the buffer, and flushes at ``buffer_k``. Sanitation failures
        are REJECTED (fail loudly, like sync); too-stale or base-less
        updates are recorded to the history's ``rejected`` map and the
        sender is RE-SYNCED with the current global (``NOT_WAIT`` — the
        sync straggler treatment: tolerated by the aggregator, averaged
        never)."""
        cname, ns, now = event.cname, event.num_samples, event.now
        if cname not in state.cohort:
            if cname in state.ledger:
                state = state._replace(
                    ledger=_health_ledger.record_offer(
                        state.ledger, cname, outcome="rejected",
                        reason_class="not_in_cohort",
                        round=state.current_round,
                    )
                )
            return state, R.Reply(
                status=R.REJECTED, config={"reason": "not in cohort"}
            )
        cfg = state.config
        base_version = state.pulled.get(cname)
        if base_version is None:
            # No recorded pull (client pushed before pulling, or the record
            # predates a server restart that lost no statefile but a client
            # raced it): there is no base to decode/staleness this update
            # against. Resync — the client pulls fresh and retrains.
            return BufferedAggregator._resync(
                state, cname, "no recorded base version (pull before push)"
            )
        staleness = state.model_version - int(base_version)
        if staleness > cfg.max_staleness:
            return BufferedAggregator._resync(
                state,
                cname,
                f"too stale: base version {base_version} is {staleness} "
                f"behind (max_staleness={cfg.max_staleness})",
                staleness=staleness,
            )
        base_blob = state.base_blobs.get(int(base_version))
        if base_blob is None:
            # Inside the staleness window but the base was not retained —
            # only possible across a config change or a pre-round-14
            # statefile. Same treatment as too-stale.
            return BufferedAggregator._resync(
                state, cname, f"base version {base_version} no longer retained"
            )
        blob, wire_len, codec_name, problem, norm = R.decode_and_validate_update(
            event.blob,
            ns,
            template=state.template,
            base_fn=lambda: _decoded_base(state, int(base_version), base_blob),
            base_version=int(base_version),
            sanitize=cfg.sanitize_updates,
        )
        if problem is not None:
            rejected = dict(state.rejected)
            rejected[cname] = problem
            state = state._replace(
                rejected=rejected,
                ledger=_health_ledger.record_offer(
                    state.ledger, cname, outcome="rejected",
                    reason_class="sanitation", num_samples=ns,
                    wire_len=wire_len, round=state.current_round,
                    staleness=staleness,
                ),
            )
            return state, R.Reply(
                status=R.REJECTED,
                config={"reason": f"update rejected: {problem}"},
            )
        seq = sum(1 for e in state.buffer if e["cname"] == cname)
        entry = {
            "cname": cname,
            "seq": seq,
            "blob": blob,
            "ns": int(ns),
            "staleness": int(staleness),
            "weight": staleness_weight(staleness, cfg.staleness_alpha),
            "base_version": int(base_version),
            "wire_len": int(wire_len),
            "codec": codec_name,
        }
        state = state._replace(
            buffer=state.buffer + (entry,),
            ledger=_health_ledger.record_offer(
                state.ledger, cname, outcome="accepted", num_samples=ns,
                wire_len=wire_len, round=state.current_round,
                staleness=staleness, norm=norm,
            ),
        )
        if (
            state.phase == R.PHASE_RUNNING
            and len(state.buffer) >= cfg.buffer_k
        ):
            state = BufferedAggregator.flush(state, now)
            # The reply carries the freshly flushed global: the sender now
            # holds the new version (recorded, so its next framed delta is
            # pinned to what it actually adopted).
            state = BufferedAggregator.record_pull(state, cname)
            if cname in state.history[-1]["quarantined"]:
                # The flush-triggering client was quarantined out of its
                # own flush: NOT_WAIT (the sanitation-reject treatment) so
                # the direct reply fires the client-side codec rollback —
                # a topk sender's error-feedback residual re-enters
                # instead of being dropped as "sent". Mirrors the sync
                # barrier's quarantined-trigger path.
                return state, R.Reply(
                    status=R.NOT_WAIT,
                    blob=state.broadcast_blob,
                    config=R._ready_config(state, R.NOT_WAIT),
                )
            status = R.FIN if state.phase == R.PHASE_FINISHED else R.RESP_ARY
            return state, R.Reply(
                status=status,
                blob=state.broadcast_blob,
                config=R._ready_config(state, status),
            )
        return state, R.Reply(
            status=R.RESP_ACY, config=R._ready_config(state, R.RESP_ACY)
        )

    @staticmethod
    def _resync(
        state: R.ServerState, cname: str, reason: str, staleness: int = 0
    ) -> tuple[R.ServerState, R.Reply]:
        """Record the refusal (observable forever, averaged never) and hand
        the sender the current global so it rejoins instead of dying."""
        rejected = dict(state.rejected)
        rejected[cname] = reason
        state = state._replace(
            rejected=rejected,
            ledger=_health_ledger.record_offer(
                state.ledger, cname, outcome="resync",
                round=state.current_round, staleness=staleness,
            ),
        )
        state = BufferedAggregator.record_pull(state, cname)
        return state, R.Reply(
            status=R.NOT_WAIT,
            blob=state.broadcast_blob,
            config=R._ready_config(state, R.NOT_WAIT),
        )

    # -- the flush --

    @staticmethod
    def flush(state: R.ServerState, now: float) -> R.ServerState:
        """Fold the buffer into a new global version.

        The fold is SORTED by ``(cname, seq)`` — arrival-order independent
        by construction (test-pinned: permuted arrival orders flush to
        byte-identical globals) — and each entry weighs
        ``num_samples * staleness_weight``. The buffer mean is then
        ANCHORED on the current global FedAsync-style: ``new = (1 - mix) *
        current + mix * buffer_mean`` with ``mix`` the sample-weighted
        MEAN staleness weight of the flush. Within-buffer weights set
        relative contributions; ``mix`` is what keeps a stale-dominated
        flush (e.g. the deadline backstop firing on one straggler) from
        REPLACING the global with a model trained on an old base — the
        weights would otherwise normalize away (the FedAsync mixing rule,
        generalized to a buffer). An all-fresh buffer has ``mix == 1.0``
        EXACTLY (every weight is exactly 1.0), so the anchor is skipped
        and ``staleness_alpha = 0`` + ``buffer_k == cohort_size`` still
        reproduces the sync barrier's aggregation bit-exactly. The FedOpt
        server step and the history/accounting shape mirror
        ``rounds._aggregate``.
        """
        import numpy as np

        entries, counts, eff, trees = decode_buffer(
            state.buffer, state.template
        )
        # Health ledger (round 18): score this flush's geometry on the
        # already-decoded trees, in the fold's own sorted order. The base
        # is the CURRENT global for every entry — a uniform reference
        # despite per-entry pull bases; norms at the gate kept the
        # per-base geometry, this window scores cohort coherence. Round
        # 21 moved the scoring BEFORE the fold so the scores can GATE it
        # (quarantine_z), mirroring rounds._aggregate.
        new_ledger, scores = _health_ledger.observe_flush(
            state.ledger,
            [(e["cname"], t) for e, t in zip(entries, trees)],
            tree_from_bytes(state.global_blob, template=state.template),
        )
        quarantined = _aggregation.quarantine_set(
            scores, [e["cname"] for e in entries], state.config.quarantine_z
        )
        for qname in sorted(quarantined):
            new_ledger = _health_ledger.record_quarantine(new_ledger, qname)
        keep = [
            i for i, e in enumerate(entries)
            if e["cname"] not in quarantined
        ]
        avg = _aggregation.fold(
            _aggregation.from_config(state.config),
            [(entries[i]["cname"], eff[i], trees[i]) for i in keep],
        )
        # The FedAsync mix anchor is computed over the KEPT entries only —
        # a quarantined update must pull the global toward nothing, not
        # even through the mix ratio.
        kept_counts = [counts[i] for i in keep]
        kept_eff = [eff[i] for i in keep]
        mix = 1.0
        total_ns = float(sum(kept_counts))
        if any(c > 0 for c in kept_counts):
            mix = float(sum(kept_eff)) / total_ns
        if mix < 1.0:
            current = tree_from_bytes(state.global_blob, template=state.template)
            keep, take = np.float32(1.0 - mix), np.float32(mix)
            avg = jax.tree_util.tree_map(
                lambda c, u: keep * np.asarray(c, np.float32)
                + take * np.asarray(u, np.float32),
                current,
                avg,
            )
        avg, opt_state = R.apply_fedopt(state, avg)
        new_blob = tree_to_bytes(avg)
        cast = R._wire_cast(state.config)
        new_wire_blob = tree_to_bytes(avg, cast_dtype=cast) if cast else b""
        new_version = state.model_version + 1
        new_round = state.current_round + 1
        finished = new_round > state.config.max_rounds
        wall = (
            now - state.round_started_at
            if state.round_started_at is not None
            else None
        )
        entry = {
            "round": state.current_round,
            "mode": MODE_BUFFERED,
            "clients": [e["cname"] for e in entries],
            "samples": counts,
            "staleness": [e["staleness"] for e in entries],
            "weights": [e["weight"] for e in entries],
            "mix": mix,
            "buffer_fill": len(entries),
            "global_version": new_version,
            "completed_at": now,
            "wall_clock_s": wall,
            "updates_per_sec": (
                len(entries) / wall if wall is not None and wall > 0 else None
            ),
            "bytes_received": sum(e["wire_len"] for e in entries),
            "decoded_bytes_received": sum(len(e["blob"]) for e in entries),
            "codecs": [e["codec"] for e in entries],
            "bytes_broadcast": len(new_wire_blob or new_blob),
            "cohort_size": len(state.cohort),
            "rejected": dict(state.rejected),
            # Round 21: cname -> the robust-z score that excluded it from
            # the fold (empty = everyone folded). The per-entry lists
            # above keep their historical meaning (what the BUFFER held).
            "quarantined": quarantined,
        }
        # DP accountant on the async plane (round 23): every buffered entry
        # is ONE local training run whose noise is already in the blob, so
        # each entry charges its sender ``dp_steps_per_round`` — including
        # quarantined entries (the budget was SPENT client-side; exclusion
        # from the fold refunds nothing). Mirrors rounds._aggregate: the
        # epsilon map lands in the flush history entry and a breached
        # budget finishes the federation loudly.
        privacy_steps = state.privacy_steps
        if state.config.dp_noise_multiplier > 0.0:
            steps_per = (
                state.config.dp_steps_per_round or state.config.local_epochs
            )
            privacy_steps = dict(privacy_steps)
            for e in entries:
                privacy_steps[e["cname"]] = (
                    privacy_steps.get(e["cname"], 0) + int(steps_per)
                )
            epsilons = R._epsilons_for(state.config, privacy_steps)
            entry["epsilon"] = epsilons
            budget = state.config.dp_epsilon_budget
            if budget > 0.0 and epsilons and max(epsilons.values()) >= budget:
                entry["epsilon_budget_exhausted"] = True
                finished = True
        # Retained-base window: the new broadcast joins, versions older
        # than max_staleness leave — the delta-decode memory bound.
        bases = {
            v: b
            for v, b in sorted(state.base_blobs.items())
            if new_version - v <= state.config.max_staleness
        }
        bases[new_version] = new_wire_blob or new_blob
        return state._replace(
            ledger=new_ledger,
            privacy_steps=privacy_steps,
            global_blob=new_blob,
            wire_blob=new_wire_blob,
            current_round=new_round,
            model_version=new_version,
            buffer=(),
            rejected={},
            base_blobs=bases,
            round_started_at=now,
            phase=R.PHASE_FINISHED if finished else R.PHASE_RUNNING,
            history=state.history + (entry,),
            server_opt_state=opt_state,
        )

    @staticmethod
    def advance_time(state: R.ServerState, now: float) -> R.ServerState:
        """Buffered-mode pure time effects, called from
        ``rounds._advance_time`` AFTER the shared enrollment machinery: a
        buffer that reached K while enrollment was still open flushes on
        the transition to RUNNING, and ``round_deadline_s`` becomes the
        flush-liveness backstop — a PARTIAL buffer older than the deadline
        flushes rather than stalling the version counter behind absent
        clients (there is no cohort to shrink; the buffer is the quorum)."""
        cfg = state.config
        if state.phase != R.PHASE_RUNNING:
            return state
        if state.buffer and len(state.buffer) >= cfg.buffer_k:
            return BufferedAggregator.flush(state, now)
        if (
            cfg.round_deadline_s > 0
            and state.round_started_at is not None
            and now - state.round_started_at >= cfg.round_deadline_s
        ):
            if state.buffer:
                return BufferedAggregator.flush(state, now)
            # Nothing buffered: re-arm the window instead of hot-firing on
            # every tick.
            return state._replace(round_started_at=now)
        return state


def async_summary(history: tuple) -> dict:
    """Reduce a buffered-mode history to the async-plane headline numbers:
    total accepted updates, global versions, the per-update staleness
    distribution (p50/p95/p99 via the obs reservoir — exact until
    capacity), and mean buffer fill. Sync entries (no ``buffer_fill``) are
    ignored, so mixed histories summarize their buffered portion."""
    from fedcrack_tpu.obs.metrics import StreamingPercentiles

    stale = StreamingPercentiles(seed=0)
    updates = 0
    fills = []
    versions = 0
    for h in history:
        if "buffer_fill" not in h:
            continue
        versions += 1
        fills.append(h["buffer_fill"])
        for s in h.get("staleness", ()):
            stale.add(float(s))
            updates += 1
    return {
        "accepted_updates": updates,
        "global_versions": versions,
        "mean_buffer_fill": (sum(fills) / len(fills)) if fills else None,
        "staleness": stale.summary(),
    }
