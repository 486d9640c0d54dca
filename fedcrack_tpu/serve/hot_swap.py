"""Live hot-swap of the served global model — serve-while-training.

The federation publishes its global model two ways: orbax round-boundary
checkpoints (``ckpt/manager.py``, one step per ``model_version``) and the
mid-round durable statefile (``ckpt/statefile.py``, msgpack with
``model_version`` + ``global_blob``). The :class:`ModelVersionManager`
watches either (or both — highest version wins), loads newer weights OFF the
serving path, places them on device via ``engine.prepare``, and installs the
new ``(version, variables)`` snapshot with one pointer flip under a lock.

The batcher reads snapshots at its request-boundary barrier, so a swap:

- never drops or stalls in-flight batches (they finish on the snapshot they
  took);
- never tears a batch across versions (one snapshot per batch);
- costs the serving path only the pointer flip — the checkpoint read,
  msgpack decode and host->device transfer all happen in the poll thread
  (``last_swap['load_ms']`` records them).

Post-swap outputs are BIT-identical to a cold start of the same round's
weights (same compiled program, same device values — test-pinned in
tests/test_serve.py).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any

import msgpack

from fedcrack_tpu.analysis.sanitizers import make_lock
from fedcrack_tpu.obs import spans as tracing
from fedcrack_tpu.obs.registry import REGISTRY

log = logging.getLogger("fedcrack.serve.hot_swap")


def read_statefile_weights(path: str, template: Any | None = None):
    """(model_version, variables) from a federation statefile, or None.

    Reads the raw msgpack payload (``ckpt.statefile.STATE_FORMAT``) without
    reconstructing a ServerState — serving needs only the version counter
    and the global weights, not cohort/phase/receipts."""
    from fedcrack_tpu.ckpt.statefile import STATE_FORMAT
    from fedcrack_tpu.fed.serialization import tree_from_bytes

    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError:
        return None
    try:
        payload = msgpack.unpackb(blob, raw=False)
        if payload.get("format") != STATE_FORMAT:
            raise ValueError(f"unknown statefile format {payload.get('format')!r}")
        version = int(payload["model_version"])
        variables = tree_from_bytes(bytes(payload["global_blob"]), template=template)
    except Exception:
        log.exception("statefile %s unreadable for serving; keeping current model", path)
        return None
    return version, variables


def publish_statefile(
    path: str,
    variables: Any = None,
    model_version: int = 0,
    *,
    blob: bytes | None = None,
) -> None:
    """Write a minimal, format-compatible statefile carrying ``variables``
    (or a pre-encoded msgpack ``blob`` of them) at ``model_version`` (atomic
    write+fsync+rename). The tests, the drills and ``tools/load_gen`` use
    this to stand in for a live federation publishing a new round. Pass ``blob`` when the publish
    must be cheap at trigger time (serializing a full model mid-load-test
    costs seconds under GIL contention — encode before the run instead)."""
    from fedcrack_tpu.ckpt.statefile import STATE_FORMAT
    from fedcrack_tpu.ioutils import atomic_write_bytes

    if blob is None:
        from fedcrack_tpu.fed.serialization import tree_to_bytes

        blob = tree_to_bytes(variables)
    payload = {
        "format": STATE_FORMAT,
        "phase": "FINISHED",
        "cohort": [],
        "departed": [],
        "current_round": int(model_version),
        "model_version": int(model_version),
        "failed_rounds": 0,
        "global_blob": blob,
        "received": {},
        "logs": {},
        "history": [],
        "rejected": {},
        "opt_state": None,
    }
    atomic_write_bytes(path, msgpack.packb(payload, use_bin_type=True))


class WeightSourceWatcher:
    """The federation-output watcher shared by the single-process
    :class:`ModelVersionManager` and the fleet-wide
    ``serve.fleet.FleetVersionManager`` (round 17 refactor): knows where new
    global models come from (statefile and/or orbax checkpoint dir), which
    one currently wins (highest version), and how to read them — nothing
    about serving. Corrupt/unreadable sources are logged and skipped; the
    caller keeps its current model."""

    def __init__(
        self,
        *,
        ckpt_dir: str | None = None,
        state_path: str | None = None,
        template: Any | None = None,
    ):
        self._ckpt_dir = ckpt_dir or None
        self._state_path = state_path or None
        self._template = template
        self._ckptr = None

    def _checkpointer(self):
        from fedcrack_tpu.ckpt.manager import FedCheckpointer

        if self._ckptr is None:
            self._ckptr = FedCheckpointer(self._ckpt_dir)
        else:
            # orbax caches the step listing; newer managers expose reload().
            reload = getattr(self._ckptr._mngr, "reload", None)
            if callable(reload):
                try:
                    reload()
                except Exception:
                    pass
        return self._ckptr

    def best_available(self, newer_than: int):
        """Highest-version (version, host_variables) across sources that
        beats ``newer_than``; None when nothing newer exists."""
        best = None
        if self._state_path and os.path.exists(self._state_path):
            got = read_statefile_weights(self._state_path, template=self._template)
            if got is not None and got[0] > newer_than:
                best = got
        if self._ckpt_dir and os.path.isdir(self._ckpt_dir):
            try:
                ckptr = self._checkpointer()
                latest = ckptr.latest_version()
            except Exception:
                log.exception("checkpoint dir %s unreadable; skipping", self._ckpt_dir)
                latest = None
            if latest is not None and latest > newer_than and (
                best is None or latest > best[0]
            ):
                try:
                    ckpt = ckptr.restore(self._template)
                    if ckpt is not None:
                        best = (int(ckpt.model_version), ckpt.variables)
                except Exception:
                    log.exception("checkpoint restore failed; keeping current model")
        return best

    def close(self) -> None:
        if self._ckptr is not None:
            try:
                self._ckptr.close()
            except Exception:
                pass
            self._ckptr = None


class ModelVersionManager:
    """Watches federation outputs and owns the served weights snapshot.

    ``snapshot()`` is the batcher's request-boundary read: O(lock) — never
    touches disk or device. ``poll_once()`` does all heavy lifting and is
    driven by a daemon thread every ``poll_s`` (or called directly by tests
    and chaos hooks to force a deterministic swap point).
    """

    def __init__(
        self,
        engine: Any,
        initial_variables: Any,
        *,
        initial_version: int = 0,
        ckpt_dir: str | None = None,
        state_path: str | None = None,
        poll_s: float = 2.0,
        template: Any | None = None,
        metrics: Any | None = None,
        canary: Any | None = None,
    ):
        self.engine = engine
        self._watcher = WeightSourceWatcher(
            ckpt_dir=ckpt_dir, state_path=state_path, template=template
        )
        self._poll_s = poll_s
        self._metrics = metrics
        # Canary evaluator (round 18, health/canary.py): probed at the TAIL
        # of install(), in the poll thread, after the pointer flip — a
        # raising canary can never fail or block a swap (test-pinned).
        self.canary = canary
        self._lock = make_lock("serve.hot_swap.snapshot")
        self._current = (int(initial_version), engine.prepare(initial_variables))
        # Swap wire contexts by installed version (round 16): the batcher
        # links the FIRST batch served on a version to its swap span via
        # swap_context(). Bounded — only recent versions matter.
        self._swap_ctx: dict[int, str] = {}
        self.swaps: list[dict] = []
        self.last_swap: dict | None = None
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()

    # ---- the serving-path read ----

    def snapshot(self) -> tuple[int, Any]:
        with self._lock:
            return self._current

    def swap_context(self, version: int) -> str | None:
        """The wire context of the swap that installed ``version`` (None
        for the initial weights or long-evicted versions) — what the first
        batch served on a version links its span to."""
        with self._lock:
            return self._swap_ctx.get(int(version))

    @property
    def version(self) -> int:
        return self.snapshot()[0]

    # ---- polling ----

    def poll_once(self) -> bool:
        """Check sources; install a newer model if one exists. Returns
        whether a swap happened. Heavy work (decode + device transfer) runs
        here, outside the snapshot lock."""
        current_version, _ = self.snapshot()
        got = self._watcher.best_available(current_version)
        if got is None:
            return False
        return self.install(*got)

    def install(self, version: int, host_variables: Any) -> bool:
        """Place ``host_variables`` on device and flip the served snapshot to
        ``version`` (no-op unless strictly newer). The tail of every poll —
        also the public entry for harnesses that already hold the new round's
        weights (an in-process smoke must not pay a multi-second msgpack
        decode under the serving load's GIL just to reach the flip)."""
        current_version = self.snapshot()[0]
        if version <= current_version:
            return False
        # Round 16: the swap joins the version-lineage trace and links to
        # the flush that PUBLISHED this version — whose context is
        # deterministic (spans.flush_context), so the link needs nothing
        # beyond the version counter the statefile/checkpoint already
        # carries. A version published by something other than a flush
        # (harness publish, checkpoint import) leaves the link dangling —
        # the stitcher reports it unresolved, nothing breaks.
        fctx = tracing.flush_context(version)
        sctx = tracing.TraceContext(fctx.trace, f"swap:v{version}")
        with tracing.span(
            "serve.swap",
            trace=fctx.trace,
            ctx=sctx.to_wire(),
            remote_parent=fctx.to_wire(),
            from_version=current_version,
            to_version=version,
        ) as span_handle:
            t0 = time.monotonic()
            device_variables = self.engine.prepare(host_variables)
            load_ms = (time.monotonic() - t0) * 1e3
            with self._lock:
                if version <= self._current[0]:
                    # Raced with a concurrent poll: the span records the
                    # wasted load attempt, flagged so span consumers can
                    # count installed=true against serve_swaps_total.
                    if span_handle is not None:
                        span_handle.set(installed=False)
                    return False
                # Context registered in the SAME locked section as the
                # pointer flip: a batch snapshotting the new version right
                # after the flip must find its swap_context (the batcher's
                # first-batch link is one-shot — a miss is permanent).
                self._swap_ctx[version] = sctx.to_wire()
                while len(self._swap_ctx) > 8:
                    self._swap_ctx.pop(min(self._swap_ctx))
                self._current = (version, device_variables)
            if span_handle is not None:
                span_handle.set(installed=True)
        from fedcrack_tpu.obs import flight

        flight.note(
            "serve.swap", from_version=current_version, to_version=version,
            load_ms=round(load_ms, 3),
        )
        REGISTRY.counter(
            "serve_swaps_total", "hot swaps installed by the version manager"
        ).inc()
        REGISTRY.histogram(
            "serve_swap_pause_seconds",
            "off-path load cost of a swap (decode + device placement; the "
            "serving path pays only the pointer flip)",
        ).observe(load_ms / 1e3)
        record = {
            "from_version": current_version,
            "to_version": version,
            "load_ms": round(load_ms, 3),
            # Deadline/interval math above is monotonic (t0/load_ms); the
            # wall clock appears ONLY as this display field, named "ts" per
            # the obs JSONL convention ("t" = monotonic there).
            # fedlint: disable=DET001 -- human-readable record timestamp
            "ts": time.time(),
        }
        self.swaps.append(record)
        self.last_swap = record
        log.info("hot-swapped served model: v%d -> v%d (%.1f ms load)",
                 current_version, version, load_ms)
        if self._metrics is not None:
            self._metrics.log("serve_swap", **record)
        if self.canary is not None:
            # After the flip, still in the poll thread: the serving path
            # already moved on — the probe set reuses the engine's compiled
            # bucket programs, so no recompile and no swap-path stall.
            try:
                self.canary.evaluate(version, device_variables)
            except Exception:
                log.exception("canary eval failed for v%d (swap unaffected)",
                              version)
        return True

    # ---- lifecycle ----

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()

        def loop():
            while not self._stop.wait(self._poll_s):
                try:
                    self.poll_once()
                except Exception:
                    log.exception("hot-swap poll failed; retrying next period")

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout=10)
        self._thread = None
        self._watcher.close()

    def __enter__(self) -> "ModelVersionManager":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
