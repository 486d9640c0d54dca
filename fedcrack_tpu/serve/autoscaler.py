"""SLO-driven fleet autoscaler — the capacity half of round 22's loop.

The r17 fleet is statically sized: off-peak it burns replicas, at peak it
sheds. This controller closes the loop the ROADMAP's "Elastic fleet" item
promised, built entirely from parts that already exist:

- **Signals**: the controller consumes the registry's OWN Prometheus
  exposition (the r15 parser over ``registry.exposition()`` — the same
  text a dashboard scrapes, the r16 watchdog idiom), after asking the
  router to :meth:`~fedcrack_tpu.serve.router.FleetRouter.refresh_gauges`.
  It reads exactly the signals admission control acts on:
  ``serve_rolling_p95_seconds``, per-bucket
  ``serve_router_queue_depth_total``, and ``serve_fleet_replicas``.
- **Scale-up** (:meth:`ServeFleet.add_replica`): the new replica is
  prepared and warmed OFF the serving path — shared-engine fleets reuse
  the already-compiled programs, process-per-replica fleets ride the r17
  persistent compile cache — and the router only sees it once its weights
  slot is committed and its batcher live.
- **Scale-down** (:meth:`ServeFleet.remove_replica` → the r17
  ``kill_replica`` reroute): queued requests move to survivors with their
  original futures, so zero ACCEPTED requests drop (test-pinned).
- **Hysteresis**: one action per evaluation, a ``scale_cooldown_s`` dead
  time after every action, and scale-down only after
  ``scale_down_idle_evals`` consecutive calm evaluations — a storm gust
  cannot flap the fleet. Shedding stays the loud backstop at the router:
  the controller's job is to make it the exception, never the steady
  state.

The controller also integrates **replica-seconds** (live replicas × wall
time) — the headline cost meter. What the autoscaled fleet saves against
static-max under a diurnal profile is not measured on a chip.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any

from fedcrack_tpu.analysis.sanitizers import make_lock
from fedcrack_tpu.obs import flight
from fedcrack_tpu.obs.promexp import parse_prometheus_text, sample_value
from fedcrack_tpu.obs.registry import REGISTRY, MetricsRegistry

log = logging.getLogger("fedcrack.serve.autoscaler")

SCALE_UP = "up"
SCALE_DOWN = "down"
# Calm is deliberately stricter than the scale-up trigger (half of it):
# the gap between "grow above X" and "shrink below X/2" is the hysteresis
# band that keeps a load level sitting near the trigger from flapping.
CALM_P95_FACTOR = 0.5


class FleetAutoscaler:
    """Scale a :class:`~fedcrack_tpu.serve.fleet.ServeFleet` between
    ``ServeConfig.min_replicas`` and ``max_replicas`` from its scraped
    pressure signals. Construction requires an ARMED config
    (``min_replicas >= 1`` — ``configs.py`` validates the band)."""

    def __init__(
        self,
        fleet: Any,
        *,
        registry: MetricsRegistry | None = None,
        clock=time.monotonic,
    ):
        cfg = fleet.router.serve_config
        if cfg.min_replicas < 1:
            raise ValueError(
                "autoscaler needs an armed band: set ServeConfig.min_replicas"
                " >= 1 (and max_replicas >= min_replicas)"
            )
        self.fleet = fleet
        self.cfg = cfg
        self.registry = registry if registry is not None else REGISTRY
        self._clock = clock
        self._lock = make_lock("serve.autoscaler.control")
        self._cooldown_until = 0.0
        self._calm_evals = 0
        self._evaluations = 0
        self._replica_seconds = 0.0
        self._last_t: float | None = None
        self.actions: list[dict] = []
        self._m_events = REGISTRY.counter(
            "serve_scale_events_total",
            "autoscaler fleet resizes by direction",
            labels=("direction",),
        )
        self._m_replica_seconds = REGISTRY.gauge(
            "serve_replica_seconds_total",
            "integrated live-replicas x wall-time — the elastic fleet's "
            "cost meter (what static-max burns and autoscaling saves)",
        )
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()

    # ---- signal read ----

    def read_signals(self, parsed: dict | None = None) -> dict:
        """The controller's inputs, from a parsed exposition. ``parsed`` is
        a :func:`parse_prometheus_text` result; None refreshes the router
        gauges and parses the registry's own exposition — the production
        path (tests inject synthetic expositions)."""
        if parsed is None:
            self.fleet.router.refresh_gauges()
            parsed = parse_prometheus_text(self.registry.exposition())
        live = sample_value(parsed, "serve_fleet_replicas")
        p95_s = sample_value(parsed, "serve_rolling_p95_seconds")
        fam = parsed.get("serve_router_queue_depth_total")
        queued = 0.0
        if fam is not None:
            queued = sum(
                v
                for k, v in fam["samples"].items()
                if not any(name == "__sample__" for name, _ in k)
            )
        return {
            "live": int(live) if live is not None else 0,
            "p95_ms": (p95_s or 0.0) * 1e3,
            "queued": int(queued),
        }

    # ---- the control law ----

    def _wants_up(self, sig: dict) -> str | None:
        """Reason to grow, or None. Queue pressure is per-live-replica
        (N queued on 4 replicas is calmer than N on 1); the p95 trigger
        fires BEFORE the SLO breaches (``scale_up_p95_frac`` of it) so
        capacity arrives before the shed probe would."""
        live = max(1, sig["live"])
        if sig["queued"] >= self.cfg.scale_up_queue_depth * live:
            return (
                f"queued {sig['queued']} >= "
                f"{self.cfg.scale_up_queue_depth}/replica x {live}"
            )
        slo = self.cfg.slo_p95_ms
        if slo > 0 and sig["p95_ms"] >= self.cfg.scale_up_p95_frac * slo:
            return (
                f"p95 {sig['p95_ms']:.1f} ms >= "
                f"{self.cfg.scale_up_p95_frac:.2f} x SLO {slo:.1f} ms"
            )
        return None

    def _is_calm(self, sig: dict) -> bool:
        """Calm = empty queues AND p95 well inside the hysteresis band —
        the precondition a scale-down must hold for
        ``scale_down_idle_evals`` consecutive evaluations."""
        if sig["queued"] > 0:
            return False
        slo = self.cfg.slo_p95_ms
        if slo > 0:
            band = CALM_P95_FACTOR * self.cfg.scale_up_p95_frac * slo
            if sig["p95_ms"] >= band:
                return False
        return True

    def evaluate(self, parsed: dict | None = None) -> dict:
        """One control-loop tick: read signals, integrate replica-seconds,
        take at most ONE scaling action. Returns the decision record (also
        appended to :attr:`actions` when an action fired)."""
        with self._lock:
            sig = self.read_signals(parsed)
            now = self._clock()
            self._evaluations += 1
            if self._last_t is not None:
                self._replica_seconds += sig["live"] * (now - self._last_t)
            self._last_t = now
            self._m_replica_seconds.set(self._replica_seconds)
            decision = {
                "evaluation": self._evaluations,
                "action": None,
                "reason": "",
                **sig,
            }
            if now < self._cooldown_until:
                decision["reason"] = "cooldown"
                return decision
            up_reason = self._wants_up(sig)
            if up_reason is not None:
                self._calm_evals = 0
                if sig["live"] >= self.cfg.max_replicas:
                    decision["reason"] = f"at max_replicas: {up_reason}"
                    return decision
                return self._scale_up(decision, up_reason, now)
            if not self._is_calm(sig):
                self._calm_evals = 0
                decision["reason"] = "steady"
                return decision
            self._calm_evals += 1
            if (
                sig["live"] > self.cfg.min_replicas
                and self._calm_evals >= self.cfg.scale_down_idle_evals
            ):
                return self._scale_down(decision, now)
            decision["reason"] = (
                f"calm {self._calm_evals}/{self.cfg.scale_down_idle_evals}"
            )
            return decision

    def _scale_up(self, decision: dict, reason: str, now: float) -> dict:
        replica = self.fleet.add_replica(warm=True)
        self._cooldown_until = now + self.cfg.scale_cooldown_s
        self._m_events.labels(direction=SCALE_UP).inc()
        decision.update(action=SCALE_UP, reason=reason, replica=replica.index)
        self.actions.append(decision)
        flight.note("serve.scale_up", replica=replica.index, reason=reason)
        log.info("scale-up -> replica %d (%s)", replica.index, reason)
        return decision

    def _scale_down(self, decision: dict, now: float) -> dict:
        # Highest-index live replica drains: indices only grow, so the
        # newest capacity leaves first and replica 0 (the tiled-path and
        # shared-engine anchor) never drains.
        victim = max(
            (r for r in self.fleet.router.live_replicas()), key=lambda r: r.index
        )
        reroute = self.fleet.remove_replica(victim.index)
        self._cooldown_until = now + self.cfg.scale_cooldown_s
        self._calm_evals = 0
        self._m_events.labels(direction=SCALE_DOWN).inc()
        decision.update(
            action=SCALE_DOWN,
            reason=f"calm for {self.cfg.scale_down_idle_evals} evals",
            replica=victim.index,
            rerouted=reroute["rerouted"],
        )
        self.actions.append(decision)
        flight.note(
            "serve.scale_down", replica=victim.index,
            rerouted=reroute["rerouted"],
        )
        log.info(
            "scale-down: drained replica %d (%d rerouted)",
            victim.index, reroute["rerouted"],
        )
        return decision

    # ---- lifecycle (the r16 watchdog loop shape) ----

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()

        def loop():
            while not self._stop.wait(self.cfg.scale_interval_s):
                try:
                    self.evaluate()
                except Exception:
                    log.exception("autoscaler tick failed; retrying next period")

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        if self._thread is not None:
            self._stop.set()
            self._thread.join(timeout=10)
            self._thread = None

    # ---- audit ----

    def replica_seconds(self) -> float:
        """The integral so far, including the un-metered tail since the
        last evaluation (so a final read after stop() is complete)."""
        with self._lock:
            total = self._replica_seconds
            if self._last_t is not None:
                live = sum(1 for r in self.fleet.router.replicas if r.alive)
                total += live * max(0.0, self._clock() - self._last_t)
            return total

    def audit(self) -> dict:
        """JSON-safe controller verdict for soak and drill artifacts: how many
        ticks, every action taken, the cost integral, the band."""
        with self._lock:
            actions = list(self.actions)
            evaluations = self._evaluations
        ups = sum(1 for a in actions if a["action"] == SCALE_UP)
        downs = sum(1 for a in actions if a["action"] == SCALE_DOWN)
        return {
            "evaluations": evaluations,
            "scale_ups": ups,
            "scale_downs": downs,
            "actions": actions,
            "replica_seconds": round(self.replica_seconds(), 3),
            "band": [self.cfg.min_replicas, self.cfg.max_replicas],
            "live": sum(1 for r in self.fleet.router.replicas if r.alive),
        }
