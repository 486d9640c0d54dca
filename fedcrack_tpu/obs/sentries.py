"""Leak sentries — RSS + device-memory watermarks with ``assert_steady``.

The RecompileSentry pattern (``analysis.sanitizers``) applied to memory: a
long-lived session (the soak, a production serve fleet) must reach steady
state and STAY there — a drifting resident set or device-memory watermark
is a leak even when every request succeeds. :class:`LeakSentry` samples

- **host RSS** via ``/proc/self/statm`` (falling back to
  ``resource.getrusage`` peak-RSS on hosts without procfs), and
- **device memory in use** via ``jax.Device.memory_stats()`` summed over
  local devices (CPU backends report nothing — the gauge stays 0 and the
  device half of the audit is vacuously steady there; on TPU it is the HBM
  leak detector),

tracks the high-watermark of each, exports all four series as collect-time
gauges (``process_resident_bytes``, ``process_resident_watermark_bytes``,
``device_memory_in_use_bytes``, ``device_memory_watermark_bytes``), and —
after :meth:`mark` pins the steady-state baseline — :meth:`assert_steady`
raises :class:`LeakError` when growth since the mark exceeds the configured
slack. Sampling is explicit (``sample()``), so harness loops control the
cadence and determinism; nothing spawns threads here.
"""

from __future__ import annotations

import os
import time
from typing import Any

from fedcrack_tpu.analysis.sanitizers import make_lock
from fedcrack_tpu.obs.registry import REGISTRY, MetricsRegistry

_PAGE_SIZE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096


class LeakError(AssertionError):
    """A watched memory series grew past its steady-state slack."""


def rss_bytes() -> int:
    """Current resident set size of this process, in bytes."""
    try:
        with open("/proc/self/statm", encoding="ascii") as f:
            fields = f.read().split()
        return int(fields[1]) * _PAGE_SIZE
    except (OSError, IndexError, ValueError):
        import resource

        # ru_maxrss is the PEAK (KiB on linux); a peak is still a usable
        # watermark signal on procfs-less hosts.
        return int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024


MEMORY_KEYS = ("peak_bytes_in_use", "bytes_in_use", "bytes_reserved", "bytes_limit")


def device_memory_stats(devices: Any = None) -> list[dict]:
    """``memory_stats()`` of each device (``jax.local_devices()`` without an
    argument), one dict a device, ``{}`` for one whose backend has none (CPU)
    or whose call fails. The one place this package asks a device for them."""
    if devices is None:
        try:
            import jax

            devices = jax.local_devices()
        except Exception:
            return []
    out = []
    for d in devices:
        try:
            out.append(dict(d.memory_stats() or {}))
        except Exception:
            out.append({})
    return out


def device_memory_bytes() -> int:
    """Sum of ``bytes_in_use`` over local jax devices; 0 when the backend
    exposes no memory stats (CPU)."""
    return sum(int(s.get("bytes_in_use", 0)) for s in device_memory_stats())


def fullest_device_memory(devices: Any) -> dict:
    """:data:`MEMORY_KEYS` of the one of ``devices`` that holds most, by
    ``peak_bytes_in_use + bytes_reserved``: the allocator's buffers at their
    peak and the scratch the runtime sets aside while a program is loaded,
    both held at once while a round runs (a ballast run proved the sum:
    ``benchmark/study/memory_headroom.py``). ``{}`` where no device reports
    (CPU)."""
    held = [s for s in device_memory_stats(devices) if s]
    if not held:
        return {}
    most = max(held, key=lambda s: int(s.get("peak_bytes_in_use", 0)) + int(s.get("bytes_reserved", 0)))
    return {k: int(most.get(k, 0)) for k in MEMORY_KEYS}


class LeakSentry:
    """Watermark tracker + steady-state assertion over host and device
    memory. ``registry=None`` exports against the process default."""

    def __init__(
        self,
        registry: MetricsRegistry | None = None,
        rss_slack_bytes: int = 192 * 1024 * 1024,
        device_slack_bytes: int = 64 * 1024 * 1024,
        sample_on_collect: bool = False,
    ):
        self._lock = make_lock("obs.sentries.leak")
        self.rss_slack_bytes = int(rss_slack_bytes)
        self.device_slack_bytes = int(device_slack_bytes)
        self._last = {"rss": 0, "device": 0}
        self._high = {"rss": 0, "device": 0}
        self._mark: dict[str, int] | None = None
        # sample_on_collect: every scrape refreshes the reading (throttled
        # to one sample per window so four gauges share one measurement).
        # For sessions with no natural sampling hook (refscale_federation)
        # this keeps the exported watermarks LIVE instead of frozen at the
        # startup reading; harnesses that sample explicitly (the soak)
        # leave it off for deterministic cadence.
        self._sample_on_collect = bool(sample_on_collect)
        self._last_sample_t = 0.0
        reg = registry if registry is not None else REGISTRY
        reg.gauge(
            "process_resident_bytes",
            "host RSS at the last sentry sample",
        ).set_function(lambda: self._collect()["rss"])
        reg.gauge(
            "process_resident_watermark_bytes",
            "high-watermark host RSS over the sentry's lifetime",
        ).set_function(lambda: self._high["rss"])
        reg.gauge(
            "device_memory_in_use_bytes",
            "sum of device bytes_in_use at the last sentry sample "
            "(0 on backends without memory_stats)",
        ).set_function(lambda: self._collect()["device"])
        reg.gauge(
            "device_memory_watermark_bytes",
            "high-watermark device memory over the sentry's lifetime",
        ).set_function(lambda: self._high["device"])
        self.sample()

    def sample(self) -> dict[str, int]:
        """Take one measurement; updates the watermarks. Returns the
        current ``{"rss": ..., "device": ...}`` reading."""
        reading = {"rss": rss_bytes(), "device": device_memory_bytes()}
        with self._lock:
            self._last = dict(reading)
            self._last_sample_t = time.monotonic()
            for k, v in reading.items():
                if v > self._high[k]:
                    self._high[k] = v
        return reading

    def _collect(self) -> dict[str, int]:
        """Gauge-callback read: the cached reading, refreshed first when
        ``sample_on_collect`` and the throttle window (0.5 s) has passed."""
        if self._sample_on_collect:
            with self._lock:
                stale = time.monotonic() - self._last_sample_t > 0.5
            if stale:
                self.sample()
        with self._lock:
            return dict(self._last)

    def mark(self) -> dict[str, int]:
        """Steady state begins now: growth past (mark + slack) is a leak."""
        reading = self.sample()
        with self._lock:
            self._mark = dict(reading)
        return reading

    def watermarks(self) -> dict[str, int]:
        with self._lock:
            return dict(self._high)

    def deltas(self) -> dict[str, int]:
        """Growth of the CURRENT reading over the mark (not the watermark:
        a transient spike that drained back is allowed; still-resident
        growth is what leaks look like)."""
        current = self.sample()
        with self._lock:
            if self._mark is None:
                raise RuntimeError("deltas() before mark()")
            return {k: current[k] - self._mark[k] for k in current}

    def steady(self) -> bool:
        d = self.deltas()
        return (
            d["rss"] <= self.rss_slack_bytes
            and d["device"] <= self.device_slack_bytes
        )

    def assert_steady(self) -> None:
        d = self.deltas()
        problems = []
        if d["rss"] > self.rss_slack_bytes:
            problems.append(
                f"RSS grew {d['rss']} B past the mark "
                f"(slack {self.rss_slack_bytes} B)"
            )
        if d["device"] > self.device_slack_bytes:
            problems.append(
                f"device memory grew {d['device']} B past the mark "
                f"(slack {self.device_slack_bytes} B)"
            )
        if problems:
            raise LeakError(
                "memory not steady since mark(): " + "; ".join(problems)
                + " — a long-lived session must plateau, not climb"
            )

    def summary(self) -> dict[str, Any]:
        """JSON-safe audit block for soak artifacts."""
        with self._lock:
            out: dict[str, Any] = {
                "last": dict(self._last),
                "watermark": dict(self._high),
                "mark": dict(self._mark) if self._mark else None,
            }
        if self._mark is not None:
            out["deltas"] = self.deltas()
            out["steady"] = (
                out["deltas"]["rss"] <= self.rss_slack_bytes
                and out["deltas"]["device"] <= self.device_slack_bytes
            )
        return out
