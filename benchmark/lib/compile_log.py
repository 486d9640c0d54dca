"""Every backend compile this process makes, from JAX's own monitoring events
(copied from ``chip_smoke.py``'s ``CompileLog``): a compile request is
followed on its thread by an optional cache-hit event and then by its
duration, which pairs them up."""

from __future__ import annotations

import threading

import jax


class CompileLog:
    def __init__(self):
        self.events: list[tuple[str, float, bool]] = []
        self._local = threading.local()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self._local.hit = False
        elif event == "/jax/compilation_cache/cache_hits":
            self._local.hit = True

    def _on_duration(self, event: str, duration: float, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            hit = getattr(self._local, "hit", False)
            self._local.hit = False
            self.events.append((str(kw.get("fun_name")), float(duration), hit))

    def mark(self) -> int:
        return len(self.events)

    def summary(self, since: int = 0, until: int | None = None) -> dict:
        events = self.events[since:until]
        return {
            "compiles": len(events),
            "cache_hits": sum(1 for e in events if e[2]),
            "seconds": sum(e[1] for e in events),
            "slowest": sorted(((n, s) for n, s, _ in events), key=lambda e: -e[1])[:3],
        }
