"""From the profiler's trace to the device metrics of a traced run.

A whole round cannot be traced: one second of this program is some 40,000
device events, and a traced round (1 GB of ``.xplane.pb``) took minutes to
collect and ran the host out of memory (my chip runs, PR 25). So the harness
traces a slice: the last part of one round and the start of the next, with
the whole boundary between them (the fold, the read-back, the next dispatch)
inside. The slice is read with ``jax.profiler.ProfileData`` alone.

A TPU's plane is named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one
event for each executed HLO operation, named as XLA prints it. A ``while``
(the scan over SGD steps), ``conditional`` or ``call`` event only encloses
the operations of its body, which are events of their own, so enclosing
events are left out of every sum. Busy time is the union of the remaining
intervals.

The scan's step is found from the trace itself: the operation that recurs
and takes the most time marks each step by its start, the median distance
between two starts is the step's period, and the one long distance is the
boundary between the rounds. Idle time inside the boundary is counted once a
round; idle time outside it is a rate of the steady scan.

The host's plane (``/host:CPU``) carries the ``TraceAnnotation`` spans the
harness puts around its own calls (``bench.*``) and what XLA's runtime
records of itself; an idle gap is named after the host span that covers the
most of it, the innermost on a tie.
"""

from __future__ import annotations

import re
import statistics

ENCLOSING = re.compile(r"^%?(while|conditional|call)(\.[0-9]+)?$")
COLLECTIVE = re.compile(r"all-gather|all-reduce|all-to-all|collective-permute|reduce-scatter|collective-broadcast")
OPS_LINE = "XLA Ops"
TOP = 10


def _device_events(plane) -> list[tuple[float, float, str]]:
    """``(start_s, end_s, name)`` of every leaf operation on one device."""
    events = []
    for line in plane.lines:
        if line.name != OPS_LINE:
            continue
        for e in line.events:
            name = e.name.split(" = ", 1)[0]
            if ENCLOSING.match(name):
                continue
            start = e.start_ns * 1e-9
            events.append((start, start + e.duration_ns * 1e-9, name))
    events.sort()
    return events


def _gaps(events) -> tuple[float, list[tuple[float, float]]]:
    """Busy seconds (the union of the intervals) and the idle gaps between."""
    busy, gaps = 0.0, []
    cur_start = cur_end = None
    for start, end, _ in events:
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                busy += cur_end - cur_start
                gaps.append((cur_end, start))
            cur_start, cur_end = start, end
        else:
            cur_end = max(cur_end, end)
    if cur_end is not None:
        busy += cur_end - cur_start
    return busy, gaps


def _steps(events) -> tuple[float, tuple[float, float]] | None:
    """The step's period and the boundary between the rounds, or nothing
    where no operation recurs."""
    total, starts = {}, {}
    for start, end, name in events:
        if not COLLECTIVE.search(name):
            total[name] = total.get(name, 0.0) + end - start
            starts.setdefault(name, []).append(start)
    recurring = [n for n in total if len(starts[n]) >= 4]
    if not recurring:
        return None
    marks = starts[max(recurring, key=total.get)]
    diffs = [b - a for a, b in zip(marks, marks[1:])]
    period = statistics.median(diffs)
    i = max(range(len(diffs)), key=diffs.__getitem__)
    if diffs[i] < 1.5 * period:
        return period, (marks[-1], marks[-1])
    return period, (marks[i] + period, marks[i + 1])


def _host_spans(planes) -> list[tuple[float, float, str]]:
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.duration_ns > 0:
                    start = e.start_ns * 1e-9
                    spans.append((start, start + e.duration_ns * 1e-9, e.name))
    return spans


def _name_gap(gap, spans) -> str:
    best, best_key = "host (no span)", (0.0, 0.0)
    for start, end, name in spans:
        overlap = min(end, gap[1]) - max(start, gap[0])
        if overlap <= 0:
            continue
        key = (round(overlap / (gap[1] - gap[0]), 2), -(end - start))
        if key > best_key:
            best, best_key = name, key
    return best


def reduce_profile(profile, chips: int, window_s: float | None = None) -> dict:
    """``window_s`` is the slice's length by the host's clock; without it the
    extent of the device's own events stands in."""
    planes = list(profile.planes)
    devices = sorted((p for p in planes if p.name.startswith("/device:TPU:")), key=lambda p: p.name)[:chips]
    if len(devices) < chips:
        raise ValueError(f"{len(devices)} TPU plane(s) in the trace, the cell uses {chips}; planes: {[p.name for p in planes]}")
    spans = _host_spans(planes)
    per_device = []
    op_seconds: dict[str, float] = {}
    gap_seconds: dict[str, float] = {}
    for plane in devices:
        events = _device_events(plane)
        if not events:
            raise ValueError(f"no operation ran on {plane.name} in the traced slice")
        busy, gaps = _gaps(events)
        extent = events[-1][1] - events[0][0]
        row = {"busy_s": busy, "extent_s": extent, "idle_s": sum(b - a for a, b in gaps)}
        row["collective_s"] = sum(e - s for s, e, n in events if COLLECTIVE.search(n))
        steps = _steps(events)
        if steps is not None:
            period, (lo, hi) = steps
            inside = sum(min(b, hi) - max(a, lo) for a, b in gaps if min(b, hi) > max(a, lo))
            steady = extent - (hi - lo)
            row.update(
                step_period_s=period, boundary_s=hi - lo, boundary_idle_s=inside,
                steady_idle_rate=(row["idle_s"] - inside) / steady if steady > 0 else 0.0,
            )
        per_device.append(row)
        for start, end, name in events:
            op_seconds[name] = op_seconds.get(name, 0.0) + (end - start) / len(devices)
        for gap in sorted(gaps, key=lambda g: g[0] - g[1])[:50]:
            name = _name_gap(gap, spans)
            gap_seconds[name] = gap_seconds.get(name, 0.0) + (gap[1] - gap[0]) / len(devices)
    mean = lambda key: sum(d[key] for d in per_device) / len(per_device)
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    out = {
        "busy_s": mean("busy_s"),
        "window_s": window_s if window_s is not None else max(d["extent_s"] for d in per_device),
        "collective_s": mean("collective_s"),
        "per_device": per_device,
        "breakdown": {"device_ops": top(op_seconds), "idle_gaps": top(gap_seconds)},
    }
    if all("step_period_s" in d for d in per_device):
        out["step_period_s"] = mean("step_period_s")
    return out


def idle_share_of_round(reduced: dict, round_s: float) -> float | None:
    """The share of a round in which the chip that idles most runs nothing:
    its idle time inside the boundary, once, plus its steady scan's idle
    rate over the rest of the round."""
    shares = [
        (d["boundary_idle_s"] + d["steady_idle_rate"] * max(round_s - d["boundary_s"], 0.0)) / round_s
        for d in reduced["per_device"] if "boundary_idle_s" in d
    ]
    return max(shares) if len(shares) == len(reduced["per_device"]) else None
