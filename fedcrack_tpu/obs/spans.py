"""Dapper-style trace spans — correlated JSONL timelines across planes.

A span is one named interval with a **trace id** (the correlation key: a
federation round, a serve request) and an optional **parent span id**, so a
multi-plane session can be reconstructed as a tree instead of interleaved
log lines: ``round-3`` owns the driver's dispatch span, the tree edge's
flush span and the transport pushes it correlates; ``req-000042`` owns the
serve front door's request span, the batch it rode and the swap that
installed mid-flight.

Recording follows the repo's sanitizer idiom (``make_lock`` /
``install_monitor``): instrumentation calls the module-level
:func:`span` context manager unconditionally — it is a **no-op costing one
global read** (plus, in a process that has loaded JAX, one inactive
``TraceAnnotation``) until a recorder is installed (:func:`install`, or a
:class:`SpanRecorder` passed explicitly) or a ``jax.profiler`` session is
open, in which case the same spans also land on the profiler's host plane. Durations come from the
monotonic clock; the wall clock appears only as the display-only ``ts``
field, per the obs JSONL convention ("t" = monotonic offset there too).

Record shape (one JSON object per line)::

    {"name": "serve.batch", "trace": "req-000042", "span": 17,
     "parent": 12, "t": 3.104, "dur_s": 0.0021, "ts": 1789... ,
     "bucket": 128}

Span ids are a per-recorder sequence — deterministic for a deterministic
schedule, merely unique otherwise.

Cross-process propagation (round 16): a span that must be referenced from
ANOTHER process (or another recorder file) carries a **wire-safe trace
context** — :class:`TraceContext`, serialized as ``"<trace>#<key>"`` where
``key`` is a sender-chosen string unique within the trace (span ids are
per-recorder sequences, so an integer id cannot cross a file boundary
unambiguously). The sender records the context as its span's ``ctx``
attribute; the receiver records it as ``remote_parent`` (one upstream) or
``links`` (fan-in, e.g. a flush aggregating many pushes), and
``tools/trace_stitch.py`` joins the per-process JSONL files on those
strings. The trace id itself is derived from the model-version lineage —
:func:`version_trace` — because every party already learns the base
version in-band (the enroll/pull config map, the frame's ``base_version``),
so client, edge, root and serve spans of one update lifecycle agree on ONE
trace id without any extra negotiation. ``TraceContext.from_wire`` returns
``None`` on anything malformed: a dropped or corrupted context degrades to
a parentless span, never an error.

Rotation (round 16): ``SpanRecorder(path, max_bytes=..., keep=N)`` bounds
an hours-long soak's JSONL growth — the file rotates to ``path.1..path.N``
between whole-line writes under the sink lock, so a rotated set never
contains a torn JSON line.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import sys
import time
from dataclasses import dataclass
from typing import Any, Iterator

from fedcrack_tpu.analysis.sanitizers import make_lock
from fedcrack_tpu.obs import flight as _flight

# Longest wire context accepted back off the wire: contexts are
# observability, never load-bearing, so an absurd one is dropped rather
# than stored.
_MAX_WIRE_CTX = 256


def version_trace(base_version: int) -> str:
    """The lineage trace id for work rooted at global model version
    ``base_version``: a client training on the version-``B`` broadcast, the
    flush publishing ``B+1``, the swap installing it and the first batch
    served from it all join ``fedtr-vB`` — one trace id across processes,
    derived from a number every party already carries in-band."""
    return f"fedtr-v{int(base_version)}"


@dataclass(frozen=True)
class TraceContext:
    """A wire-safe span reference: the trace id plus a sender-chosen key
    unique within that trace (NOT the recorder's integer span id, which is
    a per-process sequence and ambiguous across files)."""

    trace: str
    key: str

    def to_wire(self) -> str:
        return f"{self.trace}#{self.key}"

    @classmethod
    def from_wire(cls, wire: Any) -> "TraceContext | None":
        """Parse a wire context; ``None`` for anything malformed (missing,
        wrong type, no separator, empty halves, oversized) — the dropped-
        context contract: degrade to parentless, never raise."""
        if not isinstance(wire, str) or not wire or len(wire) > _MAX_WIRE_CTX:
            return None
        trace, sep, key = wire.partition("#")
        if not sep or not trace or not key:
            return None
        return cls(trace=trace, key=key)


def flush_context(version: int) -> TraceContext:
    """The DETERMINISTIC context of the flush that published global model
    ``version``: computable by anyone who knows the version (the serve
    plane links swap→flush from the statefile's version counter alone —
    nothing extra rides the statefile, so its snapshot bytes stay a pure
    function of protocol state)."""
    return TraceContext(version_trace(version - 1), f"flush:v{int(version)}")


class SpanHandle:
    """What a ``with span(...)`` body sees: the ids to thread to children."""

    __slots__ = ("span_id", "trace", "attrs")

    def __init__(self, span_id: int, trace: str | None):
        self.span_id = span_id
        self.trace = trace
        self.attrs: dict[str, Any] = {}

    def set(self, **attrs: Any) -> None:
        """Attach attributes discovered mid-span (e.g. the model version a
        batch was answered from)."""
        self.attrs.update(attrs)


class SpanRecorder:
    """Append-only JSONL span sink; thread-safe.

    ``max_bytes`` arms size-based rotation (``keep`` old files retained as
    ``path.1`` .. ``path.keep``, newest first): an hours-long soak appends
    to a BOUNDED set instead of one unbounded JSONL. Rotation happens
    between whole-line writes under the sink lock, so no file in the set
    ever holds a torn JSON line (test-pinned). File-object sinks never
    rotate."""

    def __init__(
        self,
        path: str | os.PathLike | io.TextIOBase,
        *,
        max_bytes: int | None = None,
        keep: int = 3,
    ):
        if max_bytes is not None and max_bytes <= 0:
            raise ValueError(f"max_bytes must be positive, got {max_bytes}")
        if keep < 1:
            raise ValueError(f"keep must be >= 1, got {keep}")
        self.max_bytes = max_bytes
        self.keep = keep
        self._path: str | None = None
        if isinstance(path, io.TextIOBase):
            self._f = path
            self._owns = False
        else:
            p = os.fspath(path)
            parent = os.path.dirname(os.path.abspath(p))
            os.makedirs(parent, exist_ok=True)
            self._f = open(p, "a", encoding="utf-8")
            self._owns = True
            self._path = p
        self._bytes = (
            os.path.getsize(self._path)
            if self._path is not None and os.path.exists(self._path)
            else 0
        )
        self._lock = make_lock("obs.spans.sink")
        self._t0 = time.monotonic()
        self._seq = 0

    def _rotate_locked(self) -> None:
        """Shift path.(keep-1)→path.keep … path→path.1 and reopen. Caller
        holds the sink lock; writes only ever happen between whole lines,
        so every file in the rotated set is line-complete."""
        assert self._path is not None
        self._f.close()
        for i in range(self.keep - 1, 0, -1):
            src = f"{self._path}.{i}"
            if os.path.exists(src):
                os.replace(src, f"{self._path}.{i + 1}")
        os.replace(self._path, f"{self._path}.1")
        self._f = open(self._path, "a", encoding="utf-8")
        self._bytes = 0

    def _next_id(self) -> int:
        with self._lock:
            self._seq += 1
            return self._seq

    @contextlib.contextmanager
    def span(
        self,
        name: str,
        *,
        trace: str | None = None,
        parent: int | None = None,
        **attrs: Any,
    ) -> Iterator[SpanHandle]:
        handle = SpanHandle(self._next_id(), trace)
        t_start = time.monotonic()
        try:
            yield handle
        finally:
            dur = time.monotonic() - t_start
            record: dict[str, Any] = {
                "name": name,
                "trace": trace,
                "span": handle.span_id,
                "parent": parent,
                "t": round(t_start - self._t0, 6),
                "dur_s": round(dur, 6),
                # Interval math above is monotonic; the wall clock is the
                # display-only "ts" field (obs JSONL convention).
                # fedlint: disable=DET001 -- human-readable record timestamp
                "ts": time.time(),
            }
            for k, v in attrs.items():
                record[k] = v
            for k, v in handle.attrs.items():
                record[k] = v
            line = json.dumps(record, sort_keys=True, default=str)
            with self._lock:
                if (
                    self._owns
                    and self.max_bytes is not None
                    and self._bytes > 0
                    and self._bytes + len(line) + 1 > self.max_bytes
                ):
                    self._rotate_locked()
                self._f.write(line + "\n")
                self._f.flush()
                self._bytes += len(line.encode("utf-8")) + 1
            # Flight-recorder tee (round 16): the bounded in-memory ring
            # gets a COMPACT event per span (name/trace/duration + the
            # cross-process context when one was attached) — one global
            # read when no ring is installed.
            _flight.note(
                "span",
                name=name,
                trace=trace,
                dur_s=record["dur_s"],
                ctx=record.get("ctx"),
            )

    def close(self) -> None:
        if self._owns:
            with self._lock:
                self._f.close()

    def __enter__(self) -> "SpanRecorder":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()


# ---- the module-level recorder (sanitizer idiom: zero-cost when off) ----

_recorder: SpanRecorder | None = None
_recorder_lock = make_lock("obs.spans.install")


def install(
    path: str | os.PathLike | io.TextIOBase,
    *,
    max_bytes: int | None = None,
    keep: int = 3,
) -> SpanRecorder:
    """Install the process span recorder; returns it. Replacing an existing
    recorder closes the old one. ``max_bytes``/``keep`` arm size-based
    rotation (see :class:`SpanRecorder`)."""
    global _recorder
    rec = SpanRecorder(path, max_bytes=max_bytes, keep=keep)
    with _recorder_lock:
        old, _recorder = _recorder, rec
    if old is not None:
        old.close()
    return rec


def uninstall() -> None:
    global _recorder
    with _recorder_lock:
        old, _recorder = _recorder, None
    if old is not None:
        old.close()


def current() -> SpanRecorder | None:
    return _recorder


_NO_ANNOTATION = contextlib.nullcontext()


def _trace_annotation(name: str) -> Any:
    """``jax.profiler.TraceAnnotation(name)`` where this process has already
    loaded JAX, else ``None``: a coordinator that never touches JAX must not
    start importing it for a span. With no profiler session open the
    annotation is one inactive-check; with one, the span lands on the
    ``/host:CPU`` plane of the same trace as the device's ``XLA Ops``, on
    the profiler's clock."""
    if "jax" not in sys.modules:
        return None
    from jax.profiler import TraceAnnotation  # JAX is loaded: a dict lookup

    return TraceAnnotation(name)


@contextlib.contextmanager
def span(
    name: str,
    *,
    trace: str | None = None,
    parent: int | None = None,
    **attrs: Any,
) -> Iterator[SpanHandle | None]:
    """Record ``name`` against the installed recorder; a no-op (yielding
    ``None``) when none is installed — instrumentation sites never branch.

    Two sinks, both off by default: the JSONL recorder (:func:`install`)
    and, where JAX is loaded, the profiler's host plane
    (:func:`_trace_annotation`) — live only while a profiler session is
    open.

    When only the flight ring is installed (tracing off), the span still
    feeds the ring a compact timed event — "every plane feeds the flight
    recorder for free" — at the cost of two global reads and one deque
    append."""
    with _trace_annotation(name) or _NO_ANNOTATION:
        rec = _recorder
        if rec is not None:
            with rec.span(name, trace=trace, parent=parent, **attrs) as handle:
                yield handle
            return
        if _flight.current() is None:
            yield None
            return
        t_start = time.monotonic()
        handle = SpanHandle(0, trace)
        try:
            yield handle
        finally:
            _flight.note(
                "span",
                name=name,
                trace=trace,
                dur_s=round(time.monotonic() - t_start, 6),
                ctx=attrs.get("ctx") or handle.attrs.get("ctx"),
            )


def span_files(path: str | os.PathLike) -> list[str]:
    """The rotated set behind ``path``, oldest first (``path.N`` … ``path``)
    — what a stitcher should read so a chain is never cut by a rotation."""
    p = os.fspath(path)
    out: list[str] = []
    i = 1
    rotated: list[str] = []
    while os.path.exists(f"{p}.{i}"):
        rotated.append(f"{p}.{i}")
        i += 1
    out.extend(reversed(rotated))
    if os.path.exists(p):
        out.append(p)
    return out


def read_spans(path: str | os.PathLike, name: str | None = None) -> list[dict]:
    """Load a span JSONL, optionally filtered by span name."""
    out = []
    with open(os.fspath(path), encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            if name is None or rec.get("name") == name:
                out.append(rec)
    return out
