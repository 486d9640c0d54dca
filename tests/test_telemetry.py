"""Round-15 telemetry plane: registry, /metrics exposition, spans, sentries,
cross-replica percentile merge — and the concurrent mini-soak.

The load-bearing claims, each pinned here:

- the metric registry is get-or-create (same family twice), type/label
  mismatches are loud, names are validated against the OBS001 catalog
  contract at runtime;
- exposition is DETERMINISTIC: two registries holding the same values —
  populated in different orders — expose byte-identical Prometheus text;
- the full loop closes over REAL HTTP: expose -> GET /metrics -> parse ->
  the same numbers (the parse round-trip the acceptance criteria name);
- ``StreamingPercentiles.merge`` equals numpy percentiles of the pooled
  samples while the combined stream fits capacity (property-tested across
  seeds/splits), keeps count/sum/min/max EXACT past capacity, and is
  deterministic for a given (seed, call sequence);
- spans correlate: trace ids + parent ids thread through nested work and
  the JSONL records carry monotonic durations;
- leak sentries trip on growth past slack and stay quiet under it;
- the mini-soak (every plane at once, chaos rolling, self-scraped) ends
  with a CLEAN invariant audit — tier-1 runs a short wall, the 60-second
  version is slow-marked.
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

from fedcrack_tpu.obs.metrics import StreamingPercentiles
from fedcrack_tpu.obs.promexp import (
    CONTENT_TYPE,
    MetricsExporter,
    parse_prometheus_text,
    sample_value,
    scrape,
)
from fedcrack_tpu.obs.registry import MetricsRegistry, validate_metric_name
from fedcrack_tpu.obs import sentries, spans as tracing


# ---- registry ----


def test_registry_get_or_create_and_mismatches_are_loud():
    reg = MetricsRegistry()
    c1 = reg.counter("fed_updates_total", "updates", labels=("result",))
    c2 = reg.counter("fed_updates_total", "updates", labels=("result",))
    assert c1 is c2
    with pytest.raises(ValueError, match="already registered as counter"):
        reg.gauge("fed_updates_total")
    with pytest.raises(ValueError, match="labels"):
        reg.counter("fed_updates_total", labels=("reason",))
    h = reg.histogram("fed_flush_seconds", buckets=(0.1, 1.0))
    assert reg.histogram("fed_flush_seconds") is h  # buckets=None matches
    with pytest.raises(ValueError, match="buckets"):
        reg.histogram("fed_flush_seconds", buckets=(0.5, 1.0))


def test_registry_name_validation_is_the_obs001_contract():
    for bad in ("FedUpdates_total", "updates", "updates_count", "9_total"):
        with pytest.raises(ValueError):
            validate_metric_name(bad)
    for good in (
        "fed_updates_total", "serve_request_seconds", "edge_wire_bytes",
        "fed_buffer_fill_ratio", "fed_update_staleness_versions",
    ):
        assert validate_metric_name(good) == good
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match="unit suffix"):
        reg.counter("updates_count")
    with pytest.raises(ValueError, match="bad label name"):
        reg.counter("x_total", labels=("le",))


def test_counter_monotone_gauge_free_histogram_cumulative():
    reg = MetricsRegistry()
    c = reg.counter("x_total")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1)
    g = reg.gauge("q_ratio")
    g.set(2.0)
    g.dec(0.5)
    assert g.value == 1.5
    g.set_function(lambda: 42.0)
    assert g.value == 42.0
    g.set_function(lambda: 1 / 0)  # a raising callback reads as NaN
    assert np.isnan(g.value)
    h = reg.histogram("w_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 5 and snap["sum"] == pytest.approx(56.05)
    # Cumulative: le=0.1 -> 1, le=1.0 -> 3, le=10.0 -> 4, +Inf -> 5.
    assert [cum for _, cum in snap["buckets"]] == [1, 3, 4, 5]


def _populate(reg: MetricsRegistry, order: list[str]):
    """Build the same state through any creation/update order."""
    ops = {
        "a": lambda: reg.counter("fed_updates_total", "u", labels=("result",))
        .labels(result="accepted").inc(7),
        "b": lambda: reg.counter("fed_updates_total", "u", labels=("result",))
        .labels(result="rejected_stale").inc(2),
        "c": lambda: reg.gauge("fed_buffer_fill_ratio", "fill").set(0.5),
        "d": lambda: [
            reg.histogram("serve_request_seconds", "lat", buckets=(0.1, 1.0))
            .observe(v) for v in (0.05, 0.2, 3.0)
        ],
    }
    for key in order:
        ops[key]()


def test_exposition_deterministic_across_insertion_order():
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    _populate(r1, ["a", "b", "c", "d"])
    _populate(r2, ["d", "c", "b", "a"])
    text = r1.exposition()
    assert text == r2.exposition()
    assert text.endswith("\n")
    # Sorted families, sorted children within.
    assert text.index("fed_buffer_fill_ratio") < text.index("fed_updates_total")
    assert text.index('result="accepted"') < text.index('result="rejected_stale"')


def test_label_escaping_round_trips():
    reg = MetricsRegistry()
    weird = 'he said "hi"\\\n'
    reg.counter("x_total", labels=("msg",)).labels(msg=weird).inc()
    parsed = parse_prometheus_text(reg.exposition())
    assert sample_value(parsed, "x_total", {"msg": weird}) == 1


def test_help_escaping_round_trips():
    """A literal backslash followed by 'n' in HELP text must survive the
    escape→parse round trip (sequential str.replace would mis-decode it)."""
    reg = MetricsRegistry()
    tricky = "path\\nfoo and a real\nnewline"
    reg.counter("y_total", help=tricky).inc()
    parsed = parse_prometheus_text(reg.exposition())
    assert parsed["y_total"]["help"] == tricky


# ---- the HTTP loop ----


def test_http_scrape_round_trips_every_sample():
    reg = MetricsRegistry()
    _populate(reg, ["a", "b", "c", "d"])
    with MetricsExporter(reg) as exporter:
        req = urllib.request.urlopen(exporter.url, timeout=5)
        assert req.headers["Content-Type"] == CONTENT_TYPE
        body = req.read().decode("utf-8")
        assert body == reg.exposition()
        parsed = scrape(exporter.url)
        # liveness + 404 routes: /healthz answers a JSON body (round 16)
        # so "up" and "warm" are distinguishable.
        health = urllib.request.urlopen(
            exporter.url.replace("/metrics", "/healthz"), timeout=5
        )
        assert health.headers["Content-Type"].startswith("application/json")
        body = json.loads(health.read())
        assert body["status"] == "ok"
        assert body["families"] == 3  # the three families _populate built
        assert body["uptime_seconds"] >= 0
        assert isinstance(body["spans_installed"], bool)
        assert "git" in body  # a string in a checkout, null in a wheel
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                exporter.url.replace("/metrics", "/nope"), timeout=5
            )
    assert sample_value(
        parsed, "fed_updates_total", {"result": "accepted"}
    ) == 7
    assert sample_value(parsed, "fed_buffer_fill_ratio") == 0.5
    assert parsed["serve_request_seconds"]["type"] == "histogram"
    assert sample_value(
        parsed, "serve_request_seconds", {"__sample__": "_count"}
    ) == 3
    assert sample_value(
        parsed, "serve_request_seconds", {"__sample__": "_bucket", "le": "+Inf"}
    ) == 3
    assert sample_value(
        parsed, "serve_request_seconds", {"__sample__": "_bucket", "le": "0.1"}
    ) == 1
    # Concurrent updates during scrapes never tear the text format.
    reg.counter("fed_updates_total", labels=("result",)).labels(
        result="accepted"
    ).inc()
    parse_prometheus_text(reg.exposition())


def test_parser_rejects_garbage_loudly():
    with pytest.raises(ValueError, match="unparseable"):
        parse_prometheus_text("fed_updates_total one\n")
    with pytest.raises(ValueError, match="unparseable"):
        parse_prometheus_text('x_total{result=unquoted} 1\n')


# ---- StreamingPercentiles.merge (satellite) ----


def test_merge_exact_pooled_percentiles_under_capacity():
    """Property: across seeds and split points, while the pooled sample
    fits capacity the merged percentiles EQUAL numpy over the pool."""
    for seed in range(5):
        rng = np.random.default_rng(seed)
        samples = rng.exponential(10.0, size=200)
        cut = int(rng.integers(1, 199))
        a = StreamingPercentiles(capacity=512, seed=seed)
        b = StreamingPercentiles(capacity=512, seed=seed + 100)
        for v in samples[:cut]:
            a.add(v)
        for v in samples[cut:]:
            b.add(v)
        a.merge(b)
        assert a.count == 200
        for q in (50, 90, 95, 99):
            assert a.percentile(q) == pytest.approx(
                float(np.percentile(samples, q)), rel=1e-12
            ), (seed, cut, q)


def test_merge_past_capacity_exact_moments_sane_percentiles():
    rng = np.random.default_rng(7)
    sa = rng.normal(100.0, 5.0, size=4000)
    sb = rng.normal(200.0, 5.0, size=6000)
    a = StreamingPercentiles(capacity=1024, seed=1)
    b = StreamingPercentiles(capacity=1024, seed=2)
    for v in sa:
        a.add(v)
    for v in sb:
        b.add(v)
    a.merge(b)
    pooled = np.concatenate([sa, sb])
    # count/sum/min/max merge EXACTLY whatever the reservoir sampled.
    assert a.count == 10000
    s = a.summary()
    assert s["max"] == pytest.approx(float(pooled.max()))
    assert s["min"] == pytest.approx(float(pooled.min()))
    # The median of a 40/60 bimodal pool sits in the upper mode; the
    # weighted sample must reflect each side's stream share.
    assert abs(a.percentile(50) - float(np.percentile(pooled, 50))) < 15.0
    assert abs(a.percentile(95) - float(np.percentile(pooled, 95))) < 5.0


def test_merge_deterministic_and_self_merge_refused():
    def build():
        a = StreamingPercentiles(capacity=64, seed=3)
        b = StreamingPercentiles(capacity=64, seed=4)
        for i in range(300):
            a.add(float(i))
            b.add(float(1000 + i))
        a.merge(b)
        return a

    r1, r2 = build(), build()
    assert r1._values == r2._values  # order-pinned, seeded: bit-identical
    assert r1.count == r2.count == 600
    with pytest.raises(ValueError, match="double-count"):
        r1.merge(r1)
    # Merging an empty reservoir is the identity.
    before = list(r1._values)
    r1.merge(StreamingPercentiles(capacity=64, seed=9))
    assert r1._values == before and r1.count == 600


# ---- spans ----


def test_spans_correlate_and_record_monotonic_durations(tmp_path):
    path = tmp_path / "spans.jsonl"
    tracing.install(path)
    try:
        with tracing.span("fed.flush", trace="round-3", version=4) as h:
            with tracing.span(
                "client.push", trace="round-3", parent=h.span_id, cname="c0"
            ) as child:
                child.set(upload_bytes=123)
    finally:
        tracing.uninstall()
    records = tracing.read_spans(path)
    assert [r["name"] for r in records] == ["client.push", "fed.flush"]
    push, flush = records
    assert push["trace"] == flush["trace"] == "round-3"
    assert push["parent"] == flush["span"]
    assert push["upload_bytes"] == 123 and flush["version"] == 4
    assert 0 <= push["dur_s"] <= flush["dur_s"]
    assert flush["t"] <= push["t"]  # outer started first
    # Every line is strict JSON (the CI artifact is jq-safe).
    for line in path.read_text().splitlines():
        json.loads(line)
    assert tracing.current() is None
    with tracing.span("serve.batch", trace="bucket-16") as h:
        assert h is None  # uninstalled -> no-op, sites never branch


def test_span_is_off_until_a_sink_exists(monkeypatch):
    """No recorder, no flight ring: span() yields None. Its profiler sink (a
    TraceAnnotation, inert outside a profiler session) exists only where JAX
    is already loaded: a span must never be what imports it."""
    import sys

    assert tracing.current() is None
    assert "jax" in sys.modules and tracing._trace_annotation("serve.batch") is not None
    with tracing.span("serve.batch", trace="bucket-16") as h:
        assert h is None
    monkeypatch.delitem(sys.modules, "jax")
    assert tracing._trace_annotation("serve.batch") is None
    with tracing.span("serve.batch", trace="bucket-16") as h:
        assert h is None
    assert "jax" not in sys.modules


def test_span_recorder_rotation_never_tears_a_line(tmp_path):
    """Satellite (round 16): size-based rotation bounds an hours-long
    soak's JSONL; every file in the rotated set holds only whole JSON
    lines, at most keep+1 files exist, and the record stream survives."""
    path = tmp_path / "spans.jsonl"
    with tracing.SpanRecorder(path, max_bytes=1500, keep=2) as rec:
        for i in range(60):
            with rec.span("w.x", trace=f"t-{i}", payload="p" * 64):
                pass
    files = tracing.span_files(path)
    assert str(path) in files
    assert 2 <= len(files) <= 3  # rotated at least once, keep=2 honored
    assert not (tmp_path / "spans.jsonl.3").exists()
    total = 0
    for f in files:
        text = open(f, encoding="utf-8").read()
        assert text.endswith("\n")  # no torn tail
        for line in text.splitlines():
            rec_obj = json.loads(line)  # every line strict JSON
            assert rec_obj["name"] == "w.x"
            total += 1
        import os as _os

        assert _os.path.getsize(f) <= 1500 + 200  # one-line slack
    assert 0 < total <= 60  # keep=2 may have dropped the oldest lines
    # span_files orders oldest → newest: the newest record is in the last.
    last = tracing.read_spans(files[-1])
    assert last[-1]["trace"] == "t-59"


def test_trace_context_wire_round_trip_and_degradation():
    ctx = tracing.TraceContext("fedtr-v7", "push:c0:r3")
    assert tracing.TraceContext.from_wire(ctx.to_wire()) == ctx
    assert tracing.version_trace(7) == "fedtr-v7"
    assert tracing.flush_context(8) == tracing.TraceContext(
        "fedtr-v7", "flush:v8"
    )
    # The dropped-context contract: anything malformed parses to None.
    for garbage in (None, 7, b"x#y", "", "nohash", "#", "a#", "#b", "x" * 500):
        assert tracing.TraceContext.from_wire(garbage) is None


def test_span_recorder_thread_safe(tmp_path):
    path = tmp_path / "spans.jsonl"
    with tracing.SpanRecorder(path) as rec:
        def worker(i):
            for j in range(20):
                with rec.span("w", trace=f"t-{i}", j=j):
                    pass

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    records = tracing.read_spans(path)
    assert len(records) == 80
    assert len({r["span"] for r in records}) == 80  # ids unique


# ---- leak sentries ----


def test_leak_sentry_steady_and_tripping(monkeypatch):
    reg = MetricsRegistry()
    fake = {"rss": 1000}
    monkeypatch.setattr(sentries, "rss_bytes", lambda: fake["rss"])
    monkeypatch.setattr(sentries, "device_memory_bytes", lambda: 0)
    sentry = sentries.LeakSentry(registry=reg, rss_slack_bytes=500)
    sentry.mark()
    fake["rss"] = 1400  # inside slack
    sentry.assert_steady()
    assert sentry.steady()
    fake["rss"] = 1600  # past slack: a leak
    with pytest.raises(sentries.LeakError, match="RSS grew 600"):
        sentry.assert_steady()
    # Gauges ride the scrape: collect-time callbacks see the last sample.
    parsed = parse_prometheus_text(reg.exposition())
    assert sample_value(parsed, "process_resident_bytes") == 1600
    assert sample_value(parsed, "process_resident_watermark_bytes") == 1600
    summary = sentry.summary()
    assert summary["steady"] is False and summary["deltas"]["rss"] == 600


class _FakeDevice:
    """A device whose ``memory_stats()`` returns (or raises) what it is given."""

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


def _tpu_like(peak, reserved, in_use=7):
    return {"peak_bytes_in_use": peak, "bytes_in_use": in_use, "bytes_reserved": reserved, "bytes_limit": 100, "num_allocs": 3}


@pytest.mark.parametrize(
    "stats, want_in_use, want_fullest",
    [
        # A backend with no memory stats (the CPU's None), one whose call fails.
        ([None, RuntimeError("no stats")], 0, {}),
        # The device that holds most by peak buffers + reserved scratch, not by
        # either alone; its four readings and nothing else of its dict.
        (
            [_tpu_like(50, 10), _tpu_like(20, 45, in_use=9), None],
            16,
            {"peak_bytes_in_use": 20, "bytes_in_use": 9, "bytes_reserved": 45, "bytes_limit": 100},
        ),
    ],
    ids=["no-stats", "fullest-by-the-sum"],
)
def test_device_memory_has_one_reader(monkeypatch, stats, want_in_use, want_fullest):
    """device_memory_stats is the one caller of memory_stats(): the sentry's
    bytes-in-use sum and the round record's fullest device both read it."""
    devices = [_FakeDevice(s) for s in stats]
    assert sentries.device_memory_stats(devices) == [s if isinstance(s, dict) else {} for s in stats]
    assert sentries.fullest_device_memory(devices) == want_fullest
    import jax

    monkeypatch.setattr(jax, "local_devices", lambda: devices)
    assert sentries.device_memory_bytes() == want_in_use


def test_leak_sentry_real_process_watermarks():
    sentry = sentries.LeakSentry(registry=MetricsRegistry())
    reading = sentry.sample()
    assert reading["rss"] > 0  # a real process is resident
    assert sentry.watermarks()["rss"] >= reading["rss"] > 0
    with pytest.raises(RuntimeError, match="before mark"):
        sentries.LeakSentry(registry=MetricsRegistry()).deltas()


# ---- flight recorder (round 16) ----


def test_flight_ring_bounded_and_spans_feed_it(tmp_path):
    from fedcrack_tpu.obs import flight

    ring = flight.install(path=str(tmp_path / "flight.json"), capacity=8)
    try:
        for i in range(20):
            flight.note("x", i=i)
        events = ring.snapshot()
        assert len(events) == 8  # bounded ring: only the last 8 survive
        assert [e["i"] for e in events] == list(range(12, 20))
        assert ring._seen == 20
        # Spans feed the ring for FREE even with NO span recorder installed.
        assert tracing.current() is None
        with tracing.span("fed.flush", trace="fedtr-v1", ctx="fedtr-v1#flush:v2"):
            pass
        last = ring.snapshot()[-1]
        assert last["kind"] == "span" and last["name"] == "fed.flush"
        assert last["ctx"] == "fedtr-v1#flush:v2" and last["dur_s"] >= 0
        path = flight.dump("unit test")
        payload = json.loads(open(path).read())
        assert payload["reason"] == "unit test"
        assert payload["events_seen"] == 21
        assert payload["events"][-1]["kind"] == "span"
        assert "metrics_exposition" in payload
    finally:
        flight.uninstall()
    assert flight.current() is None
    flight.note("after", x=1)  # uninstalled: a no-op, never an error
    assert flight.dump("after") is None


@pytest.mark.filterwarnings("ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_flight_dump_on_thread_crash_and_sigusr2(tmp_path):
    """The dump triggers: an unhandled exception in a thread and SIGUSR2
    both write the ring to disk (the excepthooks are chained, so default
    reporting still happens)."""
    import os
    import signal
    import time as _time

    from fedcrack_tpu.obs import flight

    path = str(tmp_path / "flight.json")
    flight.install(path=path, capacity=64)
    try:
        flight.note("before_crash", detail="context the post-mortem needs")

        def boom():
            raise RuntimeError("injected thread death")

        t = threading.Thread(target=boom, name="doomed")
        t.start()
        t.join()
        payload = json.loads(open(path).read())
        assert "injected thread death" in payload["reason"]
        assert any(e["kind"] == "before_crash" for e in payload["events"])
        if hasattr(signal, "SIGUSR2"):
            os.remove(path)
            os.kill(os.getpid(), signal.SIGUSR2)
            for _ in range(100):  # delivery is asynchronous-ish; bounded wait
                if os.path.exists(path):
                    break
                _time.sleep(0.01)
            payload = json.loads(open(path).read())
            assert payload["reason"] == "SIGUSR2"
    finally:
        flight.uninstall()


# ---- SLO watchdog (round 16) ----


def _watchdog_registry():
    reg = MetricsRegistry()
    reg.counter("fed_updates_total", "u", labels=("result",)).labels(
        result="accepted"
    ).inc(10)
    h = reg.histogram("serve_request_seconds", "lat", buckets=(0.1, 1.0, 10.0))
    for v in [0.05] * 90 + [0.5] * 9 + [5.0]:
        h.observe(v)
    reg.gauge("serve_recompiles_total", "r").set(0)
    return reg


def test_watchdog_stats_value_quantile_and_rate(monkeypatch):
    from fedcrack_tpu.obs import watchdog as wdm

    reg = _watchdog_registry()
    rules = [
        wdm.SloRule(name="v", metric="fed_updates_total",
                    labels={"result": "accepted"}, op=">=", threshold=10),
        wdm.SloRule(name="p95", metric="serve_request_seconds", stat="p95",
                    op="<=", threshold=1.0),
        wdm.SloRule(name="p50", metric="serve_request_seconds", stat="p50",
                    op="<=", threshold=0.1),
        wdm.SloRule(name="n", metric="serve_request_seconds", stat="count",
                    op="==", threshold=100),
        wdm.SloRule(name="rate", metric="fed_updates_total",
                    labels={"result": "accepted"}, stat="rate", op=">=",
                    threshold=1.0, min_elapsed_s=0.01),
        wdm.SloRule(name="absent", metric="no_such_total", op="==", threshold=0),
    ]
    wd = wdm.Watchdog(rules, registry=reg)
    r1 = {r["rule"]: r for r in wd.evaluate()["results"]}
    assert r1["v"]["value"] == 10 and r1["v"]["ok"]
    # p95 sits in the (0.1, 1.0] bucket: 90 of 100 below 0.1, 99 below 1.0.
    assert 0.1 < r1["p95"]["value"] <= 1.0 and r1["p95"]["ok"]
    assert r1["p50"]["value"] <= 0.1 and r1["p50"]["ok"]
    assert r1["n"]["value"] == 100
    assert r1["rate"]["value"] is None  # first evaluation: no window yet
    assert r1["absent"]["value"] is None and r1["absent"]["breach"] is False
    import time as _time

    _time.sleep(0.02)
    reg.counter("fed_updates_total", labels=("result",)).labels(
        result="accepted"
    ).inc(5)
    r2 = {r["rule"]: r for r in wd.evaluate()["results"]}
    assert r2["rate"]["value"] > 0 and r2["rate"]["ok"]
    audit = wd.audit()
    assert audit["breaches"] == [] and audit["evaluations"] == 2
    assert audit["never_determinate"] == ["absent"]
    assert not audit["all_rules_evaluated"] and not audit["clean"]


def test_watchdog_consecutive_rides_out_blips():
    """The `for:`-style clause: consecutive=3 means two failing
    evaluations with a recovery between them never breach; three in a row
    do. A bursty plane (storm gust, kill→restart window) must not page."""
    from fedcrack_tpu.obs import watchdog as wdm

    reg = MetricsRegistry()
    g = reg.gauge("fed_buffer_fill_ratio", "fill")
    rule = wdm.SloRule(
        name="floor", metric="fed_buffer_fill_ratio", op=">=",
        threshold=1.0, consecutive=3,
    )
    wd = wdm.Watchdog([rule], registry=reg)

    def one(value):
        g.set(value)
        return wd.evaluate()["breaches"]

    assert one(0.0) == []          # fail #1
    assert one(0.0) == []          # fail #2
    assert one(2.0) == []          # recovery resets the streak
    assert one(0.0) == []          # fail #1 again
    assert one(0.0) == []          # fail #2
    assert one(0.0) != []          # fail #3: SUSTAINED -> breach
    audit = wd.audit()
    assert len(audit["breaches"]) == 1 and not audit["clean"]
    with pytest.raises(ValueError, match="consecutive"):
        wdm.SloRule(name="x", metric="y_total", op="==", threshold=0,
                    consecutive=0)


def test_watchdog_breach_dumps_flight_and_audits_dirty(tmp_path):
    from fedcrack_tpu.obs import flight
    from fedcrack_tpu.obs import watchdog as wdm

    reg = _watchdog_registry()
    rules = [
        wdm.SloRule(name="impossible", metric="fed_updates_total",
                    labels={"result": "accepted"}, op=">=", threshold=1e12,
                    on_missing="breach"),
    ]
    path = str(tmp_path / "flight.json")
    flight.install(path=path)
    try:
        wd = wdm.Watchdog(rules, registry=reg)
        report = wd.enforce()
        assert report["breaches"][0]["rule"] == "impossible"
        payload = json.loads(open(path).read())
        assert payload["reason"] == "watchdog breach: impossible"
        # Watchdog samples themselves feed the ring (metric-sample deltas).
        assert any(e["kind"] == "watchdog.eval" for e in payload["events"])
        wd.enforce()  # a second breach does not re-dump (once per watchdog)
        audit = wd.audit()
        assert not audit["clean"] and len(audit["breaches"]) == 2
    finally:
        flight.uninstall()
    assert wdm.BREACH_EXIT != 0


def test_watchdog_rule_files_parse_and_default_config_matches():
    """configs/slo_default.json must stay the mirror of the built-in rule
    set; malformed rule files fail loudly."""
    import os

    from fedcrack_tpu.obs import watchdog as wdm

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    loaded = wdm.load_rules(os.path.join(root, "configs", "slo_default.json"))
    assert loaded == wdm.default_rules()
    smoke = wdm.load_rules(os.path.join(root, "configs", "slo_breach_smoke.json"))
    assert smoke[0].on_missing == "breach" and smoke[0].threshold >= 1e12
    with pytest.raises(ValueError, match="unknown op"):
        wdm.SloRule(name="x", metric="y_total", op="~", threshold=1)
    with pytest.raises(ValueError, match="unknown stat"):
        wdm.SloRule(name="x", metric="y_total", op="<=", threshold=1, stat="p42")
    with pytest.raises(ValueError, match="duplicate"):
        wdm.Watchdog([wdm.SloRule(name="a", metric="x_total", op="==", threshold=0)] * 2)


# ---- the concurrent mini-soak ----


def _assert_soak_clean(artifact: dict):
    audit = artifact["audit"]
    assert audit["clean"], json.dumps(
        {"audit": audit, "watchdog": artifact["watchdog"]},
        indent=1, sort_keys=True,
    )
    assert audit["zero_torn_versions"] and audit["torn_versions"] == 0
    assert audit["serve_healthy"]
    assert audit["ef_mass_conserved"]
    assert audit["statefile_restore_bit_identical"]
    assert audit["watermarks_steady"]
    assert audit["recompiles_since_warmup"] == 0
    scrape_block = artifact["scrape"]
    assert scrape_block["all_planes_covered"], scrape_block["planes_covered"]
    assert scrape_block["mid_soak_families"] > 0  # scraped LIVE, mid-run
    assert artifact["serve"]["completed"] > 0
    assert artifact["serve"]["failed"] == 0
    assert artifact["federation"]["flushes"] > 0
    assert artifact["spans"]["total"] > 0
    for name in ("serve.batch", "fed.flush", "driver.round", "client.train"):
        assert artifact["spans"]["by_name"].get(name, 0) > 0, name
    # Round 16: the machine-checked SLO audit and the stitched trace.
    wd = artifact["watchdog"]
    assert wd["clean"] and wd["all_rules_evaluated"], wd
    assert wd["breaches"] == [] and wd["evaluations"] > 1
    assert audit["watchdog_clean"]
    tr = artifact["tracing"]
    assert tr["complete"], tr
    # One trace id crossed the client → root → serve planes.
    assert {"client", "fed", "serve"} <= set(tr["planes_crossed"])
    assert tr["trace"].startswith("fedtr-v")
    for stage in ("fed.flush", "serve.swap", "serve.batch"):
        assert stage in tr["stages"], (stage, tr)
    # Upstream reached the flush via a direct push or an edge partial
    # (the best chain may be either — both are client-plane-rooted).
    assert {"client.push", "edge.flush_partial"} & set(tr["stages"]), tr


def test_mini_soak_short_wall_clean_audit():
    """Tier-1: every plane concurrently for a few seconds — buffered
    federation, edge shard, serve + live hot-swap off the federation's
    statefile, driver leg, chaos rolling, a mid-soak server kill→restart —
    self-scraped over real HTTP and closed with a clean invariant audit."""
    from fedcrack_tpu.tools.soak import run_soak

    artifact = run_soak(duration_s=3.0, seed=0)
    _assert_soak_clean(artifact)
    assert artifact["federation"]["kill_restart"]["killed"]
    assert artifact["serve"]["swaps"] > 0  # training reached serving, live


@pytest.mark.slow
def test_mini_soak_sixty_seconds():
    """The ROADMAP's soak shrunk to a minute: long enough for hundreds of
    flushes and dozens of swaps; the same audit must stay clean."""
    from fedcrack_tpu.tools.soak import run_soak

    artifact = run_soak(duration_s=60.0, seed=0)
    _assert_soak_clean(artifact)
    assert artifact["federation"]["kill_restart"]["killed"]
    assert artifact["serve"]["swaps"] >= 5
    assert artifact["federation"]["global_versions"] >= 20
