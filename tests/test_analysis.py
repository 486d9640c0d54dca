"""fedlint — the static-analysis + runtime-sanitizer plane (round 11).

Three layers, each pinned here:

- **rules**: one tiny positive + one negative fixture per rule pack
  (determinism, durability, trace-safety, transport, lock-order, dead-code)
  so a rule regression fails on a 5-line snippet, not a 500-file tree;
- **engine**: suppression comments (`# fedlint: disable=RULE`) and the
  fingerprinted baseline file round-trip — including that EDITING a
  baselined line resurfaces the finding;
- **the gate**: the full rule set over the real `fedcrack_tpu/` tree with
  the committed `fedlint_baseline.json` reports ZERO findings (the tier-1
  CI contract: exit code 0), and the serve-plane lock graph stays acyclic;
- **sanitizers**: RecompileSentry counts jit-cache growth, the lock-order
  monitor raises on an inversion BEFORE it can deadlock, and
  `no_implicit_transfers` blocks implicit host<->device traffic while
  letting explicit device_put/get through.
"""

import json
import os
import threading

import pytest

pytestmark = pytest.mark.analysis

from fedcrack_tpu.analysis.engine import (
    Finding,
    LintEngine,
    ModuleSource,
    Severity,
    apply_baseline,
    load_baseline,
    make_baseline,
)
from fedcrack_tpu.analysis.rules import all_rules, rules_by_id

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint(src, path="fedcrack_tpu/fed/fixture.py", rules=None):
    engine = LintEngine(rules=rules if rules is not None else all_rules())
    return engine.lint_source(src, path=path)


def rule_ids(findings):
    return [f.rule for f in findings]


# ---- determinism pack ----


def test_det001_wall_clock_positive_and_negative():
    bad = "import time\ndeadline = time.time() + 5.0\n"
    assert "DET001" in rule_ids(lint(bad))
    good = "import time\ndeadline = time.monotonic() + 5.0\n"
    assert "DET001" not in rule_ids(lint(good))
    # datetime.now is the same class of bug.
    assert "DET001" in rule_ids(lint("import datetime\nts = datetime.datetime.now()\n"))


def test_det002_unseeded_random_positive_and_negative():
    assert "DET002" in rule_ids(lint("import random\nx = random.random()\n"))
    assert "DET002" in rule_ids(lint("import numpy as np\nx = np.random.uniform()\n"))
    assert "DET002" not in rule_ids(
        lint("import random\nrng = random.Random(7)\nx = rng.random()\n")
    )
    assert "DET002" not in rule_ids(
        lint("import numpy as np\nrng = np.random.default_rng(7)\nx = rng.uniform()\n")
    )


def test_det003_unsorted_listing_positive_and_negative():
    assert "DET003" in rule_ids(lint("import os\nnames = os.listdir(d)\n"))
    assert "DET003" in rule_ids(lint("import glob\nnames = glob.glob(p)\n"))
    assert "DET003" not in rule_ids(lint("import os\nnames = sorted(os.listdir(d))\n"))


def test_det004_set_iteration_positive_and_negative():
    bad = "s = set(names)\nout = []\nfor n in s:\n    out.append(n)\n"
    assert "DET004" in rule_ids(lint(bad))
    good = "s = set(names)\nout = []\nfor n in sorted(s):\n    out.append(n)\n"
    assert "DET004" not in rule_ids(rule_ids_src := lint(good)) or not rule_ids_src
    # Scoped: the same snippet outside fed/ckpt/serve does not fire.
    assert "DET004" not in rule_ids(lint(bad, path="fedcrack_tpu/tools/fixture.py"))


def test_det004_dict_view_into_serializer():
    bad = (
        "import msgpack\n"
        "blob = msgpack.packb([v for k, v in d.items()])\n"
    )
    assert "DET004" in rule_ids(lint(bad))
    good = (
        "import msgpack\n"
        "blob = msgpack.packb([v for k, v in sorted(d.items())])\n"
    )
    assert "DET004" not in rule_ids(lint(good))
    # A dict view that never reaches a serializer is fine (arrival order is
    # legitimate for, e.g., logging).
    assert "DET004" not in rule_ids(lint("for k, v in d.items():\n    log(k, v)\n"))


def test_det004_scopes_do_not_leak_across_functions():
    """A set-bound name in one function must not taint a same-named list in
    another — the per-scope walk stops at nested function boundaries."""
    src = (
        "def f1(xs):\n"
        "    s = set(xs)\n"
        "    return sorted(s)\n"
        "def f2(items):\n"
        "    s = [i * 2 for i in items]\n"
        "    out = []\n"
        "    for n in s:\n"
        "        out.append(n)\n"
        "    return out\n"
    )
    assert "DET004" not in rule_ids(lint(src))
    # Within ONE function the taint still tracks.
    leaky = (
        "def f(xs):\n"
        "    s = set(xs)\n"
        "    return [n for n in s]\n"
    )
    assert "DET004" in rule_ids(lint(leaky))


# ---- durability pack ----


def test_dur001_raw_ckpt_write_positive_and_negative():
    bad = 'with open(path, "wb") as f:\n    f.write(data)\n'
    assert "DUR001" in rule_ids(lint(bad, path="fedcrack_tpu/ckpt/fixture.py"))
    # Read mode is not a torn-write hazard.
    good = 'with open(path, "rb") as f:\n    data = f.read()\n'
    assert "DUR001" not in rule_ids(lint(good, path="fedcrack_tpu/ckpt/fixture.py"))
    # Outside ckpt/, a scratch write with no durable-state hint is fine...
    scratch = 'with open(report, "w") as f:\n    f.write(text)\n'
    assert "DUR001" not in rule_ids(lint(scratch, path="fedcrack_tpu/tools/fx.py"))
    # ...but a serialized-tree write is a checkpoint by any name.
    tree = (
        'with open(out, "wb") as f:\n'
        "    f.write(tree_to_bytes(variables))\n"
    )
    assert "DUR001" in rule_ids(lint(tree, path="fedcrack_tpu/tools/fx.py"))


# ---- trace-safety pack ----


def test_trace001_host_op_in_jitted_fn():
    bad = (
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    print(x)\n"
        "    return x * 2\n"
    )
    assert "TRACE001" in rule_ids(lint(bad, path="fedcrack_tpu/parallel/fx.py"))
    # .item() and np.* are the implicit-transfer class.
    item = (
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return x.sum().item()\n"
    )
    assert "TRACE001" in rule_ids(lint(item, path="fedcrack_tpu/parallel/fx.py"))
    # Host ops in an untraced function are legitimate driver code.
    good = "def driver(x):\n    print(x)\n    return x\n"
    assert "TRACE001" not in rule_ids(lint(good, path="fedcrack_tpu/parallel/fx.py"))
    # Scope: outside parallel//serve-engine the rule stays quiet.
    assert "TRACE001" not in rule_ids(lint(bad, path="fedcrack_tpu/obs/fx.py"))


def test_trace001_fn_passed_to_scan_and_nested_defs():
    bad = (
        "import jax\n"
        "def body(carry, x):\n"
        "    import numpy as np\n"
        "    return carry, np.sum(x)\n"
        "def run(xs):\n"
        "    return jax.lax.scan(body, 0, xs)\n"
    )
    assert "TRACE001" in rule_ids(lint(bad, path="fedcrack_tpu/parallel/fx.py"))


# ---- transport pack ----


def test_trans001_unaudited_retry_positive_and_negative():
    bad = (
        "import grpc\n"
        "def call(stub):\n"
        "    for attempt in range(5):\n"
        "        try:\n"
        "            return stub.Do()\n"
        "        except grpc.RpcError:\n"
        "            continue\n"
    )
    assert "TRANS001" in rule_ids(lint(bad, path="fedcrack_tpu/transport/fx.py"))
    good = (
        "import grpc\n"
        "def call(stub):\n"
        "    for attempt in range(5):\n"
        "        try:\n"
        "            return stub.Do()\n"
        "        except grpc.RpcError as e:\n"
        "            if e.code() in NON_RETRYABLE_CODES:\n"
        "                raise\n"
        "            continue\n"
    )
    assert "TRANS001" not in rule_ids(lint(good, path="fedcrack_tpu/transport/fx.py"))
    # A handler outside any loop is not a retry.
    one_shot = (
        "import grpc\n"
        "def call(stub):\n"
        "    try:\n"
        "        return stub.Do()\n"
        "    except grpc.RpcError:\n"
        "        return None\n"
    )
    assert "TRANS001" not in rule_ids(lint(one_shot, path="fedcrack_tpu/transport/fx.py"))


def test_trans002_unknown_status_code():
    # The reference's `grcp.`-typo class: resolved only on the error path.
    bad = "import grpc\ncode = grpc.StatusCode.UNAVAILIBLE\n"
    assert "TRANS002" in rule_ids(lint(bad, path="fedcrack_tpu/tools/fx.py"))
    good = "import grpc\ncode = grpc.StatusCode.UNAVAILABLE\n"
    assert "TRANS002" not in rule_ids(lint(good, path="fedcrack_tpu/tools/fx.py"))


# ---- compress pack ----

def test_comp001_frame_decode_must_feed_validate_update():
    bad = (
        "from fedcrack_tpu.compress import decode_update\n"
        "def take(blob, state):\n"
        "    tree, frame = decode_update(blob, state.template, base)\n"
        "    return aggregate(tree)\n"
    )
    assert "COMP001" in rule_ids(lint(bad, path="fedcrack_tpu/fed/fx.py"))
    good = (
        "from fedcrack_tpu.compress import decode_update\n"
        "from fedcrack_tpu.fed.serialization import validate_update\n"
        "def take(blob, state):\n"
        "    tree, frame = decode_update(blob, state.template, base)\n"
        "    problem = validate_update(to_bytes(tree), state.template)\n"
        "    return None if problem else aggregate(tree)\n"
    )
    assert "COMP001" not in rule_ids(lint(good, path="fedcrack_tpu/fed/fx.py"))
    # The decoder layer composing its own parses is exempt: decode_update
    # returns trees, it does not feed the aggregator.
    layer = (
        "def decode_update(blob, template, base):\n"
        "    frame = decode_frame(blob)\n"
        "    return rebuild(frame, template, base)\n"
    )
    assert "COMP001" not in rule_ids(lint(layer, path="fedcrack_tpu/compress/fx.py"))
    # Outside fed/ and compress/ the rule does not apply.
    assert "COMP001" not in rule_ids(lint(bad, path="fedcrack_tpu/tools/fx.py"))


# ---- async-plane pack ----


def test_async001_unsorted_iteration_in_flush_path():
    """ASYNC001: in fed/, inside a function whose name marks the
    buffer-flush/staleness plane, every unsorted dict-view or set
    iteration is an ERROR — iteration order IS aggregation order there."""
    bad = (
        "def flush_buffer(buf):\n"
        "    return [v for k, v in buf.items()]\n"
    )
    assert "ASYNC001" in rule_ids(lint(bad))
    bad_set = (
        "def staleness_prune(versions):\n"
        "    keep = set(versions)\n"
        "    out = []\n"
        "    for v in keep:\n"
        "        out.append(v)\n"
        "    return out\n"
    )
    assert "ASYNC001" in rule_ids(lint(bad_set))
    good = (
        "def flush_buffer(buf):\n"
        "    return [v for k, v in sorted(buf.items())]\n"
    )
    assert "ASYNC001" not in rule_ids(lint(good))
    # A list iteration in a flush path is fine (lists carry their order).
    list_ok = (
        "def flush_buffer(entries):\n"
        "    return [e for e in entries]\n"
    )
    assert "ASYNC001" not in rule_ids(lint(list_ok))
    # Functions OUTSIDE the flush/buffer/staleness plane are DET004's
    # business, not this rule's.
    unrelated = (
        "def summarize(d):\n"
        "    return [v for v in d.values()]\n"
    )
    assert "ASYNC001" not in rule_ids(lint(unrelated))
    # Outside fed/ the rule does not apply.
    assert "ASYNC001" not in rule_ids(lint(bad, path="fedcrack_tpu/serve/fx.py"))


# ---- observability pack ----


def test_obs001_metric_name_literal_with_unit_suffix():
    """OBS001: registry metric names must be snake_case string literals
    with a unit suffix — computed or free-spelled names break the greppable
    catalog and can mint unbounded series."""
    good = (
        "from fedcrack_tpu.obs.registry import REGISTRY\n"
        "REGISTRY.counter('fed_updates_total', 'updates').inc()\n"
        "REGISTRY.histogram('serve_request_seconds', 'latency')\n"
        "REGISTRY.gauge('fed_buffer_fill_ratio', 'fill')\n"
    )
    assert "OBS001" not in rule_ids(lint(good))
    # Computed name: ungreppable, potentially unbounded.
    computed = (
        "from fedcrack_tpu.obs.registry import REGISTRY\n"
        "REGISTRY.counter(f'updates_{plane}_total', 'per-plane').inc()\n"
    )
    assert "OBS001" in rule_ids(lint(computed))
    # Free spelling: no unit suffix / not snake_case.
    assert "OBS001" in rule_ids(
        lint("registry.counter('updates_count', 'x')\n")
    )
    assert "OBS001" in rule_ids(lint("registry.gauge('FedUpdates_total', 'x')\n"))
    # name= keyword path is checked the same way.
    assert "OBS001" in rule_ids(
        lint("reg.histogram(name=make_name(), help='x')\n")
    )
    assert "OBS001" not in rule_ids(
        lint("reg.histogram(name='fed_flush_seconds', help='x')\n")
    )
    # Non-registry receivers with the same method names are not ours.
    assert "OBS001" not in rule_ids(lint("collections.Counter('abc')\n"))
    assert "OBS001" not in rule_ids(lint("stats.counter('whatever')\n"))


def test_obs002_span_name_dotted_literal():
    """OBS002 (round 16): tracing.span names must be dotted plane.verb
    string literals — the literal-name contract extended to spans, so the
    stitcher's plane census and `grep -r 'fed.flush'` both stay total."""
    good = (
        "from fedcrack_tpu.obs import spans as tracing\n"
        "with tracing.span('client.push', trace='fedtr-v0'):\n"
        "    pass\n"
        "with tracing.span('edge.flush_partial', links=[]):\n"
        "    pass\n"
        "with tracing.span(name='serve.batch'):\n"
        "    pass\n"
    )
    assert "OBS002" not in rule_ids(lint(good))
    # Computed name: the span catalog becomes ungreppable.
    computed = (
        "from fedcrack_tpu.obs import spans as tracing\n"
        "with tracing.span(f'serve.{verb}'):\n"
        "    pass\n"
    )
    assert "OBS002" in rule_ids(lint(computed))
    assert "OBS002" in rule_ids(lint("tracing.span(span_name)\n"))
    # Undotted / free-spelled: no plane prefix to stitch or census by.
    assert "OBS002" in rule_ids(lint("tracing.span('push')\n"))
    assert "OBS002" in rule_ids(lint("spans.span('Client.Push')\n"))
    assert "OBS002" in rule_ids(lint("tracing.span('fed.')\n"))
    # Non-tracing receivers with a span method are not ours.
    assert "OBS002" not in rule_ids(lint("rec.span('anything goes')\n"))
    assert "OBS002" not in rule_ids(lint("soup.span('html')\n"))


# ---- federation-health pack ----


def test_health001_client_label_outside_chokepoint():
    """HEALTH001 (round 18): a metric family labeled by a client axis
    mints one series per enrolled client — only health/ledger.py's bounded
    export (client_label / MAX_CLIENT_LABELS + _overflow) may do that."""
    bad = (
        "from fedcrack_tpu.obs.registry import REGISTRY\n"
        "REGISTRY.counter('fed_updates_total', 'per-client updates',\n"
        "                 labels=('client',)).labels(client=cname).inc()\n"
    )
    assert "HEALTH001" in rule_ids(lint(bad))
    # Every client-axis spelling is caught, on any metric kind / receiver
    # alias the OBS001 idiom covers.
    assert "HEALTH001" in rule_ids(
        lint("reg.gauge('fed_norm_ratio', 'x', labels=('cname',))\n")
    )
    assert "HEALTH001" in rule_ids(
        lint("registry.histogram('fed_lag_seconds', 'x',"
             " labels=['round', 'client_id'])\n")
    )
    # Bounded, non-client label axes stay fine.
    good = (
        "from fedcrack_tpu.obs.registry import REGISTRY\n"
        "REGISTRY.counter('fed_updates_total', 'x', labels=('result',))\n"
        "REGISTRY.gauge('serve_drift_psi_ratio', 'x',"
        " labels=('bucket', 'signal'))\n"
    )
    assert "HEALTH001" not in rule_ids(lint(good))
    # The chokepoint itself is exempt: its export path bounds cardinality.
    inside = "reg.gauge('fed_client_anomaly_score_ratio', 'x', labels=('client',))\n"
    assert "HEALTH001" not in rule_ids(
        lint(inside, path="fedcrack_tpu/health/ledger.py")
    )
    assert "HEALTH001" in rule_ids(
        lint(inside, path="fedcrack_tpu/fed/rounds.py")
    )
    # Non-registry receivers are not ours.
    assert "HEALTH001" not in rule_ids(
        lint("stats.counter('x_total', labels=('client',))\n")
    )
    # The live tree must route every client label through the chokepoint.
    engine = LintEngine(rules=[rules_by_id()["HEALTH001"]])
    modules = engine.load_modules(
        [os.path.join(REPO, "fedcrack_tpu")], rel_to=REPO
    )
    assert engine.lint_modules(modules) == []


# ---- aggregation-algebra pack ----


def test_agg001_fedavg_call_outside_the_algebra():
    """AGG001 (round 21): a direct ``fedavg(...)`` call in fed/ or
    parallel/ is a fifth copy of the aggregation fold — invisible to
    ``FedConfig.aggregation``, the quarantine gate, and every robust
    combine. Only the two chokepoints may spell the primitive."""
    bad = (
        "from fedcrack_tpu.fed.algorithms import fedavg\n"
        "avg = fedavg(trees, weights)\n"
    )
    # Default fixture path is fedcrack_tpu/fed/fixture.py: in scope.
    assert "AGG001" in rule_ids(lint(bad))
    # Attribute receivers (the aliasing idioms the planes actually used).
    assert "AGG001" in rule_ids(
        lint("from fedcrack_tpu.fed import rounds as R\n"
             "avg = R.fedavg(trees, w)\n")
    )
    # The mesh plane is in scope too.
    assert "AGG001" in rule_ids(
        lint(bad, path="fedcrack_tpu/parallel/fixture.py")
    )
    # The chokepoints themselves are exempt: the algebra's instances and
    # the primitive's home.
    assert "AGG001" not in rule_ids(
        lint(bad, path="fedcrack_tpu/fed/aggregation.py")
    )
    assert "AGG001" not in rule_ids(
        lint(bad, path="fedcrack_tpu/fed/algorithms.py")
    )
    # Outside fed//parallel/ (benches, tools, tests cross-checking the
    # algebra against the primitive) is deliberately out of scope.
    assert "AGG001" not in rule_ids(
        lint(bad, path="fedcrack_tpu/tools/fixture.py")
    )
    # The sanctioned route draws no finding.
    good = (
        "from fedcrack_tpu.fed import aggregation as _aggregation\n"
        "avg = _aggregation.fold(_aggregation.FedAvg(), triples)\n"
    )
    assert "AGG001" not in rule_ids(lint(good))
    # The live tree: every fed/ and parallel/ fold goes through the
    # algebra (the round-21 refactor's enforcement bit).
    engine = LintEngine(rules=[rules_by_id()["AGG001"]])
    modules = engine.load_modules(
        [os.path.join(REPO, "fedcrack_tpu")], rel_to=REPO
    )
    assert engine.lint_modules(modules) == []


# ---- lock-order pack (project scope: lint_modules, not lint_source) ----

CYCLE_SRC = """\
import threading

class S:
    def __init__(self):
        self.a = threading.Lock()
        self.b = threading.Lock()

    def fwd(self):
        with self.a:
            with self.b:
                pass

    def rev(self):
        with self.b:
            with self.a:
                pass
"""

ORDERED_SRC = CYCLE_SRC.replace(
    "        with self.b:\n            with self.a:\n                pass\n",
    "        with self.a:\n            with self.b:\n                pass\n",
)


def _lint_modules(named_sources):
    engine = LintEngine(rules=all_rules())
    modules = [ModuleSource(p, s) for p, s in named_sources]
    return engine.lint_modules(modules)


def test_lock001_cycle_detected_and_consistent_order_clean():
    findings = _lint_modules([("fedcrack_tpu/serve/fx.py", CYCLE_SRC)])
    assert "LOCK001" in rule_ids(findings)
    assert "a" in findings[rule_ids(findings).index("LOCK001")].message
    clean = _lint_modules([("fedcrack_tpu/serve/fx.py", ORDERED_SRC)])
    assert "LOCK001" not in rule_ids(clean)


def test_lock001_call_mediated_cycle_across_methods():
    src = """\
import threading

class S:
    def __init__(self):
        self.a = threading.Lock()
        self.b = threading.Lock()

    def takes_b(self):
        with self.b:
            pass

    def takes_a(self):
        with self.a:
            pass

    def fwd(self):
        with self.a:
            self.takes_b()

    def rev(self):
        with self.b:
            self.takes_a()
"""
    findings = _lint_modules([("fedcrack_tpu/serve/fx.py", src)])
    assert "LOCK001" in rule_ids(findings)


def test_lock_graph_json_payload():
    from fedcrack_tpu.analysis.rules.locks import build_lock_graph

    graph = build_lock_graph([ModuleSource("fedcrack_tpu/serve/fx.py", CYCLE_SRC)])
    payload = graph.to_json()
    assert {n["node_id"] for n in payload["nodes"]} == {
        "fedcrack_tpu/serve/fx.py::S.a",
        "fedcrack_tpu/serve/fx.py::S.b",
    }
    assert len(payload["edges"]) == 2  # a->b and b->a
    assert payload["cycles"] == [sorted(
        ["fedcrack_tpu/serve/fx.py::S.a", "fedcrack_tpu/serve/fx.py::S.b"]
    )]


# ---- dead-code pack ----


def test_dead001_unused_import_positive_and_negative():
    assert "DEAD001" in rule_ids(lint("import os\nx = 1\n"))
    assert "DEAD001" not in rule_ids(lint("import os\nx = os.getpid()\n"))
    # __init__.py re-export surface is exempt.
    assert "DEAD001" not in rule_ids(
        lint("from fedcrack_tpu import configs\n", path="fedcrack_tpu/__init__.py")
    )
    # `import x as x` and __all__ entries are explicit re-exports.
    assert "DEAD001" not in rule_ids(lint("from a import b as b\n"))
    assert "DEAD001" not in rule_ids(
        lint("from a import b\n__all__ = ['b']\n")
    )


def test_dead002_unreachable_positive_and_negative():
    bad = "def f():\n    return 1\n    x = 2\n"
    assert "DEAD002" in rule_ids(lint(bad))
    assert "DEAD002" in rule_ids(lint("if False:\n    x = 1\n"))
    good = "def f():\n    if c:\n        return 1\n    return 2\n"
    assert "DEAD002" not in rule_ids(lint(good))


# ---- serve-plane pack ----


def test_serve001_cache_lookup_without_version_is_error():
    bad_subscript = (
        "def f(self, h, w):\n"
        "    return self._cache[(h, w)]\n"
    )
    bad_get = (
        "def f(self, digest):\n"
        "    return self._cache.get(digest)\n"
    )
    bad_traced = (
        "def f(self, digest):\n"
        "    key = (digest, 0)\n"
        "    return tile_cache.get(key)\n"
    )
    for src in (bad_subscript, bad_get, bad_traced):
        findings = lint(src, path="fedcrack_tpu/serve/fixture.py")
        assert "SERVE001" in rule_ids(findings), src
        hit = findings[rule_ids(findings).index("SERVE001")]
        assert hit.severity is Severity.ERROR
        assert "hot swap" in hit.message


def test_serve001_versioned_keys_and_writes_are_clean():
    good_direct = (
        "def f(self, digest):\n"
        "    return self._cache[(self._version, digest)]\n"
    )
    good_traced = (
        "def f(self, version, digest):\n"
        "    key = (version, digest)\n"
        "    return self._cache.get(key)\n"
    )
    write_only = (
        "def f(self, digest, probs):\n"
        "    self._cache[digest] = probs\n"
        "    del self._cache[digest]\n"
    )
    non_cache = (
        "def f(self, digest):\n"
        "    return self._index.get(digest)\n"
    )
    for src in (good_direct, good_traced, write_only, non_cache):
        assert "SERVE001" not in rule_ids(
            lint(src, path="fedcrack_tpu/serve/fixture.py")
        ), src


def test_serve001_scoped_to_serve_tree():
    bad = "def f(cache, k):\n    return cache[k]\n"
    assert "SERVE001" in rule_ids(lint(bad, path="fedcrack_tpu/serve/fx.py"))
    assert "SERVE001" not in rule_ids(lint(bad, path="fedcrack_tpu/fed/fx.py"))


# ---- kernel-plane pack ----


def test_kern001_pallas_without_twin_positive_and_negative():
    bad = (
        "from jax.experimental import pallas as pl\n"
        "def launch(x):\n"
        "    return pl.pallas_call(_kernel, out_shape=o)(x)\n"
    )
    findings = lint(bad, path="fedcrack_tpu/kernels/fx.py")
    assert "KERN001" in rule_ids(findings)
    f = next(f for f in findings if f.rule == "KERN001")
    assert f.severity is Severity.ERROR
    # Twin form 1: an interpret= kwarg threaded to the interpreter path.
    good_interpret = (
        "from jax.experimental import pallas as pl\n"
        "def launch(x, interpret=False):\n"
        "    return pl.pallas_call(_kernel, out_shape=o, interpret=interpret)(x)\n"
    )
    assert "KERN001" not in rule_ids(
        lint(good_interpret, path="fedcrack_tpu/kernels/fx.py")
    )
    # Twin form 2: a plain-XLA reference function alongside the launch.
    good_reference = (
        "from jax.experimental import pallas as pl\n"
        "def _matmul_reference(x, w):\n"
        "    return x @ w\n"
        "def launch(x):\n"
        "    return pl.pallas_call(_kernel, out_shape=o)(x)\n"
    )
    assert "KERN001" not in rule_ids(
        lint(good_reference, path="fedcrack_tpu/kernels/fx.py")
    )


def test_kern001_fires_per_site_and_ignores_non_calls():
    bad_two_sites = (
        "from jax.experimental import pallas as pl\n"
        "def a(x):\n"
        "    return pl.pallas_call(_ka, out_shape=o)(x)\n"
        "def b(x):\n"
        "    return pl.pallas_call(_kb, out_shape=o)(x)\n"
    )
    findings = [
        f
        for f in lint(bad_two_sites, path="fedcrack_tpu/ops/fx.py")
        if f.rule == "KERN001"
    ]
    assert len(findings) == 2
    # Attribute reads and docstring mentions are not kernel launches.
    quiet = (
        '"""mentions pl.pallas_call in prose only."""\n'
        "from jax.experimental import pallas as pl\n"
        "launcher = pl.pallas_call\n"
    )
    assert "KERN001" not in rule_ids(lint(quiet, path="fedcrack_tpu/ops/fx.py"))


# ---- privacy-plane pack ----


def test_priv001_unseeded_rng_in_privacy_plane():
    """PRIV001 (round 23): inside privacy/ every draw must trace to an
    explicit seed — an argless generator constructor or an ambient entropy
    source silently breaks mask recovery and DP-noise replay."""
    path = "fedcrack_tpu/privacy/fixture.py"
    # Argless construction pulls OS entropy even though it LOOKS like the
    # seeded idiom.
    assert "PRIV001" in rule_ids(
        lint("import numpy as np\ng = np.random.default_rng()\n", path=path)
    )
    assert "PRIV001" in rule_ids(
        lint("import numpy as np\nbg = np.random.Philox()\n", path=path)
    )
    assert "PRIV001" in rule_ids(
        lint("import random\nr = random.Random()\n", path=path)
    )
    # Entropy-by-design sources are never acceptable, seeded or not.
    for src in (
        "import os\nseed = os.urandom(16)\n",
        "import secrets\nseed = secrets.randbits(64)\n",
        "import uuid\nseed = uuid.uuid4().int\n",
    ):
        assert "PRIV001" in rule_ids(lint(src, path=path))
    # The shipped idiom — sha256-rooted explicit seeds into Philox — is
    # clean (this is exactly what secagg.pair_mask / dpsgd do).
    good = (
        "import numpy as np\n"
        "gen = np.random.Generator(np.random.Philox(key=int(seed)))\n"
        "g2 = np.random.default_rng(42)\n"
        "ss = np.random.SeedSequence(1234)\n"
    )
    assert "PRIV001" not in rule_ids(lint(good, path=path))
    # Scoped: the same ambient draw outside privacy/ is DET-territory, not
    # PRIV001's.
    assert "PRIV001" not in rule_ids(
        lint("import os\nseed = os.urandom(16)\n",
             path="fedcrack_tpu/fed/rounds.py")
    )
    # The live privacy package itself must be clean under the rule.
    engine = LintEngine(rules=[rules_by_id()["PRIV001"]])
    modules = engine.load_modules(
        [os.path.join(REPO, "fedcrack_tpu", "privacy")], rel_to=REPO
    )
    assert engine.lint_modules(modules) == []


# ---- suppressions ----


def test_trailing_suppression_with_reason():
    src = "import time\nts = time.time()  # fedlint: disable=DET001 -- record ts\n"
    assert "DET001" not in rule_ids(lint(src))


def test_standalone_comment_guards_next_line():
    src = (
        "import time\n"
        "# fedlint: disable=DET001 -- record ts\n"
        "ts = time.time()\n"
    )
    assert "DET001" not in rule_ids(lint(src))


def test_suppression_is_rule_specific_and_line_specific():
    # Wrong rule id: the finding survives.
    src = "import time\nts = time.time()  # fedlint: disable=DET002\n"
    assert "DET001" in rule_ids(lint(src))
    # Different line: the finding survives.
    src = (
        "import time\n"
        "# fedlint: disable=DET001\n"
        "x = 1\n"
        "ts = time.time()\n"
    )
    assert "DET001" in rule_ids(lint(src))


def test_disable_file_and_disable_all():
    src = (
        "# fedlint: disable-file=DET001\n"
        "import time\n"
        "a = time.time()\n"
        "b = time.time()\n"
    )
    assert "DET001" not in rule_ids(lint(src))
    src = "import time\nts = time.time()  # fedlint: disable=all\n"
    assert rule_ids(lint(src)) == []


# ---- baseline ----


def test_baseline_round_trip_and_edit_invalidation(tmp_path):
    src = "import time\ndeadline = time.time() + 5\n"
    findings = lint(src)
    assert rule_ids(findings) == ["DET001"]
    payload = make_baseline(findings)
    bl = tmp_path / "bl.json"
    bl.write_text(json.dumps(payload))
    loaded = load_baseline(str(bl))
    # Baselined: the same findings vanish.
    assert apply_baseline(findings, loaded) == []
    # Line numbers drift, content doesn't: a moved-but-identical line stays
    # baselined.
    moved = lint("import time\nx = 1\ny = 2\ndeadline = time.time() + 5\n")
    assert apply_baseline(moved, loaded) == []
    # EDITING the offending line invalidates the fingerprint.
    edited = lint("import time\ndeadline = time.time() + 60\n")
    assert rule_ids(apply_baseline(edited, loaded)) == ["DET001"]
    # Count-limited: a NEW second occurrence of a baselined line surfaces.
    doubled = lint(src + "deadline = time.time() + 5\n")
    assert len(apply_baseline(doubled, loaded)) == 1


def test_baseline_version_check(tmp_path):
    bl = tmp_path / "bl.json"
    bl.write_text(json.dumps({"version": 999, "entries": {}}))
    with pytest.raises(ValueError):
        load_baseline(str(bl))


# ---- the tier-1 gate ----


def test_gate_zero_findings_over_fedcrack_tpu():
    """THE CI contract: the full rule set over the real tree, with the
    committed baseline, reports zero findings. A new wall-clock deadline,
    raw checkpoint write, unsorted listing, traced host op, unaudited
    retry, or lock-order cycle anywhere in fedcrack_tpu/ fails this test."""
    engine = LintEngine(rules=all_rules())
    baseline_path = os.path.join(REPO, "fedlint_baseline.json")
    assert os.path.exists(baseline_path), "fedlint_baseline.json must be committed"
    findings = engine.lint_paths(
        [os.path.join(REPO, "fedcrack_tpu")],
        rel_to=REPO,
        baseline=load_baseline(baseline_path),
    )
    assert findings == [], "non-baselined fedlint findings:\n" + "\n".join(
        str(f) for f in findings
    )


def test_committed_lock_graph_artifact_is_current_and_acyclic():
    """tests/data/r11_serve_lock_graph.json is the acceptance artifact: it
    must match the graph the current tree produces (nodes + cycles) and
    stay acyclic — including the serve plane's three locks."""
    from fedcrack_tpu.analysis.rules.locks import build_lock_graph
    from fedcrack_tpu.tools.fedlint import repo_root

    artifact_path = os.path.join(REPO, "tests", "data", "r11_serve_lock_graph.json")
    with open(artifact_path, encoding="utf-8") as f:
        artifact = json.load(f)
    engine = LintEngine(rules=all_rules())
    lock_rule = rules_by_id()["LOCK001"]
    modules = [
        m
        for m in engine.load_modules(
            [os.path.join(repo_root(), "fedcrack_tpu")], rel_to=repo_root()
        )
        if lock_rule.applies_to(m.path)
    ]
    live = build_lock_graph(modules).to_json()
    assert artifact["cycles"] == [] and live["cycles"] == []
    assert {n["node_id"] for n in artifact["nodes"]} == {
        n["node_id"] for n in live["nodes"]
    }
    serve_locks = {n["node_id"] for n in live["nodes"] if "/serve/" in n["node_id"]}
    assert serve_locks == {
        "fedcrack_tpu/serve/batcher.py::MicroBatcher._lock",
        "fedcrack_tpu/serve/hot_swap.py::ModelVersionManager._lock",
        "fedcrack_tpu/serve/service.py::ServeService._lock",
        # Round 17: the fleet plane — commit-barrier slot lock, router
        # dispatch lock, rolling-SLO window lock (all leaf-or-acyclic;
        # router -> batcher is the graph's one sanctioned edge).
        "fedcrack_tpu/serve/fleet.py::FleetVersionManager._lock",
        "fedcrack_tpu/serve/router.py::FleetRouter._lock",
        "fedcrack_tpu/serve/router.py::RollingPercentiles._lock",
        # Round 19: the video-session manager's cross-session accounting
        # lock (leaf — per-session state is single-handler by design).
        "fedcrack_tpu/serve/stream.py::StreamSessionManager._lock",
        # Round 22: the elastic-fleet plane — autoscaler decision lock and
        # the shadow lane's mirror/controller locks (all leaves; neither
        # the scaler nor the shadow path holds a lock across fleet calls).
        "fedcrack_tpu/serve/autoscaler.py::FleetAutoscaler._lock",
        "fedcrack_tpu/serve/shadow.py::ShadowMirror._lock",
        "fedcrack_tpu/serve/shadow.py::ShadowController._lock",
    }


# ---- the CLI ----


def test_cli_list_rules_and_unknown_rule(capsys):
    from fedcrack_tpu.tools.fedlint import main

    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("DET001", "DUR001", "TRACE001", "TRANS001", "LOCK001", "DEAD001"):
        assert rid in out
    assert main(["--rules", "NOPE999"]) == 2


def test_cli_findings_exit_code_json_and_baseline_cycle(tmp_path, capsys):
    from fedcrack_tpu.tools.fedlint import main

    bad = tmp_path / "fx.py"
    bad.write_text("import time\ndeadline = time.time() + 5\n")
    out_json = tmp_path / "findings.json"
    rc = main(
        ["--no-baseline", "--no-cache", "--json", str(out_json), str(bad)]
    )
    assert rc == 1
    payload = json.loads(out_json.read_text())
    assert [f["rule"] for f in payload["findings"]] == ["DET001"]
    assert payload["findings"][0]["fingerprint"]
    # --write-baseline, then the same tree under that baseline is clean.
    bl = tmp_path / "bl.json"
    assert main(["--no-cache", "--write-baseline", str(bl), str(bad)]) == 0
    assert main(["--no-cache", "--baseline", str(bl), str(bad)]) == 0
    capsys.readouterr()
    # --json - owns stdout: the payload parses as-is, human lines go to
    # stderr, so the documented `fedlint --json - | jq` pipeline works.
    rc = main(["--no-baseline", "--no-cache", "--json", "-", str(bad)])
    captured = capsys.readouterr()
    assert rc == 1
    piped = json.loads(captured.out)
    assert [f["rule"] for f in piped["findings"]] == ["DET001"]
    assert "DET001" in captured.err and "finding(s)" in captured.err


def test_cli_lock_graph_emission(tmp_path):
    from fedcrack_tpu.tools.fedlint import main

    out = tmp_path / "graph.json"
    rc = main(["--no-cache", "--lock-graph", str(out)])
    assert rc == 0
    payload = json.loads(out.read_text())
    assert set(payload) == {"nodes", "edges", "cycles"}
    assert payload["cycles"] == []


def test_cli_result_cache_round_trip(tmp_path, capsys):
    from fedcrack_tpu.tools.fedlint import main

    bad = tmp_path / "fx.py"
    bad.write_text("import time\ndeadline = time.time() + 5\n")
    cache = tmp_path / "cache"
    argv = ["--no-baseline", "--cache-dir", str(cache), str(bad)]
    assert main(argv) == 1          # cold: finds + caches
    assert (cache / "cache.json").exists()
    assert main(argv) == 1          # warm: same findings from cache
    out = capsys.readouterr().out
    assert "DET001" in out


# ---- runtime sanitizers ----


def test_recompile_sentry_counts_and_raises():
    import jax
    import numpy as np

    from fedcrack_tpu.analysis.sanitizers import RecompileError, RecompileSentry

    fn = jax.jit(lambda x: x * 2)
    if not RecompileSentry.supported(fn):
        pytest.skip("jit wrapper exposes no _cache_size on this jax build")
    sentry = RecompileSentry()
    sentry.watch("fn", fn)
    with sentry.expect(compiles=1):
        fn(jax.device_put(np.ones((4,), np.float32)))
    sentry.mark()
    fn(jax.device_put(np.zeros((4,), np.float32)))  # same signature: cached
    sentry.assert_steady()
    fn(jax.device_put(np.ones((8,), np.float32)))   # new shape: retrace
    with pytest.raises(RecompileError, match="unexpected recompiles"):
        sentry.assert_steady()
    sentry.mark()
    with pytest.raises(RecompileError, match="expected exactly 0"):
        with sentry.expect(compiles=0):
            fn(jax.device_put(np.ones((16,), np.float32)))


def test_recompile_sentry_rejects_non_jit_objects():
    from fedcrack_tpu.analysis.sanitizers import RecompileSentry

    with pytest.raises(TypeError, match="_cache_size"):
        RecompileSentry().watch("x", lambda: None)


def test_no_implicit_transfers_guard():
    import jax
    import numpy as np

    from fedcrack_tpu.analysis.sanitizers import no_implicit_transfers

    fn = jax.jit(lambda x: x + 1)
    host = np.ones((4,), np.float32)
    dev = jax.device_put(host)
    fn(dev)  # compile outside the guard
    with no_implicit_transfers():
        out = fn(dev)                      # device-resident: fine
        host_out = jax.device_get(out)     # explicit d2h: fine
    assert host_out.shape == (4,)
    with pytest.raises(Exception, match="[Dd]isallowed"):
        with no_implicit_transfers():
            fn(host)  # implicit h2d of a numpy arg


def test_lock_order_monitor_raises_on_inversion_with_stacks():
    from fedcrack_tpu.analysis.sanitizers import (
        LockOrderMonitor,
        LockOrderViolation,
        _MonitoredLock,
    )

    mon = LockOrderMonitor()
    a = _MonitoredLock("a", mon)
    b = _MonitoredLock("b", mon)
    with a:
        with b:
            pass
    assert ("a", "b") in mon.edges()
    with b:
        with pytest.raises(LockOrderViolation) as ei:
            a.acquire()
    # Both acquisition stacks in the report: actionable, not just "deadlock".
    assert "this acquisition" in str(ei.value)
    assert "earlier" in str(ei.value)
    # Same-order re-acquisition stays legal.
    with a:
        with b:
            pass


def test_make_lock_plain_in_production_monitored_in_debug(monkeypatch):
    import fedcrack_tpu.analysis.sanitizers as san

    monkeypatch.delenv("FEDCRACK_LOCK_DEBUG", raising=False)
    san.uninstall_monitor()
    lock = san.make_lock("x")
    assert isinstance(lock, type(threading.Lock()))
    try:
        mon = san.install_monitor()
        mlock = san.make_lock("x")
        assert isinstance(mlock, san._MonitoredLock)
        with mlock:
            pass
        assert mon is san._monitor
    finally:
        san.uninstall_monitor()


def test_serve_plane_locks_recorded_under_monitor(stack_free_engine=None):
    """The serve plane's three locks are built through make_lock: with a
    monitor installed, real traffic records named acquisitions (the debug
    twin of the static LOCK001 graph)."""
    import fedcrack_tpu.analysis.sanitizers as san
    from fedcrack_tpu.serve.batcher import StaticWeights

    san.uninstall_monitor()
    mon = san.install_monitor()
    try:
        from fedcrack_tpu.serve.hot_swap import ModelVersionManager

        class _NullEngine:
            def prepare(self, v):
                return v

        mgr = ModelVersionManager(_NullEngine(), {"params": {}})
        assert mgr.snapshot()[0] == 0
        assert isinstance(mgr._lock, san._MonitoredLock)
        assert isinstance(StaticWeights({}, 0).snapshot(), tuple)
    finally:
        san.uninstall_monitor()


# ---- fleet plane (round 22) ----


def test_fleet001_replica_set_mutation_outside_chokepoints():
    """Replica-set surgery in serve/ must route through ServeFleet — a
    convenience mutation desynchronizes the router's replica list from the
    fleet manager's weights slots."""
    append = "def grow(self):\n    self.router.replicas.append(object())\n"
    assert "FLEET001" in rule_ids(lint(append, path="fedcrack_tpu/serve/router.py"))
    delete = "def shrink(self):\n    del self.router.replicas[1]\n"
    assert "FLEET001" in rule_ids(lint(delete, path="fedcrack_tpu/serve/front.py"))
    slot = "def swap(self, r):\n    self.router.replicas[0] = r\n"
    assert "FLEET001" in rule_ids(lint(slot, path="fedcrack_tpu/serve/router.py"))
    # The lifecycle verbs ARE surgery wherever they're invoked in serve/.
    verb = "def tick(self):\n    self.fleet.remove_replica(2)\n"
    assert "FLEET001" in rule_ids(lint(verb, path="fedcrack_tpu/serve/shadow.py"))


def test_fleet001_chokepoints_and_plain_assign_exempt():
    # The fleet owns both lists; the autoscaler is the controller.
    verb = "def tick(self):\n    self.fleet.remove_replica(2)\n"
    assert "FLEET001" not in rule_ids(lint(verb, path="fedcrack_tpu/serve/fleet.py"))
    assert "FLEET001" not in rule_ids(
        lint(verb, path="fedcrack_tpu/serve/autoscaler.py")
    )
    # Constructing the initial list is legal everywhere — the router
    # receives the list it routes over; it just may not reshape it.
    assign = "def __init__(self, replicas):\n    self.replicas = list(replicas)\n"
    assert "FLEET001" not in rule_ids(
        lint(assign, path="fedcrack_tpu/serve/router.py")
    )
    # Outside serve/ (drills, benches driving kill_replica as the crash
    # hook) is deliberately out of scope.
    drill = "def crash(fleet):\n    fleet.router.kill_replica(1)\n"
    assert "FLEET001" not in rule_ids(
        lint(drill, path="fedcrack_tpu/tools/chaos_drill.py")
    )


def test_fleet001_own_serve_tree_is_clean():
    """The shipped serving plane obeys its own rule."""
    import glob

    engine = LintEngine(rules=[rules_by_id()["FLEET001"]])
    for path in sorted(glob.glob(os.path.join(REPO, "fedcrack_tpu", "serve", "*.py"))):
        with open(path) as f:
            src = f.read()
        rel = os.path.relpath(path, REPO)
        assert rule_ids(engine.lint_source(src, path=rel)) == [], rel
