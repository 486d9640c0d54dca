"""``BENCHMARK.json`` against the layout: every name finds its files."""

import os
import re

import pytest

from conftest import BENCH_DIR, ROOT, read_json

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return read_json("BENCHMARK.json")


def test_names_units_and_files(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"] and bench["command"][-1] == "benchmark/run.py"
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and c["file"].startswith("benchmark/configs/")
        held = read_json(c["file"])
        assert held["name"] == c["name"]
        for key in c["reduced"]:
            assert NAME.match(key) and key in held["published"], key
        assert os.path.exists(os.path.join(BENCH_DIR, "reference", held["reference"] + ".py"))
    pairs = set()
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        traffic = read_json("benchmark", "traffic", w["traffic"] + ".json")
        assert traffic["mesh"][0] * traffic["mesh"][1] == w["chips"]
        assert os.path.exists(os.path.join(BENCH_DIR, "lib", traffic["kind"] + ".py"))
        assert os.path.exists(os.path.join(BENCH_DIR, "limits", w["name"] + ".json"))
    assert {c["name"] for c in bench["configs"]} == {w["config"] for w in bench["workloads"]}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(bench["workloads"]) // 4)


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    end = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in end and end["setup_s"]["bound"] <= 0.1
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert NAME.match(m["name"]) and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert m["moves"] in end and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert set(m.get("workloads", [])) <= cells
        assert os.path.exists(os.path.join(BENCH_DIR, "metrics", m["name"] + ".py")), m["name"]
        layers.add(m["layer"])
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for layer in layers:
        assert layer in perf, layer


def test_every_file_under_the_benchmark_is_named_from_the_allowed_characters():
    for folder, _, files in os.walk(BENCH_DIR):
        if "__pycache__" in folder:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(folder, f), ROOT)
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
