"""The benchmark's own checks, on the CPU at tiny sizes (``pytest benchmark/tests``).
The repo's root ``conftest.py`` pins JAX to eight virtual CPU devices."""

import json
import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for path in (ROOT, BENCH_DIR):
    if path not in sys.path:
        sys.path.insert(0, path)


def read_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture
def tiny_spec(tmp_path):
    """A cell's spec at a size a test can hold: 32 px, 6 steps of 4, float32
    compute so that a sound run sits far inside the cell's own limits."""

    def make(workload_name: str, mesh: list | None = None) -> dict:
        import run

        spec = run.load_spec(workload_name)
        if mesh is not None:
            # A cell that BENCHMARK.json does not list yet: another mesh.
            spec["traffic"] = dict(spec["traffic"], mesh=mesh)
            spec["workload"] = dict(spec["workload"], chips=mesh[0] * mesh[1])
        spec["root"] = str(tmp_path)
        config = spec["config"]
        config["model"] = dict(config["model"], img_size=32, compute_dtype="float32")
        config["batch_size"], config["train_samples"] = 4, 24
        spec["traffic"] = dict(spec["traffic"], base_samples=8)
        return spec

    return make
