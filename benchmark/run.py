"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic, its per-layer metrics and its
limits are found by name: ``BENCHMARK.json`` at the root of the checkout,
``configs/<config>.json``, ``traffic/<traffic>.json``, ``metrics/<metric>.py``
and ``limits/<workload>.json`` here. The traffic file's ``kind`` names the
module under ``lib/`` that drives the system. The last line of standard
output is the result; the numbers compared for ``correct`` are also the last
lines of standard error.
"""

import time

T_START = time.perf_counter()

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_spec(workload_name: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    workloads = {w["name"]: w for w in bench["workloads"]}
    if workload_name not in workloads:
        raise SystemExit(f"benchmark: no workload {workload_name!r}; BENCHMARK.json has {sorted(workloads)}")
    workload = workloads[workload_name]
    config_entry = next(c for c in bench["configs"] if c["name"] == workload["config"])

    def read(*parts):
        with open(os.path.join(ROOT, *parts)) as f:
            return json.load(f)

    def in_cell(metric):
        return workload_name in metric.get("workloads", [workload_name])

    return {
        "root": ROOT,
        "workload": workload,
        "config": read(config_entry["file"]),
        "traffic": read("benchmark", "traffic", workload["traffic"] + ".json"),
        "limits": read("benchmark", "limits", workload_name + ".json"),
        "end_to_end": [m for m in bench["end_to_end"] if in_cell(m)],
        "per_layer": [m for m in bench["per_layer"] if in_cell(m)],
    }


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args()
    spec = load_spec(args.workload)

    if not os.path.isdir(os.path.join(ROOT, "fedcrack_tpu")):
        print("benchmark: the program (fedcrack_tpu/) is not in this checkout", file=sys.stderr)
        return 3
    sys.path[:0] = [ROOT, BENCH_DIR]

    import importlib

    import jax

    # The compile cache lives in the checkout (or where the caller put it),
    # with no floor: every program this process compiles is found again.
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    driver = importlib.import_module("lib." + spec["traffic"]["kind"])
    result = driver.run(spec, args.seed, args.seconds, bool(args.trace), T_START)

    for name, pair in result["compared"].items():
        print(f"compared {name} {pair['value']!r} limit {pair['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
