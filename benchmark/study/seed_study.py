"""How far the program lies from the reference, and how far the control and
the planted faults do: the readings every limit of ``correct`` is set from.

    python3 benchmark/study/seed_study.py <workload> <first seed> <seeds> <control seeds> [kinds] [rounds]

In one process, for each seed: the cell's weights and data, the program
through its checked rounds (the run's own ``Cell.drive``), the float32
reference, and every number of ``lib/check.py`` between the two. On the first
``control seeds`` of them also, each put in the program's place and compared
with the same reference:

- ``control_fp8``: the reference with every convolution's operands in
  float8_e4m3fn, the nearest precision below the configuration's bfloat16;
- ``witness_bf16``: the reference with bfloat16 operands, what the
  configuration states (it should read like the program);
- ``fault_half_batch``: the reference leaving out the second half of every
  batch, every mean taken over the rest;
- ``fault_stale_slab`` (cells that check two rounds or more): the reference
  fed round 0's slab again in round 1, a stale buffer of the overlapped
  staging;
- ``fault_lost_carry`` (the same cells): the reference starting round 1 from
  the seed's weights again, a carry from round to round that is lost;
- ``fault_no_exchange`` (cells of several clients): the reference handing
  back its first client's model in place of the average.

One JSON line a reading goes to ``chiprun_out/study_<workload>.jsonl``; a
table of minimum, median and maximum by kind and number is printed last.
"""

import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [ROOT, BENCH_DIR]

import jax
import numpy as np

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

from lib import check, federated_rounds as fr
from lib.compile_log import CompileLog
from run import load_spec


def main():
    workload, first, n_seeds, n_control = sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4])
    only = set(sys.argv[5].split(",")) if len(sys.argv) > 5 else None
    # The checked rounds that are followed and compared (all by default).
    rounds = {int(k) for k in sys.argv[6].split(",")} if len(sys.argv) > 6 else set(range(8))
    spec = load_spec(workload)
    chips = spec["workload"]["chips"]
    used = jax.devices()[:chips]
    if used[0].platform != "tpu" or len(used) < chips:
        raise SystemExit("seed study: needs the cell's chips")
    compiles = CompileLog()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", f"study_{workload}.jsonl"), "a")
    rows = []

    def record(kind, seed, numbers, **extra):
        row = {"workload": workload, "kind": kind, "seed": seed, **numbers, **extra}
        rows.append(row)
        out.write(json.dumps(row) + "\n")
        out.flush()
        print(json.dumps(row), flush=True)

    for n in range(n_seeds):
        seed = first + 7919 * n
        t = time.perf_counter()
        cell = fr.Cell(spec, seed, used)
        t_build = time.perf_counter() - t
        driven = cell.drive(0.0, None, time.perf_counter(), compiles)
        cell.round_fn = None
        # Only the rounds asked for are followed; every round keeps its start.
        starts = cell.starts(driven["program_rounds"])
        followed = [s if k in rounds else None for k, s in enumerate(starts)]
        t = time.perf_counter()
        reference = cell.reference(followed)
        t_ref = time.perf_counter() - t
        record(
            "program", seed, check.compare(starts, driven["program_rounds"], reference),
            build_s=t_build, reference_s=t_ref, round_s=[r.wall_clock_s for r in driven["records"]],
            loss=[r["loss"] for r in driven["program_rounds"]], ref_loss=[r and r["loss"] for r in reference],
        )
        if n >= n_control:
            continue
        variants = {
            "control_fp8": {"operands": "float8_e4m3fn"},
            "witness_bf16": {"operands": "bfloat16"},
            "fault_half_batch": {"fault": "half_batch"},
        }
        if cell.checked > 1:
            variants["fault_stale_slab"] = {"fault": "stale_slab"}
            variants["fault_lost_carry"] = {"fault": "lost_carry"}
        if cell.clients > 1:
            variants["fault_no_exchange"] = {"fault": "no_exchange"}
        for kind, variant in variants.items():
            if only is not None and kind not in only:
                continue
            stood_in = cell.reference(followed, **variant)
            record(kind, seed, check.compare(starts, stood_in, reference))

    names = [k for k in rows[0] if k.startswith(("loss_r", "acc_r", "change_", "total_", "stats_", "direction_"))]
    print(f"{'kind':18s} {'number':18s} {'n':>3s} {'min':>10s} {'median':>10s} {'max':>10s}")
    for kind in dict.fromkeys(r["kind"] for r in rows):
        for name in names:
            v = [r[name] for r in rows if r["kind"] == kind and name in r]
            if v:
                print(f"{kind:18s} {name:18s} {len(v):3d} {min(v):10.5f} {float(np.median(v)):10.5f} {max(v):10.5f}")


if __name__ == "__main__":
    main()
