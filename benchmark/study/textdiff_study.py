"""How far the block-diffusion cell's program lies from the reference, and how
far the control and the planted faults do: the readings every limit of its
``correct`` is set from (``seed_study.py``'s twin for the second traffic kind).

    python3 benchmark/study/textdiff_study.py <workload> <first seed> <seeds> <control seeds> [kinds] [rounds]

In one process, for each seed: the cell's weights and data, the program
through its checked rounds (the run's own ``Cell.drive``), the float32
reference, and every number of ``lib/federated_textdiff_rounds.compare``
between the two. On the first ``control seeds`` of them also, each put in the
program's place and compared with the same reference:

- ``control_fp8``: the reference with every matrix product's operands in
  float8_e4m3fn (gradients e5m2), the nearest precision below bfloat16;
- ``witness_bf16``: the reference with bfloat16 operands, what the
  configuration states (it should read like the program);
- ``fault_all_experts``: no pair left out (an absent expert's pair is computed
  by held expert ``e mod held``);
- ``fault_no_renorm``: ``w_e = g_e``, the chosen experts' weights not
  renormalised;
- ``fault_causal_clean``: the clean half attends token by token, not block by
  block;
- ``fault_stale_slab``: round 0's data again in round 1.

One JSON line a reading goes to ``chiprun_out/study_<workload>.jsonl``; a
table of minimum, median and maximum by kind and number is printed last.
``rounds`` (default all) limits which checked rounds are followed: a fault
that round 0 shows needs no second round.
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

from lib import federated_textdiff_rounds as ft
from lib.compile_log import CompileLog
from run import load_spec

VARIANTS = {
    "control_fp8": {"operands": "float8_e4m3fn"},
    "witness_bf16": {"operands": "bfloat16"},
    "fault_all_experts": {"fault": "all_experts"},
    "fault_no_renorm": {"fault": "no_renorm"},
    "fault_causal_clean": {"fault": "causal_clean"},
    "fault_stale_slab": {"fault": "stale_slab"},
}


def main():
    workload, first, n_seeds, n_control = sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4])
    only = set(sys.argv[5].split(",")) if len(sys.argv) > 5 and sys.argv[5] != "all" else None
    rounds = {int(k) for k in sys.argv[6].split(",")} if len(sys.argv) > 6 else set(range(8))
    spec = load_spec(workload)
    used = jax.devices()[: spec["workload"]["chips"]]
    if used[0].platform != "tpu":
        raise SystemExit("textdiff study: needs the cell's chip")
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
        cell = ft.Cell(spec, seed, used)
        t_build = time.perf_counter() - t
        driven = cell.drive(0.0, None, time.perf_counter(), compiles)
        cell.round_fn = None
        starts = cell.starts(driven["program_rounds"])
        followed = [s if k in rounds else None for k, s in enumerate(starts)]
        t = time.perf_counter()
        reference = cell.reference(followed)
        t_ref = time.perf_counter() - t
        record(
            "program", seed, ft.compare(starts, driven["program_rounds"], reference),
            build_s=t_build, reference_s=t_ref, round_s=[r.wall_clock_s for r in driven["records"]],
            loss=[r["loss"] for r in driven["program_rounds"]], ref_loss=[r and r["loss"] for r in reference],
        )
        if n >= n_control:
            continue
        for kind, variant in VARIANTS.items():
            if only is not None and kind not in only:
                continue
            # A stale slab shows in a later round only.
            wanted = [s if (k > 0 or kind != "fault_stale_slab") else None for k, s in enumerate(followed)]
            if all(s is None for s in wanted):
                continue
            t = time.perf_counter()
            stood_in = cell.reference(wanted, **variant)
            record(kind, seed, ft.compare(starts, stood_in, [r if s is not None else None for r, s in zip(reference, wanted)]),
                   variant_s=time.perf_counter() - t)

    names = [k for k in rows[0] if k.startswith(("loss_r", "step_loss_r", "total_", "direction_", "masked_acc_r", "expert_rows_r"))]
    print(f"{'kind':20s} {'number':18s} {'n':>3s} {'min':>10s} {'median':>10s} {'max':>10s}")
    for kind in dict.fromkeys(r["kind"] for r in rows):
        for name in names:
            v = [r[name] for r in rows if r["kind"] == kind and name in r]
            if v:
                print(f"{kind:20s} {name:18s} {len(v):3d} {min(v):10.5f} {float(np.median(v)):10.5f} {max(v):10.5f}")


if __name__ == "__main__":
    main()
