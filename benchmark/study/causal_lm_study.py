"""How far the causal language model's cell's program lies from the reference,
and how far the control and the planted faults do: the readings every limit of
its ``correct`` is set from (``textdiff_study.py``'s twin for the third
traffic kind), each reading judged by the harness's own ``check.judge`` under
the cell's own limits.

    python3 benchmark/study/causal_lm_study.py <workload> <first seed> <seeds> <control seeds> [kinds] [rounds] [deadline s]

In one process, for each seed: the cell's weights and data, the program
through its checked rounds (the run's own ``Cell.drive``), the float32
reference, and every number of ``lib/federated_causal_lm_rounds.compare``
between the two. On the first ``control seeds`` of them also, each put in the
program's place and compared with the same reference:

- ``control_fp8``: the reference with every matrix product's operands in
  float8_e4m3fn (gradients e5m2), the nearest precision below bfloat16;
- ``witness_bf16``: the reference with bfloat16 operands, what the
  configuration states (it should read like the program);
- ``fault_no_bias``: the eight experts chosen by ``s`` alone;
- ``fault_no_scale``: ``routed_scaling_factor`` dropped;
- ``fault_no_shared``: no shared expert;
- ``fault_rope_on_all``: rotary over all 192 lanes of queries and keys;
- ``fault_latent_norm_off``: neither latent is normed;
- ``fault_noncausal``: every query sees every key;
- ``fault_no_mtp``: ``lambda`` 0 (only with the module on);
- ``fault_bias_moves``: the chosen experts' weights read from ``s + b``, so
  that the selection bias takes a gradient and Adam moves it;
- ``fault_stale_slab``: round 0's data again in round 1.

``kinds`` (default ``all``) is a comma list, followed in its order, of
``<kind>`` or ``<kind>@<round>[+<round>]``: the checked rounds that variant
follows; ``rounds`` (default all) is what a kind without its own follows
(``fault_stale_slab`` always follows round 1 and later). No variant is started
once ``deadline s`` have passed since the process started.

One JSON line a reading goes to ``chiprun_out/study_<workload>.jsonl``, with
``verdict`` (``correct`` or ``not correct``: ``check.judge`` over the numbers
of the rounds it followed, ``window_compiles`` and ``failed_rounds`` at 0) and
``failed_by`` (the limits it broke); a table of minimum, median and maximum by
kind and number is printed last. The benchmark's own runs of the cell are
further readings of the program: their ``info.numbers``.

    python3 benchmark/study/causal_lm_study.py judge <workload> <rows.jsonl> [...]

judges recorded readings again under the cell's limits as they stand now (no
chip needed): limits are set after the readings they are set from.
"""

import gc
import json
import os
import re
import sys
import time

T_START = time.perf_counter()
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [ROOT, BENCH_DIR]

import jax
import numpy as np

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

from lib import check, federated_causal_lm_rounds as fc
from lib.compile_log import CompileLog
from run import load_spec

VARIANTS = {
    "control_fp8": {"operands": "float8_e4m3fn"},
    "witness_bf16": {"operands": "bfloat16"},
    "fault_no_bias": {"fault": "no_bias"},
    "fault_no_scale": {"fault": "no_scale"},
    "fault_no_shared": {"fault": "no_shared"},
    "fault_rope_on_all": {"fault": "rope_on_all"},
    "fault_latent_norm_off": {"fault": "latent_norm_off"},
    "fault_noncausal": {"fault": "noncausal"},
    "fault_no_mtp": {"fault": "no_mtp"},
    "fault_bias_moves": {"fault": "bias_moves"},
    "fault_stale_slab": {"fault": "stale_slab"},
}
NUMBERS = ("loss_r", "step_loss_r", "total_", "direction_", "next_", "mtp_loss_r", "expert_rows_r", "router_bias")


def plan(kinds: str, rounds: set) -> list:
    """``[(kind, rounds it follows)]`` in the order given."""
    if kinds == "all":
        return [(kind, rounds) for kind in VARIANTS]
    out = []
    for item in kinds.split(","):
        kind, _, own = item.partition("@")
        if kind not in VARIANTS:
            raise SystemExit(f"causal LM study: no variant {kind!r}")
        out.append((kind, {int(k) for k in own.split("+")} if own else rounds))
    return out


def verdict(numbers: dict, limits: dict) -> dict:
    """``check.judge`` over the rounds these numbers cover: the cell's limits
    on those rounds' numbers, and the two exact ones at 0."""
    of_round = lambda name: (re.search(r"_r(\d+)$", name) or [None, None])[1]
    followed = {of_round(name) for name in numbers}
    held = {k: v for k, v in limits.items() if of_round(k) is None or of_round(k) in followed}
    ok, compared = check.judge(dict(numbers, window_compiles=0.0, failed_rounds=0.0), held)
    return {
        "verdict": "correct" if ok else "not correct",
        "failed_by": [k for k, c in compared.items() if not c["value"] <= c["limit"]],
    }


def study_seed(spec: dict, seed: int, used, variants: list, record, deadline_s: float) -> None:
    """The program, the reference and ``variants`` on one seed."""
    limits = spec["limits"]
    t = time.perf_counter()
    cell = fc.Cell(spec, seed, used)
    t_build = time.perf_counter() - t
    driven = cell.drive(0.0, None, time.perf_counter(), CompileLog())
    cell.round_fn = None
    starts = cell.starts(driven["program_rounds"])
    t = time.perf_counter()
    reference = cell.reference(starts)
    t_ref = time.perf_counter() - t
    numbers = fc.compare(starts, driven["program_rounds"], reference)
    record(
        "program", seed, numbers, **verdict(numbers, limits),
        build_s=t_build, reference_s=t_ref, round_s=[r.wall_clock_s for r in driven["records"]],
        loss=[r["loss"] for r in driven["program_rounds"]], ref_loss=[r and r["loss"] for r in reference],
    )
    with_module = bool(spec["config"]["num_nextn_predict_layers"])
    for kind, rounds in variants:
        if kind == "fault_no_mtp" and not with_module:
            continue
        if time.perf_counter() - T_START > deadline_s:
            print(f"deadline: {kind} and what follows it not started", flush=True)
            return
        # A stale slab shows in a later round only.
        follows = (lambda k: k > 0) if kind == "fault_stale_slab" else (lambda k: k in rounds)
        wanted = [s if follows(k) else None for k, s in enumerate(starts)]
        if all(s is None for s in wanted):
            continue
        t = time.perf_counter()
        stood_in = cell.reference(wanted, **VARIANTS[kind])
        numbers = fc.compare(starts, stood_in, [r if s is not None else None for r, s in zip(reference, wanted)])
        record(kind, seed, numbers, **verdict(numbers, limits), variant_s=time.perf_counter() - t)
        # A variant is a program of its own (its fault and precision are
        # static): let go of it and of its 2 GB result before the next
        # (ten of them held at once ran a 40 GiB host out of memory).
        del stood_in
        jax.clear_caches()
        gc.collect()


def judge_again(workload: str, paths: list) -> None:
    limits = load_spec(workload)["limits"]
    for path in paths:
        for line in open(path):
            row = json.loads(line)
            said = verdict({k: v for k, v in row.items() if re.search(r"_r\d+$", k)}, limits)
            print(f"{row['kind']:22s} {row['seed']:11d} {said['verdict']:12s} {','.join(said['failed_by'])}")


def main():
    if sys.argv[1] == "judge":
        return judge_again(sys.argv[2], sys.argv[3:])
    workload, first, n_seeds, n_control = sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4])
    rounds = {int(k) for k in sys.argv[6].split(",")} if len(sys.argv) > 6 and sys.argv[6] != "all" else set(range(8))
    variants = plan(sys.argv[5] if len(sys.argv) > 5 else "all", rounds)
    deadline_s = float(sys.argv[7]) if len(sys.argv) > 7 else float("inf")
    spec = load_spec(workload)
    used = jax.devices()[: spec["workload"]["chips"]]
    if used[0].platform != "tpu":
        raise SystemExit("causal LM study: needs the cell's chip")
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
        study_seed(spec, first + 7919 * n, used, variants if n < n_control else [], record, deadline_s)

    print(f"{'kind':22s} {'number':22s} {'n':>3s} {'min':>10s} {'median':>10s} {'max':>10s}  verdicts")
    for kind in dict.fromkeys(r["kind"] for r in rows):
        of_kind = [r for r in rows if r["kind"] == kind]
        said = ",".join(r["verdict"] for r in of_kind)
        for name in dict.fromkeys(k for r in of_kind for k in r if k.startswith(NUMBERS)):
            v = [r[name] for r in of_kind if name in r]
            print(f"{kind:22s} {name:22s} {len(v):3d} {min(v):10.6f} {float(np.median(v)):10.6f} {max(v):10.6f}  {said}")


if __name__ == "__main__":
    main()
