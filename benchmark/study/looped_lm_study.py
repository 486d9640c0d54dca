"""How far the looped language model's cell's program lies from the reference,
and how far the control and the planted faults do: the readings every limit of
its ``correct`` is set from, each judged by the harness's own ``check.judge``
under the cell's own limits.

    python3 benchmark/study/looped_lm_study.py <workload> <first seed> <seeds> <control seeds> [kinds] [rounds] [deadline s]
    python3 benchmark/study/looped_lm_study.py judge <workload> <rows.jsonl> [...]

The command line, the plan of kinds and rounds, the verdicts, the table and
the output file (``chiprun_out/study_<workload>.jsonl``) are
``causal_lm_study.py``'s, called and not copied, as ``hybrid_lm_study.py``
calls them: this file loads an instance of that module for itself and binds
in it the names that differ for this traffic kind (``fc``: the driver;
``VARIANTS``; ``NUMBERS``; ``study_seed``). On the first ``control seeds``
seeds, each put in the program's place and compared with the same reference:

- ``control_fp8``: the reference with every matrix product's operands in
  float8_e4m3fn (gradients e5m2), the nearest precision below bfloat16;
- ``witness_bf16``: the reference with bfloat16 operands, what the
  configuration states (it should read like the program);
- ``fault_one_loop`` (the stack runs once), ``fault_last_exit_only`` (the loss
  on the last exit alone), ``fault_no_entropy`` (``beta`` 0),
  ``fault_no_post_norm`` (no norm on a block's output),
  ``fault_norm_not_carried`` (the next pass starts from the stream before the
  final norm): the reference's own faults (``reference/ouro_looped_lm.py``);
- ``fault_stale_slab``: round 0's data again in round 1.
"""

import gc
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]

import jax

from lib import federated_looped_lm_rounds as fl
from lib.compile_log import CompileLog
from lib.federated_rounds import _load_module

_study = _load_module(os.path.join(BENCH_DIR, "study", "causal_lm_study.py"), "bench_study_causal_lm_of_looped")

FAULTS = ("one_loop", "last_exit_only", "no_entropy", "no_post_norm", "norm_not_carried", "stale_slab")
VARIANTS = {
    "control_fp8": {"operands": "float8_e4m3fn"},
    "witness_bf16": {"operands": "bfloat16"},
    **{f"fault_{name}": {"fault": name} for name in FAULTS},
}
NUMBERS = ("loss_r", "step_loss_r", "total_", "direction_", "next_", "exit_r", "loop_nll_r")


def study_seed(spec: dict, seed: int, used, variants: list, record, deadline_s: float) -> None:
    """The program, the reference and ``variants`` on one seed."""
    limits = spec["limits"]
    t = time.perf_counter()
    cell = fl.Cell(spec, seed, used)
    t_build = time.perf_counter() - t
    driven = cell.drive(0.0, None, time.perf_counter(), CompileLog())
    cell.round_fn = None
    starts = cell.starts(driven["program_rounds"])
    t = time.perf_counter()
    reference = cell.reference(starts)
    t_ref = time.perf_counter() - t
    numbers = fl.compare(starts, driven["program_rounds"], reference)
    memory = used[0].memory_stats() or {}
    record(
        "program", seed, numbers, **_study.verdict(numbers, limits),
        build_s=t_build, reference_s=t_ref, round_s=[r.wall_clock_s for r in driven["records"]],
        loss=[r["loss"] for r in driven["program_rounds"]], ref_loss=[r and r["loss"] for r in reference],
        exit_mass=[r["exit_mass"] for r in driven["program_rounds"]], ref_exit_mass=[r and r["exit_mass"] for r in reference],
        loop_nll=[r["loop_nll"] for r in driven["program_rounds"]], ref_loop_nll=[r and r["loop_nll"] for r in reference],
        peak_bytes_in_use=memory.get("peak_bytes_in_use"), bytes_reserved=memory.get("bytes_reserved"),
    )
    for kind, rounds in variants:
        if time.perf_counter() - _study.T_START > deadline_s:
            print(f"deadline: {kind} and what follows it not started", flush=True)
            return
        # A stale slab shows in a later round only.
        follows = (lambda k: k > 0) if kind == "fault_stale_slab" else (lambda k: k in rounds)
        wanted = [s if follows(k) else None for k, s in enumerate(starts)]
        if all(s is None for s in wanted):
            continue
        t = time.perf_counter()
        stood_in = cell.reference(wanted, **VARIANTS[kind])
        numbers = fl.compare(starts, stood_in, [r if s is not None else None for r, s in zip(reference, wanted)])
        record(kind, seed, numbers, **_study.verdict(numbers, limits), variant_s=time.perf_counter() - t)
        # A variant is a program of its own: let go of it and of its result before the next.
        del stood_in
        jax.clear_caches()
        gc.collect()


_study.fc, _study.VARIANTS, _study.NUMBERS, _study.study_seed = fl, VARIANTS, NUMBERS, study_seed

if __name__ == "__main__":
    _study.main()
