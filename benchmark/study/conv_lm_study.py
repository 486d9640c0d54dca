"""How far the convolution language model's cell's program lies from the
reference, and how far the control and the planted faults do: the readings
every limit of its ``correct`` is set from, each judged by the harness's own
``check.judge`` under the cell's own limits.

    python3 benchmark/study/conv_lm_study.py <workload> <first seed> <seeds> <control seeds> [kinds] [rounds] [deadline s]
    python3 benchmark/study/conv_lm_study.py judge <workload> <rows.jsonl> [...]

The command line, the plan of kinds and rounds, the verdicts, the table and
the output file (``chiprun_out/study_<workload>.jsonl``) are
``causal_lm_study.py``'s, called and not copied: this file loads an instance
of that module for itself and binds in it the names that differ for this
traffic kind (``fc``: the driver; ``VARIANTS``; ``NUMBERS``; ``study_seed``,
whose accepted form asks the configuration for a multi-token-prediction
module). On the first ``control seeds`` seeds, each put in the program's
place and compared with the same reference:

- ``control_fp8``: the reference with every matrix product's operands in
  float8_e4m3fn (gradients e5m2), the nearest precision below bfloat16;
- ``witness_bf16``: the reference with bfloat16 operands, what the
  configuration states (it should read like the program);
- ``fault_taps_shifted`` (the taps one position later: not causal),
  ``fault_no_b_gate`` (``u = x~``), ``fault_bias_in_weights`` (the chosen
  experts weighed by ``s + b``), ``fault_no_qk_norm``: the reference's own
  faults (``reference/lfm2_conv_moe.py``);
- ``fault_stale_slab``: round 0's data again in round 1.
"""

import gc
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]

import jax

from lib import federated_conv_lm_rounds as fv
from lib.compile_log import CompileLog
from lib.federated_rounds import _load_module

_study = _load_module(os.path.join(BENCH_DIR, "study", "causal_lm_study.py"), "bench_study_causal_lm_of_conv")

FAULTS = ("taps_shifted", "no_b_gate", "bias_in_weights", "no_qk_norm", "stale_slab")
VARIANTS = {
    "control_fp8": {"operands": "float8_e4m3fn"},
    "witness_bf16": {"operands": "bfloat16"},
    **{f"fault_{name}": {"fault": name} for name in FAULTS},
}
NUMBERS = (
    "loss_r", "step_loss_r", "total_", "direction_", "conv_direction_", "attn_direction_", "next_", "expert_rows_r",
    "expert_bias",
)


def study_seed(spec: dict, seed: int, used, variants: list, record, deadline_s: float) -> None:
    """The program, the reference and ``variants`` on one seed
    (``hybrid_lm_study.py``'s, with this kind's driver and no decays)."""
    limits = spec["limits"]
    t = time.perf_counter()
    cell = fv.Cell(spec, seed, used)
    t_build = time.perf_counter() - t
    driven = cell.drive(0.0, None, time.perf_counter(), CompileLog())
    cell.round_fn = None
    starts = cell.starts(driven["program_rounds"])
    t = time.perf_counter()
    reference = cell.reference(starts)
    t_ref = time.perf_counter() - t
    numbers = fv.compare(starts, driven["program_rounds"], reference)
    record(
        "program", seed, numbers, **_study.verdict(numbers, limits),
        build_s=t_build, reference_s=t_ref, round_s=[r.wall_clock_s for r in driven["records"]],
        loss=[r["loss"] for r in driven["program_rounds"]], ref_loss=[r and r["loss"] for r in reference],
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
        numbers = fv.compare(starts, stood_in, [r if s is not None else None for r, s in zip(reference, wanted)])
        record(kind, seed, numbers, **_study.verdict(numbers, limits), variant_s=time.perf_counter() - t)
        # A variant is a program of its own: let go of it and of its result before the next.
        del stood_in
        jax.clear_caches()
        gc.collect()


_study.fc, _study.VARIANTS, _study.NUMBERS, _study.study_seed = fv, VARIANTS, NUMBERS, study_seed

if __name__ == "__main__":
    _study.main()
