"""The whole round's share of the chips' bf16 peak: the operations the
forward and backward passes of every step of every client need, over the
window's seconds, the chips and the peak. Staging, fold and read-back are in
the seconds and add no operations."""


def read(run):
    if not run["rounds"] or run["peaks"] is None:
        return None
    needed = run["step_flops"] * run["steps"] * run["clients"] * run["rounds"]
    return 100.0 * needed / (run["elapsed_s"] * run["chips"] * run["peaks"]["bf16_flops_per_s"])
