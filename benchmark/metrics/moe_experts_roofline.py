"""The held experts' share of their roofline: the least time the chip could
take for a step's grouped products over the pairs the program's ``held_pairs``
counter says it kept, forward and backward (the larger of operations over the
bf16 peak and bytes over the HBM peak, ``lib/flops_sdar.py``: absent experts
and padding rows never count), over the time measured under ``moe_experts``."""


def read(run):
    seconds = (run.get("scope_seconds") or {}).get("moe_experts")
    work, peaks = (run.get("kernel_work") or {}).get("moe_experts"), run.get("peaks")
    if not seconds or work is None or peaks is None:
        return None
    least = max(work[0] / peaks["bf16_flops_per_s"], work[1] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
