"""The looped model's exits' share of their roofline: the least time the chip
could take for a step's exits, the head's and the gate's products of every
exit, forward and backward (the larger of their operations over the bf16 peak
and their bytes over the HBM peak, ``lib/flops_ouro.py``: the head's logits
recomputed in the backward pass never count), over the time measured under
``loop_exit``."""


def read(run):
    seconds = (run.get("scope_seconds") or {}).get("loop_exit")
    work, peaks = (run.get("kernel_work") or {}).get("loop_exit"), run.get("peaks")
    if not seconds or work is None or peaks is None:
        return None
    least = max(work[0] / peaks["bf16_flops_per_s"], work[1] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
