"""The 64-lane grouped-query attention's share of its roofline: the least time
the chip could take for a step's causal scores, 64 lanes of ``q k^T`` and 64
of ``p v`` a pair, forward and backward (the larger of their operations over
the bf16 peak and their bytes over the HBM peak, ``lib/flops_lfm2.py``: pairs
the mask forbids never count), over the time measured under ``lfm_attn``."""


def read(run):
    seconds = (run.get("scope_seconds") or {}).get("lfm_attn")
    work, peaks = (run.get("kernel_work") or {}).get("lfm_attn"), run.get("peaks")
    if not seconds or work is None or peaks is None:
        return None
    least = max(work[0] / peaks["bf16_flops_per_s"], work[1] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
