"""Latent attention's share of its roofline: the least time the chip could
take for a step's causal scores, 192 lanes of ``q k^T`` and 128 of ``p v`` a
pair, forward and backward (the larger of their operations over the bf16
peak and their bytes over the HBM peak, ``lib/flops_joyai.py``: pairs the
mask forbids, lanes a kernel pads to and the rematerialised forward never
count), over the time measured under ``mla_attn``."""


def read(run):
    seconds = (run.get("scope_seconds") or {}).get("mla_attn")
    work, peaks = (run.get("kernel_work") or {}).get("mla_attn"), run.get("peaks")
    if not seconds or work is None or peaks is None:
        return None
    least = max(work[0] / peaks["bf16_flops_per_s"], work[1] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
