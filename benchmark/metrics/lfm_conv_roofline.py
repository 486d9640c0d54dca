"""The gated short convolution's share of its roofline: the least time the
chip could take for a step's gates and taps, forward and backward (the
larger of their bytes over the HBM peak, ``B``, ``C``, ``x~`` and ``z`` and
their cotangents once each in bf16, and their operations over the bf16 peak,
``lib/flops_lfm2.py``; the rematerialised forward never counts), over the time
measured under ``lfm_conv``."""


def read(run):
    seconds = (run.get("scope_seconds") or {}).get("lfm_conv")
    work, peaks = (run.get("kernel_work") or {}).get("lfm_conv"), run.get("peaks")
    if not seconds or work is None or peaks is None:
        return None
    least = max(work[0] / peaks["bf16_flops_per_s"], work[1] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
