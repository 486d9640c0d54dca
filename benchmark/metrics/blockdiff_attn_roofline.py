"""The block-diffusion attention's share of its roofline: the least time the
chip could take for a step's allowed scores, forward and backward (the larger
of operations over the bf16 peak and bytes over the HBM peak,
``lib/flops_sdar.py``: masked-out tiles never count), over the time measured
under the ``blockdiff_attn`` scope."""


def read(run):
    seconds = (run.get("scope_seconds") or {}).get("blockdiff_attn")
    work, peaks = (run.get("kernel_work") or {}).get("blockdiff_attn"), run.get("peaks")
    if not seconds or work is None or peaks is None:
        return None
    least = max(work[0] / peaks["bf16_flops_per_s"], work[1] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
