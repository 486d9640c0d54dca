"""The gated delta rule's share of its roofline: the least time the chip
could take for a step's recurrence itself, three ``128 x 128`` products a
token a value head, forward and backward, or for reading ``q``, ``k``, ``v``,
the log-decay and ``beta`` and writing ``o`` once a pass (the larger of the
operations over the bf16 peak and the bytes over the HBM peak,
``lib/flops_qwen3next.py``: the chunked form's extra products, its inverse
and the rematerialised forward never count), over the time measured under
``gdn_rule``."""


def read(run):
    seconds = (run.get("scope_seconds") or {}).get("gdn_rule")
    work, peaks = (run.get("kernel_work") or {}).get("gdn_rule"), run.get("peaks")
    if not seconds or work is None or peaks is None:
        return None
    least = max(work[0] / peaks["bf16_flops_per_s"], work[1] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
