"""The hybrid language model's held routed experts' share of their roofline:
the least time the chip could take for a step's grouped products over the
pairs the program's ``held_pairs`` counter says it kept, forward and backward
(the larger of operations over the bf16 peak and bytes over the HBM peak,
``lib/flops_qwen3next.py``: absent experts and padding rows never count), over
the time measured under ``moe_experts``. The reckoning is
``moe_experts_roofline``'s own (its ``read``, not a copy of it), under the
hybrid cell's name."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metric_moe_experts_roofline", os.path.join(os.path.dirname(os.path.abspath(__file__)), "moe_experts_roofline.py")
)
_accepted = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_accepted)
read = _accepted.read
