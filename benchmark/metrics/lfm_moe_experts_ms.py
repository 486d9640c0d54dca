"""Milliseconds a step the chip spends under the ``moe_experts`` scope of
every expert layer of the convolution model, forward and backward: the
grouped products of the 8 held routed experts. The reading is
``moe_experts_ms``'s own (its ``read``, not a copy of it), under this cell's
name."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metric_moe_experts_ms", os.path.join(os.path.dirname(os.path.abspath(__file__)), "moe_experts_ms.py")
)
_accepted = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_accepted)
read = _accepted.read
