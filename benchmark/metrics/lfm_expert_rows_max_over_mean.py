"""How unevenly the sigmoid router with its expert bias loads the held
experts of the convolution model: over the window's rounds, the rows the
busiest held expert of any layer computed over the mean over all of them
(``RoundRecord.metrics["expert_rows"]``, ``[clients, sparse layers, held]`` a
round). 1 is an even load. The reading is ``expert_rows_max_over_mean``'s own
(its ``read``, not a copy of it), under this cell's name."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metric_expert_rows_max_over_mean", os.path.join(os.path.dirname(os.path.abspath(__file__)), "expert_rows_max_over_mean.py")
)
_accepted = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_accepted)
read = _accepted.read
