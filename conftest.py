"""Pytest root conftest: run the suite on a virtual 8-device CPU mesh.

JAX's host-platform device emulation gives the suite 8 virtual CPU devices so
mesh/collective sharding code runs for real without multi-chip hardware
(SURVEY.md §4 "distributed-without-a-cluster"). The suite never runs on an
accelerator: ``chip_smoke.py`` is what runs there.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from fedcrack_tpu.jaxcompat import enable_compilation_cache, ensure_cpu_devices

ensure_cpu_devices(8)
# The U-Net programs take O(10 s) each to compile on CPU; keep them across
# test runs.
enable_compilation_cache()

# Keep TF (used only by h5-importer parity tests) off any accelerator and quiet.
os.environ.setdefault("CUDA_VISIBLE_DEVICES", "-1")
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
