"""Device-mesh construction for the federated data plane.

Axes:

- ``clients`` — one federated client per mesh row (the reference's
  cross-process FedAvg cohort, fl_server.py:45-81, becomes a mesh axis).
- ``batch``  — intra-client data parallelism over the local batch
  (configs/c5_bf16_batch_dp.json: per-client data parallelism).

On a v5e-8 the default is ``(8, 1)`` — 8 clients, one chip each; the same
code runs on a virtual CPU mesh in CI via
``--xla_force_host_platform_device_count`` (SURVEY.md §4).
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh


def make_mesh(
    n_clients: int,
    n_batch: int = 1,
    devices: Sequence[jax.Device] | None = None,
    axis_names: tuple[str, str] = ("clients", "batch"),
) -> Mesh:
    """Build a two-axis ``Mesh`` (default axes ``('clients', 'batch')``).

    Uses the first ``n_clients * n_batch`` devices. Raises if the host does
    not expose enough devices (the caller decides whether to shrink the
    cohort or multiplex clients per chip).
    """
    if n_clients <= 0 or n_batch <= 0:
        raise ValueError(f"mesh axes must be positive, got ({n_clients}, {n_batch})")
    devs = list(devices) if devices is not None else jax.devices()
    need = n_clients * n_batch
    if len(devs) < need:
        raise ValueError(
            f"mesh ({n_clients} {axis_names[0]} x {n_batch} {axis_names[1]}) "
            f"needs {need} devices, host exposes {len(devs)}"
        )
    grid = np.asarray(devs[:need], dtype=object).reshape(n_clients, n_batch)
    return Mesh(grid, axis_names)
