"""Multi-host bring-up: ``jax.distributed`` over ICI/DCN.

The reference's only distribution mechanism is one gRPC server and N client
processes on a LAN (SURVEY.md §5.8) — every byte crosses the DCN through
pickle blobs. On a TPU pod slice the data plane instead spans hosts through
XLA's collectives: each host runs one process, ``jax.distributed.initialize``
wires them into a single logical device set, and the same ``shard_map``
programs in this package (``fedavg_mesh``, ``spatial``) run unchanged with
their ``psum``/``ppermute`` traffic riding ICI within a slice and DCN across
slices. The gRPC control plane remains for cross-trust-boundary federation
(clients that are NOT part of the pod).

Single-process usage (tests, one chip, CPU meshes) needs no initialization —
every helper here degrades to a no-op.

The round builders accept cross-process inputs directly: stage each
process's client shards with ``jax.make_array_from_process_local_data`` over
the global mesh and call ``build_federated_round``'s round_fn unchanged —
``tests/test_multihost.py::test_two_process_federated_round`` runs one
FedAvg round across two OS processes and pins bit-equality of the resulting
global model against the single-process round.
"""

from __future__ import annotations

import logging
import os

import jax

log = logging.getLogger("fedcrack.multihost")


def initialize_if_needed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Initialize ``jax.distributed`` when running as one process of a
    multi-host job; no-op otherwise.

    Resolution order (standard JAX bring-up):

    1. explicit arguments;
    2. TPU pod metadata / cluster env (``jax.distributed.initialize()`` with
       no args auto-detects on Cloud TPU and SLURM);
    3. ``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID``
       environment variables.

    Returns True when distributed mode was (already or newly) initialized.
    """
    # NB: probed WITHOUT jax.process_count() — that call initializes the XLA
    # backend, after which jax.distributed.initialize() unconditionally
    # raises ("must be called before any JAX calls").
    if jax.distributed.is_initialized():
        return True
    env_addr = os.environ.get("JAX_COORDINATOR_ADDRESS")
    if coordinator_address is None and env_addr:
        coordinator_address = env_addr
        num_processes = num_processes or int(os.environ.get("JAX_NUM_PROCESSES", "0"))
        process_id = (
            process_id
            if process_id is not None
            else int(os.environ.get("JAX_PROCESS_ID", "-1"))
        )
    if coordinator_address is None:
        # Auto-detection path: on a TPU pod slice initialize() discovers the
        # topology itself; off-pod it raises, which we treat as single-host.
        try:
            jax.distributed.initialize()
        except (ValueError, RuntimeError):
            return False
        return jax.process_count() > 1
    if not num_processes or process_id is None or process_id < 0:
        raise ValueError(
            "multi-host bring-up needs coordinator_address, num_processes and "
            f"process_id together (got {coordinator_address=}, "
            f"{num_processes=}, {process_id=})"
        )
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    log.info(
        "jax.distributed up: process %d/%d, %d local / %d global devices",
        jax.process_index(),
        jax.process_count(),
        jax.local_device_count(),
        jax.device_count(),
    )
    return True


def global_mesh_devices() -> list[jax.Device]:
    """All devices across all processes, in (process, local) order — the
    device list to hand to ``make_mesh``/``make_spatial_mesh`` so mesh rows
    align with hosts (collectives between row-neighbors stay on-host or
    one ICI hop where possible)."""
    return sorted(jax.devices(), key=lambda d: (d.process_index, d.id))


def is_coordinator() -> bool:
    """True on the process that should run the gRPC control plane and write
    checkpoints (process 0 by convention)."""
    return jax.process_index() == 0
