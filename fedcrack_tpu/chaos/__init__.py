"""Deterministic fault injection for both federation planes.

The reference system hangs forever when any client dies mid-round
(fl_server.py's collect barrier, SURVEY.md §2.4/§5.3). This package makes
the opposite claim TESTABLE: every failure mode the port hardens against is
a seeded, replayable chaos scenario — client crashes at each upload phase,
stragglers, network flaps, poisoned payloads (corrupt / truncated / NaN /
stale-replay), mid-round server kill-and-restart, mesh-plane
preemption / silent numerical corruption, and serving-plane faults
(hot-swap installed mid-batch, device loss during a served batch —
``ServeChaos`` for the round-10 serving plane's batcher).

Split: :mod:`plan` is the pure, seeded fault schedule;
:mod:`inject` adapts it to the transport client (``FedClient(chaos=...)``)
and the mesh driver (``run_mesh_federation(fault_injector=...)``). Nothing
here runs in production paths unless a plan is explicitly attached — the
hooks are a ``None`` check when disabled.

The scenario suite lives in tests/test_chaos.py (tier-1, CPU, seconds);
``python -m fedcrack_tpu.tools.chaos_drill`` runs the kill→restart recovery
drill standalone and times it.
"""

from fedcrack_tpu.chaos.inject import (
    ClientChaos,
    InjectedCrash,
    InjectedDeviceFailure,
    InjectedRpcError,
    MeshChaos,
    ServeChaos,
)
from fedcrack_tpu.chaos.plan import (
    ALL_KINDS,
    CLIENT_KINDS,
    CRASH_AFTER_UPLOAD,
    CRASH_BEFORE_UPLOAD,
    CRASH_DURING_UPLOAD,
    CORRUPT_PAYLOAD,
    MESH_DEVICE_FAIL,
    MESH_KINDS,
    MESH_NONFINITE,
    NAN_UPDATE,
    NETWORK_FLAP,
    SECAGG_DROPOUT,
    SERVE_DEVICE_LOSS,
    SERVE_KINDS,
    SERVE_SWAP_MIDFLIGHT,
    STALE_REPLAY,
    STRAGGLER_DELAY,
    TRUNCATE_PAYLOAD,
    Fault,
    FaultPlan,
)

__all__ = [
    "ALL_KINDS",
    "CLIENT_KINDS",
    "CRASH_AFTER_UPLOAD",
    "CRASH_BEFORE_UPLOAD",
    "CRASH_DURING_UPLOAD",
    "CORRUPT_PAYLOAD",
    "ClientChaos",
    "Fault",
    "FaultPlan",
    "InjectedCrash",
    "InjectedDeviceFailure",
    "InjectedRpcError",
    "MESH_DEVICE_FAIL",
    "MESH_KINDS",
    "MESH_NONFINITE",
    "MeshChaos",
    "NAN_UPDATE",
    "NETWORK_FLAP",
    "SECAGG_DROPOUT",
    "SERVE_DEVICE_LOSS",
    "SERVE_KINDS",
    "SERVE_SWAP_MIDFLIGHT",
    "STALE_REPLAY",
    "STRAGGLER_DELAY",
    "ServeChaos",
    "TRUNCATE_PAYLOAD",
]
