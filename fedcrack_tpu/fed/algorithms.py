"""Federated aggregation as pure pytree math.

The reference's FedAvg is a Python loop over pickled Keras weight lists:
element-wise sum then division by the client count (reference:
fl_server.py:92-105 ``updateWeight``), with two accidents fixed here
(SURVEY.md §2.2(1,2)): the average is actually broadcast, and the buffer is
per-round. BatchNorm moving statistics are averaged along with the kernels —
the reference implicitly does the same since ``get_weights()`` includes BN
moments (SURVEY.md §7 "hard parts").

These functions are pure jnp and run identically on the gRPC control plane
(host, numpy arrays) and inside the one-program mesh round
(``fedcrack_tpu.parallel``, via masked psum — see fedavg_mesh.py).
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def _fedavg_native(updates: Sequence[Any], weights: Sequence[float]) -> Any | None:
    """Host fast path: the server-side aggregation runs on msgpack-decoded
    numpy trees (fed/serialization.py), where the native OpenMP
    ``weighted_accumulate``/``scale_inplace`` kernels beat per-leaf jnp
    dispatch. Returns None (caller falls back to jnp) unless every leaf of
    every update is a float32 ndarray with a common structure."""
    from fedcrack_tpu import native

    flat0, treedef = jax.tree_util.tree_flatten(updates[0])
    columns: list[list[np.ndarray]] = [[leaf] for leaf in flat0]
    for update in updates[1:]:
        flat, td = jax.tree_util.tree_flatten(update)
        if td != treedef:
            return None
        for col, leaf in zip(columns, flat):
            col.append(leaf)
    for col in columns:
        if not all(
            isinstance(x, np.ndarray) and x.dtype == np.float32 for x in col
        ):
            return None
    total = float(np.sum(np.asarray(weights, np.float64)))
    out = []
    for col in columns:
        acc = np.zeros_like(col[0])
        for wi, x in zip(weights, col):
            native.weighted_accumulate(acc, x, float(wi))
        native.scale_inplace(acc, 1.0 / total)
        out.append(acc)
    return jax.tree_util.tree_unflatten(treedef, out)


def fedavg(updates: Sequence[Any], weights: Sequence[float] | None = None) -> Any:
    """Weighted element-wise mean of K client pytrees.

    ``weights`` are per-client sample counts (proper FedAvg); ``None`` gives
    the reference's unweighted mean (fl_server.py:101-102 divides the sum by
    the client count). All-float32-numpy trees (the gRPC server's decoded
    payloads) take the native accumulate/scale kernels; anything else (device
    arrays, mixed dtypes) takes the jnp path — both are cross-checked in
    tests.
    """
    if not updates:
        raise ValueError("fedavg over zero clients")
    k = len(updates)
    if weights is None:
        raw_w = [1.0] * k
    else:
        if len(weights) != k:
            raise ValueError(f"{len(weights)} weights for {k} updates")
        raw_w = [float(x) for x in weights]
        if sum(raw_w) <= 0:
            raise ValueError("non-positive total weight")

    native_result = _fedavg_native(updates, raw_w)
    if native_result is not None:
        return native_result

    w = jnp.asarray(raw_w, jnp.float32)
    w = w / jnp.sum(w)

    def avg_leaf(*leaves):
        acc = jnp.zeros_like(leaves[0], dtype=jnp.float32)
        for wi, leaf in zip(w, leaves):
            acc = acc + wi * leaf.astype(jnp.float32)
        return acc.astype(leaves[0].dtype)

    return jax.tree_util.tree_map(avg_leaf, *updates)


def sample_cohort(
    n_clients: int,
    cohort_size: int,
    round_idx: int,
    seed: int = 0,
) -> np.ndarray:
    """The round's cohort: a seeded, sorted, without-replacement sample of
    ``cohort_size`` client indices from the ``n_clients`` population
    (round 13 — cross-device FL samples a fresh cohort per round instead
    of training every client every round; Bonawitz et al., MLSys 2019).

    Determinism contract (property-pinned in tests/test_fed.py): the draw
    is a pure function of ``(seed, round_idx)`` — the whole multi-round
    cohort SEQUENCE reproduces from one seed, independent of call order or
    prior draws (each round seeds a fresh ``SeedSequence([seed,
    round_idx])``; no shared RNG state to advance). Sorted output keeps
    downstream group packing / edge partitioning deterministic too.
    """
    if n_clients <= 0:
        raise ValueError(f"n_clients must be positive, got {n_clients}")
    if not 0 < cohort_size <= n_clients:
        raise ValueError(
            f"cohort_size must be in [1, n_clients={n_clients}], got {cohort_size}"
        )
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), int(round_idx)]))
    picks = rng.choice(n_clients, size=cohort_size, replace=False)
    return np.sort(picks.astype(np.int64))


def fedprox_penalty(params: Any, anchor: Any, mu: float) -> jax.Array:
    """(mu/2)||params - anchor||^2 — the FedProx proximal term added to the
    client loss on non-IID shards (configs/c4_noniid_fedprox.json)."""
    sq = jax.tree_util.tree_map(
        lambda a, b: jnp.sum((a.astype(jnp.float32) - b.astype(jnp.float32)) ** 2),
        params,
        anchor,
    )
    return 0.5 * mu * jax.tree_util.tree_reduce(jnp.add, sq, jnp.zeros((), jnp.float32))


# ---- FedOpt: server-side optimizers on the round pseudo-gradient ----
#
# (Reddi et al., "Adaptive Federated Optimization".) FedAvg treats the round
# as "replace the global model with the client average"; FedOpt treats
# ``global - average`` as a pseudo-gradient and feeds it to a server
# optimizer, giving FedAvgM (momentum) and FedAdam. The reference has plain
# FedAvg only (fl_server.py:92-105); ``server_optimizer="avg"`` reproduces
# it exactly. Only ``params`` go through the optimizer — BatchNorm moving
# statistics are plain-averaged (momentum on running moments is meaningless).


def _fedopt_adaptive(lr: float, b1: float, b2: float, eps: float, variant: str):
    """Reddi et al.'s adaptive server updates, exactly as in the paper —
    ``m = b1*m + (1-b1)*g`` and a per-variant second moment, step
    ``-lr * m / (sqrt(v) + eps)`` with NO bias correction (``optax.adam``
    bias-corrects, which changes the effective step size of early rounds
    relative to the paper's algorithm, so the moments are hand-rolled):

    - ``adam`` (FedAdam):  ``v = b2*v + (1-b2)*g^2``
    - ``yogi`` (FedYogi):  ``v = v - (1-b2)*sign(v - g^2)*g^2`` — the
      additive update reacts slower when ``v`` overshoots, which the paper
      found more stable under heterogeneous client drift. From ``v = 0``
      the first step coincides with FedAdam.
    """
    import optax

    def init(params):
        zeros = lambda t: jax.tree_util.tree_map(
            lambda p: jnp.zeros_like(p, jnp.float32), t
        )
        return (zeros(params), zeros(params))

    def _v_update(vi, g):
        g2 = jnp.square(g.astype(jnp.float32))
        if variant == "yogi":
            return vi - (1.0 - b2) * jnp.sign(vi - g2) * g2
        return b2 * vi + (1.0 - b2) * g2

    def update(grads, state, params=None):
        del params
        m, v = state
        m = jax.tree_util.tree_map(
            lambda mi, g: b1 * mi + (1.0 - b1) * g.astype(jnp.float32), m, grads
        )
        v = jax.tree_util.tree_map(_v_update, v, grads)
        updates = jax.tree_util.tree_map(
            lambda mi, vi: -lr * mi / (jnp.sqrt(vi) + eps), m, v
        )
        return updates, (m, v)

    return optax.GradientTransformation(init, update)


def make_server_optimizer(kind: str, lr: float = 1.0, momentum: float = 0.9):
    """An optax transform for the server update, or None for plain FedAvg."""
    import optax

    if kind in ("", "avg", "fedavg", "none"):
        return None
    if kind in ("momentum", "fedavgm"):
        return optax.sgd(lr, momentum=momentum)
    if kind in ("adam", "fedadam"):
        # Paper hyperparameters AND paper update rule (no bias correction).
        return _fedopt_adaptive(lr, b1=0.9, b2=0.99, eps=1e-3, variant="adam")
    if kind in ("yogi", "fedyogi"):
        return _fedopt_adaptive(lr, b1=0.9, b2=0.99, eps=1e-3, variant="yogi")
    raise ValueError(f"unknown server optimizer {kind!r}")


def apply_server_opt(global_params, avg_params, tx, opt_state):
    """One FedOpt step: pseudo-gradient = global - average (so SGD with
    lr=1, no momentum, recovers plain FedAvg). Returns (new_params,
    new_opt_state)."""
    import optax

    grad = jax.tree_util.tree_map(
        lambda g, a: g.astype(jnp.float32) - a.astype(jnp.float32),
        global_params,
        avg_params,
    )
    updates, new_opt_state = tx.update(grad, opt_state, global_params)
    new_params = optax.apply_updates(global_params, updates)
    new_params = jax.tree_util.tree_map(
        lambda n, g: n.astype(g.dtype), new_params, global_params
    )
    return new_params, new_opt_state
