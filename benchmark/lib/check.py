"""The comparison that decides ``correct`` for the federated-round cells.

What the timed path produced in its first rounds (the global model after
each round, each client's round loss and pixel accuracy) is set against the
plain float32 reference that followed each of those rounds on the same bytes
from the same start.
Every number is a gap, 0 when the two agree; ``PERF.md`` has the readings the
limits were set from.

- ``loss_r<k>``: round ``k``'s loss, worst client, as a share of the
  reference's.
- ``acc_r<k>``: round ``k``'s pixel accuracy, worst client, absolute.
- ``change_r<k>`` / ``change_median_r<k>``: the norm of each leaf's change
  over round ``k``, from the state the round started from (the seed's
  weights in round 0, what the program handed on in a later round): the gap between the
  program's norm and the reference's (not the norm of their difference,
  which Adam's sign flips on near-zero gradients inflate), against the
  reference's norm of that leaf or of the median leaf, whichever is larger;
  worst leaf and median leaf.
- ``total_change_r<k>``, ``stats_change_r<k>``, ``direction_r<k>``: the same
  gap for all moving parameters, and for all BatchNorm statistics, taken as
  one vector each; and 1 minus the cosine between the program's and the
  reference's change of the parameters.
- ``direction_q1_r<k>``, ``direction_median_r<k>``, ``direction_q3_r<k>``:
  1 minus that cosine leaf by leaf: the lower quartile, the median and the
  upper quartile over the moving leaves.

Parameter leaves whose gradient is nought to rounding in the reference (a
convolution's bias in front of a BatchNorm) move under Adam by round-off
alone: a leaf whose mean gradient norm is under a thousandth of the median
leaf's is left out of the change, by that rule and not by name.
"""

from __future__ import annotations

import numpy as np

NULL_GRADIENT_SHARE = 1e-3


def _flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k in sorted(tree):
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(tree[k], dict):
            out.update(_flatten(tree[k], path))
        else:
            out[path] = np.asarray(tree[k], np.float32)
    return out


def moving_leaves(grad_norms: dict) -> set:
    """Paths (under ``params/``) of the leaves the reference's gradient moves."""
    norms = {f"params/{k}": float(np.mean(v)) for k, v in _flatten(grad_norms).items()}
    floor = NULL_GRADIENT_SHARE * float(np.median(list(norms.values())))
    return {k for k, v in norms.items() if v >= floor}


def change_numbers(start: dict, program: dict, reference: dict, moving: set) -> dict:
    """The change over a round from its ``start``, program against reference.

    ``change``/``change_median``: each leaf's gap of change norms against the
    reference's norm of that leaf or of the median leaf, whichever is larger
    (worst leaf, median leaf). Parameter leaves outside ``moving`` are
    dropped; BatchNorm statistics always stay. ``total_change``: the same gap
    for all moving parameters taken as one vector. ``direction``: 1 minus the
    cosine between the two changes of that vector. ``stats_change``: the gap
    for all BatchNorm statistics as one vector. ``direction_q1``,
    ``direction_median`` and ``direction_q3``: that 1 minus cosine leaf by
    leaf over the moving parameter leaves, the quartiles: order statistics,
    which a far-off seed's large kernels move less than the one vector."""
    s, p, r = _flatten(start), _flatten(program), _flatten(reference)
    if set(p) != set(r):
        raise ValueError("program and reference hold different leaves")
    kept = [k for k in r if k in moving or not k.startswith("params/")]
    ref_norm = {k: float(np.linalg.norm(r[k] - s[k])) for k in kept}
    prog_norm = {k: float(np.linalg.norm(p[k] - s[k])) for k in kept}
    median = float(np.median(list(ref_norm.values())))
    gaps = {k: abs(prog_norm[k] - ref_norm[k]) / max(ref_norm[k], median) for k in kept}
    worst = max(gaps, key=gaps.get)
    out = {"change": gaps[worst], "change_median": float(np.median(list(gaps.values()))), "_worst_leaf": worst}
    turns = []
    for k in kept:
        if k.startswith("params/") and ref_norm[k] > 0:
            dp, dr = (p[k] - s[k]).ravel().astype(np.float64), (r[k] - s[k]).ravel().astype(np.float64)
            turns.append(1.0 - float(dp @ dr) / (prog_norm[k] * ref_norm[k]) if prog_norm[k] > 0 else 1.0)
    out["direction_q1"] = float(np.percentile(turns, 25))
    out["direction_median"] = float(np.median(turns))
    out["direction_q3"] = float(np.percentile(turns, 75))
    for name, keys in (
        ("total_change", [k for k in kept if k.startswith("params/")]),
        ("stats_change", [k for k in kept if not k.startswith("params/")]),
    ):
        dp = np.concatenate([(p[k] - s[k]).ravel() for k in keys]).astype(np.float64)
        dr = np.concatenate([(r[k] - s[k]).ravel() for k in keys]).astype(np.float64)
        np_, nr = float(np.linalg.norm(dp)), float(np.linalg.norm(dr))
        out[name] = abs(np_ - nr) / nr
        if name == "total_change":
            out["direction"] = 1.0 - float(dp @ dr) / (np_ * nr) if np_ > 0 else 1.0
    return out


def compare(starts: list, program_rounds: list, reference_rounds: list) -> dict:
    """Every candidate number, by name. ``starts[k]`` is what round ``k``
    started from, on both sides; ``program_rounds[k]`` holds ``variables``,
    ``loss`` and ``pixel_acc`` (one entry a client); ``reference_rounds[k]``
    the same plus ``grad_norms``, or ``None`` for a round that was not
    followed."""
    out = {}
    for k, (start, prog, ref) in enumerate(zip(starts, program_rounds, reference_rounds)):
        if ref is None:
            continue
        moving = moving_leaves(ref["grad_norms"])
        lp, lr = np.asarray(prog["loss"], np.float64), np.asarray(ref["loss"], np.float64)
        out[f"loss_r{k}"] = float(np.max(np.abs(lp - lr) / np.abs(lr)))
        ap, ar = np.asarray(prog["pixel_acc"], np.float64), np.asarray(ref["pixel_acc"], np.float64)
        out[f"acc_r{k}"] = float(np.max(np.abs(ap - ar)))
        for name, value in change_numbers(start, prog["variables"], ref["variables"], moving).items():
            out[f"_worst_leaf_r{k}" if name == "_worst_leaf" else f"{name}_r{k}"] = value
    for k, v in out.items():
        if not k.startswith("_") and not np.isfinite(v):
            out[k] = 1e30
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and the compared numbers, each beside its limit. A number
    the limits name and the run lacks fails."""
    compared = {}
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name, 1e30)
        compared[name] = {"value": value, "limit": limit}
        ok = ok and value <= limit
    return ok, compared
