"""THE aggregation algebra (round 21): one ordered fold, four planes.

Before this round the repo carried FOUR structurally-identical aggregation
folds — the rounds-plane sorted FedAvg (``fed/rounds.py``), the buffered
``fold_buffer`` (``fed/buffered.py``, shared by the root flush and the edge
tier's ``flush_partial``), the edge sync ``partial`` (``fed/tree.py``), and
the mesh-plane ordered cohort fold (``parallel/fedavg_mesh.py``). Four
copies of one shape was the failure surface the r18 health plane exposed:
the SCALED_UPDATE drill proved a sanitation-passing x1000 poisoned update
is *flagged* by the ledger yet still averaged in at full weight on every
one of them, because "how updates combine" lived in four places and none
had a seam to swap the combine.

This module is that seam. An aggregation algebra is an ordered fold over
``(name, weight, update_tree)`` triples:

    acc = algebra.init()
    for triple in triples:          # triples in CANONICAL order
        acc = algebra.combine(acc, triple)
    result = algebra.finalize(acc)

Canonical order is the caller's contract (sorted client names on the
rounds/edge planes, ``(cname, seq)`` on the buffered plane, client index
on the mesh) — the fold itself never re-orders, so the algebra composes
with the r13 ordered-fold bitwise discipline instead of fighting it.

The **null instance** (:class:`FedAvg`) accumulates the triples and
finalizes through :func:`fedcrack_tpu.fed.algorithms.fedavg` with exactly
the historical weight gate (``weights if any(w > 0) else None``) — which
is what makes it BITWISE-pinned to the four folds it replaced: same
decoded trees, same weight objects, same native-accumulate expression,
byte-identical globals (test-pinned per plane).

The **robust instances** plug in the literature:

- :class:`TrimmedMean` — coordinate-wise beta-trimmed mean (Yin et al.,
  "Byzantine-Robust Distributed Learning: Towards Optimal Statistical
  Rates", ICML 2018): per coordinate, sort the n client values, drop the
  ``floor(beta * n)`` smallest and largest, mean the rest.
- :class:`CoordinateMedian` — the same paper's coordinate-wise median.
- :class:`Krum` — Krum / Multi-Krum (Blanchard et al., "Machine Learning
  with Adversaries: Byzantine Tolerant Gradient Descent", NeurIPS 2017):
  score each update by the sum of its ``n - f - 2`` smallest squared
  distances to the others; Krum SELECTS the lowest-scoring update
  verbatim, Multi-Krum unweighted-means the ``n - f`` lowest-scoring.

Robust combines deliberately IGNORE the client-reported sample weights: a
Byzantine client self-reports ``num_samples``, so any weight it can
inflate is an attack surface — the whole point of the robust fold is that
no single client controls its own influence. (FedAvg keeps weights; it is
the null instance, pinned to history.)

The **mesh instance** is the same fold shape traced: :func:`mesh_zero_sums`
(init) / :func:`mesh_ordered_fold` (combine, one client at a time in
client-index order via ``all_gather`` + ``fori_loop``) /
:func:`mesh_finish_cohort_mean` (finalize, with the in-mesh empty-cohort
guard). ``parallel/fedavg_mesh.py`` aliases these under its historical
names so every traced program is the identical expression tree
(``groups_bitwise_equal`` unchanged).

Edge tiers refuse non-null algebras loudly (``EdgeAggregator`` ctor): a
trimmed partial of a partial is NOT a trimmed total — robust statistics do
not commute with hierarchical averaging the way the weighted mean does,
so a robust edge would silently change what the root computes. Robust
combines run where the full cohort is visible: the gRPC rounds plane and
the buffered root.

fedlint AGG001 pins the seam statically: a ``fedavg`` call in ``fed/`` or
``parallel/`` outside this module and ``fed/algorithms.py`` is an ERROR —
the fifth copy of the fold never lands.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# Private name, imported here and nowhere else. The fold's result must be
# typed INVARIANT over the clients axis: its carry starts as the invariant
# zeros of ``mesh_zero_sums`` and every round program returns it under
# ``out_specs=P()``. The public ``lax.all_gather`` types its result varying
# (``to=`` offers only 'varying' | 'reduced'), which changes the fori_loop
# carry's type mid-fold and fails to trace; this primitive is the same
# collective (it shares all_gather's lowering on every platform) typed
# Varying -> Invariant. The public alternative — a varying fold closed by a
# ``lax.pmax`` of equal values — costs a second all-reduce of the whole tree.
from jax._src.lax.parallel import all_gather_invariant

from fedcrack_tpu.fed.algorithms import fedavg

# One triple per contributing update, in the plane's canonical order.
Triple = tuple  # (name: str, weight: float, tree: Any)

# The FedConfig.aggregation vocabulary ("median" is accepted as shorthand
# for "coordinate_median"; from_config canonicalizes).
AGGREGATIONS = (
    "fedavg", "trimmed_mean", "median", "coordinate_median", "krum",
    "multi_krum",
)


class AggregationAlgebra:
    """One aggregation algebra: ``init`` / ``combine`` / ``finalize``.

    The default ``init``/``combine`` accumulate the ordered triples into a
    list — the free monoid, which every instance here folds over, because
    every combine in this family (weighted mean, trimmed mean, median,
    Krum) needs the full cohort to finalize. An instance that CAN stream
    (a plain weighted sum) may override ``init``/``combine`` with a
    constant-space carry; the mesh fold does exactly that, traced.
    """

    name = "abstract"

    def init(self) -> list:
        return []

    def combine(self, acc: list, triple: Triple) -> list:
        acc.append(triple)
        return acc

    def finalize(self, acc: list) -> Any:
        raise NotImplementedError

    def __repr__(self) -> str:  # config surfaces / drill artifacts
        return f"{type(self).__name__}({self.name!r})"


def fold(algebra: AggregationAlgebra, triples: Iterable[Triple]) -> Any:
    """THE ordered fold: run ``triples`` (already in the plane's canonical
    order) through ``algebra``. Every host-plane aggregation routes here —
    fedlint AGG001 makes any other route an ERROR."""
    acc = algebra.init()
    for t in triples:
        acc = algebra.combine(acc, t)
    return algebra.finalize(acc)


class FedAvg(AggregationAlgebra):
    """The null instance: sample-weighted mean, bitwise-pinned to the four
    historical folds. The weight gate is the historical one — weights are
    USED iff any is positive, else the mean is unweighted — and the weight
    OBJECTS pass through untouched (ints on the sync plane, ``ns * (1+s)^-
    alpha`` floats on the buffered plane), so the downstream ``fedavg``
    expression is byte-for-byte the one each plane ran before."""

    name = "fedavg"

    def finalize(self, acc: list) -> Any:
        if not acc:
            raise ValueError("aggregation fold over zero updates")
        trees = [t for (_, _, t) in acc]
        weights = [w for (_, w, _) in acc]
        use = weights if any(w > 0 for w in weights) else None
        return fedavg(trees, use)


def _stacked_leaf_combine(trees: Sequence[Any], leaf_fn: Callable) -> Any:
    """Per-leaf combine over the cohort: stack each leaf position across
    the n trees as float32 and reduce with ``leaf_fn(stacked) ->
    np.ndarray``, casting back to the first tree's leaf dtype. Order-
    independent by construction (the reductions here sort or select per
    coordinate), which is what the permuted-arrival tests pin."""

    def per_leaf(*leaves):
        stacked = np.stack([np.asarray(l, np.float32) for l in leaves])
        out = np.asarray(leaf_fn(stacked), np.float32)
        return out.astype(np.asarray(leaves[0]).dtype)

    return jax.tree_util.tree_map(per_leaf, *trees)


class TrimmedMean(AggregationAlgebra):
    """Coordinate-wise beta-trimmed mean (Yin et al., ICML 2018). Ignores
    client-reported weights (see module docstring). ``trim_fraction`` in
    ``[0, 0.5)`` guarantees at least one survivor per coordinate."""

    name = "trimmed_mean"

    def __init__(self, trim_fraction: float = 0.1):
        if not 0.0 <= trim_fraction < 0.5:
            raise ValueError(
                f"trim_fraction must be in [0, 0.5), got {trim_fraction}"
            )
        self.trim_fraction = float(trim_fraction)

    def finalize(self, acc: list) -> Any:
        if not acc:
            raise ValueError("aggregation fold over zero updates")
        n = len(acc)
        k = int(math.floor(self.trim_fraction * n))

        def leaf_fn(stacked):
            s = np.sort(stacked, axis=0)
            return s[k : n - k].mean(axis=0, dtype=np.float32)

        return _stacked_leaf_combine([t for (_, _, t) in acc], leaf_fn)


class CoordinateMedian(AggregationAlgebra):
    """Coordinate-wise median (Yin et al., ICML 2018). Ignores weights."""

    name = "coordinate_median"

    def finalize(self, acc: list) -> Any:
        if not acc:
            raise ValueError("aggregation fold over zero updates")
        return _stacked_leaf_combine(
            [t for (_, _, t) in acc],
            lambda stacked: np.median(stacked, axis=0),
        )


class Krum(AggregationAlgebra):
    """Krum / Multi-Krum (Blanchard et al., NeurIPS 2017). Each update i
    scores ``sum of its max(1, n - f - 2) smallest squared distances`` to
    the other updates; honest updates cluster, so the poisoned one's
    distances — and score — explode. Krum selects the single lowest-score
    update VERBATIM (bitwise one client's tree); Multi-Krum unweighted-
    means the ``max(1, n - f)`` lowest. Ties break on ``(score, name,
    canonical index)`` so the selection is arrival-order independent.
    Distances accumulate in float64 for cross-platform determinism.
    ``n <= f + 2`` clamps the neighbor count to 1 rather than refusing —
    the drill's 3-client cohorts are exactly this regime and the clamp
    keeps the score ordering (nearest honest neighbor) meaningful."""

    name = "krum"

    def __init__(self, byzantine_f: int = 1, *, multi: bool = False):
        if byzantine_f < 0:
            raise ValueError(f"byzantine_f must be >= 0, got {byzantine_f}")
        self.byzantine_f = int(byzantine_f)
        self.multi = bool(multi)
        if multi:
            self.name = "multi_krum"

    def _scores(self, vecs: list) -> list:
        n = len(vecs)
        closest = max(1, n - self.byzantine_f - 2)
        d2 = np.zeros((n, n), np.float64)
        for i in range(n):
            for j in range(i + 1, n):
                d = float(np.dot(vecs[i] - vecs[j], vecs[i] - vecs[j]))
                d2[i, j] = d2[j, i] = d
        scores = []
        for i in range(n):
            others = np.sort(np.delete(d2[i], i))
            scores.append(float(np.sum(others[:closest])))
        return scores

    def finalize(self, acc: list) -> Any:
        if not acc:
            raise ValueError("aggregation fold over zero updates")
        n = len(acc)
        if n == 1:
            return acc[0][2]
        vecs = [
            np.concatenate(
                [
                    np.asarray(l, np.float64).ravel()
                    for l in jax.tree_util.tree_leaves(t)
                ]
            )
            for (_, _, t) in acc
        ]
        scores = self._scores(vecs)
        order = sorted(range(n), key=lambda i: (scores[i], acc[i][0], i))
        if not self.multi:
            return acc[order[0]][2]
        m = max(1, n - self.byzantine_f)
        # Mean the selected set in CANONICAL index order (not score order)
        # so the summation expression is arrival-order independent.
        selected = sorted(order[:m])
        return fedavg([acc[i][2] for i in selected], None)


def from_config(cfg: Any) -> AggregationAlgebra:
    """The FedConfig -> algebra factory: ``cfg.aggregation`` names the
    combine, ``cfg.trim_fraction`` / ``cfg.byzantine_f`` parameterize it.
    Accepts any object with those attributes (FedConfig, EdgeAggregator
    kwargs bag, a test namespace); missing attributes mean the null
    instance."""
    kind = getattr(cfg, "aggregation", "fedavg") or "fedavg"
    if kind == "fedavg":
        return FedAvg()
    if kind == "trimmed_mean":
        return TrimmedMean(float(getattr(cfg, "trim_fraction", 0.1)))
    if kind in ("median", "coordinate_median"):
        return CoordinateMedian()
    if kind == "krum":
        return Krum(int(getattr(cfg, "byzantine_f", 1)))
    if kind == "multi_krum":
        return Krum(int(getattr(cfg, "byzantine_f", 1)), multi=True)
    raise ValueError(
        f"unknown aggregation {kind!r} (choose from {AGGREGATIONS})"
    )


def quarantine_set(
    scores: dict, names: Sequence[str], quarantine_z: float
) -> dict:
    """The ledger->fold coupling: which of this flush's contributors are
    EXCLUDED from the fold. ``scores`` is the per-client max robust-z the
    r18 ledger just computed (:func:`fedcrack_tpu.health.ledger.
    observe_flush`); a client at or above ``quarantine_z`` is quarantined.
    ``quarantine_z <= 0`` disables (the default — detection without
    response, exactly r18's behavior). A verdict that would quarantine the
    ENTIRE cohort is dropped: robust-z needs a majority reference, and a
    fold over zero updates cannot advance the round — better to take the
    round and let the alert threshold page. Returns ``{name: score}``
    (scores rounded to 6, like the ledger's own norms) for the history's
    ``quarantined`` map."""
    if quarantine_z <= 0.0:
        return {}
    out = {}
    for n in names:
        s = float(scores.get(n, 0.0))
        if s >= quarantine_z:
            out[n] = round(s, 6)
    if out and len(out) >= len(set(names)):
        return {}
    return out


# --------------------------------------------------------------------------
# The mesh instance: the same init/combine/finalize fold shape, traced.
# Relocated verbatim from parallel/fedavg_mesh.py (round 13) so the one
# module owning "how updates combine" owns it on the mesh plane too;
# fedavg_mesh aliases these under its historical names, keeping every
# traced program the identical expression tree (groups_bitwise_equal).
# --------------------------------------------------------------------------


def mesh_ordered_fold(
    tree: Any, weight: jax.Array, init: tuple, *, axis_name: str = "clients"
) -> tuple:
    """Deterministically-ORDERED masked weighted sums over ``axis_name``,
    continuing the partial-sum carry ``init = (num_tree_f32, den_scalar_
    f32)``: each leaf is all_gathered and left-folded into the carry one
    client at a time, in client-index order.

    Why not ``lax.psum``: an all-reduce's float addition order is
    backend/topology-defined (CPU XLA reduces rank-sequentially, a TPU ring
    reduces in ring order), so group-partial psums do NOT compose bitwise —
    ``psum_4(x) != psum_2(x[:2]) + psum_2(x[2:])`` (measured). The fold
    pins ONE expression tree — ``(((0 + w0*x0) + w1*x1) + ...)`` — that is
    identical whether the cohort runs as one C-wide mesh or as sequential
    groups of G continuing the carry (round 13's time-multiplexed cohort
    contract, test-pinned bitwise for groups in {1, 2, 4}). Zero-weight
    padding clients contribute ``±0.0``, which is a bitwise no-op on any
    partial sum reachable from the ``+0.0`` init, so ragged cohorts pad
    clean. Cost vs psum: an all_gather (G x leaf bytes on the ICI) plus a
    serial length-G fold — noise next to the round's epochs x steps scan.
    """
    num, den = init
    gathered = jax.tree_util.tree_map(
        lambda x: all_gather_invariant(
            weight * x.astype(jnp.float32), axis_name
        ),
        tree,
    )
    gw = all_gather_invariant(weight, axis_name)

    def body(i, acc):
        acc_num, acc_den = acc
        acc_num = jax.tree_util.tree_map(
            lambda a, g: a + g[i], acc_num, gathered
        )
        return acc_num, acc_den + gw[i]

    return lax.fori_loop(0, gw.shape[0], body, (num, den))


def mesh_zero_sums(tree: Any) -> tuple:
    """The fold's identity carry: f32 zeros per update leaf + a 0 weight."""
    return (
        jax.tree_util.tree_map(
            lambda x: jnp.zeros(jnp.shape(x), jnp.float32), tree
        ),
        jnp.zeros((), jnp.float32),
    )


def mesh_finish_cohort_mean(
    num: Any, total_w: jax.Array, fallback: Any
) -> Any:
    """Divide the ordered sums into the FedAvg mean, with the empty-cohort
    guard: zero total weight returns ``fallback`` (the round's incoming
    global model) unchanged. Elementwise ops only — bitwise deterministic
    regardless of which program (in-round tail, grouped finalize) runs it."""
    denom = jnp.maximum(total_w, 1e-9)
    averaged = jax.tree_util.tree_map(
        lambda s, orig: (s / denom).astype(orig.dtype), num, fallback
    )
    keep = total_w > 0.0
    return jax.tree_util.tree_map(
        lambda avg, orig: jnp.where(keep, avg, orig.astype(avg.dtype)),
        averaged,
        fallback,
    )
