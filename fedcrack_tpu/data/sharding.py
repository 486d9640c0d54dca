"""Client shard assignment for federated training.

The reference has no sharding at all — every client reads the same local
dataset directory (client_fit_model.py:58-59). Here the coordinator (or an
offline tool) assigns disjoint shards: IID uniform, or non-IID with
per-client crack-density skew (configs/c4_noniid_fedprox.json: non-IID client
shards, per-client crack-type skew, with FedProx mu>0).
"""

from __future__ import annotations

from typing import Sequence

import numpy as np


def partition_iid(
    n_samples: int, num_clients: int, seed: int = 0
) -> list[np.ndarray]:
    """Uniform random disjoint shards, near-equal sizes."""
    if num_clients <= 0:
        raise ValueError("num_clients must be positive")
    order = np.arange(n_samples)
    np.random.default_rng(seed).shuffle(order)
    return [np.sort(s) for s in np.array_split(order, num_clients)]


def partition_skew(
    scores: Sequence[float],
    num_clients: int,
    alpha: float = 0.3,
    seed: int = 0,
) -> list[np.ndarray]:
    """Non-IID shards skewed by a per-sample score (e.g. crack density).

    Samples are bucketed into ``num_clients`` score quantiles; a Dirichlet(α)
    mixing matrix assigns each bucket across clients, so small α → each client
    sees mostly one crack-density regime (heavy cracks vs hairline vs clean).
    Every sample lands on exactly one client; shards are disjoint and cover.
    """
    if num_clients <= 0:
        raise ValueError("num_clients must be positive")
    scores = np.asarray(scores, np.float64)
    n = scores.shape[0]
    rng = np.random.default_rng(seed)
    by_score = np.argsort(scores, kind="stable")
    buckets = np.array_split(by_score, num_clients)

    shards: list[list[int]] = [[] for _ in range(num_clients)]
    for b, bucket in enumerate(buckets):
        # proportions of this quantile bucket going to each client; biased
        # toward client b so α→0 degenerates to "client b owns quantile b"
        props = rng.dirichlet(np.full(num_clients, alpha) + (np.arange(num_clients) == b))
        counts = np.floor(props * len(bucket)).astype(int)
        counts[b] += len(bucket) - counts.sum()  # remainder to the home client
        perm = rng.permutation(bucket)
        start = 0
        for c in range(num_clients):
            shards[c].extend(perm[start : start + counts[c]].tolist())
            start += counts[c]
    # No shard may come out empty (an empty shard would silently drop a
    # client from the federation at startup): deterministically move one
    # sample from the largest shard until every shard has at least one, when
    # the dataset allows it.
    if n >= num_clients:
        while any(len(s) == 0 for s in shards):
            src = max(range(num_clients), key=lambda c: len(shards[c]))
            dst = next(c for c in range(num_clients) if not shards[c])
            shards[dst].append(shards[src].pop())
    return [np.sort(np.asarray(s, dtype=np.int64)) for s in shards]


def crack_density(masks: np.ndarray) -> np.ndarray:
    """Per-sample fraction of crack pixels — the default skew score."""
    masks = np.asarray(masks)
    return masks.reshape(masks.shape[0], -1).mean(axis=1)


def mask_density_scores(
    pairs: Sequence[tuple[str, str]], img_size: int = 64
) -> np.ndarray:
    """Crack-density score per (image, mask) pair, decoding masks only at a
    small size — the scoring pass for non-IID sharding over an on-disk
    dataset.

    Deliberately pinned to the PIL + first-party-native decode path (NOT the
    pipeline's cv2 fast path): every client must compute bit-identical
    scores or the uncoordinated shard assignment stops being disjoint, and
    cv2 vs PIL grayscale conversions can differ by a bit on some inputs.
    Decodes run on a thread pool — this is a startup pass over the whole
    train split."""
    from concurrent.futures import ThreadPoolExecutor

    from PIL import Image

    from fedcrack_tpu import native

    def score_one(pair):
        _, mask_path = pair
        mask = np.asarray(Image.open(mask_path).convert("L"))
        return float(native.resize_binarize(mask, img_size).mean())

    with ThreadPoolExecutor(max_workers=8) as pool:
        scores = list(pool.map(score_one, pairs))
    return np.asarray(scores, np.float64)


def shard_pairs(
    pairs: Sequence[tuple[str, str]],
    num_clients: int,
    client_index: int,
    partition: str = "iid",
    alpha: float = 0.3,
    seed: int = 0,
) -> list[tuple[str, str]]:
    """This client's shard of the pair list — the CLI-facing composition of
    the partitioners (every client process runs the same deterministic
    assignment and picks its own row, so shards are disjoint and cover
    without any coordination)."""
    if not 0 <= client_index < num_clients:
        raise ValueError(
            f"client_index {client_index} out of range for {num_clients} clients"
        )
    if num_clients == 1:
        return list(pairs)
    if partition == "iid":
        shards = partition_iid(len(pairs), num_clients, seed=seed)
    elif partition == "skew":
        shards = partition_skew(
            mask_density_scores(pairs), num_clients, alpha=alpha, seed=seed
        )
    else:
        raise ValueError(f"unknown partition {partition!r} (iid or skew)")
    return [pairs[i] for i in shards[client_index]]
