"""Seeded data for the federated-round traffic: one general generator.

A traffic file gives ``base_samples`` and a ``foreground`` range; everything
else (crop size, samples, batch) is the configuration's. A client's pool is
``base_samples`` synthetic crack photographs (a smooth concrete-like texture,
darker along a few wavy cracks, the mask on the crack) recombined by seeded
flips and rolls until the client has its samples: all rows differ, and the
masks' foreground share spreads over the stated range, about the crack data
set's 7% in the mean. Everything is drawn in bulk numpy calls from
``numpy.random.default_rng([seed, client, ...])``: the same seed gives the
same bytes.

``RoundFeed`` is the feed: a fresh permutation of the pool for every round,
a pure function of (seed, client, round). Inside every batch the rows
are ordered by foreground share. The order of a batch's rows changes nothing
in what a step computes, but it makes any contiguous part of a batch unlike
the whole, so that a step which leaves part of its batch out cannot produce
the right loss.
"""

from __future__ import annotations

import numpy as np


def _base_samples(rng: np.random.Generator, n: int, size: int, fg_range) -> tuple[np.ndarray, np.ndarray]:
    """``n`` images ``[n, size, size, 3]`` and masks ``[n, size, size, 1]``, uint8."""
    yy = np.arange(size, dtype=np.float32)[None, :, None] / size
    xx = np.arange(size, dtype=np.float32)[None, None, :] / size
    target = rng.uniform(fg_range[0], fg_range[1], n).astype(np.float32)
    n_cracks = 3
    # Each crack covers about 2 * half_width of the height at every column.
    half_width = (target / (2.0 * n_cracks))[:, None, None]
    mask = np.zeros((n, size, size), bool)
    for _ in range(n_cracks):
        p = rng.uniform(0.0, 1.0, (6, n, 1, 1)).astype(np.float32)
        centre = (
            p[0] + (p[1] - 0.5) * xx
            + 0.08 * np.sin(2 * np.pi * (1 + 3 * p[2]) * xx + 6.28 * p[3])
        ) % 1.0
        dist = np.abs(yy - centre)
        mask |= np.minimum(dist, 1.0 - dist) < half_width * (0.6 + 0.8 * p[4])
    coarse = rng.uniform(0.35, 0.85, (n, size // 16, size // 16, 3)).astype(np.float32)
    image = np.repeat(np.repeat(coarse, 16, axis=1), 16, axis=2)
    image += rng.normal(0.0, 0.04, (n, size, size, 1)).astype(np.float32)
    image *= np.where(mask, 0.45, 1.0)[..., None].astype(np.float32)
    images = np.clip(np.rint(image * 255.0), 0, 255).astype(np.uint8)
    return images, mask[..., None].astype(np.uint8)


def client_pool(seed: int, client: int, n_samples: int, size: int, traffic: dict) -> dict:
    """One client's sample pool and each sample's foreground share."""
    rng = np.random.default_rng([seed, client, 0])
    n_base = int(traffic["base_samples"])
    base_i, base_m = _base_samples(rng, n_base, size, traffic["foreground"])
    images = np.empty((n_samples, size, size, 3), np.uint8)
    masks = np.empty((n_samples, size, size, 1), np.uint8)
    for g, lo in enumerate(range(0, n_samples, n_base)):
        hi = min(lo + n_base, n_samples)
        dy, dx = (int(v) for v in rng.integers(0, size, 2))
        if g == 0:
            dy = dx = 0
        for src, dst in ((base_i, images), (base_m, masks)):
            # dst[lo:hi] = flip(roll(src, (dy, dx))), written as four block
            # copies into the flipped view: no temporary, one pass.
            out = dst[lo:hi]
            if g & 1:
                out = out[:, :, ::-1]
            if g & 2:
                out = out[:, ::-1]
            t = src[: hi - lo]
            out[:, dy:, dx:] = t[:, : size - dy, : size - dx]
            out[:, :dy, dx:] = t[:, size - dy :, : size - dx]
            out[:, dy:, :dx] = t[:, : size - dy, size - dx :]
            out[:, :dy, :dx] = t[:, size - dy :, size - dx :]
    foreground = masks.reshape(n_samples, -1).mean(axis=1, dtype=np.float32)
    return {"images": images, "masks": masks, "foreground": foreground}


def round_indices(pool: dict, seed: int, client: int, round_idx: int, steps: int, batch: int) -> np.ndarray:
    """Which pool samples the client trains on in one round, in order."""
    n = pool["images"].shape[0]
    need = steps * batch
    if n < need:
        raise ValueError(f"pool has {n} samples, a round needs {need}")
    rng = np.random.default_rng([seed, client, 1 + round_idx])
    idx = rng.permutation(n)[:need].reshape(steps, batch)
    order = np.argsort(pool["foreground"][idx], axis=1, kind="stable")
    return np.take_along_axis(idx, order, axis=1).reshape(-1)


class RoundFeed:
    """``feed(r)`` gives round ``r``'s ``[clients, steps, batch, ...]`` uint8
    images and masks. The bytes are gathered into two sets of buffers used in
    turn (a round's data is dead once the next but one is asked for), so a
    round costs the gather and no fresh pages."""

    def __init__(self, pools: list, seed: int, steps: int, batch: int):
        self.pools, self.seed, self.steps, self.batch = pools, seed, steps, batch
        lead = (len(pools), steps, batch)
        self._buffers = [
            tuple(np.zeros(lead + pools[0][k].shape[1:], np.uint8) for k in ("images", "masks"))
            for _ in range(2)
        ]

    def __call__(self, round_idx: int) -> tuple[np.ndarray, np.ndarray]:
        images, masks = self._buffers[round_idx % 2]
        for c, pool in enumerate(self.pools):
            idx = round_indices(pool, self.seed, c, round_idx, self.steps, self.batch)
            for src, dst in ((pool["images"], images), (pool["masks"], masks)):
                np.take(src, idx, axis=0, out=dst[c].reshape(-1, *src.shape[1:]), mode="clip")
        return images, masks
