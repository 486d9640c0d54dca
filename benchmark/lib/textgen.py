"""Seeded data for the block-diffusion traffic: packed token sequences.

A client holds ``train_samples`` sequences of ``seq_len`` tokens whose ids
are uniform over the held vocabulary slice without its last row, the mask
token. ``TextFeed`` is the feed: for round ``r`` a fresh permutation of every
client's sequences and fresh block-diffusion noise (every block of
``block_length`` tokens draws ``t ~ U[t_range]``, masks each of its tokens
with probability ``t`` and weighs a masked token ``1/t``), through the
program's own ``data.textdiff.stage_pair``. Everything is drawn from
``numpy.random.default_rng([seed, ...])``: the same seed gives the same
bytes, a pure function of (seed, round).
"""

from __future__ import annotations

import numpy as np

from fedcrack_tpu.data.textdiff import stage_pair


def client_sequences(seed: int, clients: int, n: int, seq_len: int, vocab_held: int) -> np.ndarray:
    """``[clients, n, seq_len]`` int32 ids over rows ``0..vocab_held-2``."""
    rng = np.random.default_rng([seed, 0])
    return rng.integers(0, vocab_held - 1, (clients, n, seq_len), dtype=np.int32)


class TextFeed:
    """``feed(r)`` gives round ``r``'s ``(ids int32, weight float32)``, each
    ``[clients, steps, batch, seq_len]``, in two sets of buffers used in turn
    (a round's data is dead once the next but one is asked for)."""

    def __init__(self, sequences: np.ndarray, seed: int, steps: int, batch: int, block_length: int, t_range):
        self.sequences, self.seed, self.steps, self.batch = sequences, seed, steps, batch
        self.block_length, self.t_range = block_length, tuple(t_range)
        shape = (sequences.shape[0], steps, batch, sequences.shape[2])
        self._buffers = [(np.zeros(shape, np.int32), np.zeros(shape, np.float32)) for _ in range(2)]

    def __call__(self, round_idx: int) -> tuple[np.ndarray, np.ndarray]:
        rng = np.random.default_rng([self.seed, 1 + round_idx])
        return stage_pair(
            self.sequences, self.steps, self.batch, self.block_length, rng, self.t_range,
            out=self._buffers[round_idx % 2],
        )
