"""Packed token sequences to the text rounds' staged pair.

The round program's text tasks read a pair of ``[C, steps, B, L]`` arrays:
``ids`` int32, the clean tokens, and ``weight`` float32. For block diffusion
(``tasks.TextDiffusionTask``) ``weight`` is 0 where a token stays and ``1/t``
of its block where it is masked: the noise is drawn here, on the host, from a
seed: it is data, so the program and anything that follows it read the same
bytes. For next-token training (``tasks.CausalLMTask``) there is no noise
(``block_length`` ``None``) and every token weighs 1.
"""

from __future__ import annotations

import numpy as np


def block_diffusion_weights(
    shape: tuple[int, ...], block_length: int, rng: np.random.Generator, t_range: tuple[float, float] = (0.1, 1.0)
) -> np.ndarray:
    """``weight`` float32 of ``shape`` ``[..., L]``: every block of
    ``block_length`` tokens draws ``t ~ U[t_range]`` and masks each of its
    tokens with probability ``t``; a masked token weighs ``1/t``."""
    seq_len = shape[-1]
    if seq_len % block_length:
        raise ValueError(f"sequences of {seq_len} tokens are not whole blocks of {block_length}")
    t = rng.uniform(t_range[0], t_range[1], shape[:-1] + (seq_len // block_length,)).astype(np.float32)
    t = np.repeat(t, block_length, axis=-1)
    masked = rng.random(shape, np.float32) < t
    return np.where(masked, np.float32(1.0) / t, np.float32(0.0)).astype(np.float32)


def stage_pair(
    sequences: np.ndarray, steps: int, batch: int, block_length: int | None, rng: np.random.Generator,
    t_range: tuple[float, float] = (0.1, 1.0), out: tuple[np.ndarray, np.ndarray] | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """One round's ``(ids, weight)`` ``[C, steps, B, L]`` from each client's
    packed sequences ``[C, N, L]`` (``N >= steps * batch``): a fresh
    permutation of every client's sequences and fresh noise, both from
    ``rng``; without a ``block_length`` no noise is drawn and every token
    weighs 1. ``out`` reuses a pair of buffers of that shape."""
    clients, n, seq_len = sequences.shape
    need = steps * batch
    if n < need:
        raise ValueError(f"a client holds {n} sequences, a round needs {need}")
    ids = np.empty((clients, steps, batch, seq_len), np.int32) if out is None else out[0]
    for c in range(clients):
        np.take(sequences[c], rng.permutation(n)[:need], axis=0, out=ids[c].reshape(need, seq_len))
    if block_length is None:
        weight = np.ones(ids.shape, np.float32)
    else:
        weight = block_diffusion_weights(ids.shape, block_length, rng, t_range)
    if out is not None:
        out[1][...] = weight
        weight = out[1]
    return ids, weight
