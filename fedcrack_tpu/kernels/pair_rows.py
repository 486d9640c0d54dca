"""The held-expert layer's row movement as Pallas kernels that read at run
time how many rows are kept: the gather of the kept pairs' tokens into the
rows the grouped products take (``take_rows``) and the weighted sum of each
token's own pairs' rows back into ``[T, H]`` (``add_pairs``).

The layer (``models/moe_layers.py``) sorts its ``T x top_k`` (token, slot)
pairs by held expert: the first ``kept`` places of ``order`` are the kept
pairs, held expert by held expert and token by token within an expert, and
``pos`` ``[T, top_k]`` is each pair's place (every held pair's is below
``kept``). Only the kept pairs' rows move:

- ``take_rows``: ``[rows, H]`` in the compute dtype, row ``i < kept`` being
  ``x32[order[i] // top_k]`` cast once; the rows past ``kept`` are UNDEFINED
  (never written), as ``grouped_product`` treats them. A grid over tiles of
  the sorted rows, one DMA a live row; a tile's token indices come into SMEM
  a tile at a time; a tile wholly past ``kept`` does nothing, and the index
  maps hold the last live tile, so nothing is fetched or written back for it.
- ``add_pairs``: ``[T, H]`` in ``rows``' dtype, ``sum_k held[t, k]
  weight[t, k] rows[pos[t, k]]`` in float32, cast once. A grid over tiles of
  tokens. A tile's pairs are, held expert by held expert, one run of the
  sorted rows each (``make_plan``): each run comes into VMEM by a few DMAs of
  ``CHUNK`` rows, then token by token its held pairs' rows, weighted, are
  summed from zero held expert by held expert (the sorted order, as XLA's
  scatter-add adds them) and the tile is written once. No row past ``kept``
  is ever read, there are no write conflicts, and no scalar work is spent on
  slots that are not held.

The VJPs use the same two access patterns: ``take_rows``' cotangent for
``x32`` is the per-token sum of its rows' cotangents with weight 1, in
float32; ``add_pairs``' cotangent for ``rows`` is ``weight g[token]``, a
gather by token over the sorted rows (every kept row written once, with no
read-modify-write; the rows past ``kept`` are left unwritten and nothing
reads them), and its cotangent for ``weight``, ``<rows[r], g[token]>`` in
float32, is taken in the same pass.

**Rows as tiles.** The chip's DMA engine moves whole tiles of an array's
layout: 8 float32 or 16 bf16 rows of a ``[n, H]`` array, never one. So an
array whose rows are gathered is first rewritten (``pair_rows_pack``, the
counted rows' tiles only) as ``[n, W / 128, 128]`` 32-bit words, in which
each row is tiles of its own and a vreg or two in VMEM: float32 rows as they
are (``W = H``), bf16 rows two columns a word, ``j`` and ``j + H / 2``
(``W = H / 2``), so that packing and unpacking are lane-aligned shifts and
masks, exact both ways.

**Traced once a shape.** The functions that build the kernels (``_plan``,
``_pack``, ``_take``, ``_sum``) are jitted on their static arguments, so
that a program of several expert layers, whose backward pass runs each
branch again, traces and lowers each kernel once a shape and not once a
call. Every process pays that lowering, even where the compile cache holds
the program: four expert layers of the convolution cell's shapes lower for a
v5e in 5.4 s so, 9.1 s otherwise, against 3.5 s in XLA's form.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Sorted rows a grid step of the gathers by token; a tile of tokens of the
# per-token sum holds at most ``PLAN_ROWS`` kept rows; rows one DMA of a run
# moves. Chosen by measurement (PERF.md).
ROWS = 512
PLAN_ROWS = 2048
CHUNK = 16
# Row and token counts are whole tiles of this many rows (a bf16 tile's).
UNIT = 16
F32 = jnp.float32
U32 = jnp.uint32
HIGH = np.uint32(0xFFFF0000)  # a bf16's bits in a float32's


class _Spec(NamedTuple):
    """What the kernels are built for (hashable: a ``custom_vjp``'s static
    argument): the tiles of sorted rows and of tokens, the held experts, the
    rows' dtype."""

    rows_tile: int
    tokens_tile: int
    held_n: int
    dtype: str
    interpret: bool

    @property
    def packed(self) -> bool:
        return jnp.dtype(self.dtype).itemsize == 2

    def sublanes(self, hidden: int) -> int:
        """A row's words in lane tiles."""
        return (hidden // 2 if self.packed else hidden) // 128


def _tile(size: int, limit: int) -> int | None:
    """Rows a grid step over ``size``: the largest multiple of 128 up to
    ``limit`` that divides it (a block of the SMEM indices' lane tiles), or
    ``size`` itself where it is no more than ``limit``."""
    whole = [b for b in range(min(limit, size) // 128 * 128, 0, -128) if size % b == 0]
    return whole[0] if whole else (size if size <= limit else None)


def _tokens_tile(tokens: int, slots: int, held_n: int) -> int | None:
    return _tile(tokens, max(1, PLAN_ROWS // min(slots, held_n)))


def fits(tokens: int, slots: int, held_n: int, hidden: int, rows: int, dtype) -> bool:
    """Whether the kernels take these shapes: float32 or bf16 rows whose
    words are whole lane tiles (bf16 rows are half as many words wide), row
    and token counts of whole tiles, and a grid over each."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(F32), jnp.dtype(jnp.bfloat16)):
        return False
    lanes = 256 if dtype.itemsize == 2 else 128
    return (
        hidden % lanes == 0 and rows % UNIT == 0 and tokens % UNIT == 0
        and _tile(rows, ROWS) is not None and _tokens_tile(tokens, slots, held_n) is not None
    )


def _spec(tokens: int, slots: int, held_n: int, dtype, interpret: bool) -> _Spec:
    return _Spec(ROWS, _tokens_tile(tokens, slots, held_n), held_n, jnp.dtype(dtype).name, interpret)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics, vmem_limit_bytes=64 * 1024 * 1024)


# ---- the rows as words -----------------------------------------------------------


def _to_words(x, dtype, packed: bool):
    """Rows ``x`` ``[n, H]`` cast to ``dtype`` as 32-bit words ``[n, W]``."""
    if not packed:
        return x.astype(F32)
    half = x.shape[1] // 2
    bits = lambda v: lax.bitcast_convert_type(v.astype(dtype).astype(F32), U32)
    return (bits(x[:, :half]) >> 16) | (bits(x[:, half:]) & HIGH)


def _from_words(words, packed: bool):
    """The float32 values of words, a block each of the row's columns they
    hold: the words' own, or the low halves' (the first ``W``) then the
    high halves' (the next ``W``)."""
    if not packed:
        return [words]
    return [lax.bitcast_convert_type(words << 16, F32), lax.bitcast_convert_type(words & HIGH, F32)]


def _live(tile: int):
    """Index map of a grid over tiles of rows, of which those from
    ``count`` on hold nothing: the last tile that holds a counted row (tile
    0 where none does), so that no later step fetches or writes a block."""
    return lambda i, count_ref: jnp.minimum(i, jnp.maximum(count_ref[0] - 1, 0) // tile)


def _pack_kernel(spec: _Spec, count_ref, x_ref, out_ref):
    tile = x_ref.shape[0]

    @pl.when(pl.program_id(0) * tile < count_ref[0])
    def _():
        out_ref[...] = _to_words(x_ref[...], jnp.dtype(spec.dtype), spec.packed).reshape(out_ref.shape)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _pack(spec: _Spec, x, count, pad: int = 0):
    """``x``'s first ``count`` rows (whole tiles of them; the rest
    undefined) as words of ``spec.dtype``, ``[n + pad, S, 128]``."""
    n, hidden = x.shape
    tile = _tile(n, spec.rows_tile)
    sublanes = spec.sublanes(hidden)
    at = _live(tile)
    return pl.pallas_call(
        functools.partial(_pack_kernel, spec),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // tile,),
            in_specs=[pl.BlockSpec((tile, hidden), lambda i, c: (at(i, c), 0))],
            out_specs=pl.BlockSpec((tile, sublanes, 128), lambda i, c: (at(i, c), 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n + pad, sublanes, 128), U32 if spec.packed else F32),
        compiler_params=_params("arbitrary"),
        interpret=spec.interpret,
        name="pair_rows_pack",
    )(jnp.reshape(count, (1,)).astype(jnp.int32), x)


# ---- gathers by token, over the sorted rows ---------------------------------------


def _take_kernel(spec: _Spec, kept_ref, token_ref, *refs, scaled: bool):
    """A tile of sorted rows: each live row's token's words by DMA, then the
    values in ``out``'s dtype; ``scaled``: times the row's weight, and
    ``<values, dense row>`` in float32 into ``dot``."""
    if scaled:
        weight_ref, dense_ref, words_hbm, out_ref, dot_ref, buf, sem = refs
    else:
        words_hbm, out_ref, buf, sem = refs
    tile, sublanes = out_ref.shape[0], buf.shape[1]
    live = jnp.minimum(kept_ref[0] - pl.program_id(0) * tile, tile)

    @pl.when(live > 0)
    def _():
        def start(j, carry):
            pltpu.make_async_copy(words_hbm.at[token_ref[0, j]], buf.at[j], sem).start()
            return carry

        def wait(j, carry):
            pltpu.make_async_copy(words_hbm.at[0], buf.at[0], sem).wait()
            return carry

        lax.fori_loop(0, live, start, 0)
        lax.fori_loop(0, live, wait, 0)
        width = sublanes * 128
        dot = None
        for half, values in enumerate(_from_words(buf[...].reshape(tile, width), spec.packed)):
            cols = slice(half * width, (half + 1) * width)
            if scaled:
                out_ref[:, cols] = (values * weight_ref[...]).astype(out_ref.dtype)
                part = jnp.sum(values * dense_ref[:, cols].astype(F32), axis=1, keepdims=True)
                dot = part if dot is None else dot + part
            else:
                out_ref[:, cols] = values.astype(out_ref.dtype)
        if scaled:
            dot_ref[...] = dot


@functools.partial(jax.jit, static_argnums=(0,))
def _take(spec: _Spec, words, token, kept, scale=None):
    """``[rows, H]`` in ``spec.dtype``: row ``i < kept`` is the values of
    ``words[token[i]]``. With ``scale = (weight [rows, 1], dense [rows,
    H])``: times ``weight``, and also ``<values, dense>`` ``[rows, 1]``."""
    rows = token.shape[0]
    sublanes = words.shape[1]
    hidden = sublanes * 128 * (2 if spec.packed else 1)
    tile = _tile(rows, spec.rows_tile)
    at = _live(tile)
    block = pl.BlockSpec((tile, hidden), lambda i, k: (at(i, k), 0))
    column = pl.BlockSpec((tile, 1), lambda i, k: (at(i, k), 0))
    out_shape = jax.ShapeDtypeStruct((rows, hidden), jnp.dtype(spec.dtype))
    in_specs = [pl.BlockSpec((1, tile), lambda i, k: (0, at(i, k)), memory_space=pltpu.SMEM)]
    if scale is None:
        in_specs, out_specs, args = in_specs + [pl.BlockSpec(memory_space=pl.ANY)], block, (words,)
    else:
        in_specs = in_specs + [column, block, pl.BlockSpec(memory_space=pl.ANY)]
        out_specs, out_shape = [block, column], [out_shape, jax.ShapeDtypeStruct((rows, 1), F32)]
        args = (*scale, words)
    return pl.pallas_call(
        functools.partial(_take_kernel, spec, scaled=scale is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // tile,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((tile, sublanes, 128), words.dtype), pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=out_shape,
        compiler_params=_params("arbitrary"),
        interpret=spec.interpret,
        name="pair_rows_scatter" if scale is not None else "pair_rows_take",
    )(jnp.reshape(kept, (1,)).astype(jnp.int32), token.astype(jnp.int32).reshape(1, rows), *args)


# ---- sums over each token's kept rows ---------------------------------------------


class Plan(NamedTuple):
    """How a tile of tokens' kept rows come into VMEM and where each goes
    (``make_plan``): ``runs`` ``[tiles, 1, 3 held_n + 1]`` (each held
    expert's first sorted row of the tile, its count and its first staging
    row, then the staging rows' end); each token's held pairs, held expert by
    held expert (``rank`` ``[T, top_k]``: a pair's place among its token's,
    ``J`` where it is not held), their staging rows (``stage`` ``[J, T]``)
    and count (``count`` ``[1, T]``), ``J = min(top_k, held_n)``; and each
    pair's place in the sorted order (``pos`` ``[T, top_k]``)."""

    runs: jax.Array
    rank: jax.Array
    stage: jax.Array
    count: jax.Array
    pos: jax.Array


@functools.partial(jax.jit, static_argnums=(3,))
def _plan(order, held, group_sizes, tile: int) -> Plan:
    """The staging plan of the per-token sums in tiles of ``tile`` tokens.
    ``order`` (all of it) sorts the ``T x top_k`` pairs by held expert
    (``group_sizes`` a held expert), token by token within an expert, so a
    tile's rows of an expert are one run of the sorted rows; a run is staged
    from a whole
    multiple of ``CHUNK`` rows, so that its DMAs write no other run's rows.
    Built from ``held`` and the group sizes by sums, compares and one sort
    (the inverse permutation): XLA's element gathers and scatters run at a
    few ns an element on the chip, slower than the rows they would steer."""
    tokens, top_k = held.shape
    held_n = group_sizes.shape[0]
    n_tiles = tokens // tile
    pos = lax.sort((order, jnp.arange(order.shape[0], dtype=jnp.int32)), num_keys=1)[1].reshape(tokens, top_k)
    ends = jnp.cumsum(group_sizes)
    experts = jnp.arange(held_n, dtype=jnp.int32)
    expert = jnp.sum(pos[..., None] >= ends, axis=-1).astype(jnp.int32)  # held_n where not held
    chose = jnp.sum(held[..., None] & (expert[..., None] == experts), axis=1, dtype=jnp.int32)  # [T, held_n]
    count = jnp.sum(chose.reshape(n_tiles, tile, held_n), axis=1)
    lo = (ends - group_sizes) + jnp.cumsum(count, axis=0) - count
    span = -(-count // CHUNK) * CHUNK
    at = jnp.cumsum(span, axis=1) - span
    end = at[:, -1:] + span[:, -1:]
    runs = jnp.concatenate([lo, count, at, end], axis=1)[:, None, :]
    delta = jnp.repeat(at - lo, tile, axis=0)  # [T, held_n]
    stage_of = pos + jnp.sum(jnp.where(expert[..., None] == experts, delta[:, None, :], 0), axis=-1)
    slots = min(top_k, held_n)
    rank = jnp.sum(held[:, None, :] & (expert[:, None, :] < expert[:, :, None]), axis=-1).astype(jnp.int32)
    rank = jnp.where(held, rank, slots)
    stage = _compact(stage_of, rank, slots)
    return Plan(runs, rank, stage, jnp.sum(held, axis=1, dtype=jnp.int32)[None, :], pos)


def _compact(values, rank, slots: int):
    """``values`` ``[T, top_k]`` at each token's held pairs' places
    (``rank``), ``[slots, T]``; 0 where a token holds fewer."""
    return jnp.sum(jnp.where(rank[None, :, :] == jnp.arange(slots)[:, None, None], values[None], 0), axis=-1)


def _sum_kernel(spec: _Spec, runs_ref, stage_ref, weight_ref, count_ref, words_hbm, out_ref, stage, acc, sem):
    """A tile of tokens: each held expert's run of rows by DMAs of
    ``CHUNK`` rows into ``stage``; then token by token its held pairs'
    staged rows' values times their weights, summed from zero in float32
    held expert by held expert, and the tile written once."""
    held_n, sublanes = spec.held_n, stage.shape[1]
    issued = jnp.int32(0)
    for e in range(held_n):
        lo, at = runs_ref[0, e], runs_ref[0, 2 * held_n + e]
        chunks = (runs_ref[0, held_n + e] + CHUNK - 1) // CHUNK

        def start(c, carry, lo=lo, at=at):
            pltpu.make_async_copy(
                words_hbm.at[pl.ds(lo + c * CHUNK, CHUNK)], stage.at[pl.ds(at + c * CHUNK, CHUNK)], sem
            ).start()
            return carry

        lax.fori_loop(0, chunks, start, 0)
        issued = issued + chunks

    def wait(c, carry):
        pltpu.make_async_copy(words_hbm.at[pl.ds(0, CHUNK)], stage.at[pl.ds(0, CHUNK)], sem).wait()
        return carry

    lax.fori_loop(0, issued, wait, 0)
    halves = 2 if spec.packed else 1

    def token(t, carry):
        def pair(j, total):
            values = _from_words(stage[stage_ref[j, t]], spec.packed)
            w = weight_ref[j, t]
            return tuple(a + v * w for a, v in zip(total, values))

        zero = jnp.zeros((sublanes, 128), F32)
        total = lax.fori_loop(0, count_ref[0, t], pair, (zero,) * halves)
        for half, a in enumerate(total):
            acc[t, pl.ds(half * sublanes, sublanes), :] = a
        return carry

    tile = out_ref.shape[0]
    lax.fori_loop(0, tile, token, 0)
    out_ref[...] = acc[...].reshape(out_ref.shape).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _sum(spec: _Spec, words, plan_: Plan, weight, out_dtype):
    """``[T, H]`` in ``out_dtype``: each token's held pairs' rows' values
    times ``weight`` (``[T, top_k]``), summed in float32 held expert by held
    expert. ``words`` hold ``CHUNK`` rows past the last kept one."""
    n_tiles, _, width = plan_.runs.shape
    slots, tokens = plan_.stage.shape
    tile = spec.tokens_tile
    sublanes = words.shape[1]
    hidden = sublanes * 128 * (2 if spec.packed else 1)
    per_token = lambda n: pl.BlockSpec((n, tile), lambda i: (0, i), memory_space=pltpu.SMEM)
    staged = tile * slots + spec.held_n * CHUNK
    return pl.pallas_call(
        functools.partial(_sum_kernel, spec),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((None, 1, width), lambda i: (i, 0, 0), memory_space=pltpu.SMEM),
            per_token(slots), per_token(slots), per_token(1), pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((tile, hidden), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((tokens, hidden), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((staged, sublanes, 128), words.dtype),
            pltpu.VMEM((tile, hidden // 128, 128), F32),
            pltpu.SemaphoreType.DMA(()),
        ],
        compiler_params=_params("parallel"),
        interpret=spec.interpret,
        name="pair_rows_sum",
    )(plan_.runs, plan_.stage, _compact(weight.astype(F32), plan_.rank, slots), plan_.count, words)


# ---- the two operations and their VJPs --------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _take_rows(spec, x32, token, kept, held, plan_):
    return _take(spec, _pack(spec, x32, x32.shape[0]), token, kept)


def _take_rows_fwd(spec, x32, token, kept, held, plan_):
    return _take_rows(spec, x32, token, kept, held, plan_), (kept, held, plan_)


def _take_rows_bwd(spec, res, d_rows):
    kept, held, plan_ = res
    d_x = _sum(spec, _pack(spec, d_rows, kept, CHUNK), plan_, held.astype(F32), F32)
    return d_x, None, None, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _add_pairs(spec, rows, weight, order, kept, held, plan_):
    return _sum(spec, _pack(spec, rows, kept, CHUNK), plan_, weight, rows.dtype)


def _add_pairs_fwd(spec, rows, weight, order, kept, held, plan_):
    return _add_pairs(spec, rows, weight, order, kept, held, plan_), (rows, weight, order, kept, held, plan_)


def _add_pairs_bwd(spec, res, g):
    rows, weight, order, kept, held, plan_ = res
    tokens, top_k = held.shape
    n = rows.shape[0]
    # Each sorted row's weight, and each pair's cotangent from its row's, by
    # sorting the pairs by place and the places by pair: element gathers
    # would be slower than the rows they steer.
    by_place = lax.sort((plan_.pos.reshape(-1), weight.reshape(-1)), num_keys=1)[1]
    d_rows, dot = _take(spec, _pack(spec, g, tokens), order[:n] // top_k, kept, (by_place[:n, None], rows))
    by_pair = lax.sort((order, jnp.pad(dot[:, 0], (0, order.shape[0] - n))), num_keys=1)[1]
    d_weight = jnp.where(held, by_pair.reshape(tokens, top_k), 0.0)
    return d_rows, d_weight, None, None, None, None


_add_pairs.defvjp(_add_pairs_fwd, _add_pairs_bwd)


def make_plan(order, group_sizes, held) -> Plan:
    """The staging plan both operations' per-token sums read (one a layer
    call: ``take_rows``' backward and ``add_pairs``' forward share it)."""
    tokens, slots = held.shape
    return _plan(order, held, group_sizes, _tokens_tile(tokens, slots, group_sizes.shape[0]))


def take_rows(x32, order, kept, held, plan_: Plan, *, rows: int, dtype, interpret: bool = False):
    """``[rows, H]`` in ``dtype``: row ``i < kept`` is ``x32[order[i] //
    top_k]`` cast once, the rest undefined (``order`` sorts every pair).
    The backward pass sums each token's rows' cotangents in float32 by
    ``plan_`` (``make_plan``). ``fits`` says whether the shapes are the
    kernels'."""
    tokens, slots = held.shape
    spec = _spec(tokens, slots, (plan_.runs.shape[-1] - 1) // 3, dtype, interpret)
    return _take_rows(spec, x32, order[:rows] // slots, kept, held, plan_)


def add_pairs(rows, weight, order, kept, held, plan_: Plan, *, interpret: bool = False):
    """``[T, H]`` in ``rows``' dtype: ``sum_k held[t, k] weight[t, k]
    rows[pos[t, k]]`` in float32 held expert by held expert, cast once.
    ``rows`` ``[n, H]`` in the sorted order of ``order`` (which sorts every
    pair; the first ``kept`` rows defined); ``weight``, ``held`` ``[T,
    top_k]``; ``plan_`` from ``make_plan``."""
    tokens, slots = held.shape
    spec = _spec(tokens, slots, (plan_.runs.shape[-1] - 1) // 3, rows.dtype, interpret)
    return _add_pairs(spec, rows, weight.astype(F32), order, kept, held, plan_)
