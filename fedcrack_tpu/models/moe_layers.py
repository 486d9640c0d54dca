"""What the mixture-of-experts language models share.

The four families (``models/sdar_moe.py``, ``models/mla_moe.py``,
``models/gdn_moe.py``, ``models/lfm2_moe.py``) hold one chip's share of their
expert layers and of their vocabulary, and read these from here: RMSNorm, the
causal models' rotary angles, the builder of the splash-attention kernel for a
mask (and the causal mask the causal ones hand it), the softmax router two of
them score by, the SwiGLU of a dense or shared expert, the grouped matrix
product over the held experts, the held-expert layer itself and its counters,
and the chunked head's losses.

**The held-expert layer.** It is told which experts it holds
(``first_expert``, the leading axis of the experts' weights) and how the
router scores (``route``: the family's own function from the normed tokens
and the router's matrix to each token's chosen experts and their weights).
It keeps the (token, slot) pairs whose expert is held, orders them by
expert, runs the three matrix products as one grouped product over the held
experts and adds the weighted rows back. What absent experts would add is
left out: no code stands in for the absent chips or their exchange. A shared
expert, where a family has one, is the caller's own dense layer added once to
this part.

**Two branches, one ``lax.cond``.** A chip that holds 16 of 512 experts keeps
3% of the pairs, so the layer has a row budget (``row_budget``: ``ROW_BUDGET``
times the uniform load, in whole kernel tiles) that bounds what it MOVES and
what it allocates. Where the kept pairs fit it, every array between the sort
and the ``[T, H]`` result has ``budget`` rows; where they do not (a router
that sends the held experts more than ``ROW_BUDGET`` times their share), a
row for every pair. The two branches are one form at two lengths. On both the
grouped products run over the kept pairs alone, in whole tiles of
``GMM_TILE_M`` rows for each held expert's group (``grouped_tiles`` counts
them): their time follows the routing, the arrays' shapes do not. No pair is
ever dropped and nothing is approximated on either branch. The shapes alone
say whether there is a choice: where the budget is every pair there is one
branch and no ``cond``.

**The rows' movement.** Where the kernels run (below) and the shapes are
theirs (``pair_rows.fits``: rows of whole lane tiles), a branch moves its rows
on this repo's row kernels (``_moved``, ``kernels/pair_rows.py``): a gather of
the kept pairs' tokens and a per-token float32 sum of their rows that read
``kept`` at run time, so they move the kept rows alone. Elsewhere, and off the
chip, ``_xla_form`` (the tests' oracle): XLA gathers the tokens of the row
array's pairs and adds each kept row, times its pair's weight, to its token in
float32.

**What it counts.** ``EXPERT_COUNTERS`` names the layer's counters and how a
round reduces each. ``held_expert_layer`` returns them as one record beside
its part, and ``layer_totals`` makes a model's record of its layers'. The
models hand the record on whole and their tasks list ``EXPERT_COUNTERS``, so a
new counter is this module's alone.

Kernels: JAX's splash-attention Pallas kernel and JAX's megablox ``gmm``, on
a TPU at sizes their tiles divide; elsewhere a masked dense softmax (the
caller's) and ``jax.lax.ragged_dot``. ``kernels`` steers that for tests
(``"pallas"``, ``"interpret"``, ``"xla"``). Neither library kernel declares
over which mesh axes its result varies, so a ``shard_map`` that holds one of
these models runs with ``check_vma=False`` (``tasks.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from fedcrack_tpu.kernels import pair_rows

# Positions a chunk of the head: [chunk, vocab_held] float32 logits are all
# of the logits that ever exist (1024 x 18,992 x 4 B = 78 MB).
HEAD_CHUNK = 1024
# Tiles of the kernels: (m, k, n) of the grouped product and the attention's
# square tile of queries and keys.
GMM_TILE_M = 512
ATTN_TILE = 512
# The held-expert layer moves and allocates this multiple of the rows a
# uniform router would send to the held experts (``row_budget``), whatever the
# routing sends, as long as that fits: what moves does not change with the
# routing until the load is three times the uniform one; beyond it the layer
# takes its other branch, with room for every pair. The grouped products run
# over the kept pairs only, in whole tiles of ``GMM_TILE_M`` rows for each
# group, and follow the routing within the budget. With fresh weights the
# attention's output, an average over thousands of keys, outweighs a token's
# own embedding, so most positions of a sequence route alike: a layer's held
# load is about 0, 1, 2 or 3 times the uniform one as 0, 1, 2 or 3 of those
# eight shared choices are held here (measured 1.6 times in the mean of four
# layers; at a budget of 2 one round in five held a layer beyond it and read
# 0.3-0.6% slower).
ROW_BUDGET = 3.0
# What ``held_expert_layer`` counts a call, each float32, and how a round
# reduces it (``tasks``): ``expert_rows`` ``[experts_held]`` (rows each held
# expert computed), ``held_pairs`` (pairs kept of ``T x top_k``),
# ``budget_overflows`` (1 where the kept pairs did not fit the budget and the
# call's row arrays held every pair), ``expert_tiles`` (the ``grouped_tiles``
# each of the call's grouped products ran over) and ``moved_rows`` (the rows
# the call's gather and per-token sum moved: the kept pairs' on the row
# kernels, the row arrays' length in the XLA form).
EXPERT_COUNTERS = (
    ("expert_rows", "sum"), ("held_pairs", "sum"), ("budget_overflows", "sum"), ("expert_tiles", "sum"),
    ("moved_rows", "sum"),
)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over the last axis in float32; returns float32. A family whose
    norm is zero-centred (its weight ``w`` starts at 0) hands ``1 + w``."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return x32 * lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rotary_tables(seq_len: int, rotary_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """``cos``, ``sin`` ``[L, rotary_dim / 2]`` for positions ``0..L-1``: one
    angle a pair of lanes, whichever two lanes the family rotates together."""
    inv_freq = 1.0 / (theta ** (np.arange(0, rotary_dim, 2, dtype=np.float64) / rotary_dim))
    angles = np.arange(seq_len, dtype=np.float64)[:, None] * inv_freq[None, :]
    return jnp.asarray(np.cos(angles), jnp.float32), jnp.asarray(np.sin(angles), jnp.float32)


def resolve_kernels(kernels: str | None) -> str:
    if kernels is None:
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if kernels not in ("pallas", "interpret", "xla"):
        raise ValueError(f"kernels must be None, 'pallas', 'interpret' or 'xla', got {kernels!r}")
    return kernels


@functools.lru_cache(maxsize=8)
def splash_kernel(
    make_mask, mask_args: tuple, heads: int, shared_kv: bool, tile: int, interpret: bool,
    residual_name: str | None = None,
):
    """The splash-attention kernel for ``heads`` query heads under the mask
    ``make_mask(*mask_args)`` (a module-level function and hashable
    arguments, so that one kernel is built a mask): over one key/value head
    that all of them read (``shared_kv``; ``[heads, S, d]`` queries beside
    ``[S, d]`` keys and values) or over a key/value head each. The mask's
    tiles are worked out once, on the host, as the program is traced. The
    values' width is the values' own (the kernel reads it off ``v``).
    ``residual_name``, where given, names the forward kernel's output and
    logsumexp (``checkpoint_name``), so that a ``jax.checkpoint`` around the
    caller whose policy saves that name keeps the two arrays the backward
    kernels read and does not run the forward kernel again."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    mask = make_mask(*mask_args)
    sizes = sk.BlockSizes(
        block_q=tile, block_kv=tile, block_kv_compute=tile,
        block_q_dkv=tile, block_kv_dkv=tile, block_kv_dkv_compute=tile,
        block_q_dq=tile, block_kv_dq=tile,
    )
    make = sk.make_splash_mqa if shared_kv else sk.make_splash_mha
    # The kernel's mask tables must be plain constants of whatever program is
    # being traced, not tracers of the first one that asked.
    with jax.ensure_compile_time_eval():
        return make(
            sm.MultiHeadMask([mask] * heads), block_sizes=sizes,
            head_shards=1, q_seq_shards=1, interpret=interpret,
            residual_checkpoint_name=residual_name,
        )


def causal_splash_mask(seq_len: int):
    """The causal mask of one document of ``seq_len`` tokens, for
    ``splash_kernel`` (a module-level function: one kernel a length)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as sm

    return sm.CausalMask((seq_len, seq_len))


def softmax_route(n32: jax.Array, router: jax.Array, top_k: int, norm_topk: bool):
    """``g = softmax(W_r n)`` over all the router's experts in float32; the
    ``top_k`` largest and their weights (renormalised over the chosen
    ``top_k`` where ``norm_topk``). ``[T, top_k]`` each."""
    logits = jnp.dot(n32, router.astype(jnp.float32), precision=lax.Precision.HIGHEST)
    gates = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = lax.top_k(gates, top_k)
    if norm_topk:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return top_e, top_w


def swiglu(n: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array, cd) -> jax.Array:
    """``W_down (silu(W_gate n) * W_up n)`` for ``n`` ``[T, H]`` in ``cd``;
    float32 ``[T, H]``."""
    gate = jnp.dot(n, w_gate.astype(cd), preferred_element_type=jnp.float32)
    up = jnp.dot(n, w_up.astype(cd), preferred_element_type=jnp.float32)
    mid = (jax.nn.silu(gate) * up).astype(cd)
    return jnp.dot(mid, w_down.astype(cd), preferred_element_type=jnp.float32)


def grouped_product(
    rows: jax.Array, weights: jax.Array, group_sizes: jax.Array, *, kernels: str | None = None
) -> jax.Array:
    """``rows[start_g : start_g + size_g] @ weights[g]`` for every group, the
    groups laid end to end from row 0. Rows past the last group are zeros
    from ``ragged_dot`` and UNDEFINED from the kernel, forward and backward
    (the kernel runs only the ``grouped_tiles`` the groups touch): the caller
    reads none of them on the way out or on the way back (``_xla_form``,
    ``_moved``).
    ``rows`` ``[m, k]``, ``weights`` ``[groups, k, n]``; returns ``[m, n]`` in
    ``rows``' dtype, accumulated in float32."""
    mode = resolve_kernels(kernels)
    m, k = rows.shape
    n = weights.shape[-1]
    if mode != "xla" and m % GMM_TILE_M == 0 and k % 128 == 0 and n % 128 == 0:
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        tiling = (GMM_TILE_M, min(k, 1024), min(n, 1024))
        return megablox.gmm(rows, weights, group_sizes, rows.dtype, tiling, None, None, False, mode == "interpret")
    return lax.ragged_dot(rows, weights, group_sizes, preferred_element_type=jnp.float32).astype(rows.dtype)


def grouped_tiles(group_sizes: jax.Array) -> jax.Array:
    """Tiles of ``GMM_TILE_M`` rows one grouped product over ``group_sizes``
    runs, by megablox's rule for its forward kernel: a group that is not empty
    and lies from row ``start`` to ``end`` takes ``ceil(end / tm) - floor(start
    / tm)`` tiles, a tile two groups share counting for each. int32 ``[]``."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    tiles = -(-ends // GMM_TILE_M) - starts // GMM_TILE_M
    return jnp.sum(jnp.where(group_sizes > 0, tiles, 0))


def row_budget(pairs: int, held_n: int, router_width: int) -> int:
    """Rows the held-expert layer moves for ``pairs`` (token, slot) pairs
    with ``held_n`` of the router's ``router_width`` experts held:
    ``ROW_BUDGET`` times the uniform load in whole ``GMM_TILE_M`` (the
    megablox kernel takes whole tiles; ``grouped_product`` would fall to
    ``ragged_dot`` otherwise), and never more than every pair."""
    tiles = -(-int(ROW_BUDGET * pairs * held_n / router_width) // GMM_TILE_M)
    return min(pairs, tiles * GMM_TILE_M)


def _experts(rows, weights, group_sizes, kernels):
    """``W_down (silu(W_gate r) * W_up r)``, every row of a group by its
    expert; rows past the last group undefined (``grouped_product``)."""
    w_gate, w_up, w_down = weights
    with jax.named_scope("moe_experts"):
        gate = grouped_product(rows, w_gate, group_sizes, kernels=kernels)
        up = grouped_product(rows, w_up, group_sizes, kernels=kernels)
        mid = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)).astype(rows.dtype)
        return grouped_product(mid, w_down, group_sizes, kernels=kernels)


@jax.custom_vjp
def _kept_rows(rows, kept):
    """``rows`` as they are. The backward pass lets the first ``kept`` rows'
    cotangents through and zeroes the rest: the grouped products run over the
    kept pairs alone and leave the other rows' cotangents UNDEFINED
    (``grouped_product``), which the gather's transpose would add to real
    tokens."""
    del kept
    return rows


def _kept_rows_fwd(rows, kept):
    return rows, kept


def _kept_rows_bwd(kept, g):
    is_kept = jnp.arange(g.shape[0], dtype=jnp.int32) < kept
    return jnp.where(is_kept[:, None], g, jnp.zeros((), g.dtype)), None


_kept_rows.defvjp(_kept_rows_fwd, _kept_rows_bwd)


def _xla_form(length, kernels, n32, top_w, weights, routing):
    """The layer in XLA's operations where the kept pairs fit ``length`` rows
    (the budget, or every pair): every array between the sort and the ``[T,
    H]`` result has ``length`` rows. The rows past the kept pairs are other
    tokens: the grouped products run over the kept pairs' groups alone, and
    what they leave undefined past them is selected away before anything
    multiplies it, on the way out here and on the way back in ``_kept_rows``.
    The kept rows are added to their tokens in float32."""
    order, held, group_sizes, kept = routing
    top_k = held.shape[1]
    compute_dtype = weights[0].dtype
    # Every index is one of ``order``'s: a pair, or its token.
    in_bounds = dict(mode="promise_in_bounds")
    with jax.named_scope("moe_dispatch"):
        chosen = order[:length]
        token = chosen // top_k
        rows = _kept_rows(n32.at[token].get(**in_bounds).astype(compute_dtype), kept)
    down = _experts(rows, weights, group_sizes, kernels)
    with jax.named_scope("moe_combine"):
        weight = top_w.reshape(-1).at[chosen].get(unique_indices=True, **in_bounds)
        is_kept = jnp.arange(length, dtype=jnp.int32) < kept
        down = jnp.where(is_kept[:, None], down, jnp.zeros((), compute_dtype))
        part = jnp.zeros(n32.shape, jnp.float32).at[token].add(down.astype(jnp.float32) * weight[:, None], **in_bounds)
    return part.astype(compute_dtype)


def _moved(length, kernels, n32, top_w, weights, routing):
    """The layer on the row kernels (``kernels/pair_rows.py``): its row
    arrays have ``length`` rows (the budget, or every pair on the overflow's
    branch), and only the kept pairs' rows move: ``take_rows`` gathers their
    tokens and ``add_pairs`` sums each token's own pairs' rows back, both
    reading ``kept`` at run time. The rows past the kept pairs are undefined
    and nothing reads them, forward or backward."""
    order, held, group_sizes, kept = routing
    interpret = resolve_kernels(kernels) == "interpret"
    with jax.named_scope("moe_dispatch"):
        plan = pair_rows.make_plan(order, group_sizes, held)
        rows = pair_rows.take_rows(
            n32, order, kept, held, plan, rows=length, dtype=weights[0].dtype, interpret=interpret
        )
    down = _experts(rows, weights, group_sizes, kernels)
    with jax.named_scope("moe_combine"):
        return pair_rows.add_pairs(down, top_w, order, kept, held, plan, interpret=interpret)


def _moves_rows(kernels, n32, held, held_n: int, length: int, compute_dtype) -> bool:
    """Whether a branch of ``length`` rows runs on the row kernels: a Pallas
    mode and shapes they take; elsewhere, and off the chip, the XLA form."""
    tokens, hidden = n32.shape
    return resolve_kernels(kernels) != "xla" and pair_rows.fits(
        tokens, held.shape[1], held_n, hidden, length, compute_dtype
    )


def _branch(length: int, kernels, moves: bool):
    """The layer with row arrays of ``length`` rows, in the form that runs."""
    return functools.partial(_moved if moves else _xla_form, length, kernels)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _two_pass(budget, fast, overflow, n32, top_w, weights, routing):
    """``fast`` (the layer in ``budget`` rows) where the kept pairs fit the
    budget and ``overflow`` (in a row for every pair) where they do not: one
    ``lax.cond``, forward and backward. The backward pass keeps the inputs
    alone and is a ``cond`` over the two branches' own VJPs, where a plain
    ``cond`` hands it the residuals of BOTH branches, the untaken one's as
    zeros ``pairs`` rows long."""
    *_, kept = routing
    return lax.cond(kept > budget, overflow, fast, n32, top_w, weights, routing)


def _two_pass_fwd(budget, fast, overflow, n32, top_w, weights, routing):
    return _two_pass(budget, fast, overflow, n32, top_w, weights, routing), (n32, top_w, weights, routing)


def _two_pass_bwd(budget, fast, overflow, res, g):
    *moving, routing = res
    *_, kept = routing

    def pull(branch):
        return lambda g, *moving: jax.vjp(lambda *m: branch(*m, routing), *moving)[1](g)

    return (*lax.cond(kept > budget, pull(overflow), pull(fast), g, *moving), None)


_two_pass.defvjp(_two_pass_fwd, _two_pass_bwd)


def held_expert_layer(
    n32: jax.Array,
    router: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    first_expert: int,
    route,
    compute_dtype,
    kernels: str | None = None,
):
    """The held experts' part of the expert layer for tokens ``n32`` ``[T, H]``
    (normed, float32). ``route(n32, router)`` gives every token's chosen
    experts (indices among the router's outputs) and their weights,
    ``[T, top_k]`` each: the family's scoring form. Where ``row_budget`` is
    less than every pair the layer has two branches under one ``lax.cond``,
    its row arrays the budget long or a row for every pair, taken by whether
    the kept pairs fit the budget; where it is every pair (a chip that holds a
    third of the experts or more) there is one. Each branch runs on the row
    kernels or in the XLA form (``_moves_rows``). Returns the part ``[T, H]``
    in ``compute_dtype`` and the call's record: ``EXPERT_COUNTERS`` by name,
    each float32."""
    held_n = w_gate.shape[0]
    with jax.named_scope("router"):
        top_e, top_w = route(n32, router)
    with jax.named_scope("moe_dispatch"):
        local = top_e - first_expert
        held = (local >= 0) & (local < held_n)
        # Pairs of absent experts sort behind every held one.
        key = jnp.where(held, local, held_n).reshape(-1).astype(jnp.int32)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        group_sizes = jnp.sum(key[:, None] == jnp.arange(held_n, dtype=jnp.int32)[None, :], axis=0, dtype=jnp.int32)
        kept = jnp.sum(group_sizes)
        pairs = order.shape[0]
        budget = row_budget(pairs, held_n, router.shape[-1])
    with jax.named_scope("moe_experts"):
        weights = tuple(w.astype(compute_dtype) for w in (w_gate, w_up, w_down))
    # Rows a branch moves: the kept pairs' on the row kernels, its row
    # arrays' length in the XLA form.
    moves = {length: _moves_rows(kernels, n32, held, held_n, length, compute_dtype) for length in {budget, pairs}}
    moved = {length: kept if moves[length] else jnp.int32(length) for length in moves}
    every_pair = _branch(pairs, kernels, moves[pairs])
    if budget == pairs:
        layer, moved_rows = every_pair, moved[pairs]
    else:
        layer = functools.partial(_two_pass, budget, _branch(budget, kernels, moves[budget]), every_pair)
        moved_rows = jnp.where(kept > budget, moved[pairs], moved[budget])
    part = layer(n32, top_w, weights, (order, held, group_sizes, kept))
    counters = (group_sizes, kept, kept > budget, grouped_tiles(group_sizes), moved_rows)
    return part, {name: counter.astype(jnp.float32) for (name, _), counter in zip(EXPERT_COUNTERS, counters)}


def layer_totals(counted: list[dict], experts_held: int) -> dict:
    """The model's record of its expert layers' (``counted``, one record a
    layer, each summed over the layer's calls, in the layers' order):
    ``expert_rows`` stacked ``[layers, experts_held]``, every other counter
    summed over the layers. A model with no expert layer reports no layer's
    rows and zeros."""
    def stacked(name, shape):
        if counted:
            return jnp.stack([record[name] for record in counted])
        return jnp.zeros((0, *shape), jnp.float32)

    return {
        name: stacked(name, (experts_held,)) if name == "expert_rows" else jnp.sum(stacked(name, ()))
        for name, _ in EXPERT_COUNTERS
    }


def token_losses(hidden32: jax.Array, head: jax.Array, targets: jax.Array, compute_dtype):
    """Cross-entropy of every position against its target and whether the
    largest logit is the target, the logits existing a chunk of positions at
    a time (and again, a chunk at a time, in the backward pass)."""
    positions = hidden32.shape[0]
    chunk = min(HEAD_CHUNK, positions)
    if positions % chunk:
        chunk = positions
    head_c = head.astype(compute_dtype)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def one(args):
        h, t = args
        logits = jnp.dot(h.astype(compute_dtype), head_c, preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        return lse - picked, (jnp.argmax(logits, axis=-1) == t).astype(jnp.float32)

    nll, hit = lax.map(one, (hidden32.reshape(-1, chunk, hidden32.shape[-1]), targets.reshape(-1, chunk)))
    return nll.reshape(positions), hit.reshape(positions)
