"""The held-expert layer's row kernels (``kernels/pair_rows.py``) in the
interpreter: ``take_rows`` and ``add_pairs`` and both their VJPs against the
XLA form they replace on the chip (``moe_layers._xla_form``'s gather and
float32 scatter-add), at small shapes across several tiles of rows and of
tokens; then ``held_expert_layer`` on the kernels against its XLA form on
both branches, with each family's router; and the kernels compiled for a
described v5e at the cells' widths."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from fedcrack_tpu.kernels import pair_rows as K
from fedcrack_tpu.models import mla_moe, moe_layers

TOKENS, TOP_K, HIDDEN, EXPERTS, HELD = 256, 4, 256, 8, 4


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 128 sorted rows and of 128 tokens, so that a short row array
    and a short sequence cross several of each."""
    monkeypatch.setattr(K, "ROWS", 128)
    monkeypatch.setattr(K, "PLAN_ROWS", 128 * min(TOP_K, HELD))


def _routing(case: str, seed: int = 0):
    """``top_e`` ``[T, top_k]`` over ``EXPERTS`` of which the first ``HELD``
    are held: token ``t`` holds ``t % (top_k + 1)`` slots (0 to ``top_k``),
    except ``none`` (no pair kept), ``two_each`` (two slots a token: the
    kept pairs fill a row array of ``2 T`` whole) and ``last_empty`` (the
    last held expert chosen by none)."""
    rng = np.random.default_rng(seed)
    top_e = np.empty((TOKENS, TOP_K), np.int32)
    for t in range(TOKENS):
        held = HELD - 1 if case == "last_empty" else HELD
        n = {"none": 0, "two_each": 2}.get(case, min(t % (TOP_K + 1), held))
        chosen = [*rng.permutation(held)[:n], *(HELD + rng.permutation(EXPERTS - HELD)[: TOP_K - n])]
        top_e[t] = rng.permutation(chosen)
    return top_e


def _sorted(top_e):
    """What ``held_expert_layer`` hands its branches: the pairs ordered by
    held expert, which pairs are held, how many, each pair's place, and the
    held experts' group sizes."""
    held = top_e < HELD
    key = np.where(held, top_e, HELD).reshape(-1)
    order = np.argsort(key, kind="stable").astype(np.int32)
    pos = np.empty(order.shape, np.int32)
    pos[order] = np.arange(order.size)
    sizes = np.bincount(key, minlength=HELD + 1)[:HELD].astype(np.int32)
    return (
        jnp.asarray(order), jnp.asarray(held), jnp.int32(held.sum()), jnp.asarray(pos.reshape(held.shape)),
        jnp.asarray(sizes),
    )


CASES = {"mixed": None, "none": None, "two_each": None, "last_empty": None}
# Row arrays: the kept pairs not a whole tile of them (``mixed``: 512 kept
# of 640 rows, tiles of 128: the last live tile partly kept, one tile past
# every kept row), none kept, and every row kept.
ROWS = {"mixed": 640, "none": 384, "two_each": 512, "last_empty": 640}


def _xla_take(x32, order, kept, rows, dtype):
    """``_xla_form``'s gather: the first ``rows`` places' tokens, cast, and
    the rows past ``kept`` taking no cotangent (``_kept_rows``)."""
    return moe_layers._kept_rows(x32[order[:rows] // TOP_K].astype(dtype), kept)


def _xla_add(rows_, weight, order, kept):
    """``_xla_form``'s combine: the rows past ``kept`` selected away, each
    kept row times its pair's weight added to its token in float32."""
    n = rows_.shape[0]
    chosen = order[:n]
    is_kept = jnp.arange(n) < kept
    down = jnp.where(is_kept[:, None], rows_, jnp.zeros((), rows_.dtype))
    w = weight.reshape(-1)[chosen]
    part = jnp.zeros((TOKENS, rows_.shape[1]), jnp.float32).at[chosen // TOP_K].add(down.astype(jnp.float32) * w[:, None])
    return part.astype(rows_.dtype)


def _inputs(rows, dtype, seed=1):
    rng = np.random.default_rng(seed)
    x32 = jnp.asarray(rng.normal(size=(TOKENS, HIDDEN)), jnp.float32)
    weight = jnp.asarray(rng.random((TOKENS, TOP_K)), jnp.float32)
    r = jnp.asarray(rng.normal(size=(rows, HIDDEN)), dtype)
    g_rows = jnp.asarray(rng.normal(size=(rows, HIDDEN)), dtype)
    g_tokens = jnp.asarray(rng.normal(size=(TOKENS, HIDDEN)), dtype)
    return x32, weight, r, g_rows, g_tokens


def _f32(a):
    return np.asarray(jnp.asarray(a, jnp.float32))


def _close_to_rounding(ours, theirs):
    """Equal to float32 rounding of a sum (its terms' rounding, against the
    array's scale, where they cancel); in bf16 the cast may then take the
    neighbouring value (one step, at most 2^-7 of a value), in at most one
    place in a hundred."""
    if ours.dtype == jnp.bfloat16:
        ours, theirs = _f32(ours), _f32(theirs)
        np.testing.assert_allclose(ours, theirs, rtol=2.0**-7, atol=1e-6 * np.abs(theirs).max())
        assert np.mean(ours != theirs) < 1e-2
    else:
        np.testing.assert_allclose(_f32(ours), _f32(theirs), rtol=1e-6, atol=1e-6)


class TestTheKernels:
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
    @pytest.mark.parametrize("case", list(CASES))
    def test_take_rows_and_its_vjp_against_the_xla_form(self, small_tiles, case, dtype):
        """The kept rows equal the XLA gather's bit for bit; the cotangent
        of ``x32`` sums each token's 0 to ``top_k`` kept rows' cotangents in
        float32, and the rows past ``kept`` (NaN here) reach no token."""
        order, held, kept, pos, sizes = _sorted(_routing(case))
        rows = ROWS[case]
        x32, _, _, g_rows, _ = _inputs(rows, dtype)
        g_rows = g_rows.at[int(kept):].set(jnp.nan)
        plan = K.make_plan(order, sizes, held)
        kernel = lambda x: K.take_rows(x, order, kept, held, plan, rows=rows, dtype=dtype, interpret=True)
        ours, pull = jax.vjp(kernel, x32)
        theirs, pull_xla = jax.vjp(lambda x: _xla_take(x, order, kept, rows, dtype), x32)
        k = int(kept)
        assert ours.shape == (rows, HIDDEN) and ours.dtype == dtype
        np.testing.assert_array_equal(_f32(ours[:k]), _f32(theirs[:k]))
        (d_ours,), (d_theirs,) = pull(g_rows), pull_xla(g_rows)
        assert d_ours.dtype == jnp.float32 and np.all(np.isfinite(_f32(d_ours)))
        np.testing.assert_allclose(_f32(d_ours), _f32(d_theirs), rtol=1e-6, atol=1e-6)
        if case == "none":
            assert float(jnp.max(jnp.abs(d_ours))) == 0.0

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
    @pytest.mark.parametrize("case", list(CASES))
    def test_add_pairs_and_its_vjp_against_the_xla_form(self, small_tiles, case, dtype):
        """Every row past ``kept`` is NaN: the sum reads none of them. The
        sum equals the float32 scatter-add's to float32 rounding (the slots'
        order is fixed; a sum whose last float32 bit differs may round to
        the neighbouring bf16 value, one in 65,536 here); the
        cotangent of the kept rows is ``weight g[token]`` to the bit, that of
        the weights ``<row, g>`` within float32 rounding, zero where a slot
        is not held."""
        order, held, kept, pos, sizes = _sorted(_routing(case))
        rows = ROWS[case]
        _, weight, r, _, g_tokens = _inputs(rows, dtype)
        k = int(kept)
        r = r.at[k:].set(jnp.nan)
        plan = K.make_plan(order, sizes, held)
        np.testing.assert_array_equal(np.asarray(plan.pos), np.asarray(pos))
        add = lambda r, w: K.add_pairs(r, w, order, kept, held, plan, interpret=True)
        ours, pull = jax.vjp(add, r, weight)
        theirs, pull_xla = jax.vjp(lambda r, w: _xla_add(r, w, order, kept), r, weight)
        assert ours.shape == (TOKENS, HIDDEN) and ours.dtype == dtype
        assert np.all(np.isfinite(_f32(ours)))
        _close_to_rounding(ours, theirs)
        (d_r, d_w), (d_r_xla, d_w_xla) = pull(g_tokens), pull_xla(g_tokens)
        np.testing.assert_array_equal(_f32(d_r[:k]), _f32(d_r_xla[:k]))
        assert np.all(np.isfinite(_f32(d_w)))
        np.testing.assert_allclose(_f32(d_w), _f32(d_w_xla), rtol=1e-5, atol=1e-5)
        assert float(jnp.max(jnp.abs(jnp.where(held, 0.0, d_w)))) == 0.0

    def test_the_kernels_take_bf16_or_float32_rows_of_whole_lane_tiles_only(self):
        assert K.fits(8192, 4, 8, 2048, 24576, jnp.bfloat16) and K.fits(4096, 10, 16, 2048, 40960, jnp.bfloat16)
        assert K.fits(8192, 8, 16, 2048, 65536, jnp.bfloat16) and K.fits(8192, 8, 8, 2048, 6144, jnp.bfloat16)
        assert K.fits(1024, 2, 2, 128, 512, jnp.float32)
        assert not K.fits(1024, 2, 2, 128, 512, jnp.bfloat16)  # a bf16 row's half is not a lane tile
        assert not K.fits(64, 2, 2, 64, 128, jnp.float32)  # the families' small test widths
        assert not K.fits(1024, 2, 2, 256, 520, jnp.bfloat16)  # rows not whole tiles
        assert not K.fits(1024, 2, 2, 256, 512, jnp.float16)


# ---- the layer on the kernels against its XLA form, each family's router ----

# Each family's small test configuration's experts (8, top-2, 2 held from
# expert 2) at a width the kernels take (the configurations' own 64 lanes are
# not whole lane tiles): 1,024 tokens make 2,048 pairs and a budget of 1,536.
LAYER_T, LAYER_H, LAYER_W, LAYER_E, LAYER_K, LAYER_HELD, LAYER_FIRST = 1024, 256, 128, 8, 2, 2, 2


def _family_route(family: str, bias):
    """The router as each family hands it to ``held_expert_layer``."""
    if family in ("block_diffusion", "hybrid"):
        return functools.partial(moe_layers.softmax_route, top_k=LAYER_K, norm_topk=True)
    scale, eps = (2.5, 1e-20) if family == "causal" else (1.0, 1e-6)
    return functools.partial(mla_moe.sigmoid_route, bias=bias, top_k=LAYER_K, norm_topk=True, scale=scale, eps=eps)


def _layer_inputs(overflow: bool, seed=3):
    """Tokens and weights; where ``overflow``, one lane of every token and
    the router's row for it draw the routing to the held experts, so that
    the kept pairs pass the budget."""
    rng = np.random.default_rng(seed)
    draw = lambda *shape: rng.normal(size=shape) * 0.1
    n = rng.normal(size=(LAYER_T, LAYER_H))
    router, bias = draw(LAYER_H, LAYER_E), draw(LAYER_E)
    if overflow:
        n[:, 0] = 3.0
        router[0, LAYER_FIRST : LAYER_FIRST + LAYER_HELD] = 4.0
        bias[LAYER_FIRST : LAYER_FIRST + LAYER_HELD] = 5.0
    as32 = lambda a: jnp.asarray(a, jnp.float32)
    p = {
        "router": as32(router), "w_gate": as32(draw(LAYER_HELD, LAYER_H, LAYER_W)),
        "w_up": as32(draw(LAYER_HELD, LAYER_H, LAYER_W)), "w_down": as32(draw(LAYER_HELD, LAYER_W, LAYER_H)),
    }
    return as32(n), p, as32(bias)


@pytest.mark.parametrize("overflow", [False, True], ids=["budget", "every_pair"])
@pytest.mark.parametrize("family", ["block_diffusion", "causal", "hybrid", "convolution"])
def test_the_layer_on_the_row_kernels_equals_its_xla_form(family, overflow, monkeypatch):
    """Values and every gradient leaf, bf16 products, whichever branch the
    routing takes, against the same layer with its rows moved in XLA's form
    (``pair_rows.fits`` refusing) and the same grouped products (megablox in
    the interpreter); ``moved_rows`` reads ``kept`` on the kernels and the
    row arrays' length in the XLA form; the other counters read alike. On
    both branches both forms sum a token's rows' cotangents in float32, and
    the two agree to float32 rounding (the router's gradient sums ``<row,
    g>`` in another order)."""
    n, p, bias = _layer_inputs(overflow)
    pairs = LAYER_T * LAYER_K
    budget = moe_layers.row_budget(pairs, LAYER_HELD, LAYER_E)
    assert budget == 1536 < pairs

    def run():
        def f(n, p):
            part, counters = moe_layers.held_expert_layer(
                n, p["router"], p["w_gate"], p["w_up"], p["w_down"], first_expert=LAYER_FIRST,
                route=_family_route(family, bias), compute_dtype=jnp.bfloat16, kernels="interpret",
            )
            cos = jnp.cos(jnp.arange(part.size, dtype=jnp.float32).reshape(part.shape))
            return jnp.sum(part.astype(jnp.float32) * cos), (part, counters)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(n, p)

    (_, (part, counters)), grads = run()
    monkeypatch.setattr(K, "fits", lambda *shape: False)
    (_, (ref_part, ref_counters)), ref_grads = run()
    kept, moved = counters["held_pairs"], counters.pop("moved_rows")
    assert float(counters["budget_overflows"]) == float(overflow) and (float(kept) > budget) == overflow
    assert float(moved) == float(kept) and float(ref_counters.pop("moved_rows")) == (pairs if overflow else budget)
    assert counters.keys() == ref_counters.keys()
    for name in counters:
        np.testing.assert_array_equal(np.asarray(counters[name]), np.asarray(ref_counters[name]))
    _close_to_rounding(part, ref_part)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(ref_grads)):
        assert np.all(np.isfinite(_f32(g))), path
        scale = float(jnp.max(jnp.abs(r)))
        assert float(jnp.max(jnp.abs(g - r))) <= 1e-5 * scale, (path, float(jnp.max(jnp.abs(g - r))) / scale)


# ---- compiled for the chip at the cells' widths ----


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize(
    "tokens,top_k,held_n,rows",
    [(8192, 4, 8, 24576), (8192, 8, 16, 24576), (8192, 8, 8, 6144), (4096, 10, 16, 4096), (8192, 8, 16, 65536)],
    ids=["convolution", "block_diffusion", "causal", "hybrid", "every_pair"],
)
def test_the_kernels_compile_for_the_chip_at_the_cells_widths(one_chip, tokens, top_k, held_n, rows):
    """Gather, per-token sum and both VJPs at hidden 2,048 in bf16, each
    cell's tokens a call, slots and row budget (and the block-diffusion
    cell's every-pair branch): what the chip's compiler refuses (a slice not
    aligned to the tiling, fast memory, SMEM blocks) shows here at no chip
    time."""
    from jax.experimental.compilation_cache import compilation_cache

    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x32, weight, r, order, kept, held, sizes):
        plan = K.make_plan(order, sizes, held)
        rows_ = K.take_rows(x32, order, kept, held, plan, rows=rows, dtype=jnp.bfloat16)
        return jnp.sum(K.add_pairs(rows_ + r, weight, order, kept, held, plan).astype(jnp.float32))

    args = (
        S((tokens, 2048), jnp.float32), S((tokens, top_k), jnp.float32), S((rows, 2048), jnp.bfloat16),
        S((tokens * top_k,), jnp.int32), S((), jnp.int32), S((tokens, top_k), jnp.bool_), S((held_n,), jnp.int32),
    )
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep it out.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line and " = " in line]
    # Forward: two packs, the gather, the sum; backward: two packs, the
    # scaled gather and the sum.
    assert len(calls) == 8, len(calls)
