"""The block-diffusion mixture-of-experts model (``models/sdar_moe.py``)
against the benchmark's plain reference (``benchmark/reference/sdar_moe.py``)
at a small size: hidden 64, 2 layers, 8 experts top-2 of which 2 are held,
vocabulary 64, L 32, blocks of 4."""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from fedcrack_tpu.configs import SdarMoeConfig
from fedcrack_tpu.data.textdiff import block_diffusion_weights
from fedcrack_tpu.kernels import pair_rows
from fedcrack_tpu.models import get_model, moe_layers
from fedcrack_tpu.models import sdar_moe as M
from fedcrack_tpu.tasks import TextDiffusionTask, task_for

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_sdar", os.path.join(ROOT, "benchmark", "reference", "sdar_moe.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()
# This family's router, as the model hands it to the shared expert layer.
SOFTMAX_TOP2 = functools.partial(moe_layers.softmax_route, top_k=2, norm_topk=True)
SMALL = dict(
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, first_expert=2, experts_held=2,
    vocab_held=64, block_length=4, seq_len=32,
)


def small_config(**over) -> SdarMoeConfig:
    return SdarMoeConfig(**{**SMALL, "compute_dtype": "float32", **over})


def reference_cfg(config: SdarMoeConfig) -> dict:
    return dict(
        hidden_size=config.hidden_size, num_hidden_layers=config.num_hidden_layers,
        num_attention_heads=config.num_attention_heads, num_key_value_heads=config.num_key_value_heads,
        head_dim=config.head_dim, moe_intermediate_size=config.moe_intermediate_size,
        router_outputs=config.num_experts, num_experts_per_tok=config.num_experts_per_tok,
        norm_topk_prob=config.norm_topk_prob, rms_norm_eps=config.rms_norm_eps, rope_theta=config.rope_theta,
        first_expert=config.first_expert, experts_held=config.experts_held, vocab_held=config.vocab_held,
        block_length=config.block_length, seq_len=config.seq_len,
    )


def batch(seed=0, n=2, config=None):
    config = config or small_config()
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, config.vocab_held - 1, (n, config.seq_len)).astype(np.int32)
    return jnp.asarray(ids), jnp.asarray(block_diffusion_weights(ids.shape, config.block_length, rng))


def _close(a, b, tol):
    scale = float(jnp.max(jnp.abs(b))) + 1e-12
    assert float(jnp.max(jnp.abs(a - b))) <= tol * scale


class TestAgainstTheReference:
    def test_params_are_the_references_tree(self):
        config = small_config()
        ours = jax.eval_shape(lambda: M.SdarMoe(config).init(jax.random.key(0)))
        theirs = jax.eval_shape(lambda: REF.init_variables(jnp.zeros((2,), jnp.uint32), reference_cfg(config)))["params"]
        assert jax.tree_util.tree_structure(ours) == jax.tree_util.tree_structure(theirs)
        assert jax.tree_util.tree_leaves(ours) == jax.tree_util.tree_leaves(theirs)

    def test_logits_loss_and_every_gradient_leaf(self):
        config = small_config()
        cfg = reference_cfg(config)
        params = REF.make_variables(5, cfg)["params"]
        ids, weight = batch()
        task = TextDiffusionTask(config)
        with jax.default_matmul_precision("highest"):
            logits = M.SdarMoe(config).logits(params, ids, weight > 0)
            theirs = jnp.stack([REF.sequence_logits(params, ids[b], weight[b] > 0, cfg)[0] for b in range(2)])
            _close(logits, theirs, 1e-5)

            def loss(p):
                inputs, targets = task.unpack((ids, weight))
                outputs, _ = task.apply(p, {}, inputs)
                m = task.loss_and_metrics(outputs, targets)
                return m["loss"], m

            (ours, stats), grads = jax.value_and_grad(loss, has_aux=True)(params)
            (ref_loss, ref_stats), ref_grads = jax.value_and_grad(
                lambda p: REF.batch_loss(p, ids, weight, cfg), has_aux=True
            )(params)
        assert abs(float(ours) - float(ref_loss)) <= 1e-5 * float(ref_loss)
        for name in ("masked_tokens", "masked_hits", "expert_rows"):
            np.testing.assert_array_equal(np.asarray(stats[name]), np.asarray(ref_stats[name]))
        assert float(stats["held_pairs"]) == float(np.sum(ref_stats["expert_rows"]))
        flat, _ = jax.tree_util.tree_flatten_with_path(grads)
        ref_flat = jax.tree_util.tree_leaves(ref_grads)
        assert len(flat) == 3 + 2 * 12
        for (path, g), r in zip(flat, ref_flat):
            assert float(jnp.max(jnp.abs(r))) > 0, path
            _close(g, r, 2e-5)

    def test_bf16_compute_stays_near_the_float32_reference(self):
        config = small_config(compute_dtype="bfloat16")
        cfg = reference_cfg(config)
        params = REF.make_variables(6, cfg)["params"]
        ids, weight = batch(1)
        outputs = M.SdarMoe(config).apply(params, ids, weight > 0)
        ours = jnp.sum(weight * outputs["nll"]) / weight.size
        with jax.default_matmul_precision("highest"):
            theirs, _ = REF.batch_loss(params, ids, weight, cfg)
        assert abs(float(ours) - float(theirs)) <= 0.02 * float(theirs)

    def test_registry_and_family(self):
        config = small_config()
        assert isinstance(get_model("sdar_moe", config), M.SdarMoe)
        assert isinstance(task_for(config), TextDiffusionTask)
        with pytest.raises(ValueError, match="not among the router's"):
            small_config(first_expert=7)
        with pytest.raises(ValueError, match="whole blocks"):
            small_config(seq_len=30)


class TestTheShare:
    def test_the_shares_add_up_to_the_uncut_layer(self):
        """The layer's result from each of the ``num_experts/experts_held``
        shares, summed, equals the uncut reference's layer."""
        config = small_config()
        whole = reference_cfg(small_config(first_expert=0, experts_held=8))
        p = REF.make_variables(9, dict(whole, num_hidden_layers=1))["params"]["layer0"]
        rng = np.random.default_rng(3)
        n = jnp.asarray(rng.normal(size=(64, config.hidden_size)), jnp.float32)
        with jax.default_matmul_precision("highest"):
            uncut, uncut_rows = REF.expert_layer(n, p, whole)
            total = jnp.zeros_like(uncut)
            rows = []
            for first in range(0, 8, 2):
                part, counters = moe_layers.held_expert_layer(
                    n, p["router"], p["w_gate"][first : first + 2], p["w_up"][first : first + 2],
                    p["w_down"][first : first + 2], first_expert=first, route=SOFTMAX_TOP2,
                    compute_dtype=jnp.float32,
                )
                expert_rows = counters["expert_rows"]
                assert float(counters["held_pairs"]) == float(jnp.sum(expert_rows))
                total = total + part
                rows.append(expert_rows)
        _close(total, uncut, 1e-5)
        np.testing.assert_array_equal(np.concatenate(rows), np.asarray(uncut_rows))
        assert float(sum(r.sum() for r in rows)) == 64 * 2

    @pytest.mark.parametrize("routing", ["all_to_one_held", "none_held"])
    def test_grouped_product_is_exact_under_any_imbalance(self, routing):
        """Every token to one held expert (its group holds every row, the
        other none) and no token to any: no pair is dropped, nothing is
        invented; values and gradients equal the dense reference's."""
        config = small_config()
        cfg = reference_cfg(config)
        p = REF.make_variables(4, dict(cfg, num_hidden_layers=1))["params"]["layer0"]
        rng = np.random.default_rng(8)
        n = jnp.asarray(np.abs(rng.normal(size=(48, config.hidden_size))) + 0.1, jnp.float32)
        router = np.zeros((config.hidden_size, 8), np.float32)
        # Expert 3 (held, local 1) or expert 6 (absent) wins for every token;
        # the second choice is expert 0 (absent) or expert 7 (absent).
        router[:, 3 if routing == "all_to_one_held" else 6] = 1.0
        router[:, 0 if routing == "all_to_one_held" else 7] = 0.5
        p = dict(p, router=jnp.asarray(router))

        def ours(n, p):
            part, counters = moe_layers.held_expert_layer(
                n, p["router"], p["w_gate"], p["w_up"], p["w_down"], first_expert=2, route=SOFTMAX_TOP2,
                compute_dtype=jnp.float32,
            )
            return (
                jnp.sum(part * jnp.cos(jnp.arange(part.size).reshape(part.shape))),
                (part, counters["expert_rows"], counters["held_pairs"]),
            )

        def theirs(n, p):
            part, rows = REF.expert_layer(n, p, cfg)
            return jnp.sum(part * jnp.cos(jnp.arange(part.size).reshape(part.shape))), (part, rows)

        with jax.default_matmul_precision("highest"):
            (_, (part, rows, pairs)), grads = jax.value_and_grad(ours, argnums=(0, 1), has_aux=True)(n, p)
            (_, (ref_part, ref_rows)), ref_grads = jax.value_and_grad(theirs, argnums=(0, 1), has_aux=True)(n, p)
        if routing == "all_to_one_held":
            np.testing.assert_array_equal(np.asarray(rows), [0.0, 48.0])
            assert float(pairs) == 48.0
            _close(part, ref_part, 1e-5)
        else:
            np.testing.assert_array_equal(np.asarray(rows), [0.0, 0.0])
            assert float(pairs) == 0.0 and float(jnp.max(jnp.abs(part))) == 0.0
        np.testing.assert_array_equal(np.asarray(rows), np.asarray(ref_rows))
        for g, r in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(ref_grads)):
            assert np.all(np.isfinite(np.asarray(g)))
            assert float(jnp.max(jnp.abs(g - r))) <= 2e-5 * (float(jnp.max(jnp.abs(r))) + 1e-6)

    def test_kernel_path_of_the_expert_layer_in_the_interpreter(self):
        """The megablox kernel leaves the rows past the last group undefined
        (NaN in the interpreter): the layer masks them on the way in and out,
        so values and gradients equal the ``ragged_dot`` path's."""
        rng = np.random.default_rng(2)
        rows = jnp.asarray(rng.normal(size=(512, 128)), jnp.float32)
        weights = jnp.asarray(rng.normal(size=(3, 128, 128)), jnp.float32)
        sizes = jnp.asarray([200, 0, 120], jnp.int32)
        plain = moe_layers.grouped_product(rows, weights, sizes, kernels="xla")
        kernel = moe_layers.grouped_product(rows, weights, sizes, kernels="interpret")
        np.testing.assert_allclose(np.asarray(kernel[:320]), np.asarray(plain[:320]), rtol=2e-2, atol=2e-2)
        assert float(jnp.max(jnp.abs(plain[320:]))) == 0.0

        n = jnp.asarray(rng.normal(size=(256, 128)), jnp.float32)
        p = {
            "router": jnp.asarray(rng.normal(size=(128, 8)) * 0.1, jnp.float32),
            "w_gate": jnp.asarray(rng.normal(size=(2, 128, 128)) * 0.1, jnp.float32),
            "w_up": jnp.asarray(rng.normal(size=(2, 128, 128)) * 0.1, jnp.float32),
            "w_down": jnp.asarray(rng.normal(size=(2, 128, 128)) * 0.1, jnp.float32),
        }

        def run(kernels):
            def f(n, p):
                part, counters = moe_layers.held_expert_layer(
                    n, p["router"], p["w_gate"], p["w_up"], p["w_down"], first_expert=2, route=SOFTMAX_TOP2,
                    compute_dtype=jnp.float32, kernels=kernels,
                )
                return jnp.sum(part * jnp.sin(jnp.arange(part.size).reshape(part.shape))), (part, counters["held_pairs"])
            return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(n, p)

        (_, (part, pairs)), grads = run("interpret")
        (_, (ref_part, ref_pairs)), ref_grads = run("xla")
        assert 0 < float(pairs) < 512 and float(pairs) == float(ref_pairs)
        np.testing.assert_allclose(np.asarray(part), np.asarray(ref_part), rtol=2e-2, atol=2e-3)
        for g, r in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(ref_grads)):
            assert np.all(np.isfinite(np.asarray(g)))
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=5e-2, atol=5e-3)


# ---- the row budget: a fast path of ``budget`` rows, the overflow under ``lax.cond`` ----

# 1,024 tokens x 2 slots = 2,048 pairs, experts 4 and 5 of 32 held: the budget
# is 3 x 2,048 x 2 / 32 = 384 rows, a whole tile of 512.
BUDGET_T, BUDGET_K, BUDGET_E, BUDGET_FIRST, BUDGET_HELD = 1024, 2, 32, 4, 2
BUDGET_PAIRS = BUDGET_T * BUDGET_K
BUDGET = 512
ROUTINGS = {
    "uniform": None, "kept_is_budget": BUDGET, "kept_is_budget_plus_1": BUDGET + 1, "all_to_one_held": "one",
    "none_held": 0, "last_held_empty": 300,
}


def _budget_route(routing):
    """A router form whose choices are the test's (so that the kept pairs
    are counted to the pair) and whose weights are the softmax's over the
    chosen, so that tokens and router take a gradient."""
    rng = np.random.default_rng(11)
    if routing == "uniform":
        top_e = np.stack([rng.permutation(BUDGET_E)[:BUDGET_K] for _ in range(BUDGET_T)])
    elif routing == "all_to_one_held":
        top_e = np.tile([BUDGET_FIRST + 1, 0], (BUDGET_T, 1))
    elif routing == "last_held_empty":
        # Slot 0 of ``kept`` tokens to the first held expert, every other
        # pair to absent ones: the last held expert's group is empty.
        top_e = np.tile([0, 1], (BUDGET_T, 1))
        top_e[rng.permutation(BUDGET_T)[: ROUTINGS[routing]], 0] = BUDGET_FIRST
    else:
        # Absent experts 0 and 1 everywhere, then ``kept`` pairs, scattered,
        # to a held expert: slot 0's to the first, slot 1's to the second.
        top_e = np.tile([0, 1], (BUDGET_T, 1))
        chosen = rng.permutation(BUDGET_PAIRS)[: ROUTINGS[routing]]
        top_e.reshape(-1)[chosen] = BUDGET_FIRST + chosen % BUDGET_K
    top_e = jnp.asarray(top_e, jnp.int32)

    def route(n32, router):
        gates = jax.nn.softmax(jnp.dot(n32, router, precision=jax.lax.Precision.HIGHEST), axis=-1)
        top_w = jnp.take_along_axis(gates, top_e, axis=-1)
        return top_e, top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    return route


def _budget_params(hidden, width, seed=13):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape) * 0.1, jnp.float32)
    n = jnp.asarray(rng.normal(size=(BUDGET_T, hidden)), jnp.float32)
    return n, {
        "router": draw(hidden, BUDGET_E), "w_gate": draw(BUDGET_HELD, hidden, width),
        "w_up": draw(BUDGET_HELD, hidden, width), "w_down": draw(BUDGET_HELD, width, hidden),
    }


def _budget_layer(route, kernels):
    def layer(n, p):
        return moe_layers.held_expert_layer(
            n, p["router"], p["w_gate"], p["w_up"], p["w_down"], first_expert=BUDGET_FIRST, route=route,
            compute_dtype=jnp.float32, kernels=kernels,
        )
    return layer


def _dense_layer(route):
    """Every held expert over every token, weighted by what the router gave
    it there: the layer without a sort, a budget or a branch."""
    def layer(n, p):
        top_e, top_w = route(n, p["router"])
        part, rows = jnp.zeros_like(n), []
        for e in range(BUDGET_HELD):
            chose = top_e == BUDGET_FIRST + e
            weight = jnp.sum(jnp.where(chose, top_w, 0.0), axis=-1)
            part = part + weight[:, None] * moe_layers.swiglu(n, p["w_gate"][e], p["w_up"][e], p["w_down"][e], jnp.float32)
            rows.append(jnp.sum(chose))
        return part, jnp.stack(rows).astype(jnp.float32)
    return layer


def _inner_jaxprs(eqn):
    """The jaxprs an equation holds (a call's body, a branch, a custom rule's)."""
    for value in eqn.params.values():
        for inner in value if isinstance(value, (tuple, list)) else (value,):
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                yield inner


def _equations(jaxpr, name):
    """Every equation of that primitive, outermost first (a kernel's own body
    is not entered)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield eqn
        if eqn.primitive.name != "pallas_call":
            for inner in _inner_jaxprs(eqn):
                yield from _equations(inner, name)


def _megablox_tiles(sizes, m):
    """The tiles megablox's forward kernel runs over ``sizes`` in ``m`` rows:
    its own ``make_group_metadata``."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

    sizes = jnp.asarray(sizes, jnp.int32)
    return int(make_group_metadata(
        group_sizes=sizes, m=m, tm=moe_layers.GMM_TILE_M, start_group=jnp.int32(0),
        num_nonzero_groups=sizes.shape[0], visit_empty_groups=False,
    )[1])


class TestTheRowBudget:
    @pytest.mark.parametrize("routing,kernels", [(r, k) for k in ("xla", "interpret") for r in ROUTINGS])
    def test_both_branches_equal_the_dense_layer(self, routing, kernels):
        """Values, every gradient leaf and the five counters, whichever
        branch the routing takes; the grouped products run over the kept
        pairs' groups alone, so in the kernel's interpreter every row past
        them (other tokens) comes back undefined, NaN, forward and backward,
        an empty last group's included: nothing of them reaches a value or a
        gradient. In the interpreter both branches also move their rows on
        the row kernels (``kernels/pair_rows.py``), which move the kept
        pairs' rows alone (``moved_rows``); the XLA form moves its row
        arrays' whole length."""
        route = _budget_route(routing)
        n, p = _budget_params(128, 128)
        assert moe_layers.row_budget(BUDGET_PAIRS, BUDGET_HELD, BUDGET_E) == BUDGET

        def scored(layer):
            def f(n, p):
                part, counters = layer(n, p)
                return jnp.sum(part * jnp.cos(jnp.arange(part.size).reshape(part.shape))), (part, counters)
            return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))

        with jax.default_matmul_precision("highest"):
            (_, (part, counters)), grads = scored(_budget_layer(route, kernels))(n, p)
            (_, (ref_part, ref_rows)), ref_grads = scored(_dense_layer(route))(n, p)
        rows, pairs, overflows, tiles, moved = (
            counters[name] for name in ("expert_rows", "held_pairs", "budget_overflows", "expert_tiles", "moved_rows")
        )
        kept = {"uniform": float(jnp.sum(ref_rows)), "all_to_one_held": float(BUDGET_T)}.get(routing, ROUTINGS[routing])
        assert float(pairs) == kept == float(jnp.sum(rows))
        assert float(overflows) == float(kept > BUDGET)
        if routing == "uniform":
            assert 0 < kept < BUDGET
        if routing == "last_held_empty":
            assert float(rows[0]) == kept and float(rows[-1]) == 0.0
        np.testing.assert_array_equal(np.asarray(rows), np.asarray(ref_rows))
        rows_run = BUDGET if kept <= BUDGET else BUDGET_PAIRS
        assert float(tiles) == _megablox_tiles(np.asarray(rows, np.int32), rows_run)
        assert float(moved) == (kept if kernels == "interpret" else rows_run)
        assert np.all(np.isfinite(np.asarray([pairs, overflows, tiles, moved, *np.asarray(rows)])))
        assert np.all(np.isfinite(np.asarray(part)))
        tol = 2e-5 if kernels == "xla" else 5e-2
        _close(part, ref_part, tol)
        for g, r in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(ref_grads)):
            assert np.all(np.isfinite(np.asarray(g)))
            assert float(jnp.max(jnp.abs(g - r))) <= tol * (float(jnp.max(jnp.abs(r))) + 1e-6)

    @pytest.mark.parametrize("routing", list(ROUTINGS))
    def test_the_grouped_products_run_over_the_kept_pairs_alone(self, routing, monkeypatch):
        """Whichever branch the routing takes, each grouped product is handed
        the held experts' own group sizes: no row past the kept pairs rides in
        a group, so the kernel runs the kept pairs' tiles alone."""
        handed = []
        real = moe_layers.grouped_product

        def recorded(rows, weights, group_sizes, **kw):
            handed.append((rows.shape[0], np.asarray(group_sizes)))
            return real(rows, weights, group_sizes, **kw)

        monkeypatch.setattr(moe_layers, "grouped_product", recorded)
        n, p = _budget_params(128, 128)
        with jax.disable_jit():
            _, counters = _budget_layer(_budget_route(routing), "xla")(n, p)
        rows, pairs, tiles, moved = (
            counters[name] for name in ("expert_rows", "held_pairs", "expert_tiles", "moved_rows")
        )
        assert len(handed) == 3
        for m, sizes in handed:
            np.testing.assert_array_equal(sizes, np.asarray(rows, np.int32))
            assert m == (BUDGET if float(pairs) <= BUDGET else BUDGET_PAIRS)
        assert float(tiles) == _megablox_tiles(handed[0][1], handed[0][0])
        assert float(moved) == handed[0][0]

    @pytest.mark.parametrize("rematerialised", [False, True])
    def test_the_fast_branch_holds_no_array_sized_for_every_pair(self, rematerialised):
        """Forward and backward, outside the overflow's branches: no array of
        ``pairs`` rows by ``hidden`` or ``width`` columns and none of
        ``[T, top_k, hidden]``; the overflow's branches hold the first two
        still."""
        hidden, width = 64, 32
        n, p = _budget_params(hidden, width)
        layer = _budget_layer(_budget_route("uniform"), "xla")
        if rematerialised:  # as every caller holds it
            layer = jax.checkpoint(layer)
        jaxpr = jax.make_jaxpr(jax.grad(lambda n, p: jnp.sum(layer(n, p)[0]), argnums=(0, 1)))(n, p)
        pair_sized = {(BUDGET_PAIRS, hidden), (BUDGET_PAIRS, width)}
        seen = {"fast": set(), "overflow": set(), "conds": 0}

        def walk(jaxpr, side):
            for eqn in jaxpr.eqns:
                seen[side].update(tuple(v.aval.shape) for v in (*eqn.invars, *eqn.outvars) if hasattr(v.aval, "shape"))
                if eqn.primitive.name == "cond":
                    seen["conds"] += 1
                    budgeted, every_pair = eqn.params["branches"]  # (false, true) of ``kept > budget``
                    walk(budgeted.jaxpr, side)
                    walk(every_pair.jaxpr, "overflow")
                    continue
                for inner in _inner_jaxprs(eqn):
                    walk(inner, side)

        walk(jaxpr.jaxpr, "fast")
        assert seen["conds"] >= 2  # the layer's and its backward pass's
        assert (BUDGET, hidden) in seen["fast"] and (BUDGET, width) in seen["fast"]
        assert not seen["fast"] & (pair_sized | {(BUDGET_T, BUDGET_K, hidden)})
        assert pair_sized <= seen["overflow"]

    @pytest.mark.parametrize(
        "tokens,top_k,router_width,held,hidden,width,budget",
        [
            (4096, 10, 512, 16, 2048, 512, 4096), (8192, 8, 256, 8, 2048, 768, 6144),
            (8192, 8, 128, 16, 2048, 768, 24576), (8192, 4, 32, 8, 2048, 1792, 24576),
        ],
        ids=["hybrid", "causal", "block_diffusion", "convolution"],
    )
    def test_the_budget_is_whole_tiles_and_takes_the_kernel_at_the_published_shapes(
        self, tokens, top_k, router_width, held, hidden, width, budget
    ):
        assert moe_layers.row_budget(tokens * top_k, held, router_width) == budget
        assert budget % moe_layers.GMM_TILE_M == 0 and budget < tokens * top_k
        S = jax.ShapeDtypeStruct
        layer = lambda n, router, w_gate, w_up, w_down: moe_layers.held_expert_layer(
            n, router, w_gate, w_up, w_down, first_expert=0, compute_dtype=jnp.bfloat16, kernels="pallas",
            route=functools.partial(moe_layers.softmax_route, top_k=top_k, norm_topk=True),
        )
        jaxpr = jax.make_jaxpr(layer)(
            S((tokens, hidden), jnp.float32), S((hidden, router_width), jnp.float32), S((held, hidden, width), jnp.float32),
            S((held, hidden, width), jnp.float32), S((held, width, hidden), jnp.float32),
        )
        (cond,) = _equations(jaxpr.jaxpr, "cond")
        for branch, rows in zip(cond.params["branches"], (budget, tokens * top_k)):
            kernels = list(_equations(branch.jaxpr, "pallas_call"))
            products = [k for k in kernels if not str(k.params["name"]).startswith("pair_rows")]
            assert len(products) == 3 and not list(_equations(branch.jaxpr, "ragged_dot_general"))
            assert [k.invars[-2].aval.shape[0] for k in products] == [rows] * 3
            # Both branches move their rows on the row kernels: the tokens
            # packed, the kept pairs' gathered into the products' rows, the
            # last product's rows packed and summed back to their tokens.
            moving = sorted((k.params["name"], k.outvars[0].aval.shape) for k in kernels if k not in products)
            words = (hidden // 2 // 128, 128)  # a bf16 row as 32-bit words, two columns a word
            assert moving == sorted([
                ("pair_rows_pack", (rows + pair_rows.CHUNK, *words)), ("pair_rows_pack", (tokens, *words)),
                ("pair_rows_sum", (tokens, hidden)), ("pair_rows_take", (rows, hidden)),
            ])


    @pytest.mark.parametrize("load", ["none", "one_tile", "uniform", "budget"])
    @pytest.mark.parametrize(
        "held,budget,uniform", [(16, 4096, 1280), (8, 6144, 2048), (16, 24576, 8192), (8, 24576, 8192)],
        ids=["hybrid", "causal", "block_diffusion", "convolution"],
    )
    def test_expert_tiles_is_the_kernels_own_count(self, held, budget, uniform, load):
        """``grouped_tiles`` against megablox's ``make_group_metadata`` over
        random kept pairs at each cell's published budget and uniform load a
        call, some with the last held expert's group empty; at the
        convolution cell's uniform load the kept pairs' tiles are under half
        of what the budget padded into the last group ran."""
        kept = {"none": 0, "one_tile": moe_layers.GMM_TILE_M, "uniform": uniform, "budget": budget}[load]
        rng = np.random.default_rng(held * budget + kept)
        for draw in range(6):
            share = np.ones(held) if draw % 2 else np.r_[np.ones(held - 1), 0.0]
            sizes = rng.multinomial(kept, share / share.sum()).astype(np.int32)
            tiles = int(moe_layers.grouped_tiles(jnp.asarray(sizes)))
            assert tiles == _megablox_tiles(sizes, budget), sizes
            if load == "uniform" and held == 8 and budget == 24576:
                padded = sizes.copy()
                padded[-1] += budget - kept
                assert tiles <= 26
                if draw % 2:  # every group's end off a tile's: 48 tiles of budget and 7 shared
                    assert int(moe_layers.grouped_tiles(jnp.asarray(padded))) == _megablox_tiles(padded, budget) == 55


class TestTheMask:
    def test_the_mask_allows_exactly_the_pairs_the_equations_name(self):
        seq, block = 32, 4
        mask = M.block_diffusion_mask(seq, block)
        assert mask.shape == (2 * seq, 2 * seq) and mask.dtype == bool
        b = lambda i: i // block
        for i in range(2 * seq):
            for j in range(2 * seq):
                noisy_q, noisy_k = i < seq, j < seq
                qi, kj = i % seq, j % seq
                if noisy_q:
                    allowed = b(kj) == b(qi) if noisy_k else b(kj) < b(qi)
                else:
                    allowed = False if noisy_k else b(kj) <= b(qi)
                assert bool(mask[i, j]) == allowed, (i, j)
        assert int(mask.sum()) == seq * (seq + block)
        np.testing.assert_array_equal(mask, REF.attention_mask(seq, block))
        assert mask.any(axis=1).all()  # every query sees a key: no empty softmax

    def test_kernel_attention_in_the_interpreter_equals_the_dense_path(self):
        rng = np.random.default_rng(5)
        q = jnp.asarray(rng.normal(size=(1, 256, 4, 128)) * 0.1, jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 256, 2, 128)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 256, 2, 128)), jnp.float32)
        dense = M.blockdiff_attention(q, k, v, block_length=4, kernels="xla")
        kernel = M.blockdiff_attention(q, k, v, block_length=4, kernels="interpret")
        np.testing.assert_allclose(np.asarray(kernel), np.asarray(dense), rtol=2e-2, atol=2e-3)


# ---- the kernels at the cell's widths, compiled for a described v5e ---------


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def test_both_kernels_compile_for_the_chip_at_the_published_widths(one_chip):
    """The expert block and the attention block of one layer, forward and
    backward, one sequence of 8,192 positions at hidden 2048, 32/4 heads of
    128, 16 held experts of width 768: what the chip's compiler refuses
    (tiling, fast memory) shows here at no chip time."""
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep it out.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        config = SdarMoeConfig(num_hidden_layers=1)
        model = M.SdarMoe(config, kernels="pallas")
        shapes = jax.eval_shape(lambda: model.init(jax.random.key(0)))["layer0"]
        spec = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
        p = jax.tree_util.tree_map(spec, shapes)
        x = jax.ShapeDtypeStruct((1, 2 * config.seq_len, config.hidden_size), jnp.bfloat16, sharding=one_chip)
        cos, sin = M.rotary_tables(config.seq_len, config.head_dim, config.rope_theta)

        def loss(p, x):
            y, *_ = model._layer(p, x, cos, sin)
            return jnp.sum(y.astype(jnp.float32))

        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, x).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    # Attention forward and its two backward kernels; three grouped products
    # forward and six backward (the rematerialised forwards may merge with
    # the first ones outside a scan).
    assert text.count("tpu_custom_call") >= 3 + 9


class TestTheStagedPair:
    """``data/textdiff.py``: the noise is data, drawn on the host from a seed."""

    def test_weights_are_zero_or_one_over_the_blocks_t(self):
        rng = np.random.default_rng(3)
        weight = block_diffusion_weights((6, 3, 64), 4, rng)
        assert weight.shape == (6, 3, 64) and weight.dtype == np.float32
        blocks = weight.reshape(-1, 4)
        masked = blocks > 0
        assert 0.4 < masked.mean() < 0.7  # E[t] = 0.55
        for row, m in zip(blocks, masked):
            if m.any():
                assert np.all(row[m] == row[m][0]) and 1.0 <= row[m][0] <= 10.0 + 1e-5
        # E[weight] = E[t x 1/t] = 1: the loss is an unbiased sum over tokens.
        big = block_diffusion_weights((64, 4096), 4, np.random.default_rng(4))
        assert abs(big.mean() - 1.0) < 0.02
        with pytest.raises(ValueError, match="whole blocks"):
            block_diffusion_weights((2, 30), 4, rng)

    def test_stage_pair_permutes_and_reuses_buffers(self):
        from fedcrack_tpu.data.textdiff import stage_pair

        sequences = np.arange(2 * 6 * 8, dtype=np.int32).reshape(2, 6, 8)
        ids, weight = stage_pair(sequences, 3, 2, 4, np.random.default_rng(0))
        assert ids.shape == weight.shape == (2, 3, 2, 8) and ids.dtype == np.int32
        for c in range(2):
            rows = {r.tobytes() for r in ids[c].reshape(-1, 8)}
            assert rows == {r.tobytes() for r in sequences[c]}  # each sequence once
        again, _ = stage_pair(sequences, 3, 2, 4, np.random.default_rng(0))
        np.testing.assert_array_equal(ids, again)
        out = (np.zeros_like(ids), np.zeros_like(weight))
        ids2, weight2 = stage_pair(sequences, 3, 2, 4, np.random.default_rng(0), out=out)
        assert ids2 is out[0] and weight2 is out[1]
        np.testing.assert_array_equal(ids2, ids)
        np.testing.assert_array_equal(weight2, weight)
        with pytest.raises(ValueError, match="a round needs"):
            stage_pair(sequences, 4, 2, 4, np.random.default_rng(0))
