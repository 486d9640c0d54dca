"""The hybrid model of gated short convolutions and attention with sparse
experts (``models/lfm2_moe.py``) against the benchmark's plain reference
(``benchmark/reference/lfm2_conv_moe.py``) at a small size: hidden 64, five
layers (a convolution over a dense SwiGLU of width 96, then one period:
attention and three convolutions, each over an expert layer), 4 query heads
over 2 key/value heads of 16 lanes, 3 taps, 8 experts of width 32, top-2, of
which 2 are held, vocabulary 64, L 128 (the splash kernel's smallest tile)."""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedcrack_tpu.configs import Lfm2MoeConfig
from fedcrack_tpu.data.textdiff import stage_pair
from fedcrack_tpu.models import get_model, moe_layers
from fedcrack_tpu.models import lfm2_moe as M
from fedcrack_tpu.models.gdn_moe import causal_conv, gated_causal_attention
from fedcrack_tpu.models.mla_moe import sigmoid_route
from fedcrack_tpu.parallel import build_federated_round, make_mesh, run_mesh_federation
from fedcrack_tpu.tasks import CausalLMTask, task_for

from test_mla_moe import _kernel_calls

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_lfm2", os.path.join(ROOT, "benchmark", "reference", "lfm2_conv_moe.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()
SMALL = dict(
    hidden_size=64, num_hidden_layers=5, layer_types=("conv", "full_attention", "conv", "conv", "conv"),
    num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
    moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, first_expert=2, experts_held=2, vocab_held=64,
    seq_len=128,
)
PUBLISHED = (
    "hidden_size", "num_hidden_layers", "num_dense_layers", "num_attention_heads", "num_key_value_heads",
    "conv_L_cache", "intermediate_size", "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
    "routed_scaling_factor", "norm_eps", "rope_theta", "first_expert", "experts_held", "vocab_held", "seq_len",
)


def small_config(**over) -> Lfm2MoeConfig:
    return Lfm2MoeConfig(**{**SMALL, "compute_dtype": "float32", **over})


def reference_cfg(config: Lfm2MoeConfig) -> dict:
    return dict(
        {k: getattr(config, k) for k in PUBLISHED}, layer_types=list(config.layer_types),
        router_outputs=config.num_experts,
    )


def batch(seed=0, n=2, config=None):
    config = config or small_config()
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, config.vocab_held, (n, config.seq_len)).astype(np.int32)
    return jnp.asarray(ids), jnp.ones(ids.shape, jnp.float32)


def _close(a, b, tol):
    scale = float(jnp.max(jnp.abs(b))) + 1e-12
    assert float(jnp.max(jnp.abs(a - b))) <= tol * scale


def _loss(task, ids, weight):
    def loss(p):
        inputs, targets = task.unpack((ids, weight))
        outputs, _ = task.apply(p, {}, inputs)
        m = task.loss_and_metrics(outputs, targets)
        return m["loss"], m
    return loss


class TestTheConvolution:
    def test_the_three_taps_against_a_direct_loop(self):
        rng = np.random.default_rng(2)
        u = rng.normal(size=(2, 9, 5)).astype(np.float32)
        taps = rng.normal(size=(5, 3)).astype(np.float32)
        expected = np.zeros_like(u)
        for t in range(9):
            for j in range(3):
                if t - 2 + j >= 0:
                    expected[:, t] += taps[:, j] * u[:, t - 2 + j]
        np.testing.assert_allclose(np.asarray(causal_conv(jnp.asarray(u), jnp.asarray(taps))), expected, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(REF.causal_conv(jnp.asarray(u[1]), jnp.asarray(taps))), expected[1], rtol=1e-5, atol=1e-6)

    def test_the_operator_is_causal(self):
        """A change at position t moves nothing of the operator's output
        before t (and moves t itself); the reference's shifted taps do not
        hold to it."""
        config = small_config(num_hidden_layers=1, layer_types=("conv",))
        model = M.Lfm2Moe(config)
        p = model.init(jax.random.key(1))["layer0"]
        x = np.random.default_rng(3).normal(size=(1, 128, 64)).astype(np.float32)
        changed = x.copy()
        changed[:, 70] += 1.0
        before, after = (np.asarray(model._conv_block(p, jnp.asarray(t))) for t in (x, changed))
        np.testing.assert_array_equal(after[:, :70], before[:, :70])
        assert np.max(np.abs(after[:, 70] - before[:, 70])) > 0
        assert np.max(np.abs(after[:, 73:] - before[:, 73:])) == 0  # three taps reach two tokens on
        cfg = reference_cfg(config)
        ours, shifted = (
            np.asarray(REF.conv_operator(p, jnp.asarray(changed[0]), cfg, fault=f) - REF.conv_operator(p, jnp.asarray(x[0]), cfg, fault=f))
            for f in (None, "taps_shifted")
        )
        assert np.max(np.abs(ours[:70])) == 0 and np.max(np.abs(shifted[:70])) > 0

    def test_the_operator_is_the_gates_around_the_taps(self):
        """``W_out (C * conv(B * x~))`` with ``[B | C | x~]`` in that lane order."""
        config = small_config(num_hidden_layers=1, layer_types=("conv",))
        model = M.Lfm2Moe(config)
        p = model.init(jax.random.key(4))["layer0"]
        x = jnp.asarray(np.random.default_rng(5).normal(size=(1, 128, 64)), jnp.float32)
        with jax.default_matmul_precision("highest"):
            n = moe_layers.rms_norm(x, p["operator_norm"], config.norm_eps)
            bcx = n @ p["in_proj"]
            b, c, xt = bcx[..., :64], bcx[..., 64:128], bcx[..., 128:]
            expected = x + (c * causal_conv(b * xt, p["conv"])) @ p["out_proj"]
            _close(model._conv_block(p, x), expected, 1e-5)


class TestThePieces:
    def test_the_expert_bias_selects_but_does_not_weigh(self):
        rng = np.random.default_rng(6)
        n = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
        router = jnp.asarray(0.3 * rng.normal(size=(8, 6)), jnp.float32)
        bias = jnp.asarray([0.0, 0.0, 0.0, 0.0, 0.0, 5.0], jnp.float32)  # expert 5 always chosen
        route = functools.partial(sigmoid_route, top_k=2, norm_topk=True, scale=1.0, eps=M.ROUTER_EPS)
        with jax.default_matmul_precision("highest"):
            top_e, top_w = route(n, router, bias=bias)
            scores = jax.nn.sigmoid(n @ router)
        assert np.all(np.asarray(top_e)[:, 0] == 5)
        chosen = np.take_along_axis(np.asarray(scores), np.asarray(top_e), axis=-1)
        np.testing.assert_allclose(np.asarray(top_w), chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
        # No gradient reaches the bias through the weights.
        grad = jax.grad(lambda b: jnp.sum(route(n, router, bias=b)[1] * jnp.arange(2.0)))(bias)
        assert float(jnp.max(jnp.abs(grad))) == 0.0
        # The reference's fault weighs by ``s + b`` and so moves the bias.
        cfg = {"num_experts_per_tok": 2, "norm_topk_prob": True, "routed_scaling_factor": 1.0, "router_outputs": 6}
        grad = jax.grad(lambda b: jnp.sum(REF.route(n, router, b, cfg, "bias_in_weights") * jnp.arange(6.0)))(bias)
        assert float(jnp.max(jnp.abs(grad))) > 0.0

    def test_the_head_dim_64_grouped_attention_in_the_interpreter_equals_the_dense_path(self):
        """64 lanes a head, 4 query heads a key/value head, the causal mask."""
        rng = np.random.default_rng(7)
        q = jnp.asarray(rng.normal(size=(2, 2, 4, 256, 64)) * 0.1, jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, 2, 256, 64)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, 2, 256, 64)), jnp.float32)
        dense = gated_causal_attention(q, k, v, kernels="xla")
        kernel = gated_causal_attention(q, k, v, kernels="interpret")
        assert kernel.shape == (2, 2, 4, 256, 64)
        np.testing.assert_allclose(np.asarray(kernel), np.asarray(dense), rtol=2e-2, atol=2e-3)
        # Against the reference's attention (which scales the queries by
        # 64^-1/2 itself), a query head reading key/value head j // 4.
        with jax.default_matmul_precision("highest"):
            ref = REF.attention(8.0 * q[0].transpose(2, 0, 1, 3).reshape(256, 8, 64), k[0].transpose(1, 0, 2), v[0].transpose(1, 0, 2))
        np.testing.assert_allclose(np.asarray(dense[0]).transpose(2, 0, 1, 3).reshape(256, 8, 64), np.asarray(ref), rtol=1e-4, atol=1e-5)

    def test_layer_kinds_follow_layer_types_and_num_dense_layers(self):
        config = small_config(layer_types=("full_attention", "conv", "conv", "full_attention", "conv"), num_dense_layers=2)
        params = jax.eval_shape(lambda: M.Lfm2Moe(config).init(jax.random.key(0)))
        conv, attention = {"in_proj", "conv", "out_proj"}, {"wq", "wk", "wv", "wo", "q_norm", "k_norm"}
        for i, kind in enumerate(config.layer_types):
            keys = set(params[f"layer{i}"])
            assert (conv <= keys) == (kind == "conv") and (attention <= keys) == (kind == "full_attention"), (i, keys)
            assert ("router" in keys) == (i >= 2) and ("expert_bias" in keys) == (i >= 2)
            w_gate = params[f"layer{i}"]["w_gate"].shape
            assert w_gate == ((64, 96) if i < 2 else (2, 64, 32)), (i, w_gate)
        assert params["layer1"]["conv"].shape == (64, 3) and params["layer0"]["q_norm"].shape == (16,)
        assert set(params) == {"embed", "final_norm", *(f"layer{i}" for i in range(5))}  # no head: tied
        with pytest.raises(ValueError, match="layer_types"):
            small_config(layer_types=("conv", "sliding_attention", "conv", "conv", "conv"))
        with pytest.raises(ValueError, match="layer_types"):
            small_config(layer_types=("conv",) * 4)
        with pytest.raises(ValueError, match="not among the router's"):
            small_config(first_expert=7)
        with pytest.raises(ValueError, match="do not group"):
            small_config(num_key_value_heads=3)


class TestAgainstTheReference:
    def test_params_are_the_references_tree(self):
        config = small_config()
        ours = jax.eval_shape(lambda: M.Lfm2Moe(config).init(jax.random.key(0)))
        theirs = jax.eval_shape(lambda: REF.init_variables(jnp.zeros((2,), jnp.uint32), reference_cfg(config)))["params"]
        assert jax.tree_util.tree_structure(ours) == jax.tree_util.tree_structure(theirs)
        assert jax.tree_util.tree_leaves(ours) == jax.tree_util.tree_leaves(theirs)

    # A convolution over a dense layer alone, attention over an expert layer
    # alone, and the whole stack: every kind of operator and feed-forward.
    @pytest.mark.parametrize(
        "layer_types,dense",
        [(("conv",), 1), (("full_attention",), 0), (SMALL["layer_types"], 1)],
        ids=["conv_dense", "attention_experts", "whole"],
    )
    def test_logits_loss_and_every_gradient_leaf(self, layer_types, dense):
        config = small_config(num_hidden_layers=len(layer_types), layer_types=layer_types, num_dense_layers=dense)
        cfg = reference_cfg(config)
        params = REF.make_variables(5, cfg)["params"]
        ids, weight = batch(config=config)
        task = CausalLMTask(config)
        with jax.default_matmul_precision("highest"):
            logits = M.Lfm2Moe(config).logits(params, ids)
            theirs = [REF.sequence_logits(params, ids[b], cfg) for b in range(2)]
            (ours, stats), grads = jax.value_and_grad(_loss(task, ids, weight), has_aux=True)(params)
            (ref_loss, ref_stats), ref_grads = jax.value_and_grad(
                lambda p: REF.batch_loss(p, ids, weight, cfg), has_aux=True
            )(params)
        # Float32 on both sides, the same products in another order and
        # grouping (the batch whole against a sequence at a time, the
        # experts' rows gathered against dense masked weights, the head in
        # chunks): agreement to float32's rounding of sums of a few hundred
        # terms, some 1e-6 of the largest entry; 1e-5 leaves ten times that.
        _close(logits, jnp.stack([t[0] for t in theirs]), 1e-5)
        assert abs(float(ours) - float(ref_loss)) <= 1e-5 * float(ref_loss)
        assert float(ours) == float(stats["next_loss"]) and "mtp_loss" not in stats
        assert float(stats["tokens"]) == 2 * 127 == float(ref_stats["tokens"])
        np.testing.assert_array_equal(np.asarray(stats["expert_rows"]), np.asarray(ref_stats["expert_rows"]))
        assert stats["expert_rows"].shape == (config.sparse_layers, 2)
        assert float(stats["held_pairs"]) == float(np.sum(ref_stats["expert_rows"]))
        flat, _ = jax.tree_util.tree_flatten_with_path(grads)
        ref_flat = jax.tree_util.tree_leaves(ref_grads)
        assert len(flat) == len(ref_flat) == len(jax.tree_util.tree_leaves(params))
        for (path, g), r in zip(flat, ref_flat):
            if path[-1].key == "expert_bias":
                # Selection only: no gradient on either side.
                assert float(jnp.max(jnp.abs(g))) == 0.0 == float(jnp.max(jnp.abs(r))), path
                continue
            assert float(jnp.max(jnp.abs(r))) > 0, path
            # A gradient is a sum over every position and its backward
            # products; through the gates and the taps, or the head and the
            # embedding's two uses, a few terms more than the logits: 3e-5.
            _close(g, r, 3e-5)

    def test_bf16_compute_stays_near_the_float32_reference(self):
        config = small_config(compute_dtype="bfloat16")
        cfg = reference_cfg(config)
        params = REF.make_variables(6, cfg)["params"]
        ids, weight = batch(1)
        ours, _ = _loss(CausalLMTask(config), ids, weight)(params)
        with jax.default_matmul_precision("highest"):
            theirs, _ = REF.batch_loss(params, ids, weight, cfg)
        # bf16 products with float32 sums: the loss within 2% at these widths.
        assert abs(float(ours) - float(theirs)) <= 0.02 * float(theirs)

    def test_registry_family_flops_and_the_published_share(self):
        config = small_config()
        assert isinstance(get_model("lfm2_moe", config), M.Lfm2Moe)
        task = task_for(config)
        assert isinstance(task, CausalLMTask) and isinstance(task.model, M.Lfm2Moe)
        assert [n for n, _ in task.metric_reductions] == [
            "next_loss", "tokens", "next_hits", *(n for n, _ in moe_layers.EXPERT_COUNTERS),
        ]
        assert task.step_flops(2) == task.model.step_flops(2)
        # The published widths at the cell's cut: about 433 MFLOP a token
        # forward, 21.26 TFLOP a training step of two sequences of 8,192;
        # 507.8 M parameters (474.3 M in five layers, 33.6 M of tied
        # embedding); head_dim 64.
        published = Lfm2MoeConfig()
        assert published.head_dim == 64
        assert abs(CausalLMTask(published).step_flops(2) / 1e12 - 21.26) < 0.01
        shapes = jax.eval_shape(lambda: M.Lfm2Moe(published).init(jax.random.key(0)))
        assert abs(sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes)) / 1e6 - 507.8) < 0.1


class TestTheShare:
    def test_the_four_shares_of_eight_experts_add_up_to_the_uncut_layer(self):
        """Four chips' shares of a 32-expert layer (8 held each, top-4) add up
        to the uncut reference's layer; nothing is computed alike on every
        chip in this layer (no shared expert), and the router's choices are
        the same on every chip."""
        whole = reference_cfg(small_config(num_experts=32, num_experts_per_tok=4, first_expert=0, experts_held=32))
        whole = dict(whole, num_hidden_layers=1, layer_types=["conv"], num_dense_layers=0)
        p = REF.make_variables(9, whole)["params"]["layer0"]
        rng = np.random.default_rng(3)
        n = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
        route = functools.partial(
            sigmoid_route, bias=p["expert_bias"], top_k=4, norm_topk=True, scale=1.0, eps=M.ROUTER_EPS
        )
        with jax.default_matmul_precision("highest"):
            uncut, uncut_rows = REF.expert_layer(n, p, whole)
            total, rows = jnp.zeros_like(n), []
            for first in range(0, 32, 8):
                part, counters = moe_layers.held_expert_layer(
                    n, p["router"], p["w_gate"][first : first + 8], p["w_up"][first : first + 8],
                    p["w_down"][first : first + 8], first_expert=first, route=route, compute_dtype=jnp.float32,
                )
                expert_rows = counters["expert_rows"]
                assert float(counters["held_pairs"]) == float(jnp.sum(expert_rows))
                total = total + part
                rows.append(expert_rows)
        _close(total, uncut, 1e-5)
        np.testing.assert_array_equal(np.concatenate(rows), np.asarray(uncut_rows))
        assert float(sum(r.sum() for r in rows)) == 64 * 4


class TestWhatTheRematerialisationKeeps:
    def test_the_forward_kernel_runs_once_in_the_models_gradient(self):
        config = small_config()
        model = M.Lfm2Moe(config, kernels="interpret")
        params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
        ids = jax.ShapeDtypeStruct((2, config.seq_len), jnp.int32)
        calls = _kernel_calls(jax.make_jaxpr(jax.grad(lambda p, ids: jnp.sum(model.apply(p, ids)["nll_next"])))(params, ids).jaxpr)
        # One attention layer, one kernel call for both sequences and both
        # key/value heads. Under a plain ``jax.checkpoint`` the forward count is 2.
        assert {k: v for k, v in calls.items() if k.startswith("splash")} == {
            "splash_mqa_fwd_residuals": 1, "splash_mqa_dq_no_residuals": 1, "splash_mqa_dkv_no_residuals": 1,
        }

    @pytest.mark.parametrize("layer", [0, 1, 2], ids=["conv_dense", "attention_experts", "conv_experts"])
    def test_the_layers_gradient_is_the_bare_blocks(self, layer):
        """``_layer``'s own wrapping (the operator block on the batch, the
        feed-forward block a sequence at a time, each rematerialised) against
        the blocks with no ``jax.checkpoint`` and no loop."""
        config = small_config()
        model = M.Lfm2Moe(config)
        p = model.init(jax.random.key(2))[f"layer{layer}"]
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=(2, 128, 64)), jnp.float32)
        target = jnp.asarray(rng.normal(size=(2, 128, 64)), jnp.float32)
        cos, sin = M.rotary_tables(config.seq_len, config.head_dim, config.rope_theta)

        def through_layer(p, x):
            return jnp.sum(model._layer(p, x, cos, sin, layer)[0] * target)

        def bare(p, x):
            h = model._conv_block(p, x) if config.is_conv(layer) else model._attention_block(p, x, cos, sin)
            ff = model._dense_block if layer < config.num_dense_layers else (lambda p, h: model._expert_block(p, h)[0])
            return jnp.sum(jnp.stack([ff(p, h[b]) for b in range(2)]) * target)

        with jax.default_matmul_precision("highest"):
            kept = jax.jit(jax.grad(through_layer, argnums=(0, 1)))(p, x)
            plain = jax.jit(jax.grad(bare, argnums=(0, 1)))(p, x)
        for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(kept)[0], jax.tree_util.tree_leaves(plain)):
            if path[-1] == jax.tree_util.DictKey("expert_bias"):
                continue
            assert float(jnp.max(jnp.abs(b))) > 0, path
            _close(a, b, 2e-5)


class TestThroughTheRoundProgram:
    def test_two_rounds_of_the_causal_task_on_a_one_by_one_mesh(self):
        config = small_config()
        mesh = make_mesh(1, 1)
        round_fn = build_federated_round(mesh, config, learning_rate=1e-3, local_epochs=1)
        assert type(round_fn.task) is CausalLMTask and round_fn.task.config == config
        variables = round_fn.task.init(jax.random.key(0))
        before = jax.device_get(variables)
        rng = np.random.default_rng(0)
        sequences = rng.integers(0, 64, (1, 8, config.seq_len), dtype=np.int32)

        def data_fn(r):
            ids, weight = stage_pair(sequences, 4, 2, None, rng)
            return ids, weight, np.ones(1, np.float32), np.full(1, 8.0, np.float32)

        out, records = run_mesh_federation(round_fn, variables, data_fn, 2, mesh)
        assert len(records) == 2
        m = records[-1].metrics
        assert float(m["tokens"][0]) == 4 * 2 * (config.seq_len - 1)
        assert m["expert_rows"].shape == (1, 4, 2) and float(m["held_pairs"][0]) == float(m["expert_rows"].sum())
        assert "mtp_loss" not in m and float(m["loss"][0]) == float(m["next_loss"][0])
        assert float(m["loss"][0]) < float(records[0].metrics["loss"][0])  # it learns the eight sequences
        after = jax.device_get(out)["params"]
        for name, leaf in (("layer0", "in_proj"), ("layer0", "conv"), ("layer0", "w_gate"), ("layer1", "wq"),
                           ("layer1", "q_norm"), ("layer1", "router"), ("layer2", "out_proj"), ("embed", None)):
            now, then = (after[name], before["params"][name]) if leaf is None else (after[name][leaf], before["params"][name][leaf])
            assert not np.array_equal(now, then), (name, leaf)
        # The bias selects and is never moved by the round.
        np.testing.assert_array_equal(after["layer3"]["expert_bias"], before["params"]["layer3"]["expert_bias"])
