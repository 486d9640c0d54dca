"""The hybrid linear-attention mixture-of-experts causal model
(``models/gdn_moe.py``) against the benchmark's plain reference
(``benchmark/reference/qwen3next_gdn_moe.py``) at a small size: hidden 64,
three Gated DeltaNet layers (2 key heads and 4 value heads of 16) and one
gated full-attention layer (4 query heads over 2 key/value heads of 16, rotary
on 4 lanes), 8 experts top-2 of which 2 are held, vocabulary 64, L 128 (two
chunks of the delta rule)."""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedcrack_tpu.configs import GDN_CHUNK, GdnMoeConfig
from fedcrack_tpu.data.textdiff import stage_pair
from fedcrack_tpu.models import get_model, moe_layers
from fedcrack_tpu.models import gdn_moe as M
from fedcrack_tpu.parallel import build_federated_round, make_mesh, run_mesh_federation
from fedcrack_tpu.tasks import CausalLMTask, task_for

from test_mla_moe import _kernel_calls, small_config as small_mla_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_qwen3next", os.path.join(ROOT, "benchmark", "reference", "qwen3next_gdn_moe.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()
SMALL = dict(
    hidden_size=64, num_hidden_layers=4, full_attention_interval=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=16,
    moe_intermediate_size=32, shared_expert_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
    first_expert=2, experts_held=2, vocab_held=64, seq_len=128,
)
PUBLISHED = (
    "hidden_size", "num_hidden_layers", "full_attention_interval", "num_attention_heads", "num_key_value_heads",
    "head_dim", "partial_rotary_factor", "rope_theta", "linear_num_key_heads", "linear_num_value_heads",
    "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim", "moe_intermediate_size",
    "shared_expert_intermediate_size", "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps", "first_expert",
    "experts_held", "vocab_held", "seq_len",
)


def small_config(**over) -> GdnMoeConfig:
    return GdnMoeConfig(**{**SMALL, "compute_dtype": "float32", **over})


def reference_cfg(config: GdnMoeConfig) -> dict:
    return dict({k: getattr(config, k) for k in PUBLISHED}, router_outputs=config.num_experts)


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


def _rule_inputs(seed, seq_len, decay, heads=3, d_k=16, d_v=8):
    """``q`` (normalised, scaled), ``k`` (normalised), ``v``, the log-decay
    and ``beta`` of one sequence; ``decay`` is the mean of ``-log alpha``."""
    rng = np.random.default_rng(seed)
    q = M.l2_normalise(jnp.asarray(rng.normal(size=(seq_len, heads, d_k)), jnp.float32)) * d_k**-0.5
    k = M.l2_normalise(jnp.asarray(rng.normal(size=(seq_len, heads, d_k)), jnp.float32))
    v = jnp.asarray(rng.normal(size=(seq_len, heads, d_v)), jnp.float32)
    a = jnp.asarray(np.log(decay) + 0.5 * rng.normal(size=(seq_len, heads)), jnp.float32)  # log(-log alpha)
    b = jnp.asarray(rng.normal(size=(seq_len, heads)), jnp.float32)
    return q, k, v, a, b


class TestTheChunkedRule:
    # Two lengths (two and four chunks), a memory of hundreds of tokens and
    # one of a token or two.
    @pytest.mark.parametrize("seq_len", [2 * GDN_CHUNK, 4 * GDN_CHUNK])
    @pytest.mark.parametrize("decay", [0.01, 3.0], ids=["weak_decay", "strong_decay"])
    def test_values_and_gradients_against_the_recurrence(self, seq_len, decay):
        q, k, v, a, b = _rule_inputs(seq_len, seq_len, decay)
        target = jnp.asarray(np.random.default_rng(1).normal(size=v.shape), jnp.float32)

        def chunked(q, k, v, a, b):
            o = M.chunked_delta_rule(
                q[None], k[None], v[None], -jnp.exp(a)[None], jax.nn.sigmoid(b)[None], compute_dtype=jnp.float32
            )[0]
            return jnp.sum(o * target), o

        def token_by_token(q, k, v, a, b):
            o = REF.delta_rule(q, k, v, jnp.exp(-jnp.exp(a)), jax.nn.sigmoid(b))
            return jnp.sum(o * target), o

        with jax.default_matmul_precision("highest"):
            (_, ours), grads = jax.value_and_grad(chunked, argnums=(0, 1, 2, 3, 4), has_aux=True)(q, k, v, a, b)
            (_, theirs), ref_grads = jax.value_and_grad(token_by_token, argnums=(0, 1, 2, 3, 4), has_aux=True)(q, k, v, a, b)
        assert ours.shape == (seq_len, 3, 8) and float(jnp.max(jnp.abs(theirs))) > 0.1
        _close(ours, theirs, 2e-5)
        for name, g, r in zip("qkvab", grads, ref_grads):
            assert float(jnp.max(jnp.abs(r))) > 0, name
            _close(g, r, 1e-4)

    def test_a_long_memory_carries_the_first_chunk_into_the_last(self):
        """With ``alpha`` near 1 the last chunk's output depends on the first
        chunk's values; with ``alpha`` near 0 it does not."""
        q, k, v, a, b = _rule_inputs(3, 4 * GDN_CHUNK, 1.0)

        def last(v, log_decay):
            o = M.chunked_delta_rule(
                q[None], k[None], v[None], jnp.full(a.shape, log_decay)[None], jax.nn.sigmoid(b)[None],
                compute_dtype=jnp.float32,
            )
            return jnp.sum(o[0, -GDN_CHUNK:] ** 2)

        reach = lambda log_decay: float(jnp.max(jnp.abs(jax.grad(last)(v, log_decay)[:GDN_CHUNK])))
        assert reach(-1e-3) > 1e-4 and reach(-20.0) == 0.0

    def test_the_inverse_by_halves_is_the_inverse(self):
        rng = np.random.default_rng(0)
        a = jnp.asarray(np.tril(rng.normal(size=(3, 2, 64, 64)) * 0.3, -1), jnp.float32)
        t = M.unit_lower_inverse(a)
        expected = np.linalg.inv(np.eye(64) + np.asarray(a, np.float64))
        np.testing.assert_allclose(np.asarray(t), expected, rtol=2e-4, atol=2e-5)
        # The closed-form cotangent against differentiating the levels themselves.
        g = jnp.asarray(rng.normal(size=a.shape), jnp.float32)
        with jax.default_matmul_precision("highest"):
            ours = jax.grad(lambda a: jnp.sum(M.unit_lower_inverse(a) * g))(a)
            plain = jax.grad(lambda a: jnp.sum(M._inverse_by_halves(jnp.tril(a, -1)) * g))(a)
        _close(ours, plain, 1e-4)
        assert float(jnp.max(jnp.abs(jnp.triu(ours)))) == 0.0

    def test_the_configuration_refuses_a_length_that_is_not_whole_chunks(self):
        with pytest.raises(ValueError, match="whole chunks"):
            small_config(seq_len=96)
        task = CausalLMTask(small_config())
        task.validate(np.zeros((1, 2, 2, 128), np.int32))
        with pytest.raises(ValueError, match="sequences of 64 tokens"):
            task.validate(np.zeros((1, 2, 2, 64), np.int32))


class TestThePieces:
    def test_the_convolution_is_four_shifted_sums_with_zero_history(self):
        rng = np.random.default_rng(2)
        x = rng.normal(size=(2, 9, 5)).astype(np.float32)
        taps = rng.normal(size=(5, 4)).astype(np.float32)
        expected = np.zeros_like(x)
        for t in range(9):
            for j in range(4):
                if t - 3 + j >= 0:
                    expected[:, t] += taps[:, j] * x[:, t - 3 + j]
        np.testing.assert_allclose(np.asarray(M.causal_conv(jnp.asarray(x), jnp.asarray(taps))), expected, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(REF.causal_conv(jnp.asarray(x[0]), jnp.asarray(taps))), expected[0], rtol=1e-5, atol=1e-6)
        # Causal: a later token changes nothing before it.
        later = x.copy()
        later[:, 6:] += 1.0
        np.testing.assert_array_equal(
            np.asarray(M.causal_conv(jnp.asarray(later), jnp.asarray(taps)))[:, :6], np.asarray(M.causal_conv(jnp.asarray(x), jnp.asarray(taps)))[:, :6]
        )

    def test_rotary_touches_the_first_quarter_of_a_head_and_pairs_by_halves(self):
        config = small_config()
        assert config.rotary_dim == 4
        cos, sin = M.rotary_tables(config.seq_len, config.rotary_dim, config.rope_theta)
        assert cos.shape == (128, 2)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(128, 3, 16)), jnp.float32)
        y = np.asarray(M.apply_rotary_halves(x, cos, sin))
        np.testing.assert_array_equal(y[0], np.asarray(x[0]))  # position 0: no turn
        np.testing.assert_array_equal(y[..., 4:], np.asarray(x[..., 4:]))  # three quarters pass
        for i in range(2):  # lane i turns with lane i + 2
            a, b = np.asarray(x[5, 1, i]), np.asarray(x[5, 1, i + 2])
            angle = 5.0 * config.rope_theta ** (-2.0 * i / 4)
            np.testing.assert_allclose(y[5, 1, i], a * np.cos(angle) - b * np.sin(angle), rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(y[5, 1, i + 2], b * np.cos(angle) + a * np.sin(angle), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(y, np.asarray(REF.rotary_halves(x, 4, config.rope_theta)), rtol=1e-5, atol=1e-6)

    def test_the_zero_centred_norm_is_one_plus_w(self):
        model = M.GdnMoe(small_config())
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=(5, 64)), jnp.float32)
        w = jnp.asarray(0.1 * rng.normal(size=(64,)), jnp.float32)
        expected = np.asarray(x) / np.sqrt(np.mean(np.asarray(x) ** 2, axis=-1, keepdims=True) + 1e-6) * (1.0 + np.asarray(w))
        np.testing.assert_allclose(np.asarray(model._norm(x, w)), expected, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(model._norm(x, w)), np.asarray(moe_layers.rms_norm(x, 1.0 + w, 1e-6)), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(REF.norm(x, w, 1e-6)), expected, rtol=1e-5)
        # Fresh weights are zeros and norm by 1; the gated norm's are ones.
        params = model.init(jax.random.key(0))
        assert float(jnp.max(jnp.abs(params["layer0"]["mixer_norm"]))) == 0.0 == float(jnp.max(jnp.abs(params["final_norm"])))
        assert float(jnp.min(params["layer0"]["gdn_norm"])) == 1.0 == float(jnp.min(params["layer0"]["dt_bias"]))
        a = np.exp(np.asarray(params["layer1"]["A_log"]))
        assert a.min() > 0 and a.max() < 16

    def test_kernel_attention_in_the_interpreter_equals_the_dense_path(self):
        """256 wide, 8 query heads a key/value head, the causal mask."""
        rng = np.random.default_rng(5)
        q = jnp.asarray(rng.normal(size=(1, 2, 8, 256, 256)) * 0.05, jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 2, 256, 256)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 2, 256, 256)), jnp.float32)
        dense = M.gated_causal_attention(q, k, v, kernels="xla")
        kernel = M.gated_causal_attention(q, k, v, kernels="interpret")
        assert kernel.shape == (1, 2, 8, 256, 256)
        np.testing.assert_allclose(np.asarray(kernel), np.asarray(dense), rtol=2e-2, atol=2e-3)
        # The first query sees its own key only; a head reads its own key/value head.
        np.testing.assert_allclose(np.asarray(dense[0, :, :, 0]), np.broadcast_to(np.asarray(v[0, :, None, 0]), (2, 8, 256)), rtol=1e-5)


class TestAgainstTheReference:
    def test_params_are_the_references_tree(self):
        config = small_config()
        ours = jax.eval_shape(lambda: M.GdnMoe(config).init(jax.random.key(0)))
        theirs = jax.eval_shape(lambda: REF.init_variables(jnp.zeros((2,), jnp.uint32), reference_cfg(config)))["params"]
        assert jax.tree_util.tree_structure(ours) == jax.tree_util.tree_structure(theirs)
        assert jax.tree_util.tree_leaves(ours) == jax.tree_util.tree_leaves(theirs)
        assert set(ours) == {"embed", "final_norm", "lm_head", "layer0", "layer1", "layer2", "layer3"}
        assert "w_qkvz" in ours["layer2"] and "wq" not in ours["layer2"] and ours["layer2"]["w_qkvz"].shape == (64, 192)
        assert "wq" in ours["layer3"] and "w_qkvz" not in ours["layer3"] and ours["layer3"]["wq"].shape == (64, 128)
        assert ours["layer0"]["conv"].shape == (128, 4) and ours["layer0"]["shared_expert_gate"].shape == (64,)

    # A Gated DeltaNet layer alone, a gated-attention layer alone, and the
    # whole period: every kind of layer against the reference.
    @pytest.mark.parametrize("layers,interval", [(1, 4), (1, 1), (4, 4)], ids=["gdn_layer", "attention_layer", "whole"])
    def test_logits_loss_and_every_gradient_leaf(self, layers, interval):
        config = small_config(num_hidden_layers=layers, full_attention_interval=interval)
        cfg = reference_cfg(config)
        params = REF.make_variables(5, cfg)["params"]
        ids, weight = batch(config=config)
        task = CausalLMTask(config)
        with jax.default_matmul_precision("highest"):
            logits = M.GdnMoe(config).logits(params, ids)
            theirs = [REF.sequence_logits(params, ids[b], cfg) for b in range(2)]
            _close(logits, jnp.stack([t[0] for t in theirs]), 1e-5)
            (ours, stats), grads = jax.value_and_grad(_loss(task, ids, weight), has_aux=True)(params)
            (ref_loss, ref_stats), ref_grads = jax.value_and_grad(
                lambda p: REF.batch_loss(p, ids, weight, cfg), has_aux=True
            )(params)
        assert abs(float(ours) - float(ref_loss)) <= 1e-5 * float(ref_loss)
        assert float(ours) == float(stats["next_loss"]) and "mtp_loss" not in stats
        assert float(stats["tokens"]) == 2 * 127 == float(ref_stats["tokens"])
        np.testing.assert_array_equal(np.asarray(stats["expert_rows"]), np.asarray(ref_stats["expert_rows"]))
        assert stats["expert_rows"].shape == (layers, 2)
        assert float(stats["held_pairs"]) == float(np.sum(ref_stats["expert_rows"]))
        linear = config.linear_layers
        assert stats["gdn_decay_mean"].shape == (linear,) and linear == (0 if interval == 1 else layers - layers // 4)
        if linear:
            np.testing.assert_allclose(np.asarray(stats["gdn_decay_mean"]), np.asarray(ref_stats["gdn_decay_mean"]), rtol=1e-5)
        flat, _ = jax.tree_util.tree_flatten_with_path(grads)
        ref_flat = jax.tree_util.tree_leaves(ref_grads)
        assert len(flat) == len(ref_flat) == len(jax.tree_util.tree_leaves(params))
        for (path, g), r in zip(flat, ref_flat):
            assert float(jnp.max(jnp.abs(r))) > 0, path
            if path[-1].key in ("A_log", "dt_bias"):
                # The decay's two leaves see the loss through exp(-exp(.)) of
                # a memory of a token or two: gradients of 1e-5 down to 1e-8,
                # which float32 sums in another order to three digits at best.
                assert float(jnp.max(jnp.abs(g - r))) <= 5e-3 * max(float(jnp.max(jnp.abs(r))), 1e-5), path
            else:
                _close(g, r, 3e-5)

    def test_bf16_compute_stays_near_the_float32_reference(self):
        config = small_config(compute_dtype="bfloat16")
        cfg = reference_cfg(config)
        params = REF.make_variables(6, cfg)["params"]
        ids, weight = batch(1)
        ours, _ = _loss(CausalLMTask(config), ids, weight)(params)
        with jax.default_matmul_precision("highest"):
            theirs, _ = REF.batch_loss(params, ids, weight, cfg)
        assert abs(float(ours) - float(theirs)) <= 0.02 * float(theirs)

    def test_registry_family_and_flops(self):
        config = small_config()
        assert isinstance(get_model("qwen3_next", config), M.GdnMoe)
        task = task_for(config)
        assert isinstance(task, CausalLMTask) and isinstance(task.model, M.GdnMoe)
        assert [config.is_linear(i) for i in range(4)] == [True, True, True, False]
        with pytest.raises(ValueError, match="not among the router's"):
            small_config(first_expert=7)
        with pytest.raises(ValueError, match="do not group"):
            small_config(linear_num_value_heads=3)
        # The published widths at the cell's cut: 7.41 TFLOP forward a step of
        # two sequences, 424.3 M parameters.
        assert abs(CausalLMTask(GdnMoeConfig()).step_flops(2) / 3e12 - 7.41) < 0.01
        shapes = jax.eval_shape(lambda: M.GdnMoe(GdnMoeConfig()).init(jax.random.key(0)))
        assert abs(sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes)) / 1e6 - 424.3) < 0.1


class TestTheShare:
    def test_the_shares_add_up_to_the_uncut_layer(self):
        """Four shares of a 32-expert layer, the shared expert with its gate
        counted once, equal the uncut reference's layer."""
        config = small_config(num_experts=32, num_experts_per_tok=4, first_expert=0, experts_held=8)
        whole = reference_cfg(small_config(num_experts=32, num_experts_per_tok=4, first_expert=0, experts_held=32))
        p = REF.make_variables(9, dict(whole, num_hidden_layers=1))["params"]["layer0"]
        rng = np.random.default_rng(3)
        n = jnp.asarray(rng.normal(size=(64, config.hidden_size)), jnp.float32)
        route = functools.partial(moe_layers.softmax_route, top_k=4, norm_topk=True)
        with jax.default_matmul_precision("highest"):
            uncut, uncut_rows = REF.expert_layer(n, p, whole)
            uncut = uncut + REF.shared_expert(n, p)
            opened = jax.nn.sigmoid(n @ p["shared_expert_gate"])
            total = opened[:, None] * moe_layers.swiglu(n, p["shared_gate"], p["shared_up"], p["shared_down"], jnp.float32)  # once
            rows = []
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

    def test_the_expert_block_a_chunk_of_tokens_at_a_time_is_the_whole_sequences(self, monkeypatch):
        config = small_config(num_hidden_layers=1)
        model = M.GdnMoe(config)
        p = model.init(jax.random.key(3))["layer0"]
        x = jnp.asarray(np.random.default_rng(6).normal(size=(2, 128, 64)), jnp.float32)
        cos, sin = M.rotary_tables(config.seq_len, config.rotary_dim, config.rope_theta)
        whole = model._layer(p, x, cos, sin, True)
        monkeypatch.setattr(M, "EXPERT_TOKENS", 32)
        parts = model._layer(p, x, cos, sin, True)
        _close(parts[0], whole[0], 1e-5)
        counters, whole_counters = parts[1], whole[1]
        whole_rows = whole_counters["expert_rows"]
        np.testing.assert_array_equal(np.asarray(counters["expert_rows"]), np.asarray(whole_rows))
        assert float(counters["held_pairs"]) == float(whole_counters["held_pairs"]) == float(jnp.sum(whole_rows))


class TestWhatTheRematerialisationKeeps:
    """``_layer`` keeps the splash kernel's output and logsumexp
    (``ATTN_RESIDUALS``) across the attention block's rematerialisation, and
    nothing of a Gated DeltaNet block. The kernel engages from 128 positions on."""

    def test_the_forward_kernel_runs_once_in_the_models_gradient(self):
        config = small_config()
        model = M.GdnMoe(config, kernels="interpret")
        params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
        ids = jax.ShapeDtypeStruct((2, config.seq_len), jnp.int32)
        calls = _kernel_calls(jax.make_jaxpr(jax.grad(lambda p, ids: jnp.sum(model.apply(p, ids)["nll_next"])))(params, ids).jaxpr)
        # One attention layer, one kernel call for both sequences and both
        # key/value heads. Under a plain ``jax.checkpoint`` the forward count is 2.
        assert {k: v for k, v in calls.items() if k.startswith("splash")} == {
            "splash_mqa_fwd_residuals": 1, "splash_mqa_dq_no_residuals": 1, "splash_mqa_dkv_no_residuals": 1,
        }

    @pytest.mark.parametrize("linear", [True, False], ids=["gdn_layer", "attention_layer"])
    def test_the_layers_gradient_is_the_bare_blocks(self, linear):
        """``_layer``'s own wrapping (the blocks rematerialised apart, the
        Gated DeltaNet block a sequence at a time and in three parts, the
        expert block a chunk of tokens at a time) against the blocks on the
        whole batch with no ``jax.checkpoint`` and no loop."""
        config = small_config(num_hidden_layers=1, full_attention_interval=4 if linear else 1)
        model = M.GdnMoe(config)
        p = model.init(jax.random.key(2))["layer0"]
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=(2, 128, 64)), jnp.float32)
        target = jnp.asarray(rng.normal(size=(2, 128, 64)), jnp.float32)
        cos, sin = M.rotary_tables(config.seq_len, config.rotary_dim, config.rope_theta)

        def through_layer(p, x):
            return jnp.sum(model._layer(p, x, cos, sin, linear)[0] * target)

        def bare(p, x):
            if linear:
                q, k, v, z, log_decay, beta = model._gdn_inputs(p, x)
                q, k = (jnp.repeat(t, 2, axis=2) for t in (q, k))
                h = model._gdn_output(p, x, M.chunked_delta_rule(q, k, v, log_decay, beta, compute_dtype=jnp.float32), z)
            else:
                h = model._attention_block(p, x, cos, sin)
            return jnp.sum(jnp.stack([model._expert_block(p, h[b])[0] for b in range(2)]) * target)

        with jax.default_matmul_precision("highest"):
            kept = jax.jit(jax.grad(through_layer, argnums=(0, 1)))(p, x)
            plain = jax.jit(jax.grad(bare, argnums=(0, 1)))(p, x)
        for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(kept)[0], jax.tree_util.tree_leaves(plain)):
            assert float(jnp.max(jnp.abs(b))) > 0, path
            _close(a, b, 2e-5)


class TestThroughTheRoundProgram:
    @pytest.mark.parametrize("family", ["qwen3_next", "joyai_llm_flash"])
    def test_two_rounds_of_the_one_causal_task_on_a_one_by_one_mesh(self, family):
        config = small_config() if family == "qwen3_next" else small_mla_config()
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
        assert float(m["held_pairs"][0]) == float(m["expert_rows"].sum()) and 0.0 <= float(m["next_acc"][0]) <= 1.0
        assert float(m["loss"][0]) < float(records[0].metrics["loss"][0])  # it learns the eight sequences
        after = jax.device_get(out)["params"]
        if family == "qwen3_next":
            # The model says what the task reports: no second loss term, the decays.
            assert "mtp_loss" not in m and m["step_loss"].shape == (1, 1, 4) and m["expert_rows"].shape == (1, 4, 2)
            assert float(m["loss"][0]) == float(m["next_loss"][0])
            assert m["gdn_decay_mean"].shape == (1, 3) and np.all((m["gdn_decay_mean"] > 0) & (m["gdn_decay_mean"] < 1))
            for name, leaf in (("layer0", "w_qkvz"), ("layer1", "conv"), ("layer2", "A_log"), ("layer3", "wq"),
                               ("layer3", "q_norm"), ("layer0", "shared_expert_gate"), ("layer2", "gdn_norm")):
                assert not np.array_equal(after[name][leaf], before["params"][name][leaf]), (name, leaf)
        else:
            assert "gdn_decay_mean" not in m and m["expert_rows"].shape == (1, 3, 2)
            assert np.isclose(float(m["loss"][0]), float(m["next_loss"][0]) + 0.3 * float(m["mtp_loss"][0]), rtol=1e-5)

    def test_the_model_says_what_the_task_reads(self):
        ours, joyai = CausalLMTask(small_config()), CausalLMTask(small_mla_config())
        expert = [n for n, _ in moe_layers.EXPERT_COUNTERS]
        assert [n for n, _ in joyai.metric_reductions] == ["next_loss", "mtp_loss", "tokens", "next_hits", *expert]
        assert [n for n, _ in ours.metric_reductions] == ["next_loss", "tokens", "next_hits", *expert, "gdn_decay_mean"]
        assert "gdn_rule" in ours.block_scope and "mla_attn" not in ours.block_scope
        assert "mla_attn" in joyai.block_scope and "gdn_rule" not in joyai.block_scope
        assert ours.step_flops(2) == ours.model.step_flops(2) and joyai.step_flops(1) == joyai.model.step_flops(1)
