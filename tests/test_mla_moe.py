"""The latent-attention mixture-of-experts causal model (``models/mla_moe.py``)
against the benchmark's plain reference (``benchmark/reference/joyai_mla_moe.py``)
at a small size: hidden 64, one dense and two sparse layers and the
multi-token-prediction module, 4 heads of 16 + 8 against values of 16, 8
experts top-2 of which 2 are held, vocabulary 64, L 32."""

import collections
import functools
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from fedcrack_tpu.configs import MlaMoeConfig
from fedcrack_tpu.data.textdiff import stage_pair
from fedcrack_tpu.models import get_model, moe_layers
from fedcrack_tpu.models import mla_moe as M
from fedcrack_tpu.parallel import build_federated_round, make_mesh, run_mesh_federation
from fedcrack_tpu.tasks import CausalLMTask, task_for

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_joyai", os.path.join(ROOT, "benchmark", "reference", "joyai_mla_moe.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()
SMALL = dict(
    hidden_size=64, num_hidden_layers=3, num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
    n_routed_experts=8, num_experts_per_tok=2, first_expert=2, experts_held=2, vocab_held=64, seq_len=32,
)


def small_config(**over) -> MlaMoeConfig:
    return MlaMoeConfig(**{**SMALL, "compute_dtype": "float32", **over})


def reference_cfg(config: MlaMoeConfig) -> dict:
    keys = (
        "hidden_size", "num_hidden_layers", "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "rope_theta", "first_k_dense_replace", "intermediate_size",
        "moe_intermediate_size", "n_shared_experts", "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
        "num_nextn_predict_layers", "rms_norm_eps", "first_expert", "experts_held", "vocab_held", "seq_len",
        "mtp_loss_weight",
    )
    return dict({k: getattr(config, k) for k in keys}, router_outputs=config.n_routed_experts)


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


class TestAgainstTheReference:
    def test_params_are_the_references_tree(self):
        config = small_config()
        ours = jax.eval_shape(lambda: M.MlaMoe(config).init(jax.random.key(0)))
        theirs = jax.eval_shape(lambda: REF.init_variables(jnp.zeros((2,), jnp.uint32), reference_cfg(config)))["params"]
        assert jax.tree_util.tree_structure(ours) == jax.tree_util.tree_structure(theirs)
        assert jax.tree_util.tree_leaves(ours) == jax.tree_util.tree_leaves(theirs)
        assert set(ours) == {"embed", "final_norm", "lm_head", "layer0", "layer1", "layer2", "mtp"}
        assert "router" not in ours["layer0"] and ours["layer0"]["w_gate"].shape == (64, 96)  # the dense layer
        assert ours["mtp"]["eh_proj"].shape == (128, 64) and ours["mtp"]["w_gate"].shape == (2, 64, 32)

    # The dense layer alone, a sparse layer alone, both with the module, and
    # the whole small model: every kind of layer against the reference.
    @pytest.mark.parametrize("layers,dense,mtp", [(1, 1, 0), (1, 0, 0), (2, 1, 1), (3, 1, 1)],
                             ids=["dense_layer", "sparse_layer", "dense_sparse_mtp", "whole"])
    def test_logits_loss_and_every_gradient_leaf(self, layers, dense, mtp):
        config = small_config(num_hidden_layers=layers, first_k_dense_replace=dense, num_nextn_predict_layers=mtp)
        cfg = reference_cfg(config)
        params = REF.make_variables(5, cfg)["params"]
        ids, weight = batch(config=config)
        task = CausalLMTask(config)
        with jax.default_matmul_precision("highest"):
            logits, mtp_logits = M.MlaMoe(config).logits(params, ids)
            theirs = [REF.sequence_logits(params, ids[b], cfg) for b in range(2)]
            _close(logits, jnp.stack([t[0] for t in theirs]), 1e-5)
            if mtp:
                # The last position reads a wrapped token and weighs nothing.
                _close(mtp_logits[:, :-1], jnp.stack([t[1] for t in theirs])[:, :-1], 1e-5)
            (ours, stats), grads = jax.value_and_grad(_loss(task, ids, weight), has_aux=True)(params)
            (ref_loss, ref_stats), ref_grads = jax.value_and_grad(
                lambda p: REF.batch_loss(p, ids, weight, cfg), has_aux=True
            )(params)
        assert abs(float(ours) - float(ref_loss)) <= 1e-5 * float(ref_loss)
        for name in ("next_loss", "mtp_loss"):
            assert abs(float(stats[name]) - float(ref_stats[name])) <= 1e-5 * max(float(ref_stats[name]), 1e-6)
        assert bool(mtp) == (float(stats["mtp_loss"]) > 0)
        assert abs(float(ours) - float(stats["next_loss"]) - 0.3 * float(stats["mtp_loss"])) < 1e-5
        assert float(stats["tokens"]) == 2 * 31 == float(ref_stats["tokens"])
        np.testing.assert_array_equal(np.asarray(stats["expert_rows"]), np.asarray(ref_stats["expert_rows"]))
        assert stats["expert_rows"].shape == (layers - dense + mtp, 2)
        assert float(stats["held_pairs"]) == float(np.sum(ref_stats["expert_rows"]))
        flat, _ = jax.tree_util.tree_flatten_with_path(grads)
        ref_flat = jax.tree_util.tree_leaves(ref_grads)
        assert len(flat) == len(ref_flat) == len(jax.tree_util.tree_leaves(params))
        for (path, g), r in zip(flat, ref_flat):
            if "router_bias" in str(path):
                assert float(jnp.max(jnp.abs(g))) == 0.0 == float(jnp.max(jnp.abs(r))), path
                continue
            assert float(jnp.max(jnp.abs(r))) > 0, path
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
        assert isinstance(get_model("joyai_llm_flash", config), M.MlaMoe)
        assert isinstance(task_for(config), CausalLMTask)
        assert config.qk_head_dim == 24 and config.sparse_layers == 3
        with pytest.raises(ValueError, match="not among the router's"):
            small_config(first_expert=7)
        with pytest.raises(ValueError, match="0 or 1"):
            small_config(num_nextn_predict_layers=2)
        # The published widths at the cell's cut: 9.18 TFLOP forward a sequence.
        assert abs(CausalLMTask(MlaMoeConfig()).step_flops(1) / 3e12 - 9.18) < 0.02
        assert abs(CausalLMTask(MlaMoeConfig(num_nextn_predict_layers=0)).step_flops(1) / 3e12 - 7.28) < 0.02


class TestTheShare:
    def test_the_shares_add_up_to_the_uncut_layer(self):
        """Four shares of a 32-expert layer, the shared expert counted once,
        equal the uncut reference's layer."""
        config = small_config(n_routed_experts=32, num_experts_per_tok=4, first_expert=0, experts_held=8)
        whole = reference_cfg(small_config(n_routed_experts=32, num_experts_per_tok=4, first_expert=0, experts_held=32))
        p = REF.make_variables(9, dict(whole, num_hidden_layers=1, first_k_dense_replace=0, num_nextn_predict_layers=0))["params"]["layer0"]
        rng = np.random.default_rng(3)
        n = jnp.asarray(rng.normal(size=(64, config.hidden_size)), jnp.float32)
        route = functools.partial(M.sigmoid_route, bias=p["router_bias"], top_k=4, norm_topk=True, scale=2.5)
        with jax.default_matmul_precision("highest"):
            uncut, uncut_rows = REF.expert_layer(n, p, whole)
            uncut = uncut + REF.shared_expert(n, p)
            total = moe_layers.swiglu(n, p["shared_gate"], p["shared_up"], p["shared_down"], jnp.float32)  # once
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

    def test_selection_uses_s_plus_b_and_weights_use_s(self):
        rng = np.random.default_rng(1)
        n = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
        router = jnp.asarray(rng.normal(size=(8, 6)), jnp.float32)
        # A bias that lifts expert 5 over everything: chosen by every token.
        bias = jnp.asarray([0.0, 0.0, 0.0, 0.0, 0.0, 10.0], jnp.float32)
        s = np.asarray(jax.nn.sigmoid(jnp.dot(n, router, precision=jax.lax.Precision.HIGHEST)))
        top_e, top_w = M.sigmoid_route(n, router, bias=bias, top_k=2, norm_topk=True, scale=2.5)
        top_e, top_w = np.asarray(top_e), np.asarray(top_w)
        assert np.all(top_e[:, 0] == 5)
        other = np.argmax(s[:, :5], axis=1)
        np.testing.assert_array_equal(top_e[:, 1], other)
        rows = np.arange(16)
        chosen = np.stack([s[rows, 5], s[rows, other]], axis=1)
        # The weights are s without b, over their sum, times the scale.
        np.testing.assert_allclose(top_w, 2.5 * chosen / chosen.sum(axis=1, keepdims=True), rtol=1e-6)
        np.testing.assert_allclose(top_w.sum(axis=1), 2.5, rtol=1e-6)
        plain_e, _ = M.sigmoid_route(n, router, bias=jnp.zeros(6), top_k=2, norm_topk=True, scale=2.5)
        assert not np.array_equal(np.asarray(plain_e), top_e)
        unnormed = M.sigmoid_route(n, router, bias=bias, top_k=2, norm_topk=False, scale=1.0)[1]
        np.testing.assert_allclose(np.asarray(unnormed), chosen, rtol=1e-6)
        # No gradient reaches b.
        g = jax.grad(lambda b: jnp.sum(M.sigmoid_route(n, router, bias=b, top_k=2, norm_topk=True, scale=2.5)[1]))(bias)
        assert float(jnp.max(jnp.abs(g))) == 0.0


class TestRotaryAndAttention:
    def test_rotary_touches_the_last_lanes_only_and_pairs_adjacent_ones(self):
        config = small_config()
        cos, sin = M.rotary_tables(config.seq_len, 8, config.rope_theta)
        assert cos.shape == (32, 4)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(32, 2, 8)), jnp.float32)
        y = np.asarray(M.apply_rotary_pairs(x, cos, sin))
        np.testing.assert_array_equal(y[0], np.asarray(x[0]))  # position 0: no turn
        for i in range(4):
            a, b = np.asarray(x[5, 1, 2 * i]), np.asarray(x[5, 1, 2 * i + 1])
            angle = 5.0 * config.rope_theta ** (-2.0 * i / 8)
            np.testing.assert_allclose(y[5, 1, 2 * i], a * np.cos(angle) - b * np.sin(angle), rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(y[5, 1, 2 * i + 1], b * np.cos(angle) + a * np.sin(angle), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.linalg.norm(y, axis=-1), np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
        np.testing.assert_allclose(y, np.asarray(REF.rotary_pairs(x, config.rope_theta)), rtol=1e-5, atol=1e-6)
        # In the layer: a score between positions depends on the position only
        # through the last 8 of a head's 24 lanes. With W_qb and W_kva zeroed
        # there the attention block forgets the order of the earlier tokens.
        model = M.MlaMoe(small_config(num_hidden_layers=1, first_k_dense_replace=1, num_nextn_predict_layers=0))
        p = model.init(jax.random.key(1))["layer0"]
        wq_b = p["wq_b"].reshape(48, 4, 24).at[:, :, 16:].set(0.0).reshape(48, 96)
        p = dict(p, wq_b=wq_b, wkv_a=p["wkv_a"].at[:, 32:].set(0.0))
        h = jnp.asarray(np.random.default_rng(2).normal(size=(32, 64)), jnp.float32)
        order = np.concatenate([np.random.default_rng(3).permutation(31), [31]])
        last = model._attention_block(p, h, cos, sin)[-1]
        np.testing.assert_allclose(np.asarray(model._attention_block(p, h[order], cos, sin)[-1]), np.asarray(last), rtol=1e-4, atol=1e-5)

    def test_kernel_attention_in_the_interpreter_equals_the_dense_path(self):
        """Queries and keys 192 wide, values 128 wide, the causal mask."""
        rng = np.random.default_rng(5)
        q = jnp.asarray(rng.normal(size=(2, 256, 192)) * 0.1, jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, 256, 192)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, 256, 128)), jnp.float32)
        dense = M.causal_attention(q, k, v, kernels="xla")
        kernel = M.causal_attention(q, k, v, kernels="interpret")
        assert kernel.shape == (2, 256, 128)
        np.testing.assert_allclose(np.asarray(kernel), np.asarray(dense), rtol=2e-2, atol=2e-3)
        # The first query sees its own key only.
        np.testing.assert_allclose(np.asarray(dense[:, 0]), np.asarray(v[:, 0]), rtol=1e-5)


def _kernel_calls(jaxpr, counts=None) -> collections.Counter:
    """How often each Pallas kernel is called in ``jaxpr``, by the kernel's
    name, through every nested jaxpr (rematerialised blocks, scans, calls)."""
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            counts[eqn.params["name"]] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(sub, counts)
    return counts


class TestWhatTheRematerialisationKeeps:
    """``_layer`` keeps the splash kernel's output and logsumexp
    (``ATTN_RESIDUALS``) across the attention block's rematerialisation. The
    kernel engages from 128 positions on, so these run at L 128."""

    def test_the_forward_kernel_runs_once_a_block_in_the_models_gradient(self):
        # A dense layer, a sparse layer and the module: three attention blocks.
        config = small_config(num_hidden_layers=2, seq_len=128)
        model = M.MlaMoe(config, kernels="interpret")
        params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
        ids = jax.ShapeDtypeStruct((1, config.seq_len), jnp.int32)

        def loss(p, ids):
            out = model.apply(p, ids)
            return jnp.sum(out["nll_next"]) + jnp.sum(out["nll_mtp"])

        calls = _kernel_calls(jax.make_jaxpr(jax.grad(loss))(params, ids).jaxpr)
        # Under a plain ``jax.checkpoint`` the forward count is 6.
        assert calls == {"splash_mha_fwd_residuals": 3, "splash_mha_dq_no_residuals": 3,
                         "splash_mha_dkv_no_residuals": 3}

    @pytest.mark.parametrize("kernels", ["interpret", "xla"])
    def test_the_layers_gradient_is_the_bare_blocks(self, kernels):
        """``_layer``'s own wrapping against the two blocks with no
        ``jax.checkpoint`` at all: every parameter's gradient and the
        input's, to the last bit."""
        config = small_config(num_hidden_layers=1, first_k_dense_replace=1, num_nextn_predict_layers=0, seq_len=128)
        model = M.MlaMoe(config, kernels=kernels)
        p = model.init(jax.random.key(2))["layer0"]
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=(1, 128, 64)), jnp.float32)
        target = jnp.asarray(rng.normal(size=(1, 128, 64)), jnp.float32)
        cos, sin = M.rotary_tables(config.seq_len, config.qk_rope_head_dim, config.rope_theta)

        def through_layer(p, x):
            return jnp.sum(model._layer(p, x, cos, sin, sparse=False)[0] * target)

        def bare(p, x):
            return jnp.sum(model._dense_block(p, model._attention_block(p, x[0], cos, sin))[None] * target)

        kept = jax.jit(jax.grad(through_layer, argnums=(0, 1)))(p, x)
        plain = jax.jit(jax.grad(bare, argnums=(0, 1)))(p, x)
        for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(kept)[0], jax.tree_util.tree_leaves(plain)):
            assert float(jnp.max(jnp.abs(b))) > 0, path
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(path))
        calls = _kernel_calls(jax.make_jaxpr(jax.grad(through_layer, argnums=(0, 1)))(p, x).jaxpr)
        # One forward kernel for the one block; the dense path has no kernel
        # and names nothing, so there the block is rematerialised whole.
        assert calls == ({"splash_mha_fwd_residuals": 1, "splash_mha_dq_no_residuals": 1,
                          "splash_mha_dkv_no_residuals": 1} if kernels == "interpret" else {})


class TestThroughTheRoundProgram:
    def test_two_rounds_on_a_one_by_one_mesh(self):
        config = small_config()
        mesh = make_mesh(1, 1)
        round_fn = build_federated_round(mesh, config, learning_rate=1e-3, local_epochs=1)
        assert isinstance(round_fn.task, CausalLMTask)
        variables = round_fn.task.init(jax.random.key(0))
        before = jax.device_get(variables)
        rng = np.random.default_rng(0)
        sequences = rng.integers(0, 64, (1, 8, 32), dtype=np.int32)

        def data_fn(r):
            ids, weight = stage_pair(sequences, 4, 2, None, rng)
            assert weight.dtype == np.float32 and np.all(weight == 1.0)
            return ids, weight, np.ones(1, np.float32), np.full(1, 8.0, np.float32)

        out, records = run_mesh_federation(round_fn, variables, data_fn, 2, mesh)
        assert len(records) == 2
        m = records[-1].metrics
        assert m["step_loss"].shape == (1, 1, 4) and m["expert_rows"].shape == (1, 3, 2)
        assert float(m["tokens"][0]) == 4 * 2 * 31 and float(m["held_pairs"][0]) == float(m["expert_rows"].sum())
        assert np.isclose(float(m["loss"][0]), float(m["next_loss"][0]) + 0.3 * float(m["mtp_loss"][0]), rtol=1e-5)
        assert float(m["loss"][0]) < float(records[0].metrics["loss"][0])  # it learns the eight sequences
        after = jax.device_get(out)["params"]
        # b takes no gradient and does not move; everything else does.
        for name in ("layer1", "layer2", "mtp"):
            np.testing.assert_array_equal(after[name]["router_bias"], before["params"][name]["router_bias"])
            assert not np.array_equal(after[name]["router"], before["params"][name]["router"])
        assert not np.array_equal(after["layer0"]["w_gate"], before["params"]["layer0"]["w_gate"])
        assert not np.array_equal(after["mtp"]["eh_proj"], before["params"]["mtp"]["eh_proj"])


# ---- the kernels at the cell's widths, compiled for a described v5e ---------


def test_the_layer_compiles_for_the_chip_at_the_published_widths():
    """One sparse layer, forward and backward, one sequence of 8,192 tokens
    at hidden 2048, 32 heads of 192 against 128, 8 held experts of width 768:
    what the chip's compiler refuses (a 192-wide head, tiling, fast memory)
    shows here at no chip time."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one_chip = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        config = MlaMoeConfig(num_hidden_layers=1, first_k_dense_replace=0, num_nextn_predict_layers=0)
        model = M.MlaMoe(config, kernels="pallas")
        shapes = jax.eval_shape(lambda: model.init(jax.random.key(0)))["layer0"]
        spec = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
        p = jax.tree_util.tree_map(spec, shapes)
        x = jax.ShapeDtypeStruct((1, config.seq_len, config.hidden_size), jnp.bfloat16, sharding=one_chip)
        cos, sin = M.rotary_tables(config.seq_len, config.qk_rope_head_dim, config.rope_theta)

        def loss(p, x):
            y, _ = model._layer(p, x, cos, sin, sparse=True)
            return jnp.sum(y.astype(jnp.float32))

        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, x).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    # Attention forward and its two backward kernels; three grouped products
    # forward and six backward.
    assert text.count("tpu_custom_call") >= 3 + 9
    # The forward kernel stands in the compiled program once for the one
    # layer: its output and logsumexp are kept (``ATTN_RESIDUALS``), not
    # computed again for the backward kernels.
    kernels = collections.Counter(re.findall(r"^\s*%?(splash_mha_[a-z]+)[\w.]* = ", text, re.MULTILINE))
    assert kernels == {"splash_mha_fwd": 1, "splash_mha_dq": 1, "splash_mha_dkv": 1}, kernels
