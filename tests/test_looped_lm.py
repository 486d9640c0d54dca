"""The looped causal language model (``models/looped_lm.py``) against the
benchmark's plain reference (``benchmark/reference/ouro_looped_lm.py``) at a
small size: hidden 64, two layers of 4 heads of 16 (rotary over the whole
head) run four times a token, SwiGLU width 96, vocabulary 64, L 128 (the
splash kernel's smallest tile, so that the interpreter runs it)."""

import importlib
import importlib.util
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedcrack_tpu.configs import LoopedLmConfig
from fedcrack_tpu.data.textdiff import stage_pair
from fedcrack_tpu.models import get_model
from fedcrack_tpu.models import looped_lm as M
from fedcrack_tpu.parallel import build_federated_round, make_mesh, run_mesh_federation
from fedcrack_tpu.tasks import CausalLMTask, task_for

from test_mla_moe import _kernel_calls

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "ouro_round_l8192_b1_1chip"


def _reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_ouro", os.path.join(BENCH, "reference", "ouro_looped_lm.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()
SMALL = dict(
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    intermediate_size=96, vocab_size=64, total_ut_steps=4, seq_len=128,
)
PUBLISHED = (
    "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim", "intermediate_size",
    "rms_norm_eps", "rope_theta", "vocab_size", "total_ut_steps", "exit_entropy_beta", "seq_len",
)
FAULTS = ("one_loop", "last_exit_only", "no_entropy", "no_post_norm", "norm_not_carried")


def small_config(**over) -> LoopedLmConfig:
    return LoopedLmConfig(**{**SMALL, "compute_dtype": "float32", **over})


def reference_cfg(config: LoopedLmConfig) -> dict:
    return {k: getattr(config, k) for k in PUBLISHED}


def batch(seed=0, n=2, config=None):
    config = config or small_config()
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, config.vocab_size, (n, config.seq_len)).astype(np.int32)
    return jnp.asarray(ids), jnp.ones(ids.shape, jnp.float32)


def _close(a, b, tol):
    scale = float(jnp.max(jnp.abs(b))) + 1e-12
    assert float(jnp.max(jnp.abs(a - b))) <= tol * scale


def _exit_logits(model, params, ids):
    """Float32 logits ``[T, B, L, vocab_size]`` of every exit, whole, from the
    states the passes hand on, and the exit distribution ``p`` ``[T, B, L]``."""
    cd = jnp.dtype(model.config.compute_dtype)
    gates, _, _, handed_on = model.passes(params, ids)
    head = params["lm_head"].astype(cd)
    logits = jnp.stack([jnp.dot(h, head, preferred_element_type=jnp.float32) for h in handed_on])
    return logits, jnp.exp(M.exit_log_distribution(gates))


def _loss(task, ids, weight):
    def loss(p):
        inputs, targets = task.unpack((ids, weight))
        outputs, _ = task.apply(p, {}, inputs)
        m = task.loss_and_metrics(outputs, targets)
        return m["loss"], m
    return loss


@pytest.fixture(scope="module")
def kind():
    """The benchmark's driver of the looped cell, for its ``compare`` and the
    cell's limits (``benchmark/`` on the path while it is imported)."""
    sys.path.insert(0, BENCH)
    try:
        module = importlib.import_module("lib.federated_looped_lm_rounds")
    finally:
        sys.path.remove(BENCH)
    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        return module, json.load(f)


class TestTheExitDistribution:
    def test_the_log_form_is_the_products_and_sums_to_one(self):
        gates = jnp.asarray(np.random.default_rng(0).normal(0.0, 3.0, (4, 5, 7)), jnp.float32)
        p = jnp.exp(M.exit_log_distribution(gates))
        # The product form loses digits in ``1 - lambda`` where lambda is near
        # 1 (gates of 3 standard deviations): float32 agreement to 1e-4 of
        # each entry, where the log form keeps them.
        np.testing.assert_allclose(np.asarray(p), np.asarray(REF.exit_distribution(gates)), rtol=1e-4, atol=1e-7)
        np.testing.assert_allclose(np.asarray(jnp.sum(p, axis=0)), 1.0, rtol=1e-6)
        lam = jax.nn.sigmoid(gates)
        np.testing.assert_allclose(np.asarray(p[1]), np.asarray(lam[1] * (1 - lam[0])), rtol=1e-4)
        np.testing.assert_allclose(np.asarray(p[3]), np.asarray((1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])), rtol=1e-4)
        # One pass: one exit, which takes everything; the last gate weighs nothing.
        assert float(jnp.exp(M.exit_log_distribution(gates[:1]))[0, 0, 0]) == 1.0
        moved = gates.at[-1].add(5.0)
        np.testing.assert_array_equal(np.asarray(M.exit_log_distribution(moved)), np.asarray(M.exit_log_distribution(gates)))


class TestAgainstTheReference:
    def test_params_are_the_references_tree(self):
        config = small_config()
        ours = jax.eval_shape(lambda: M.LoopedLm(config).init(jax.random.key(0)))
        theirs = jax.eval_shape(lambda: REF.init_variables(jnp.zeros((2,), jnp.uint32), reference_cfg(config)))["params"]
        assert jax.tree_util.tree_structure(ours) == jax.tree_util.tree_structure(theirs)
        assert jax.tree_util.tree_leaves(ours) == jax.tree_util.tree_leaves(theirs)
        assert set(ours) == {"embed", "final_norm", "exit_gate", "exit_gate_bias", "lm_head", "layer0", "layer1"}
        assert ours["exit_gate_bias"].shape == () and ours["layer1"]["w_down"].shape == (96, 64)

    # The dense path and the splash kernel in the interpreter: every exit's
    # logits, the exit distribution, the loss and its statistics, and every
    # leaf's gradient. Float32 on both sides, the reference's products at
    # "highest": the gaps are float32 sums taken in another order, a few
    # parts in a million of the largest entry (3e-5 leaves room for the
    # gradients, which add four passes' parts and 254 positions' terms).
    @pytest.mark.parametrize("kernels", ["xla", "interpret"])
    def test_every_exit_the_distribution_the_loss_and_every_gradient_leaf(self, kernels):
        config = small_config()
        cfg = reference_cfg(config)
        params = REF.make_variables(5, cfg)["params"]
        ids, weight = batch()
        task = CausalLMTask(config, kernels=kernels)
        with jax.default_matmul_precision("highest"):
            logits, p = _exit_logits(M.LoopedLm(config, kernels=kernels), params, ids)
            exits = [REF.sequence_exits(params, ids[b], cfg) for b in range(2)]
            ref_logits = jnp.stack([jnp.einsum("tsh,hv->tsv", hs, params["lm_head"]) for _, hs in exits], axis=1)
            ref_p = jnp.stack([REF.exit_distribution(gates) for gates, _ in exits], axis=1)
            (ours, stats), grads = jax.value_and_grad(_loss(task, ids, weight), has_aux=True)(params)
            (ref_loss, ref_stats), ref_grads = jax.value_and_grad(
                lambda p: REF.batch_loss(p, ids, weight, cfg), has_aux=True
            )(params)
        assert logits.shape == (4, 2, 128, 64) and p.shape == (4, 2, 128)
        _close(logits, ref_logits, 1e-5)
        _close(p, ref_p, 1e-5)
        assert abs(float(ours) - float(ref_loss)) <= 1e-5 * float(ref_loss)
        assert float(stats["tokens"]) == 2 * 127 == float(ref_stats["tokens"]) and "mtp_loss" not in stats
        for name in ("next_loss", "exit_mass", "loop_nll", "exit_entropy"):
            np.testing.assert_allclose(np.asarray(stats[name]), np.asarray(ref_stats[name]), rtol=2e-5, err_msg=name)
        assert stats["exit_mass"].shape == (4,) and abs(float(jnp.sum(stats["exit_mass"])) - 1.0) < 1e-5
        assert float(stats["next_loss"]) == pytest.approx(float(stats["loop_nll"][-1]), rel=1e-6)
        # The objective is the exits' expected loss less beta times the entropy, not the last exit's.
        assert float(ours) != pytest.approx(float(stats["next_loss"]), rel=1e-3)
        flat, _ = jax.tree_util.tree_flatten_with_path(grads)
        ref_flat = jax.tree_util.tree_leaves(ref_grads)
        assert len(flat) == len(ref_flat) == len(jax.tree_util.tree_leaves(params))
        for (path, g), r in zip(flat, ref_flat):
            assert float(jnp.max(jnp.abs(r))) > 0, path
            _close(g, r, 3e-5)

    def test_bf16_compute_stays_near_the_float32_reference(self):
        config = small_config(compute_dtype="bfloat16")
        cfg = reference_cfg(config)
        params = REF.make_variables(6, cfg)["params"]
        ids, weight = batch(1)
        ours, stats = _loss(CausalLMTask(config), ids, weight)(params)
        with jax.default_matmul_precision("highest"):
            theirs, ref_stats = REF.batch_loss(params, ids, weight, cfg)
        assert abs(float(ours) - float(theirs)) <= 0.02 * float(theirs)
        np.testing.assert_allclose(np.asarray(stats["exit_mass"]), np.asarray(ref_stats["exit_mass"]), atol=0.02)

    def test_a_layers_gradient_is_the_sum_of_its_passes_gradients(self):
        """Untied in the reference (each pass its own copy of the layers), the
        four copies' gradients add up to the tied gradient, the program's and
        the reference's; each pass's part is its own."""
        config = small_config()
        cfg = reference_cfg(config)
        params = REF.make_variables(7, cfg)["params"]
        ids, weight = batch(2)
        with jax.default_matmul_precision("highest"):
            tied = jax.grad(lambda p: REF.batch_loss(p, ids, weight, cfg)[0])(params)
            untied = jax.grad(lambda passes: REF.batch_loss(params, ids, weight, cfg, passes=passes)[0])([params] * 4)
            ours = jax.grad(lambda p: _loss(CausalLMTask(config), ids, weight)(p)[0])(params)
        for i in range(2):
            for leaf in ("wq", "wo", "w_gate", "w_down", "attn_out_norm", "mlp_norm"):
                parts = [untied[t][f"layer{i}"][leaf] for t in range(4)]
                _close(sum(parts), tied[f"layer{i}"][leaf], 1e-5)
                _close(ours[f"layer{i}"][leaf], tied[f"layer{i}"][leaf], 3e-5)
                assert all(float(jnp.max(jnp.abs(part))) > 0 for part in parts), (i, leaf)
                assert float(jnp.max(jnp.abs(parts[0] - parts[3]))) > 1e-3 * float(jnp.max(jnp.abs(parts[0]))), (i, leaf)
        # The untied copies read no embedding, head or gate of their own.
        assert float(jnp.max(jnp.abs(untied[0]["lm_head"]))) == 0.0


class TestTheModel:
    def test_registry_configuration_and_flops(self):
        config = small_config()
        assert isinstance(get_model("ouro", config), M.LoopedLm)
        task = task_for(config)
        assert isinstance(task, CausalLMTask) and isinstance(task.model, M.LoopedLm)
        with pytest.raises(ValueError, match="at least once"):
            small_config(total_ut_steps=0)
        with pytest.raises(ValueError, match="tiles"):
            small_config(seq_len=100)
        with pytest.raises(ValueError, match="tiles"):
            LoopedLmConfig(seq_len=8192 + 128)
        with pytest.raises(ValueError, match="key/value head"):
            small_config(num_key_value_heads=2)
        # The published widths at the cell's cut: 24.46 TFLOP forward a step
        # of one sequence (73.39 a step), 406.88 M parameters.
        assert abs(CausalLMTask(LoopedLmConfig()).step_flops(1) / 1e12 - 73.39) < 0.01
        shapes = jax.eval_shape(lambda: M.LoopedLm(LoopedLmConfig()).init(jax.random.key(0)))
        assert abs(sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes)) / 1e6 - 406.88) < 0.01

    def test_the_model_says_what_the_task_reads(self):
        task = CausalLMTask(small_config())
        assert [n for n, _ in task.metric_reductions] == [
            "next_loss", "tokens", "next_hits", "exit_mass", "loop_nll", "exit_entropy",
        ]
        assert "loop_attn" in task.block_scope and "moe_experts" not in task.block_scope
        assert task.step_flops(2) == task.model.step_flops(2)

    def test_the_forward_kernel_runs_once_an_application_in_the_gradient(self):
        """Every layer application keeps its kernel's output and logsumexp
        (``mla_moe.ATTN_RESIDUALS``) across its rematerialisation: the forward
        kernel runs once for each of the ``T x layers`` applications."""
        config = small_config()
        model = M.LoopedLm(config, kernels="interpret")
        params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
        ids = jax.ShapeDtypeStruct((1, config.seq_len), jnp.int32)
        jaxpr = jax.make_jaxpr(jax.grad(lambda p, ids: jnp.sum(model.apply(p, ids)["objective"])))(params, ids).jaxpr
        calls = _kernel_calls(jaxpr)
        assert {k: v for k, v in calls.items() if k.startswith("splash")} == {
            "splash_mha_fwd_residuals": 8, "splash_mha_dq_no_residuals": 8, "splash_mha_dkv_no_residuals": 8,
        }

    def test_every_application_has_its_own_scope(self):
        config = small_config(total_ut_steps=2)
        model = M.LoopedLm(config)
        params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
        ids = jax.ShapeDtypeStruct((1, config.seq_len), jnp.int32)
        text = jax.jit(lambda p, ids: model.apply(p, ids)["objective"]).lower(params, ids).as_text(debug_info=True)
        # (A transformation's name, ``checkpoint``, may stand between the scopes.)
        for t in range(2):
            for i in range(2):
                for kind in ("loop_attn_proj", "loop_attn", "loop_mlp"):
                    assert re.search(rf"loop{t}/layer{i}/([\w()]+/)*{kind}/", text), (t, i, kind)
            assert re.search(rf"loop{t}/loop_exit/", text), t


class TestThroughTheRoundProgram:
    def _reference_round(self, kind, start, ids, weight, cfg, lr, fault=None):
        """The reference's round in the shape the cell's ``compare`` takes:
        each client's local fit from ``start``, then the average."""
        results = [jax.device_get(REF.client_round(start, ids[c], weight[c], cfg, lr, fault=fault)) for c in range(ids.shape[0])]
        return {
            "variables": REF.weighted_average([r[0] for r in results], [1.0] * len(results)),
            "loss": [float(r[1]["loss"]) for r in results],
            "step_loss": [np.asarray(r[1]["step_loss"]).tolist() for r in results],
            "next_acc": [float(r[1]["next_hits"]) / float(r[1]["tokens"]) for r in results],
            "exit_mass": [np.asarray(r[1]["exit_mass"]).tolist() for r in results],
            "loop_nll": [np.asarray(r[1]["loop_nll"]).tolist() for r in results],
            "grad_norms": jax.tree_util.tree_map(lambda *g: float(np.mean(g)), *[r[1]["grad_norms"] for r in results]),
        }

    def _staged(self, clients=2, steps=2, b=2, seed=0):
        rng = np.random.default_rng(seed)
        sequences = rng.integers(0, SMALL["vocab_size"], (clients, steps * b, SMALL["seq_len"]), dtype=np.int32)
        return stage_pair(sequences, steps, b, None, rng)

    def test_a_round_of_two_clients_against_the_references_round(self, kind):
        fl, limits = kind
        config = small_config()
        cfg = reference_cfg(config)
        mesh = make_mesh(2, 1)
        round_fn = build_federated_round(mesh, config, learning_rate=1e-3, local_epochs=1)
        assert type(round_fn.task) is CausalLMTask and round_fn.task.config == config
        start = jax.device_get(REF.make_variables(3, cfg))
        ids, weight = self._staged()
        out, records = run_mesh_federation(
            round_fn, start, lambda r: (ids, weight, np.ones(2, np.float32), np.full(2, 4.0, np.float32)), 1, mesh,
        )
        m = records[0].metrics
        assert m["exit_mass"].shape == (2, 4) and m["loop_nll"].shape == (2, 4) and m["exit_entropy"].shape == (2,)
        program = {"variables": jax.device_get(out), **{k: np.asarray(m[k]).tolist() for k in fl.PROGRAM_METRICS}}
        reference = self._reference_round(fl, start, np.asarray(ids), np.asarray(weight), cfg, 1e-3)
        numbers = fl.compare([start], [program], [reference])
        # Float32 on both sides: well inside the cell's limits, which are set
        # for bf16 products (every compared number of round 0). Not at zero:
        # Adam divides each gradient by its own root mean square, so a leaf's
        # entry whose gradient is round-off on both sides moves by up to the
        # learning rate either way.
        for name in limits.keys() & numbers.keys():
            assert numbers[name] <= 0.1 * limits[name], (name, numbers[name])

    @pytest.mark.parametrize("fault", FAULTS)
    def test_each_planted_fault_moves_a_compared_number_past_its_limit(self, kind, fault):
        """Each of the reference's faults, put in the program's place, reads
        past at least one of the cell's limits against the sound reference."""
        fl, limits = kind
        cfg = reference_cfg(small_config())
        start = jax.device_get(REF.make_variables(4, cfg))
        ids, weight = (np.asarray(x) for x in self._staged(clients=1, seed=1))
        sound = self._reference_round(fl, start, ids, weight, cfg, 1e-3)
        faulty = self._reference_round(fl, start, ids, weight, cfg, 1e-3, fault=fault)
        numbers = fl.compare([start], [faulty], [sound])
        assert max(numbers[name] / limits[name] for name in limits.keys() & numbers.keys()) > 5.0, numbers
