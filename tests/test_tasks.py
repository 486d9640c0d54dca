"""The task seam of the round program (``fedcrack_tpu/tasks.py``): both
families through the same builders, driver and host step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedcrack_tpu.configs import ModelConfig
from fedcrack_tpu.data.textdiff import stage_pair
from fedcrack_tpu.models import FAMILIES
from fedcrack_tpu.models.moe_layers import EXPERT_COUNTERS
from fedcrack_tpu.parallel import (
    build_federated_cohort_round,
    build_federated_round,
    build_federated_round_segments,
    make_mesh,
    run_mesh_federation,
)
from fedcrack_tpu.tasks import SegmentationTask, TextDiffusionTask, task_for
from fedcrack_tpu.train.local import create_train_state, train_step

from test_gdn_moe import small_config as small_gdn_config
from test_lfm2_moe import small_config as small_lfm2_config
from test_mla_moe import small_config as small_mla_config
from test_sdar_moe import REF, reference_cfg, small_config

LR = 1e-3


def text_round_data(config, clients=1, steps=3, batch=2, seed=0):
    rng = np.random.default_rng(seed)
    sequences = rng.integers(0, config.vocab_held - 1, (clients, steps * batch, config.seq_len)).astype(np.int32)
    return stage_pair(sequences, steps, batch, config.block_length, rng)


def _max_gap(a, b):
    return max(
        float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    )


def test_the_task_follows_from_the_family_alone():
    assert isinstance(task_for(ModelConfig()), SegmentationTask)
    assert task_for(ModelConfig(), bn_axis_name="batch").bn_axis_name == "batch"
    assert isinstance(task_for(small_config()), TextDiffusionTask)
    with pytest.raises(TypeError, match="no task"):
        task_for({"img_size": 32})
    for task in (task_for(ModelConfig()), task_for(small_config())):
        assert hash(task) == hash(type(task)(task.config))  # a static argument of jit
        assert task.step_flops(2) > 0
        assert all(how in ("mean", "sum") for _, how in task.metric_reductions)


@pytest.mark.parametrize(
    "family,config",
    [("sdar_moe", small_config), ("joyai_llm_flash", small_mla_config), ("qwen3_next", small_gdn_config),
     ("lfm2_moe", small_lfm2_config)],
)
def test_every_expert_model_reports_the_layers_counters_and_its_task_reduces_them(family, config):
    """The held-expert layer's record reaches the task whole: the model's
    ``apply`` returns every counter of ``EXPERT_COUNTERS`` (``expert_rows``
    a row per expert layer, the others one number), its task lists each with
    the layer's reduction, and a step's statistics carry each."""
    config = config()
    task = task_for(config)
    assert type(task.model) is FAMILIES[family][1]
    reductions = dict(task.metric_reductions)
    assert all(reductions[name] == how for name, how in EXPERT_COUNTERS)
    params = jax.eval_shape(lambda: task.init(jax.random.key(0)))["params"]
    ids = jax.ShapeDtypeStruct((2, config.seq_len), jnp.int32)
    weight = jax.ShapeDtypeStruct((2, config.seq_len), jnp.float32)

    def step(params, ids, weight):
        inputs, targets = task.unpack((ids, weight))
        outputs, _ = task.apply(params, {}, inputs)
        return outputs, task.loss_and_metrics(outputs, targets)

    outputs, stats = jax.eval_shape(step, params, ids, weight)
    assert set(stats) == {"loss", *reductions}
    rows = outputs["expert_rows"]
    assert rows.ndim == 2 and rows.shape[0] >= 1 and rows.shape[1] == config.experts_held
    for name, _ in EXPERT_COUNTERS:
        expected = rows.shape if name == "expert_rows" else ()
        assert outputs[name].shape == stats[name].shape == expected and outputs[name].dtype == jnp.float32, name


def test_step_flops_of_the_text_task_at_the_published_widths():
    from fedcrack_tpu.configs import SdarMoeConfig

    assert abs(TextDiffusionTask(SdarMoeConfig()).step_flops(2) / 1e12 - 17.9) < 0.1


def test_unet_round_is_the_same_program_through_the_seam():
    """Found by family or handed over built: one lowered program, and the
    metrics a round reports keep their names."""
    mesh = make_mesh(1, 1, jax.devices()[:1])
    config = ModelConfig(img_size=32)
    from fedcrack_tpu.parallel import fedavg_mesh as fm
    from jax.sharding import PartitionSpec as P

    variables = SegmentationTask(config).init(jax.random.key(0))
    images = np.zeros((1, 2, 2, 32, 32, 3), np.uint8)
    masks = np.zeros((1, 2, 2, 32, 32, 1), np.uint8)
    ones = np.ones(1, np.float32)
    by_family = build_federated_round(mesh, config)
    handed = fm._build_round(
        mesh, SegmentationTask(config, bn_axis_name="batch"), 1e-3, 1, 0.0,
        inner_axis="batch", image_spec=P("clients", None, "batch"),
    )
    out_a, metrics_a = by_family(variables, images, masks, ones, ones)
    out_b, metrics_b = handed(variables, images, masks, ones, ones)
    assert _max_gap(out_a, out_b) == 0.0
    assert set(metrics_a) == {"loss", "pixel_acc", "iou", "active", "step_loss"} == set(metrics_b)
    assert by_family.task == handed.task


@pytest.fixture(scope="module")
def text_case():
    config = small_config()
    cfg = reference_cfg(config)
    variables = jax.device_get(REF.make_variables(21, cfg))
    ids, weight = text_round_data(config)
    return config, cfg, variables, ids, weight


def test_text_round_through_build_federated_round_equals_the_references_round(text_case):
    config, cfg, variables, ids, weight = text_case
    mesh = make_mesh(1, 1, jax.devices()[:1])
    round_fn = build_federated_round(mesh, config, learning_rate=LR)
    with jax.default_matmul_precision("highest"):
        new, metrics = round_fn(variables, ids, weight, np.ones(1, np.float32), np.full(1, 6.0, np.float32))
        ref_vars, ref = REF.client_round(variables, ids[0], weight[0], cfg, LR)
    assert set(metrics) == {
        "loss", "masked_tokens", "masked_acc", *(name for name, _ in EXPERT_COUNTERS), "active", "step_loss",
    }
    np.testing.assert_allclose(np.asarray(metrics["step_loss"])[0, 0], np.asarray(ref["step_loss"]), rtol=2e-5)
    assert float(metrics["masked_tokens"][0]) == float(ref["masked_tokens"])
    np.testing.assert_array_equal(np.asarray(metrics["expert_rows"])[0], np.asarray(ref["expert_rows"]))
    assert np.asarray(metrics["expert_rows"]).shape == (1, config.num_hidden_layers, config.experts_held)
    assert float(metrics["held_pairs"][0]) == float(np.sum(ref["expert_rows"]))
    # Adam moves every weight by about the learning rate a step: the round's
    # change is 3e-3 and the two agree to a hundredth of it.
    moved = _max_gap(ref_vars["params"], variables["params"])
    assert moved > LR and _max_gap(new["params"], ref_vars["params"]) < 0.02 * moved
    assert new["batch_stats"] == {}


def test_text_round_splits_a_batch_over_the_inner_axis(text_case):
    """(clients 1, batch 2) trains like (1, 1): without varying-axes tracking
    the step sums the shards' gradients itself."""
    config, cfg, variables, ids, weight = text_case
    ones, n = np.ones(1, np.float32), np.full(1, 6.0, np.float32)
    with jax.default_matmul_precision("highest"):
        whole, m1 = build_federated_round(make_mesh(1, 1, jax.devices()[:1]), config, learning_rate=LR)(variables, ids, weight, ones, n)
        split, m2 = build_federated_round(make_mesh(1, 2, jax.devices()[:2]), config, learning_rate=LR)(variables, ids, weight, ones, n)
    np.testing.assert_allclose(np.asarray(m2["step_loss"]), np.asarray(m1["step_loss"]), rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(m2["expert_rows"]), np.asarray(m1["expert_rows"]))
    moved = _max_gap(whole["params"], variables["params"])
    assert _max_gap(split["params"], whole["params"]) < 0.02 * moved


def test_two_text_clients_fold_and_the_driver_runs_them(text_case):
    config, cfg, variables, _, _ = text_case
    ids, weight = text_round_data(config, clients=2, seed=4)
    mesh = make_mesh(2, 1, jax.devices()[:2])
    round_fn = build_federated_round(mesh, config, learning_rate=LR)
    n = np.full(2, 6.0, np.float32)
    with jax.default_matmul_precision("highest"):
        final, records = run_mesh_federation(
            round_fn, variables, lambda r: (ids, weight, np.ones(2, np.float32), n), 2, mesh,
        )
        clients = [REF.client_round(variables, ids[c], weight[c], cfg, LR)[0] for c in range(2)]
    mean = REF.weighted_average(jax.device_get(clients), [6.0, 6.0])
    assert len(records) == 2 and records[0].metrics["expert_rows"].shape == (2, 2, 2)
    # Round 0's fold is the mean of the two clients' fits: follow round 0 alone.
    first, _ = build_federated_round(mesh, config, learning_rate=LR)(variables, ids, weight, np.ones(2, np.float32), n)
    moved = _max_gap(mean["params"], variables["params"])
    assert _max_gap(first["params"], mean["params"]) < 0.02 * moved
    assert _max_gap(final["params"], first["params"]) > 0.0


def test_segmented_and_cohort_builders_take_the_text_task(text_case):
    config, cfg, variables, ids, weight = text_case
    mesh = make_mesh(1, 1, jax.devices()[:1])
    ones, n = np.ones(1, np.float32), np.full(1, 6.0, np.float32)
    mono, m0 = build_federated_round(mesh, config, learning_rate=LR, local_epochs=2)(variables, ids, weight, ones, n)
    seg, m1 = build_federated_round_segments(mesh, config, learning_rate=LR, local_epochs=2, segments=2)(variables, ids, weight, ones, n)
    cohort, m2 = build_federated_cohort_round(mesh, config, learning_rate=LR, local_epochs=2)(variables, ids, weight, ones, n)
    for other, metrics in ((seg, m1), (cohort, m2)):
        assert set(metrics) == set(m0)
        assert _max_gap(other["params"], mono["params"]) < 1e-6
        np.testing.assert_allclose(np.asarray(metrics["step_loss"]), np.asarray(m0["step_loss"]), rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(metrics["expert_rows"]), np.asarray(m0["expert_rows"]))


def test_host_step_takes_the_text_task(text_case):
    config, cfg, variables, ids, weight = text_case
    state = create_train_state(jax.random.key(1), config, learning_rate=LR)
    assert isinstance(state.task, TextDiffusionTask) and state.batch_stats == {}
    state = state.replace_variables(variables)
    with jax.default_matmul_precision("highest"):
        new, metrics = train_step(state, (ids[0, 0], weight[0, 0]), state.params, jnp.float32(0.0))
        ref_loss, _ = REF.batch_loss(variables["params"], jnp.asarray(ids[0, 0]), jnp.asarray(weight[0, 0]), cfg)
    assert abs(float(metrics["loss"]) - float(ref_loss)) <= 1e-5 * float(ref_loss)
    assert int(new.step) == 1 and _max_gap(new.params, state.params) > 0


def test_host_step_of_the_unet_keeps_its_task_and_metrics():
    state = create_train_state(jax.random.key(0), ModelConfig(img_size=32))
    assert isinstance(state.task, SegmentationTask)
    batch = (np.zeros((2, 32, 32, 3), np.uint8), np.zeros((2, 32, 32, 1), np.uint8))
    _, metrics = train_step(state, batch, state.params, jnp.float32(0.0))
    assert {"loss", "pixel_acc", "iou_inter", "iou_union"} <= set(metrics)
