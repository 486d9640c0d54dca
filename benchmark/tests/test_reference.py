"""The plain reference against the program, float32 on the CPU at a tiny size."""

import jax
import numpy as np
import pytest

from lib import check, datagen
from lib.federated_rounds import load_reference

MODEL = {
    "img_size": 32, "in_channels": 3, "num_classes": 1, "stem_features": 32,
    "encoder_features": [64, 128, 256], "decoder_features": [256, 128, 64, 32],
}
TRAFFIC = {"base_samples": 8, "foreground": [0.02, 0.14]}


@pytest.fixture(scope="module")
def ref():
    return load_reference({"reference": "resunet"})


@pytest.fixture(scope="module")
def data():
    pool = datagen.client_pool(11, 0, 20, 32, TRAFFIC)
    images, masks = datagen.RoundFeed([pool], 11, steps=5, batch=4)(0)
    return images.copy(), masks.copy()


def test_weights_have_the_programs_structure_and_come_from_the_seed(ref):
    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.models.resunet import init_variables

    ours = ref.make_variables(2**31 + 5, MODEL)
    theirs = jax.eval_shape(lambda k: init_variables(k, ModelConfig(img_size=32)), jax.random.key(0))
    assert jax.tree_util.tree_structure(ours) == jax.tree_util.tree_structure(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(ours), jax.tree_util.tree_leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype
    same = ref.make_variables(2**31 + 5, MODEL)
    other = ref.make_variables(2**31 + 6, MODEL)
    kernel = lambda v: np.asarray(v["params"]["stem_conv"]["kernel"])
    assert np.array_equal(kernel(ours), kernel(same)) and not np.array_equal(kernel(ours), kernel(other))


def _program_round(variables, images, masks, compute_dtype):
    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.parallel import build_federated_round, make_mesh, run_mesh_federation

    mesh = make_mesh(1, 1)
    round_fn = build_federated_round(mesh, ModelConfig(img_size=32, compute_dtype=compute_dtype), learning_rate=1e-3)
    cohort = (np.ones(1, np.float32), np.full(1, 20.0, np.float32))
    out, records = run_mesh_federation(round_fn, variables, lambda r: (images, masks, *cohort), 1, mesh)
    return {
        "variables": jax.device_get(out),
        "loss": records[0].metrics["loss"].tolist(),
        "pixel_acc": records[0].metrics["pixel_acc"].tolist(),
    }


def _reference_round(ref, variables, images, masks, **variant):
    new, means = jax.device_get(ref.client_round(variables, images[0], masks[0], MODEL, 1e-3, **variant))
    return {"variables": new, "loss": [float(means["loss"])], "pixel_acc": [float(means["pixel_acc"])],
            "grad_norms": means["grad_norms"]}


def test_program_in_float32_follows_the_reference(ref, data):
    start = jax.device_get(ref.make_variables(3, MODEL))
    reference = _reference_round(ref, start, *data)
    numbers = check.compare([start], [_program_round(start, *data, "float32")], [reference])
    print(numbers)
    assert numbers["loss_r0"] < 1e-3
    assert numbers["acc_r0"] < 1e-3
    assert numbers["change_r0"] < 0.01
    assert numbers["direction_r0"] < 0.01


def test_null_gradient_leaves_are_found_by_rule(ref, data):
    start = jax.device_get(ref.make_variables(3, MODEL))
    moving = check.moving_leaves(_reference_round(ref, start, *data)["grad_norms"])
    # A convolution's bias in front of a BatchNorm has no gradient; the
    # BatchNorm's own scale and bias do.
    assert "params/dec0_convT1/bias" not in moving
    assert "params/enc0_sep1/pointwise/bias" not in moving
    assert {"params/dec0_bn1/scale", "params/dec0_convT1/kernel", "params/head/bias"} <= moving


def test_control_and_faults_read_above_the_program(ref, data):
    """The control (float8 operands) and the planted faults, put in the
    program's place, lie further from the reference than the bfloat16 program."""
    start = jax.device_get(ref.make_variables(3, MODEL))
    reference = _reference_round(ref, start, *data)
    read = lambda rounds: check.compare([start], [rounds], [reference])
    program = read(_program_round(start, *data, "bfloat16"))
    control = read(_reference_round(ref, start, *data, operands="float8_e4m3fn"))
    half = read(_reference_round(ref, start, *data, fault="half_batch"))
    unchanged = read({"variables": start, "loss": reference["loss"], "pixel_acc": reference["pixel_acc"]})
    assert control["direction_r0"] > 3 * program["direction_r0"]
    assert half["acc_r0"] > 3 * program["acc_r0"] and half["loss_r0"] > 3 * program["loss_r0"]
    assert unchanged["change_r0"] == pytest.approx(1.0) and unchanged["total_change_r0"] == pytest.approx(1.0)


def test_weighted_average_is_fedavg(ref):
    a = {"w": np.ones(3, np.float32)}
    b = {"w": np.full(3, 4.0, np.float32)}
    assert np.allclose(ref.weighted_average([a, b], [1.0, 2.0])["w"], 3.0)
