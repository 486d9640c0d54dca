"""Flagship-shape mesh pre-flight (VERDICT.md round-1 item 6).

Every other mesh test uses a tiny model config for CI speed; these two compile
and execute the round at the shapes the north star actually names
(configs/c3_eight_client_mesh.json: 8 clients, full-width U-Net, 128/256 px crops), so
per-chip memory layouts and halo geometry are exercised on the 8-device
virtual mesh before real multi-chip hardware ever appears.
"""

import jax
import numpy as np
import pytest

from fedcrack_tpu.configs import ModelConfig
from fedcrack_tpu.data.synthetic import synth_crack_batch
from fedcrack_tpu.parallel import (
    build_federated_round,
    build_spatial_federated_round,
    make_mesh,
    stack_client_data,
)
from fedcrack_tpu.train.local import create_train_state

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs the 8-device virtual mesh"
)


@pytest.mark.slow
def test_full_128px_resunet_round_on_8_device_mesh():
    """One step of the FULL flagship U-Net (default widths, 128x128) as a
    federated round over all 8 devices: 4 clients x 2-way intra-client DP."""
    config = ModelConfig()  # full feature widths, 128x128x3
    mesh = make_mesh(4, 2)
    steps, batch = 1, 2  # per-step batch splits over the batch axis
    per_client = [
        synth_crack_batch(steps * batch, img_size=config.img_size, seed=i)
        for i in range(4)
    ]
    images, masks = stack_client_data(per_client, steps, batch)
    variables = create_train_state(jax.random.key(0), config).variables
    round_fn = build_federated_round(mesh, config, learning_rate=1e-3, local_epochs=1)
    active = np.ones(4, np.float32)
    n_samples = np.full(4, float(steps * batch), np.float32)

    new_variables, metrics = round_fn(variables, images, masks, active, n_samples)
    jax.block_until_ready(new_variables)

    losses = np.asarray(metrics["loss"])
    assert losses.shape == (4,)
    assert np.all(np.isfinite(losses))
    # The round must actually update the global model.
    before = jax.tree_util.tree_leaves(variables["params"])[1]
    after = jax.tree_util.tree_leaves(new_variables["params"])[1]
    assert not np.allclose(np.asarray(before), np.asarray(after))


@pytest.mark.slow
def test_256px_spatial_federated_round_on_8_device_mesh():
    """Config 3's 256 px crop: 4 clients x 2-way spatial sharding (halo
    exchange + sync-BN), full-width U-Net — the composition for crops too
    large for one chip per client."""
    config = ModelConfig(img_size=256)
    mesh = make_mesh(4, 2, axis_names=("clients", "space"))
    steps, batch = 1, 1
    per_client = [
        synth_crack_batch(steps * batch, img_size=256, seed=10 + i) for i in range(4)
    ]
    images, masks = stack_client_data(per_client, steps, batch)
    variables = create_train_state(jax.random.key(1), config).variables
    round_fn = build_spatial_federated_round(
        mesh, config, learning_rate=1e-3, local_epochs=1
    )
    active = np.ones(4, np.float32)
    n_samples = np.full(4, float(steps * batch), np.float32)

    new_variables, metrics = round_fn(variables, images, masks, active, n_samples)
    jax.block_until_ready(new_variables)

    losses = np.asarray(metrics["loss"])
    assert losses.shape == (4,)
    assert np.all(np.isfinite(losses))
    iou = np.asarray(metrics["iou"])
    assert np.all((iou >= 0.0) & (iou <= 1.0))
