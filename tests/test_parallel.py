"""Mesh data-plane tests on the virtual 8-device CPU mesh (conftest.py).

The load-bearing guarantee (SURVEY.md §4 "distributed-without-a-cluster"):
the single-program mesh round must produce the SAME global weights as the
host-loop path (per-client jitted train steps + host fedavg) — i.e.
mesh FedAvg == gRPC FedAvg == numpy mean.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedcrack_tpu.configs import ModelConfig
from fedcrack_tpu.fed.algorithms import fedavg
from fedcrack_tpu.data.synthetic import synth_crack_batch
from fedcrack_tpu.parallel import (
    build_federated_round,
    make_mesh,
    mesh_fedavg,
    stack_client_data,
)
from fedcrack_tpu.train.local import create_train_state, train_step
from treecmp import assert_trees_equal, assert_trees_match as _assert_trees_match

TINY = ModelConfig(
    img_size=16, stem_features=4, encoder_features=(8,), decoder_features=(8, 4)
)
STEPS, BATCH = 2, 4


def _client_data(n_clients, seed0=0):
    per_client = [
        synth_crack_batch(STEPS * BATCH, img_size=TINY.img_size, seed=seed0 + i)
        for i in range(n_clients)
    ]
    return stack_client_data(per_client, STEPS, BATCH)


def _host_round(
    variables, images, masks, active, n_samples, lr, epochs=1, pos_weight=1.0
):
    """Reference implementation: sequential jitted steps + host fedavg."""
    trained, weights = [], []
    for c in range(images.shape[0]):
        state = create_train_state(jax.random.key(0), TINY, lr)
        state = state.replace_variables(variables)
        for _ in range(epochs):
            for s in range(images.shape[1]):
                batch = (jnp.asarray(images[c, s]), jnp.asarray(masks[c, s]))
                state, _ = train_step(
                    state,
                    batch,
                    variables["params"],
                    jnp.float32(0.0),
                    jnp.float32(pos_weight),
                )
        if active[c]:
            trained.append(state.variables)
            weights.append(n_samples[c])
    return fedavg(trained, weights)


class TestMeshMatchesHost:
    def test_mesh_round_equals_host_round(self):
        mesh = make_mesh(8, 1)
        images, masks = _client_data(8)
        variables = create_train_state(jax.random.key(7), TINY).variables
        active = np.ones(8, np.float32)
        n_samples = np.array([8, 8, 8, 8, 16, 16, 8, 8], np.float32)

        round_fn = build_federated_round(mesh, TINY, learning_rate=1e-3)
        got, metrics = round_fn(variables, images, masks, active, n_samples)
        want = _host_round(variables, images, masks, active, n_samples, 1e-3)

        _assert_trees_match(got, want)
        assert metrics["loss"].shape == (8,)
        assert np.all(np.isfinite(np.asarray(metrics["loss"])))

    @pytest.mark.slow
    def test_pos_weight_round_equals_host_round(self):
        """Crack-pixel loss weighting must train identically on both planes
        (and actually change the trajectory vs plain BCE).

        Slow-marked (round-12 tier-1 budget re-balance, the r4/r9
        precedent): a second full mesh+host compile whose parity machinery
        is tier-1-pinned at pos_weight=1 by test_mesh_round_equals_host_round
        and whose pos_weight numerics are tier-1-pinned host-side by
        test_train/test_pallas_bce."""
        mesh = make_mesh(4, 1)
        images, masks = _client_data(4)
        variables = create_train_state(jax.random.key(11), TINY).variables
        active = np.ones(4, np.float32)
        n_samples = np.full(4, 8.0, np.float32)

        round_fn = build_federated_round(mesh, TINY, learning_rate=1e-3, pos_weight=5.0)
        got, _ = round_fn(variables, images, masks, active, n_samples)
        want = _host_round(variables, images, masks, active, n_samples, 1e-3, pos_weight=5.0)
        _assert_trees_match(got, want)
        plain = _host_round(variables, images, masks, active, n_samples, 1e-3)
        leaves_w = jax.tree_util.tree_leaves(want["params"])
        leaves_p = jax.tree_util.tree_leaves(plain["params"])
        assert any(not np.allclose(w, p) for w, p in zip(leaves_w, leaves_p))

    def test_masked_cohort_shrinks_divisor(self):
        """Dropped clients (active=0) must not pollute the average and the
        divisor must shrink — no recompilation (SURVEY.md §7)."""
        mesh = make_mesh(8, 1)
        images, masks = _client_data(8)
        variables = create_train_state(jax.random.key(3), TINY).variables
        active = np.array([1, 1, 1, 0, 0, 1, 1, 1], np.float32)
        n_samples = np.full(8, 8.0, np.float32)

        round_fn = build_federated_round(mesh, TINY, learning_rate=1e-3)
        got, _ = round_fn(variables, images, masks, active, n_samples)
        want = _host_round(variables, images, masks, active, n_samples, 1e-3)
        _assert_trees_match(got, want)

    def test_intra_client_batch_dp_matches_host(self):
        """4 clients x 2-way batch DP trains exactly like the single-device
        host path: BN is synced over the `batch` axis and gradients are
        mean (not sum) over the DP shards, so splitting a client's batch
        across chips must not change the result."""
        mesh = make_mesh(4, 2)
        images, masks = _client_data(4)
        variables = create_train_state(jax.random.key(1), TINY).variables
        active = np.ones(4, np.float32)
        n_samples = np.full(4, 8.0, np.float32)
        round_fn = build_federated_round(
            mesh, TINY, learning_rate=1e-3, local_epochs=2
        )
        got, metrics = round_fn(variables, images, masks, active, n_samples)
        want = _host_round(variables, images, masks, active, n_samples, 1e-3, epochs=2)
        # 2 epochs of cross-shard collectives accumulate a little more fp
        # reassociation noise than the batch=1 path. A running mean also
        # follows the BN-shadowed bias before it (treecmp.py): `stem_bn`'s
        # gap is 0.0196 x `stem_conv`'s bias gap in each of its 4 channels
        # (5.5e-5 of 2.8e-3 at most), held to (1 - momentum) x steps = 0.04 x.
        _assert_trees_match(got, want, atol=5e-5, steps=2 * STEPS)
        assert metrics["loss"].shape == (4,)

    def test_dp_gradient_not_double_counted(self, monkeypatch):
        """Regression: `params` is batch-unvarying, so shard_map AD psums the
        grad cotangents over the `batch` axis; the step must divide by the
        shard count. With SGD(1.0) the applied update IS the gradient —
        duplicated batch halves make per-shard data identical, so the
        2-shard update must equal the 1-shard one (a double-count shows up
        as an exact 2x)."""
        import optax

        import fedcrack_tpu.parallel.fedavg_mesh as fm

        monkeypatch.setattr(fm, "make_optimizer", lambda lr: optax.sgd(1.0))
        imgs4, msks4 = synth_crack_batch(4, img_size=TINY.img_size, seed=0)
        images, masks = stack_client_data(
            [(np.concatenate([imgs4, imgs4]), np.concatenate([msks4, msks4]))],
            steps=1,
            batch_size=8,
        )
        variables = create_train_state(jax.random.key(0), TINY).variables
        active = np.ones(1, np.float32)
        n_samples = np.full(1, 8.0, np.float32)

        deltas = {}
        for nb in (1, 2):
            round_fn = fm.build_federated_round(
                make_mesh(1, nb), TINY, learning_rate=1.0, local_epochs=1
            )
            new_vars, _ = round_fn(variables, images, masks, active, n_samples)
            new_vars = jax.device_get(new_vars)
            deltas[nb] = jax.tree_util.tree_map(
                lambda old, new: np.asarray(old) - np.asarray(new),
                jax.device_get(variables)["params"],
                new_vars["params"],
            )
        g1 = jax.tree_util.tree_leaves(deltas[1])
        g2 = jax.tree_util.tree_leaves(deltas[2])
        ratio = sum(float(np.vdot(a, b)) for a, b in zip(g1, g2)) / sum(
            float(np.vdot(a, a)) for a in g1
        )
        assert 0.999 < ratio < 1.001, f"DP gradient scale off: ratio={ratio}"

    def test_all_dropped_cohort_raises(self):
        """active == 0 everywhere must raise, not silently zero the model
        (same contract as fed.algorithms.fedavg)."""
        mesh = make_mesh(8, 1)
        images, masks = _client_data(8)
        variables = create_train_state(jax.random.key(2), TINY).variables
        round_fn = build_federated_round(mesh, TINY)
        with pytest.raises(ValueError, match="non-positive"):
            round_fn(
                variables, images, masks,
                np.zeros(8, np.float32), np.full(8, 8.0, np.float32),
            )
        with pytest.raises(ValueError, match="non-positive"):
            mesh_fedavg({"k": np.ones((3, 2), np.float32)}, active=[0.0, 0.0, 0.0])

    def test_all_dropped_cohort_in_mesh_guard(self, monkeypatch):
        """In a multi-host job the cohort mask is a cross-process sharded
        array no single process can inspect, so the host-side ValueError
        can't fire; the IN-MESH guard must then return the incoming global
        model unchanged — never an all-zero psum average."""
        import fedcrack_tpu.parallel.fedavg_mesh as fm

        monkeypatch.setattr(fm, "_host_view", lambda x: None)
        mesh = make_mesh(8, 1)
        images, masks = _client_data(8)
        variables = create_train_state(jax.random.key(2), TINY).variables
        round_fn = build_federated_round(mesh, TINY)
        new_vars, metrics = round_fn(
            variables, images, masks,
            np.zeros(8, np.float32), np.full(8, 8.0, np.float32),
        )
        for got, want in zip(
            jax.tree_util.tree_leaves(new_vars), jax.tree_util.tree_leaves(variables)
        ):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_fedprox_mu_changes_result(self):
        mesh = make_mesh(8, 1)
        images, masks = _client_data(8)
        variables = create_train_state(jax.random.key(5), TINY).variables
        ones, ns = np.ones(8, np.float32), np.full(8, 8.0, np.float32)
        plain = build_federated_round(mesh, TINY)(variables, images, masks, ones, ns)[0]
        prox = build_federated_round(mesh, TINY, fedprox_mu=10.0)(
            variables, images, masks, ones, ns
        )[0]
        diffs = [
            float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(
                jax.tree_util.tree_leaves(plain["params"]),
                jax.tree_util.tree_leaves(prox["params"]),
            )
        ]
        assert max(diffs) > 1e-7


    def test_remat_round_matches_plain(self):
        """jax.checkpoint recomputes the forward during backward — the
        round's math is unchanged; only the activation-memory/FLOPs schedule
        moves. Guards the HBM lever for crops that don't otherwise fit."""
        mesh = make_mesh(4, 2)
        images, masks = _client_data(4)
        variables = create_train_state(jax.random.key(9), TINY).variables
        ones, ns = np.ones(4, np.float32), np.full(4, 8.0, np.float32)
        plain = build_federated_round(mesh, TINY)
        rematd = build_federated_round(mesh, TINY, remat=True)
        v_plain, m_plain = plain(variables, images, masks, ones, ns)
        v_remat, m_remat = rematd(variables, images, masks, ones, ns)
        np.testing.assert_allclose(
            np.asarray(m_plain["loss"]), np.asarray(m_remat["loss"]), rtol=1e-6
        )
        _assert_trees_match(v_remat["params"], v_plain["params"])

    def test_remat_spatial_round_matches_plain(self):
        """The riskier remat composition: checkpointing the halo-exchange
        spatial forward rematerializes ppermute + sync-BN collectives in
        the backward — this is the path remat exists for (crops too large
        per chip), so its parity is pinned separately."""
        from fedcrack_tpu.parallel import build_spatial_federated_round

        # Per-shard height must be a multiple of 16: 32px / 2 spatial shards.
        tiny32 = ModelConfig(
            img_size=32, stem_features=4, encoder_features=(8,),
            decoder_features=(8, 4),
        )
        per_client = [
            synth_crack_batch(STEPS * BATCH, img_size=32, seed=i) for i in range(4)
        ]
        images, masks = stack_client_data(per_client, STEPS, BATCH)
        mesh = make_mesh(4, 2, axis_names=("clients", "space"))
        variables = create_train_state(jax.random.key(9), tiny32).variables
        ones, ns = np.ones(4, np.float32), np.full(4, 8.0, np.float32)
        plain = build_spatial_federated_round(mesh, tiny32)
        rematd = build_spatial_federated_round(mesh, tiny32, remat=True)
        v_plain, m_plain = plain(variables, images, masks, ones, ns)
        v_remat, m_remat = rematd(variables, images, masks, ones, ns)
        np.testing.assert_allclose(
            np.asarray(m_plain["loss"]), np.asarray(m_remat["loss"]), rtol=1e-6
        )
        _assert_trees_match(v_remat["params"], v_plain["params"])

class TestLayoutTransformedRounds:
    """Round 6: the space-to-depth/channel-packed round programs are the
    SAME federation as the reference layout: the same weights after a whole
    round, bit for bit where the transform keeps the reduction order."""

    def test_s2d_round_weights_match_reference_round(self):
        """A WHOLE mesh round (forward, backward, Adam, FedAvg) under the
        transformed layouts returns the reference round's global weights.

        The stem's 's2d' fold keeps every sum's order, and on this backend
        its round is byte-identical, loss included: held exactly. The
        residual 'packed' layout contracts a zero-extended [1,1,4C,F]
        kernel: the zeros change no sum on paper, but XLA blocks the longer
        contraction differently, and since PRs 27 and 29 the reference layout
        composes and folds kernels too, so bitwise equality across the two
        is a property of a compiler version, not of the fold (ROADMAP D4):
        held at ten times the gap read on the CPU backend or less (treecmp):
        BN-shadowed conv biases 8.0e-4 against lr * steps = 2e-3, running
        means 3.5e-6 against 1e-5, every other leaf 2.5e-7 against 2e-6, the
        round's loss 2.4e-7 against 2e-6. A packed kernel built from the
        wrong phase, or a lost step, moves kernels by lr = 1e-3 a step."""
        mesh = make_mesh(4, 1)
        images, masks = _client_data(4)
        variables = create_train_state(jax.random.key(7), TINY).variables
        active = np.ones(4, np.float32)
        n_samples = np.full(4, 8.0, np.float32)
        lr = 1e-3

        import dataclasses as _dc

        def one_round(**layout):
            cfg = _dc.replace(TINY, **layout)
            fn = build_federated_round(mesh, cfg, learning_rate=lr)
            new_vars, metrics = fn(variables, images, masks, active, n_samples)
            return new_vars, np.asarray(metrics["loss"])

        want, loss_ref = one_round()
        got, loss_s2d = one_round(stem_layout="s2d")
        assert_trees_equal(got, want)
        np.testing.assert_array_equal(loss_s2d, loss_ref)

        got, loss_packed = one_round(stem_layout="s2d", res_layout="packed")
        _assert_trees_match(
            got,
            want,
            atol=2e-6,
            shadowed_bias_atol=lr * STEPS,
            running_mean_atol=1e-5,
        )
        np.testing.assert_allclose(loss_packed, loss_ref, rtol=0, atol=2e-6)

    def test_prepacked_staging_matches_unpacked(self):
        """Host-packed staging ([C,steps,B,H/2,W/2,4ch], the driver's
        transformed-layout staging shape) feeds the same round program
        family and produces the same weights as on-device packing."""
        from fedcrack_tpu.data.pipeline import space_to_depth_images

        mesh = make_mesh(4, 1)
        images, masks = _client_data(4)
        variables = create_train_state(jax.random.key(5), TINY).variables
        active = np.ones(4, np.float32)
        n_samples = np.full(4, 8.0, np.float32)
        import dataclasses as _dc

        s2d_cfg = _dc.replace(TINY, stem_layout="s2d")
        fn = build_federated_round(mesh, s2d_cfg, learning_rate=1e-3)
        got_unpacked, _ = fn(variables, images, masks, active, n_samples)
        got_packed, _ = fn(
            variables, space_to_depth_images(images), masks, active, n_samples
        )
        for g, w in zip(
            jax.tree_util.tree_leaves(got_packed),
            jax.tree_util.tree_leaves(got_unpacked),
        ):
            assert np.array_equal(np.asarray(g), np.asarray(w))

    def test_wrong_channel_staging_rejected(self):
        mesh = make_mesh(4, 1)
        images, masks = _client_data(4)
        variables = create_train_state(jax.random.key(5), TINY).variables
        fn = build_federated_round(mesh, TINY, learning_rate=1e-3)
        bad = np.concatenate([images, images], axis=-1)  # 6 channels
        with pytest.raises(ValueError, match="channels"):
            fn(variables, bad, masks, np.ones(4, np.float32), np.full(4, 8.0, np.float32))

    def test_spatial_round_rejects_transformed_layouts(self):
        import dataclasses as _dc

        from fedcrack_tpu.parallel import build_spatial_federated_round

        mesh = make_mesh(4, 2, axis_names=("clients", "space"))
        with pytest.raises(ValueError, match="reference layout"):
            build_spatial_federated_round(
                mesh, _dc.replace(TINY, stem_layout="s2d")
            )


class TestMeshFedavgGolden:
    def test_matches_numpy_mean(self):
        rng = np.random.default_rng(0)
        stacked = {
            "w": rng.normal(size=(4, 3, 3)).astype(np.float32),
            "b": rng.normal(size=(4, 5)).astype(np.float32),
        }
        got = mesh_fedavg(stacked)
        np.testing.assert_allclose(np.asarray(got["w"]), stacked["w"].mean(0), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(got["b"]), stacked["b"].mean(0), rtol=1e-6)

    def test_matches_host_fedavg_weighted(self):
        rng = np.random.default_rng(1)
        trees = [
            {"k": rng.normal(size=(2, 2)).astype(np.float32)} for _ in range(3)
        ]
        w = [1.0, 2.0, 5.0]
        stacked = {"k": np.stack([t["k"] for t in trees])}
        got = mesh_fedavg(stacked, weights=w)
        want = fedavg(trees, weights=w)
        np.testing.assert_allclose(np.asarray(got["k"]), np.asarray(want["k"]), rtol=1e-6)

    def test_active_mask(self):
        stacked = {"k": np.stack([np.full((2,), v, np.float32) for v in (1, 2, 9)])}
        got = mesh_fedavg(stacked, active=[1.0, 1.0, 0.0])
        np.testing.assert_allclose(np.asarray(got["k"]), np.full((2,), 1.5), rtol=1e-6)


class TestStackClientData:
    def test_shapes_and_cycling(self):
        imgs, msks = synth_crack_batch(5, img_size=16, seed=0)
        si, sm = stack_client_data([(imgs, msks)], steps=2, batch_size=4)
        assert si.shape == (1, 2, 4, 16, 16, 3)
        assert sm.shape == (1, 2, 4, 16, 16, 1)
        np.testing.assert_array_equal(si[0, 1, 1], imgs[0])  # sample 5 cycles to 0


class TestSpatialFederatedRound:
    def test_clients_by_space_matches_host(self):
        """4 clients x 2-way spatial sharding trains exactly like the
        single-device host path: halo-exchange conv + sync-BN over the
        space axis, mean gradients, FedAvg over clients."""
        from fedcrack_tpu.parallel import build_spatial_federated_round
        from fedcrack_tpu.parallel.mesh import make_mesh as mm

        # Per-shard height must be a multiple of 16: 32px / 2 spatial shards.
        tiny32 = ModelConfig(
            img_size=32, stem_features=4, encoder_features=(8,),
            decoder_features=(8, 4),
        )
        per_client = [
            synth_crack_batch(STEPS * BATCH, img_size=32, seed=i) for i in range(4)
        ]
        images, masks = stack_client_data(per_client, STEPS, BATCH)
        variables = create_train_state(jax.random.key(2), tiny32).variables
        active = np.ones(4, np.float32)
        n_samples = np.full(4, 8.0, np.float32)

        mesh = mm(4, 2, axis_names=("clients", "space"))
        round_fn = build_spatial_federated_round(
            mesh, tiny32, learning_rate=1e-3, local_epochs=2
        )
        got, metrics = round_fn(variables, images, masks, active, n_samples)

        # Host reference on the same 32px config.
        trained, weights = [], []
        for c in range(4):
            state = create_train_state(jax.random.key(0), tiny32, 1e-3)
            state = state.replace_variables(variables)
            for _ in range(2):
                for s in range(STEPS):
                    state, _ = train_step(
                        state,
                        (jnp.asarray(images[c, s]), jnp.asarray(masks[c, s])),
                        variables["params"],
                        jnp.float32(0.0),
                    )
            trained.append(state.variables)
            weights.append(n_samples[c])
        want = fedavg(trained, weights)

        # 1e-4: the host path takes the scatter-free pool backward
        # (ops/pooling.py) while the spatial path pools through its halo
        # reduce_window with XLA's default gradient — same routing, different
        # summation order, so the per-step ulp noise compounds slightly more
        # than the pre-custom-pool 5e-5 calibration allowed.
        _assert_trees_match(got, want, atol=1e-4)
        assert np.all(np.isfinite(np.asarray(metrics["loss"])))

    def test_rejects_misaligned_height(self):
        from fedcrack_tpu.parallel import build_spatial_federated_round
        from fedcrack_tpu.parallel.mesh import make_mesh as mm

        mesh = mm(2, 4, axis_names=("clients", "space"))  # needs H % 64 == 0
        round_fn = build_spatial_federated_round(mesh, TINY)
        images, masks = _client_data(2)  # H = 32
        with pytest.raises(ValueError, match="multiple of 16"):
            round_fn(
                create_train_state(jax.random.key(0), TINY).variables,
                images,
                masks,
                np.ones(2, np.float32),
                np.full(2, 8.0, np.float32),
            )
