"""End-to-end: 2 real U-Net clients federate over localhost gRPC.

This is SURVEY.md §7's "minimum slice B" (configs/c2_two_client_grpc.json) shrunk for
CI: real Flax model, real jitted local fit, real msgpack weights on the wire,
real FedAvg rounds — tiny shapes (32px, 8 imgs/client, 1 local epoch,
2 rounds)."""

import dataclasses
import threading

import numpy as np
import pytest

from fedcrack_tpu.configs import DataConfig, FedConfig, ModelConfig
from fedcrack_tpu.data.pipeline import ArrayDataset
from fedcrack_tpu.data.synthetic import synth_crack_batch
from fedcrack_tpu.fed import rounds as R
from fedcrack_tpu.fed.serialization import tree_from_bytes
from fedcrack_tpu.train.federated import make_train_fn
from fedcrack_tpu.transport import FedClient, FedServer
from fedcrack_tpu.transport.service import ServerThread


@pytest.mark.slow
def test_two_real_clients_federate():
    cfg = FedConfig(
        max_rounds=3,
        cohort_size=2,
        local_epochs=2,
        pos_weight=5.0,  # crack-pixel weighting so 3 tiny rounds show real IoU motion
        registration_window_s=10.0,
        poll_period_s=0.1,
        host="127.0.0.1",
        port=0,
        model=ModelConfig(img_size=32),
        data=DataConfig(img_size=32, batch_size=4),
    )

    def make_client(name: str, seed: int):
        images, masks = synth_crack_batch(8, 32, seed=seed)
        ds = ArrayDataset(images, masks, batch_size=4, seed=seed)
        train_fn, holder = make_train_fn(cfg, ds, batch_size=4, seed=seed)
        return FedClient(cfg, train_fn, cname=name), holder

    import jax

    from fedcrack_tpu.train.local import create_train_state

    server_state0 = create_train_state(jax.random.key(0), cfg.model)
    server = FedServer(cfg, server_state0.variables, tick_period_s=0.1)

    with ServerThread(server) as st:
        cfg_bound = dataclasses.replace(cfg, port=st.port)
        results = {}

        def run(name, seed):
            client, _ = make_client(name, seed)
            client.port = st.port
            results[name] = client.run_session()

        threads = [
            threading.Thread(target=run, args=("a", 1)),
            threading.Thread(target=run, args=("b", 2)),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        state = st.state

    assert state.phase == R.PHASE_FINISHED
    assert len(state.history) == cfg.max_rounds
    for name in ("a", "b"):
        r = results[name]
        assert r.enrolled and r.rounds_completed == cfg.max_rounds
        assert all(np.isfinite(h["loss"]) for h in r.history)

    # round-over-round learning: the federation must IMPROVE crack IoU, not
    # just move weights (SURVEY.md §4 "IoU above a floor"; the reference's
    # only oracle was a val-loss checkpoint, test/Segmentation.py:177-186).
    # Train-mode IoU of each client's final local epoch, per round:
    for name in ("a", "b"):
        ious = [
            h["iou_inter"] / max(h["iou_union"], 1.0) for h in results[name].history
        ]
        assert ious[-1] > ious[0], f"{name}: no IoU improvement across rounds: {ious}"

    # the broadcast final weights equal the server's global average
    final = tree_from_bytes(state.global_blob)
    for name in ("a", "b"):
        client_final = tree_from_bytes(results[name].final_weights)
        for lc, ls in zip(_leaves(client_final), _leaves(final)):
            assert np.allclose(lc, ls, atol=1e-6)

    # the global model actually moved away from its initialization
    init_leaves = _leaves(server_state0.variables["params"])
    final_leaves = _leaves(final["params"])
    assert any(
        not np.allclose(i, f, atol=1e-7) for i, f in zip(init_leaves, final_leaves)
    )


def _leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)


@pytest.mark.slow
def test_composed_production_stack(tmp_path):
    """The COMPOSED production configuration in ONE run (round-3 verdict
    item 5) — each piece is tested in isolation elsewhere; this is the
    full-system path: file-based ``CrackDataset`` (real JPEG decode), uint8
    transport, TLS + token auth, server checkpointing, the server KILLED and
    RESTARTED mid-federation (clients restart and rejoin — the reference's
    operator flow, fl_client.py:178-188), the federation completing with the
    round counter/history/weights carried across the restart, final held-out
    IoU above the calibrated 0.35 floor, and TensorBoard logs uploaded
    through the chunked 'L' sink."""
    import glob
    import os
    import time

    import jax

    from fedcrack_tpu.ckpt import FedCheckpointer
    from fedcrack_tpu.data.pipeline import CrackDataset, list_pairs
    from fedcrack_tpu.data.synthetic import write_synthetic_dataset
    from fedcrack_tpu.obs.tb import SummaryWriter, read_scalars
    from fedcrack_tpu.train.local import (
        create_train_state,
        evaluate,
        recalibrate_batch_stats,
    )
    from test_transport import _self_signed_cert  # importorskips cryptography

    pytest.importorskip("cv2")  # the on-disk fixture writer needs an encoder
    cert, key = _self_signed_cert(tmp_path)
    n_clients, img, batch = 2, 64, 8

    cfg = FedConfig(
        max_rounds=3,
        cohort_size=n_clients,
        local_epochs=3,
        pos_weight=5.0,
        registration_window_s=10.0,
        poll_period_s=0.2,
        host="127.0.0.1",
        port=0,
        auth_token="prod-tøken",  # non-ASCII: utf-8 token path
        tls_cert=cert,
        tls_key=key,
        tls_ca=cert,  # self-signed: the cert is its own root
        ckpt_dir=str(tmp_path / "ckpt"),
        logs_dir=str(tmp_path / "server_logs"),
        model=ModelConfig(img_size=img),
        data=DataConfig(img_size=img, batch_size=batch),
    )

    # File-based local shards: real JPEGs + PNG masks on disk, thick-stroke
    # quality-gate geometry, decoded through the production pipeline with
    # uint8 transport to the device.
    datasets, log_paths = {}, {}
    for i in range(n_clients):
        img_dir, mask_dir = write_synthetic_dataset(
            str(tmp_path / f"shard{i}"), n=48, img_size=img, seed=10 + i,
            min_thickness=3,
        )
        datasets[i] = CrackDataset(
            list_pairs(img_dir, mask_dir),
            img_size=img,
            batch_size=batch,
            seed=i,
            num_workers=2,
            transport_dtype="uint8",
        )
        # A real TB event file per client, shipped post-FIN via the 'L' path.
        logdir = tmp_path / f"tb{i}"
        with SummaryWriter(logdir) as w:
            w.add_scalar("train/loss", 1.0 - 0.1 * i, step=1)
        log_paths[i] = glob.glob(str(logdir / "events.out.tfevents.*"))[0]

    tmpl = create_train_state(jax.random.key(0), cfg.model)
    results: dict = {}

    def client_thread(i, attempt, port):
        def run():
            train_fn, _ = make_train_fn(cfg, datasets[i], batch_size=batch, seed=i)
            # Short RPC deadlines: with the default 300 s call timeout a
            # wait_for_ready call against the killed server would block the
            # phase-A join for minutes x max_retries.
            c = FedClient(
                cfg,
                train_fn,
                cname=f"c{i}",
                port=port,
                upload_paths=[log_paths[i]],
                max_retries=2,
                call_timeout_s=15.0,
            )
            try:
                results[(i, attempt)] = c.run_session()
            except Exception as e:  # expected for attempt 1: the server dies
                results[(i, attempt)] = e

        t = threading.Thread(target=run)
        t.start()
        return t

    # ---- phase A: server with checkpointing; killed after round 1 closes ----
    with FedCheckpointer(cfg.ckpt_dir) as ckptr1:
        server1 = FedServer(cfg, tmpl.variables, tick_period_s=0.1, checkpointer=ckptr1)
        with ServerThread(server1) as st1:
            threads = [client_thread(i, 1, st1.port) for i in range(n_clients)]
            # Kill only once round 1 has closed AND its checkpoint is on
            # disk — the save runs off-loop, and killing inside that window
            # would test a lost checkpoint, not a resume.
            deadline = time.time() + 900
            while time.time() < deadline and (
                len(st1.state.history) < 1 or ckptr1.latest_version() is None
            ):
                time.sleep(0.5)
            state_a = st1.state
            assert len(state_a.history) >= 1, "round 1 never closed"
            assert ckptr1.latest_version() is not None, "round 1 never checkpointed"
            assert state_a.phase != R.PHASE_FINISHED, (
                "federation finished before the kill — nothing left to resume"
            )
        # server process "crashed" here (ServerThread exited); the clients'
        # next RPC fails after their retry budget and their sessions error out
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads), (
            "a phase-A client is still running 300 s after the server died — "
            "it would leak into phase B"
        )
        rounds_done_a = len(state_a.history)
    for i in range(n_clients):
        assert isinstance(results[(i, 1)], Exception), (
            f"client {i} survived the server crash: {results[(i, 1)]}"
        )

    # ---- phase B: restarted server resumes from the checkpoint ----
    with FedCheckpointer(cfg.ckpt_dir) as ckptr2:
        server2 = FedServer(cfg, tmpl.variables, tick_period_s=0.1, checkpointer=ckptr2)
        # Resume semantics: round counter/version/history restored, enrollment
        # re-opened for the restarted cohort (ckpt/manager.restore_server_state).
        # (>= because another round may close between the history poll and the
        # actual server stop.)
        assert len(server2.state.history) >= rounds_done_a
        assert server2.state.current_round == len(server2.state.history) + 1
        with ServerThread(server2) as st2:
            threads = [client_thread(i, 2, st2.port) for i in range(n_clients)]
            for t in threads:
                t.join(timeout=900)
            state_b = st2.state

    # The federation COMPLETED across the restart: all rounds in one history.
    assert state_b.phase == R.PHASE_FINISHED
    assert len(state_b.history) == cfg.max_rounds
    for i in range(n_clients):
        r = results[(i, 2)]
        assert not isinstance(r, Exception), f"client {i} rejoin failed: {r}"
        assert r.enrolled and r.rounds_completed == cfg.max_rounds

    # Quality floor on the final aggregated model (BN-recalibrated held-out
    # eval at the training pos_weight — same calibration as
    # test_train.py::test_federated_reaches_absolute_iou_floor).
    ev_i, ev_m = synth_crack_batch(32, img, seed=999, min_thickness=3)
    eval_ds = ArrayDataset(ev_i, ev_m, batch_size=batch, shuffle=False, drop_last=False)
    final = tree_from_bytes(state_b.global_blob, template=tmpl.variables)
    st_model = tmpl.replace_variables(final)
    st_model = recalibrate_batch_stats(st_model, eval_ds, cfg.model)
    m = evaluate(st_model, eval_ds, pos_weight=cfg.pos_weight)
    assert m["iou"] >= 0.35, (
        f"composed-stack held-out IoU {m['iou']:.3f} under the 0.35 floor"
    )

    # Logs landed in the server's sink (namespaced per client, path
    # sanitized), byte-for-byte, and still parse as TensorBoard events.
    for i in range(n_clients):
        sunk = os.path.join(cfg.logs_dir, f"c{i}", os.path.basename(log_paths[i]))
        assert os.path.exists(sunk), f"client {i} log never reached the sink"
        with open(log_paths[i], "rb") as f_src, open(sunk, "rb") as f_dst:
            assert f_src.read() == f_dst.read()
        tags = {t for t, _, _ in read_scalars(sunk)}
        assert "train/loss" in tags
