"""Measured BASELINE c1-c5 table generator.

Produces one artifact-backed row per BASELINE config (BASELINE.json /
``configs/c1..c5_*.json``): round wall-clock, per-step time, control/data
plane bytes, val loss, pixel accuracy, crack IoU — the table the reference
never published (SURVEY.md §6) and round-2's verdict item #2.

Workloads are scaled down from the presets' reference-scale settings
(10 epochs x thousands of steps won't fit a CPU-host measurement run) and
the artifact records the exact workload + hardware for every row — the
numbers are honest about what was measured, never extrapolated. Real-chip
per-step timing for the single-chip shapes lives in the BENCH artifacts
(bench.py's sweep + reference_scale); this tool's mesh rows run wherever
it is launched (virtual 8-device CPU mesh in CI).

Run (virtual mesh):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python -m fedcrack_tpu.tools.measure_baseline \
      --out bench_runs/r03_configs_cpu.json

Quality comes from held-out synthetic fixtures (no real crack dataset in
this image): server-side eval with BN recalibration, exactly like
``fedcrack_tpu.server --eval-synthetic``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import numpy as np


def _now() -> float:
    return time.perf_counter()


def _hardware() -> dict:
    d = jax.devices()[0]
    return {
        "platform": d.platform,
        "device_kind": getattr(d, "device_kind", "unknown"),
        "n_devices": jax.device_count(),
    }


def _load_preset(name: str):
    from fedcrack_tpu.configs import FedConfig

    here = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(here, "configs", f"{name}.json")) as f:
        return FedConfig.from_json(f.read())


def _eval_quality(variables, model_cfg, n_val: int, seed: int, pos_weight: float = 1.0):
    """Held-out quality with BN recalibration (the server eval path)."""
    from fedcrack_tpu.data.pipeline import ArrayDataset
    from fedcrack_tpu.data.synthetic import synth_crack_batch
    from fedcrack_tpu.train.local import (
        create_train_state,
        evaluate,
        recalibrate_batch_stats,
    )

    images, masks = synth_crack_batch(n_val, model_cfg.img_size, seed=seed)
    ds = ArrayDataset(images, masks, batch_size=8, shuffle=False, drop_last=False)
    st = create_train_state(jax.random.key(0), model_cfg)
    st = st.replace_variables(
        jax.tree_util.tree_map(lambda t, x: np.asarray(x, t.dtype), st.variables, variables)
    )
    st = recalibrate_batch_stats(st, ds, model_cfg)
    m = evaluate(st, ds, pos_weight=pos_weight)
    return {
        "val_loss": round(float(m["loss"]), 4),
        "pixel_acc": round(float(m["pixel_acc"]), 4),
        "iou": round(float(m["iou"]), 4),
    }


def measure_c1(args) -> dict:
    """c1: single-client local fit (the centralized trainer),
    reference 128 px crops."""
    from fedcrack_tpu.data.pipeline import ArrayDataset
    from fedcrack_tpu.data.synthetic import synth_crack_batch
    from fedcrack_tpu.train.centralized import train_centralized

    cfg = _load_preset("c1_single_client_cpu")
    img = cfg.model.img_size
    n_train, n_val = args.samples, max(16, args.samples // 4)
    images, masks = synth_crack_batch(n_train + n_val, img, seed=0)
    train_ds = ArrayDataset(
        images[:n_train], masks[:n_train], batch_size=cfg.data.batch_size, seed=0
    )
    val_ds = ArrayDataset(
        images[n_train:], masks[n_train:], batch_size=cfg.data.batch_size,
        shuffle=False, drop_last=False,
    )
    t0 = _now()
    _, history = train_centralized(
        train_ds, val_ds, cfg.model, epochs=args.epochs,
        learning_rate=cfg.learning_rate, pos_weight=args.pos_weight,
        log_fn=lambda s: None,
    )
    total_s = _now() - t0
    steps = args.epochs * len(train_ds)
    best = min(history, key=lambda h: h["val_loss"])
    return {
        "config": "c1_single_client_cpu",
        "hardware": _hardware(),
        "workload": {
            "img_size": img, "batch": cfg.data.batch_size,
            "train_samples": n_train, "epochs": args.epochs,
            "pos_weight": args.pos_weight,
        },
        "wall_clock_s": round(total_s, 2),
        "per_step_ms": round(total_s / steps * 1e3, 2),
        "epoch_s": round(total_s / args.epochs, 2),
        "val_loss": round(float(best["val_loss"]), 4),
        "pixel_acc": round(float(best["val_pixel_acc"]), 4),
        "iou": round(float(best["val_iou"]), 4),
        "notes": "best-val epoch; per_step includes the per-epoch BN "
                 "recalibration + validation sweeps",
    }


def measure_c2(args, preset="c2_two_client_grpc", partition="iid", mu=None) -> dict:
    """c2/c4: K-client FedAvg over real localhost gRPC, end to end."""
    import threading

    from fedcrack_tpu.data.pipeline import ArrayDataset
    from fedcrack_tpu.data.synthetic import synth_crack_batch
    from fedcrack_tpu.fed.serialization import tree_from_bytes
    from fedcrack_tpu.train.federated import make_train_fn
    from fedcrack_tpu.train.local import (
        create_train_state,
        evaluate,
        recalibrate_batch_stats,
    )
    from fedcrack_tpu.transport.client import FedClient
    from fedcrack_tpu.transport.service import FedServer, ServerThread

    cfg = _load_preset(preset)
    n_clients = min(cfg.cohort_size, args.grpc_clients)
    img = cfg.model.img_size
    cfg = dataclasses.replace(
        cfg,
        cohort_size=n_clients,
        max_rounds=args.rounds,
        local_epochs=args.epochs,
        pos_weight=args.pos_weight,
        poll_period_s=0.2,
        registration_window_s=10.0,
        port=0,
        fedprox_mu=cfg.fedprox_mu if mu is None else mu,
        data=dataclasses.replace(cfg.data, img_size=img, batch_size=8),
    )

    # Held-out eval set (server side), distinct seed from every client shard.
    ev_images, ev_masks = synth_crack_batch(32, img, seed=999)
    eval_ds = ArrayDataset(ev_images, ev_masks, batch_size=8, shuffle=False, drop_last=False)

    state_tmpl = create_train_state(jax.random.key(cfg.seed), cfg.model)

    def eval_fn(blob: bytes) -> dict:
        st = state_tmpl.replace_variables(
            tree_from_bytes(blob, template=state_tmpl.variables)
        )
        st = recalibrate_batch_stats(st, eval_ds, cfg.model)
        return evaluate(st, eval_ds, pos_weight=cfg.pos_weight)

    server = FedServer(cfg, state_tmpl.variables, tick_period_s=0.1, eval_fn=eval_fn)
    results = {}
    t0 = _now()
    with ServerThread(server) as st_thread:
        def run_client(i):
            # Non-IID (c4): per-client crack prevalence skew via crack_prob.
            crack_prob = 0.8 if partition == "iid" else (0.35 + 0.9 * i / max(1, n_clients - 1))
            imgs, msks = synth_crack_batch(
                args.samples, img, seed=10 + i, crack_prob=min(crack_prob, 1.0)
            )
            ds = ArrayDataset(imgs, msks, batch_size=8, seed=i)
            train_fn, _ = make_train_fn(cfg, ds, batch_size=8, seed=i)
            c = FedClient(cfg, train_fn, cname=f"c{i}", port=st_thread.port)
            results[i] = c.run_session()

        threads = [threading.Thread(target=run_client, args=(i,)) for i in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # eval tasks run off-loop; wait for the last round's record
        deadline = _now() + 120
        while len(server.eval_history) < args.rounds and _now() < deadline:
            time.sleep(0.5)
        history = list(st_thread.state.history)
        eval_hist = list(server.eval_history)
    total_s = _now() - t0

    # A crashed client thread would leave its key out of `results` and a
    # values()-only check would pass vacuously — the artifact must never
    # describe a degraded run as the full cohort.
    assert len(results) == n_clients, (
        f"only {sorted(results)} of {n_clients} clients completed"
    )
    assert all(r.enrolled for r in results.values())
    steps_per_round = n_clients * args.epochs * (args.samples // 8)
    round_wall = [h["wall_clock_s"] for h in history]
    last_eval = eval_hist[-1] if eval_hist else {}

    def _q(key):
        # None (-> JSON null) when the off-loop eval missed the deadline;
        # float('nan') would serialize as bare NaN and break strict parsers.
        v = last_eval.get(key)
        return None if v is None else round(float(v), 4)
    return {
        "config": preset if mu is None else "c4_noniid_fedprox",
        "hardware": _hardware(),
        "workload": {
            "img_size": img, "batch": 8, "clients": n_clients,
            "rounds": args.rounds, "local_epochs": args.epochs,
            "samples_per_client": args.samples, "partition": partition,
            "fedprox_mu": cfg.fedprox_mu, "pos_weight": cfg.pos_weight,
        },
        "session_wall_clock_s": round(total_s, 2),
        "round_wall_clock_s": round(float(np.median(round_wall)), 3),
        "per_step_ms": round(float(np.median(round_wall)) / steps_per_round * 1e3, 2),
        "control_plane_bytes": {
            "received_per_round": int(np.median([h["bytes_received"] for h in history])),
            "broadcast_per_round": int(np.median([h["bytes_broadcast"] for h in history])),
        },
        "val_loss": _q("loss"),
        "pixel_acc": _q("pixel_acc"),
        "iou": _q("iou"),
        "notes": "real localhost gRPC, real trainers; round wall-clock from "
                 "the coordinator's round history; quality = server-side "
                 "eval of the final aggregated model on held-out fixtures",
    }


def measure_mesh(args, preset: str, n_clients: int, n_batch: int) -> dict:
    """c3/c5: one-program mesh rounds; quality from the final aggregate."""
    from fedcrack_tpu.data.synthetic import synth_crack_batch
    from fedcrack_tpu.parallel import (
        build_federated_round,
        make_mesh,
        run_mesh_federation,
        stack_client_data,
    )
    from fedcrack_tpu.train.local import create_train_state

    cfg = _load_preset(preset)
    img = cfg.model.img_size if args.mesh_img is None else args.mesh_img
    model_cfg = dataclasses.replace(cfg.model, img_size=img)
    avail = jax.device_count()
    if n_clients * n_batch > avail:
        raise SystemExit(
            f"{preset}: needs {n_clients * n_batch} devices, have {avail} — "
            "run with XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )
    mesh = make_mesh(n_clients, n_batch)
    round_fn = build_federated_round(
        mesh, model_cfg, learning_rate=cfg.learning_rate,
        local_epochs=args.epochs, fedprox_mu=cfg.fedprox_mu,
        pos_weight=args.pos_weight,
    )
    batch = cfg.data.batch_size
    per_client = [
        synth_crack_batch(args.mesh_steps * batch, img, seed=20 + i)
        for i in range(n_clients)
    ]
    images, masks = stack_client_data(per_client, args.mesh_steps, batch)
    active = np.ones(n_clients, np.float32)
    n_samples = np.full(n_clients, float(args.mesh_steps * batch), np.float32)
    state0 = create_train_state(jax.random.key(cfg.seed), model_cfg)

    # Multi-round loop through the package driver (parallel.driver): local
    # data is static across rounds, so data_fn returns None after round 0
    # and the shard is staged exactly once.
    variables, records = run_mesh_federation(
        round_fn,
        state0.variables,
        lambda r: (images, masks, active, n_samples) if r == 0 else None,
        args.rounds,
        mesh,
    )
    times = [rec.wall_clock_s for rec in records]
    # first round includes compilation; report the post-compile median
    round_s = float(np.median(times[1:])) if len(times) > 1 else times[0]
    steps_per_round = args.epochs * args.mesh_steps

    # Quality at a workload a 1-core CPU host can actually train: the
    # HOST-plane federation of the same config — bit-equal aggregation to
    # the mesh program (pinned by
    # tests/test_parallel.py::test_mesh_round_equals_host_round), without
    # 8 virtual device threads spin-waiting on collectives over one core.
    from fedcrack_tpu.data.pipeline import ArrayDataset
    from fedcrack_tpu.fed.algorithms import fedavg
    from fedcrack_tpu.train.local import local_fit

    q_samples, q_epochs, q_rounds = args.samples, args.epochs_quality, args.rounds
    q_data = [
        synth_crack_batch(q_samples, img, seed=20 + i) for i in range(n_clients)
    ]
    vars_q = state0.variables
    for _ in range(q_rounds):
        trained = []
        for ci in range(n_clients):
            st = create_train_state(
                jax.random.key(cfg.seed), model_cfg, cfg.learning_rate
            ).replace_variables(vars_q)
            ds = ArrayDataset(q_data[ci][0], q_data[ci][1], batch_size=batch, seed=ci)
            st, _ = local_fit(
                st, ds, epochs=q_epochs, pos_weight=args.pos_weight,
                mu=cfg.fedprox_mu, anchor_params=vars_q["params"],
            )
            trained.append(jax.device_get(st.variables))
        vars_q = fedavg(trained, weights=[float(q_samples)] * n_clients)
    quality = _eval_quality(
        vars_q, model_cfg, n_val=32, seed=999, pos_weight=args.pos_weight
    )
    return {
        "config": preset,
        "hardware": _hardware(),
        "workload": {
            "img_size": img, "batch": batch, "clients": n_clients,
            "batch_dp": n_batch, "rounds": args.rounds,
            "local_epochs": args.epochs, "steps_per_epoch": args.mesh_steps,
            "compute_dtype": model_cfg.compute_dtype,
            "pos_weight": args.pos_weight,
            "quality_workload": {
                "samples_per_client": q_samples, "local_epochs": q_epochs,
                "rounds": q_rounds, "path": "host-plane equivalent",
            },
        },
        "round_wall_clock_s": round(round_s, 3),
        "compile_round_s": round(times[0], 2),
        "per_step_ms": round(round_s / steps_per_round * 1e3, 2),
        "data_plane_bytes_staged": int(images.nbytes + masks.nbytes),
        **quality,
        "notes": "one-program mesh round (psum FedAvg on the clients axis) "
                 "executed for timing/correctness; quality = held-out eval "
                 "of the HOST-plane federation of the same config (bit-equal "
                 "aggregation per the mesh-vs-host golden test) at the "
                 "quality_workload — virtual-device collectives spin-wait on "
                 "a 1-core host, so training a quality-bearing workload "
                 "through the mesh program there is infeasible; timing is "
                 "wherever this ran (hardware.platform; real-chip slopes "
                 "live in the BENCH artifact)",
    }


def measure_c3_mesh_program_quality(args) -> dict:
    """c3q: a QUALITY-BEARING micro federation through the ACTUAL mesh round
    program, with its host-plane twin on the same seed and data order.

    The c3/c5 rows' quality comes from the host-plane twin because 8 virtual
    device threads spin-wait on every psum on a 1-core host (see
    measure_mesh's note) — leaving the caveat that no quality-bearing
    workload had ever run through the mesh PROGRAM on this box (round-4
    verdict, next #8). This row retires it at micro scale: a few rounds at
    32 px through ``build_federated_round`` on the virtual 8-device mesh,
    the identical workload through sequential jitted ``train_step`` + host
    ``fedavg`` (the golden cross-check's reference implementation,
    tests/test_parallel.py::_host_round), and the held-out eval of BOTH
    final aggregates recorded side by side.
    """
    import jax.numpy as jnp

    from fedcrack_tpu.data.synthetic import synth_crack_batch
    from fedcrack_tpu.fed.algorithms import fedavg
    from fedcrack_tpu.parallel import (
        build_federated_round,
        make_mesh,
        run_mesh_federation,
        stack_client_data,
    )
    from fedcrack_tpu.train.local import create_train_state, train_step

    cfg = _load_preset("c3_eight_client_mesh")
    n_clients = 8
    img = 32 if args.mesh_img is None else args.mesh_img
    batch = 4
    model_cfg = dataclasses.replace(cfg.model, img_size=img)
    avail = jax.device_count()
    if n_clients > avail:
        raise SystemExit(
            f"c3q: needs {n_clients} devices, have {avail} — run with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )
    mesh = make_mesh(n_clients, 1)
    round_fn = build_federated_round(
        mesh, model_cfg, learning_rate=cfg.learning_rate,
        local_epochs=args.epochs, pos_weight=args.pos_weight,
    )
    per_client = [
        synth_crack_batch(args.mesh_steps * batch, img, seed=20 + i)
        for i in range(n_clients)
    ]
    images, masks = stack_client_data(per_client, args.mesh_steps, batch)
    active = np.ones(n_clients, np.float32)
    n_samples = np.full(n_clients, float(args.mesh_steps * batch), np.float32)
    state0 = create_train_state(jax.random.key(cfg.seed), model_cfg)

    t0 = _now()
    mesh_vars, records = run_mesh_federation(
        round_fn,
        state0.variables,
        lambda r: (images, masks, active, n_samples) if r == 0 else None,
        args.rounds,
        mesh,
    )
    mesh_vars = jax.device_get(mesh_vars)
    mesh_s = _now() - t0

    # Host-plane twin: same rounds, same per-round fresh optimizer, same
    # epoch-outer/step-inner data order the round program's scan uses.
    t0 = _now()
    host_vars = state0.variables
    for _ in range(args.rounds):
        trained = []
        for c in range(n_clients):
            st = create_train_state(
                jax.random.key(cfg.seed), model_cfg, cfg.learning_rate
            ).replace_variables(host_vars)
            for _e in range(args.epochs):
                for s in range(args.mesh_steps):
                    batch_cs = (jnp.asarray(images[c, s]), jnp.asarray(masks[c, s]))
                    st, _ = train_step(
                        st,
                        batch_cs,
                        host_vars["params"],
                        jnp.float32(0.0),
                        jnp.float32(args.pos_weight),
                    )
            trained.append(jax.device_get(st.variables))
        host_vars = fedavg(trained, weights=[float(n) for n in n_samples])
    host_s = _now() - t0

    # Per-leaf-class divergence, mirroring the golden test's two classes
    # (tests/test_parallel.py::_assert_trees_match): conv biases that feed
    # straight into a BatchNorm have ~0 true gradient, so Adam amplifies
    # fp-reassociation noise between the two XLA programs into lr-sized
    # steps on those leaves — across R rounds they drift by O(lr*steps*R)
    # while every OTHER leaf stays at reassociation-noise scale.
    max_diff_bn_bias = 0.0
    max_diff_rest = 0.0
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(mesh_vars["params"]),
        jax.tree_util.tree_leaves(host_vars["params"]),
    ):
        key = jax.tree_util.keystr(path)
        d = float(np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))))
        bn_shadowed = key.endswith("'bias']") and any(
            s in key for s in ("stem_conv", "_sep", "_convT")
        )
        if bn_shadowed:
            max_diff_bn_bias = max(max_diff_bn_bias, d)
        else:
            max_diff_rest = max(max_diff_rest, d)

    q_mesh = _eval_quality(
        mesh_vars, model_cfg, n_val=32, seed=999, pos_weight=args.pos_weight
    )
    q_host = _eval_quality(
        host_vars, model_cfg, n_val=32, seed=999, pos_weight=args.pos_weight
    )
    return {
        "config": "c3q_mesh_program_quality",
        "hardware": _hardware(),
        "workload": {
            "img_size": img, "batch": batch, "clients": n_clients,
            "rounds": args.rounds, "local_epochs": args.epochs,
            "steps_per_epoch": args.mesh_steps,
            "compute_dtype": model_cfg.compute_dtype,
            "pos_weight": args.pos_weight,
        },
        "mesh_program": {
            "wall_clock_s": round(mesh_s, 2),
            "compile_round_s": round(records[0].wall_clock_s, 2),
            **{f"q_{k}": v for k, v in q_mesh.items()},
        },
        "host_plane_twin": {
            "wall_clock_s": round(host_s, 2),
            **{f"q_{k}": v for k, v in q_host.items()},
        },
        "max_abs_param_diff_bn_shadowed_bias": max_diff_bn_bias,
        "max_abs_param_diff_other_leaves": max_diff_rest,
        "quality_equal": bool(
            abs(float(q_mesh["iou"]) - float(q_host["iou"])) <= 0.005
            and abs(float(q_mesh["pixel_acc"]) - float(q_host["pixel_acc"])) <= 0.005
            and abs(float(q_mesh["val_loss"]) - float(q_host["val_loss"])) <= 0.01
        ),
        "notes": "same seed, same data, same order through both planes; a "
                 "quality-bearing trajectory through the mesh PROGRAM itself "
                 "(not just its host-plane stand-in). Equality criterion is "
                 "at the QUALITY level: the planes are equal up to fp "
                 "reassociation (the golden one-round cross-check's atol), "
                 "and across rounds Adam amplifies that noise on the "
                 "BN-shadowed zero-gradient conv biases — see the split "
                 "max_abs_param_diff fields",
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--out", required=True)
    p.add_argument("--configs", default="c1,c2,c3,c4,c5")
    p.add_argument("--samples", type=int, default=64, help="train samples per client")
    p.add_argument("--epochs", type=int, default=2, help="local epochs")
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--grpc-clients", type=int, default=2)
    p.add_argument("--mesh-steps", type=int, default=8, help="steps per epoch (mesh rows)")
    p.add_argument("--mesh-img", type=int, default=None,
                   help="override mesh rows' crop (CPU hosts may want 128)")
    p.add_argument("--pos-weight", type=float, default=5.0)
    p.add_argument(
        "--epochs-quality", type=int, default=2, dest="epochs_quality",
        help="local epochs for the mesh rows' host-plane quality federation",
    )
    args = p.parse_args(argv)

    want = set(args.configs.split(","))
    rows = []
    if "c1" in want:
        rows.append(measure_c1(args))
        print(json.dumps(rows[-1]), flush=True)
    if "c2" in want:
        rows.append(measure_c2(args))
        print(json.dumps(rows[-1]), flush=True)
    if "c3" in want:
        rows.append(measure_mesh(args, "c3_eight_client_mesh", 8, 1))
        print(json.dumps(rows[-1]), flush=True)
    if "c4" in want:
        rows.append(measure_c2(args, preset="c4_noniid_fedprox", partition="skew", mu=0.01))
        print(json.dumps(rows[-1]), flush=True)
    if "c5" in want:
        rows.append(measure_mesh(args, "c5_bf16_batch_dp", 4, 2))
        print(json.dumps(rows[-1]), flush=True)
    if "c3q" in want:
        rows.append(measure_c3_mesh_program_quality(args))
        print(json.dumps(rows[-1]), flush=True)

    artifact = {
        "generated_by": "fedcrack_tpu.tools.measure_baseline",
        "hardware": _hardware(),
        "rows": rows,
    }
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
    print(f"wrote {args.out} ({len(rows)} rows)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
