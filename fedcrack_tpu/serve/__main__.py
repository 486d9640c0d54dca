"""``python -m fedcrack_tpu.serve`` — boot the crack-segmentation endpoint.

Builds the engine (one compiled program per bucket), resolves initial
weights (``--weights`` msgpack > statefile > checkpoint dir > seed init, in
that order), starts the hot-swap poller against the federation's
checkpoint/statefile outputs, and serves ``fedcrack.ServePlane/Predict``
until SIGTERM/SIGINT.

Prints exactly one ``SERVING <host>:<port> ...`` line to stdout once ready —
harnesses (tools/load_gen.py --spawn, the e2e smoke) key on it.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import logging
import signal
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m fedcrack_tpu.serve", description=__doc__
    )
    p.add_argument("--config", help="FedConfig JSON preset (serve + model sections)")
    p.add_argument("--weights", help="msgpack pytree to serve initially")
    p.add_argument("--ckpt-dir", help="orbax checkpoint dir to hot-swap from")
    p.add_argument("--state-path", help="federation statefile to hot-swap from")
    p.add_argument("--host")
    p.add_argument("--port", type=int)
    p.add_argument("--buckets", help="comma-separated bucket sizes, e.g. 128,256")
    p.add_argument("--max-batch", type=int)
    p.add_argument("--max-delay-ms", type=float)
    p.add_argument("--tile-overlap", type=int,
                   help="sliding-window overlap px (must be < smallest bucket)")
    p.add_argument("--swap-poll-s", type=float)
    p.add_argument("--compute-dtype", choices=["float32", "bfloat16"])
    p.add_argument(
        "--replicas",
        type=int,
        help="replica workers behind the fleet router (>1 enables the "
        "round-17 fleet: least-outstanding dispatch, coordinated two-phase "
        "hot swap, admission control)",
    )
    p.add_argument(
        "--quant",
        choices=["none", "int8"],
        help="post-training weight quantization of the predict program; "
        "int8 installs are A/B-gated on probe mask IoU vs the reference "
        "oracle and refused below --quant-iou-floor",
    )
    p.add_argument("--quant-iou-floor", type=float)
    p.add_argument(
        "--min-replicas",
        type=int,
        help="arm the round-22 SLO autoscaler: fleet floor (>= 1; pairs "
        "with --max-replicas; --replicas is the boot size inside the band)",
    )
    p.add_argument(
        "--max-replicas",
        type=int,
        help="autoscaler fleet ceiling (>= --min-replicas)",
    )
    p.add_argument("--scale-interval-s", type=float,
                   help="autoscaler control-loop period")
    p.add_argument("--scale-cooldown-s", type=float,
                   help="dead time after any scaling action (anti-flap)")
    p.add_argument(
        "--shadow-fraction",
        type=float,
        help="arm round-22 progressive delivery: fraction of admitted "
        "traffic mirrored to a shadow candidate lane (> 0; publishes then "
        "stage through shadow and auto-promote/auto-rollback instead of "
        "installing directly)",
    )
    p.add_argument(
        "--slo-p95-ms",
        type=float,
        help="shed (RESOURCE_EXHAUSTED) when rolling p95 breaches this; 0 off",
    )
    p.add_argument(
        "--queue-bound",
        type=int,
        help="shed when queued requests across replicas reach this; 0 off",
    )
    p.add_argument(
        "--stream-cache-tiles",
        type=int,
        help="per-stream tile cache bound for video sessions (entries = "
        "tiles, keyed on (model_version, tile hash)); 0 disables caching — "
        "every frame is a full re-run",
    )
    p.add_argument(
        "--stream-max-sessions",
        type=int,
        help="open video sessions the serve process will hold at once",
    )
    p.add_argument("--metrics-path", help="JSONL metrics sink (serve_batch/serve_swap)")
    p.add_argument(
        "--metrics-port",
        type=int,
        default=0,
        help="Prometheus /metrics endpoint for the live registry (request "
        "latency per bucket, queue depth, deadline misses, swaps, "
        "recompiles); 0 disables, -1 binds an ephemeral port",
    )
    p.add_argument(
        "--spans-path",
        help="JSONL trace-span sink (serve.batch/serve.swap correlation "
        "spans); empty disables",
    )
    p.add_argument("--seed", type=int, default=0, help="init seed when no weights found")
    return p


def resolve_config(args):
    from fedcrack_tpu.configs import FedConfig

    if args.config:
        with open(args.config) as f:
            fed = FedConfig.from_json(f.read())
    else:
        fed = FedConfig()
    serve = fed.serve
    overrides = {}
    if args.buckets:
        overrides["bucket_sizes"] = tuple(
            int(s) for s in args.buckets.split(",") if s.strip()
        )
    if args.max_batch is not None:
        overrides["max_batch"] = args.max_batch
    if args.max_delay_ms is not None:
        overrides["max_delay_ms"] = args.max_delay_ms
    if args.tile_overlap is not None:
        overrides["tile_overlap"] = args.tile_overlap
    if args.swap_poll_s is not None:
        overrides["swap_poll_s"] = args.swap_poll_s
    if args.compute_dtype:
        overrides["compute_dtype"] = args.compute_dtype
    if args.host:
        overrides["host"] = args.host
    if args.port is not None:
        overrides["port"] = args.port
    if args.replicas is not None:
        overrides["replicas"] = args.replicas
    if args.quant is not None:
        overrides["quant"] = args.quant
    if args.quant_iou_floor is not None:
        overrides["quant_iou_floor"] = args.quant_iou_floor
    if args.slo_p95_ms is not None:
        overrides["slo_p95_ms"] = args.slo_p95_ms
    if args.queue_bound is not None:
        overrides["queue_bound"] = args.queue_bound
    if args.stream_cache_tiles is not None:
        overrides["stream_cache_tiles"] = args.stream_cache_tiles
    if args.stream_max_sessions is not None:
        overrides["stream_max_sessions"] = args.stream_max_sessions
    if args.min_replicas is not None:
        overrides["min_replicas"] = args.min_replicas
    if args.max_replicas is not None:
        overrides["max_replicas"] = args.max_replicas
    if args.scale_interval_s is not None:
        overrides["scale_interval_s"] = args.scale_interval_s
    if args.scale_cooldown_s is not None:
        overrides["scale_cooldown_s"] = args.scale_cooldown_s
    if args.shadow_fraction is not None:
        overrides["shadow_fraction"] = args.shadow_fraction
    if overrides:
        serve = dataclasses.replace(serve, **overrides)
    return fed.model, serve


def resolve_initial_weights(args, template, seed: int):
    """(version, variables): explicit file > statefile > ckpt dir > seed."""
    from fedcrack_tpu.serve.hot_swap import read_statefile_weights

    if args.weights:
        from fedcrack_tpu.fed.serialization import tree_from_bytes

        with open(args.weights, "rb") as f:
            return 0, tree_from_bytes(f.read(), template=template)
    if args.state_path:
        got = read_statefile_weights(args.state_path, template=template)
        if got is not None:
            return got
    if args.ckpt_dir:
        import os

        from fedcrack_tpu.ckpt.manager import FedCheckpointer

        if os.path.isdir(args.ckpt_dir):
            with FedCheckpointer(args.ckpt_dir) as ckptr:
                ckpt = ckptr.restore(template)
            if ckpt is not None:
                return ckpt.model_version, ckpt.variables
    print(
        "no weights source found; serving seed-initialized model "
        f"(seed {seed}) until the first hot-swap",
        file=sys.stderr,
    )
    return 0, template


async def _serve(args) -> int:
    import jax

    from fedcrack_tpu.models.resunet import init_variables
    from fedcrack_tpu.serve.batcher import MicroBatcher
    from fedcrack_tpu.serve.engine import InferenceEngine
    from fedcrack_tpu.serve.hot_swap import ModelVersionManager
    from fedcrack_tpu.serve.service import ServeServer, ServeService

    # Warm boot (round 17): the persistent XLA cache is on BEFORE any program
    # compiles — the 2nd..Nth replica/session reuses the 1st one's
    # executables. JAX_COMPILATION_CACHE_DIR places it; see the helper.
    from fedcrack_tpu.jaxcompat import describe_devices, enable_compilation_cache

    enable_compilation_cache()
    logging.info("jax devices: %s", describe_devices())

    model_config, serve_config = resolve_config(args)
    template = init_variables(jax.random.key(args.seed), model_config)
    version, variables = resolve_initial_weights(args, template, args.seed)

    metrics = None
    if args.metrics_path:
        from fedcrack_tpu.obs.metrics import MetricsLogger

        metrics = MetricsLogger(args.metrics_path)

    fleet = None
    if (
        serve_config.replicas > 1
        or serve_config.quant != "none"
        or serve_config.min_replicas > 0
        or serve_config.shadow_fraction > 0
    ):
        # Round-17 fleet topology (also the single-replica quantized shape:
        # the fleet manager owns the A/B gate; round 22's autoscaler and
        # shadow delivery only exist on the fleet shape).
        from fedcrack_tpu.serve.fleet import ServeFleet

        fleet = ServeFleet(
            model_config,
            serve_config,
            variables,
            initial_version=version,
            ckpt_dir=args.ckpt_dir,
            state_path=args.state_path,
            template=template,
            metrics=metrics,
        )
        engine, batcher_like, manager = fleet.engine, fleet.router, fleet.manager
    else:
        engine = InferenceEngine(model_config, serve_config)
        manager = ModelVersionManager(
            engine,
            variables,
            initial_version=version,
            ckpt_dir=args.ckpt_dir,
            state_path=args.state_path,
            poll_s=serve_config.swap_poll_s,
            template=template,
            metrics=metrics,
        )
        engine.warmup(manager.snapshot()[1])
        batcher_like = MicroBatcher(engine, manager, metrics=metrics)
    # Live telemetry (round 15): /metrics exporter + post-warmup recompile
    # sentry (serve_recompiles_total must stay 0 across hot swaps) + spans.
    from fedcrack_tpu.obs.promexp import start_exporter
    from fedcrack_tpu.serve.engine import watch_recompiles

    watch_recompiles(engine)
    exporter = start_exporter(args.metrics_port)
    if args.spans_path:
        from fedcrack_tpu.obs import spans as tracing

        tracing.install(args.spans_path)
    # Frame-coherent video serving (round 19): per-stream tile-cached
    # sessions behind the same front door; the weights source is the same
    # manager the still path pins snapshots from, so a hot swap invalidates
    # stream caches through the version in the key.
    from fedcrack_tpu.serve.stream import StreamSessionManager

    stream_manager = StreamSessionManager(engine, manager)
    server = ServeServer(
        ServeService(engine, batcher_like, manager, stream_manager=stream_manager),
        host=serve_config.host,
        port=serve_config.port,
        max_message_mb=serve_config.max_message_mb,
    )
    # Round 22: elastic capacity + progressive delivery on the fleet shape.
    autoscaler = None
    shadow_ctrl = None
    if fleet is not None and serve_config.min_replicas > 0:
        from fedcrack_tpu.serve.autoscaler import FleetAutoscaler

        autoscaler = FleetAutoscaler(fleet)
        autoscaler.start()
    if fleet is not None and serve_config.shadow_fraction > 0:
        from fedcrack_tpu.serve.shadow import ShadowController

        shadow_ctrl = ShadowController(fleet, metrics=metrics)
        # The shadow controller RUNS the delivery poll: publishes stage
        # through the shadow lane and auto-promote/rollback instead of the
        # manager's install-everything-at-once loop.
        shadow_ctrl.start()
    else:
        manager.start()
    port = await server.start()
    metrics_note = (
        f" metrics_port={exporter.bound_port}" if exporter is not None else ""
    )
    print(
        f"SERVING {serve_config.host}:{port} "
        f"buckets={','.join(str(s) for s in serve_config.bucket_sizes)} "
        f"max_batch={serve_config.max_batch} version={manager.version}"
        f" replicas={serve_config.replicas} quant={serve_config.quant}"
        f"{metrics_note}",
        flush=True,
    )

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            loop.add_signal_handler(sig, stop.set)
        except (NotImplementedError, RuntimeError):
            pass
    await stop.wait()
    await server.stop()
    if autoscaler is not None:
        autoscaler.stop()
    if shadow_ctrl is not None:
        shadow_ctrl.stop()
    if fleet is not None:
        fleet.close()
    else:
        manager.stop()
        batcher_like.close()
    if exporter is not None:
        exporter.stop()
    if metrics is not None:
        import json

        stats = fleet.stats() if fleet is not None else batcher_like.stats()
        if autoscaler is not None:
            stats["autoscaler"] = autoscaler.audit()
        if shadow_ctrl is not None:
            stats["shadow"] = shadow_ctrl.audit()
        print(json.dumps({"serve_stats": stats}), flush=True)
        metrics.close()
    return 0


def main(argv=None) -> int:
    logging.basicConfig(level=logging.INFO)
    args = build_parser().parse_args(argv)
    return asyncio.run(_serve(args))


if __name__ == "__main__":
    sys.exit(main())
