"""A/B: the fused Pallas BCE+stats kernel vs plain XLA on the training hot path.

``ops/pallas_bce.py`` offers a fused one-HBM-pass kernel for the four
loss/metric reductions. Both variants are built in ONE process —
``FEDCRACK_BCE_IMPL`` pins the impl at trace time — and timed with chained,
host-readback-synced rounds at two scan lengths, with the variants' timed
reps INTERLEAVED (A,B,A,B,...) so slow drift hits both equally. The slope of
the two-scan fit is the per-step time.

Run on the TPU:
    python -m fedcrack_tpu.tools.ab_pallas_bce --out chiprun_out/pallas_bce_ab.json

CPU smoke (single impl — the Pallas interpreter cannot run inside the
shard_map round program on CPU, and the compiled kernel needs a real TPU;
numerics parity is tests/test_pallas_bce.py's job):
    python -m fedcrack_tpu.tools.ab_pallas_bce --sizes 32 --steps 2 \
        --batch 2 --reps 1 --impls jnp --dtype float32 --out /tmp/ab.json

Artifact schema: ``points[<dtype>_<size>] = {"impls": {<impl>: point...},
"speedup_first_over_second": float?}`` — per-impl dicts under "impls",
derived scalars as sibling keys (never mixed into the impl map).
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np


def _median_time(fn, reps: int) -> float:
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _make_runner(round_fn, variables, si, sm, active, n_samples):
    """Chained, readback-synced round: each call consumes the previous
    call's weights, and the host reads a metric back before the clock
    stops."""
    state = {"v": variables}

    def run():
        new_vars, metrics = round_fn(state["v"], si, sm, active, n_samples)
        state["v"] = new_vars
        float(np.asarray(metrics["loss"])[0])

    return run


def run_ab(args) -> dict:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.obs.flops import mfu, train_step_flops
    from fedcrack_tpu.parallel import (
        build_federated_round,
        make_mesh,
        stack_client_data,
        stage_round_data,
    )
    from fedcrack_tpu.data.synthetic import synth_crack_batch
    from fedcrack_tpu.train.local import create_train_state

    impls = [s.strip() for s in args.impls.split(",") if s.strip()]
    sizes = [int(s) for s in args.sizes.split(",")]
    mesh = make_mesh(1, 1)
    device = jax.devices()[0]
    active = np.ones(1, np.float32)
    fit = max(2, args.fit_factor)
    prior_impl = os.environ.get("FEDCRACK_BCE_IMPL")

    out: dict = {
        "generated_by": "fedcrack_tpu.tools.ab_pallas_bce",
        "hardware": {
            "platform": device.platform,
            "device_kind": getattr(device, "device_kind", "unknown"),
        },
        "workload": {
            "impls": impls,
            "sizes": sizes,
            "steps": args.steps,
            "batch": args.batch,
            "reps": args.reps,
            "fit_factor": fit,
            "dtype": args.dtype,
        },
        "points": {},
    }

    try:
        for img in sizes:
            config = ModelConfig(img_size=img, compute_dtype=args.dtype)
            state0 = create_train_state(jax.random.key(args.seed), config)
            imgs, msks = synth_crack_batch(
                args.steps * args.batch, img, seed=args.seed
            )
            images, masks = stack_client_data([(imgs, msks)], args.steps, args.batch)
            si, sm = stage_round_data(images, masks, mesh)
            sharding = NamedSharding(mesh, P("clients", None, "batch"))
            tile = jax.jit(
                lambda a: jax.numpy.concatenate([a] * fit, axis=1),
                out_shardings=sharding,
            )
            si_long, sm_long = tile(si), tile(sm)
            jax.block_until_ready((si_long, sm_long))
            n_samp = np.full(1, float(args.steps * args.batch), np.float32)
            n_samp_long = np.full(1, float(fit * args.steps * args.batch), np.float32)

            # Build + warm each impl's round program (env var is read at
            # TRACE time, i.e. during the first call of each signature).
            runners = {}
            for impl in impls:
                os.environ["FEDCRACK_BCE_IMPL"] = impl
                round_fn = build_federated_round(
                    mesh, config, learning_rate=1e-3, local_epochs=1
                )
                short = _make_runner(
                    round_fn, state0.variables, si, sm, active, n_samp
                )
                long = _make_runner(
                    round_fn, state0.variables, si_long, sm_long, active, n_samp_long
                )
                for r in (short, long):
                    r()  # compile (host-pytree signature)
                    r()  # committed-device-input signature the timed reps use
                runners[impl] = (short, long)

            # Interleaved timed reps: one (short, long) pair per impl per
            # pass, so slow drift is shared across variants.
            shorts = {impl: [] for impl in impls}
            longs = {impl: [] for impl in impls}
            for _ in range(args.reps):
                for impl in impls:
                    shorts[impl].append(_median_time(runners[impl][0], 1))
                for impl in impls:
                    longs[impl].append(_median_time(runners[impl][1], 1))

            flops = train_step_flops(config, args.batch)
            pts = {}
            for impl in impls:
                short_s = float(np.median(shorts[impl]))
                long_s = float(np.median(longs[impl]))
                slope = (long_s - short_s) / ((fit - 1) * args.steps)
                fit_ok = slope > 0.0
                util = mfu(slope, flops, device) if fit_ok else None
                pts[impl] = {
                    "round_s_short": short_s,
                    "round_s_long": long_s,
                    "per_step_ms": round(slope * 1e3, 4) if fit_ok else None,
                    "mfu": None if util is None else round(util, 4),
                }
            # Per-impl point dicts live under "impls"; derived scalars (the
            # speedup) are SIBLING keys, so consumers can iterate
            # points[key]["impls"] with no non-dict special case.
            point = {"impls": pts}
            if all(pts[i]["per_step_ms"] is not None for i in impls) and len(impls) == 2:
                a, b = impls
                point["speedup_first_over_second"] = round(
                    pts[b]["per_step_ms"] / pts[a]["per_step_ms"], 4
                )
            out["points"][f"{args.dtype}_{img}"] = point
            del si, sm, si_long, sm_long
    finally:
        if prior_impl is None:
            os.environ.pop("FEDCRACK_BCE_IMPL", None)
        else:
            os.environ["FEDCRACK_BCE_IMPL"] = prior_impl
    return out


def main(argv=None) -> int:
    from fedcrack_tpu.jaxcompat import enable_compilation_cache

    enable_compilation_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--out", required=True)
    p.add_argument("--impls", default="pallas,jnp")
    p.add_argument("--sizes", default="128,256")
    p.add_argument("--steps", type=int, default=32)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--fit-factor", type=int, default=4)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    artifact = run_ab(args)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
    print(json.dumps(artifact["points"]))
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
