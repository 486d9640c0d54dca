"""Profiled step decomposition of the one-program mesh round at one shape.

This tool makes shape profiles reproducible artifacts instead of one-off
session lore:

- builds the production round program (``parallel.build_federated_round``)
  at ``--img``/``--dtype``, stages one round of data, warms twice
  (compile + committed-signature), then records ``--rounds`` chained
  rounds under ``jax.profiler.trace``;
- converts the captured ``.xplane.pb`` with xprof's ``hlo_stats`` tool and
  aggregates device self-time by HLO category (convolution, fusion,
  reduce, copy, ...), keeping the top ops with their flop rates and
  ``bound_by`` verdicts;
- cross-checks the profile against the measured wall: total profiled
  device self-time vs rounds x measured round wall-clock.

Run on the TPU (the 256 px north-star profile):
    python -m fedcrack_tpu.tools.profile_step --img 256 \
        --out chiprun_out/profile_256.json

CPU smoke (tiny shape; exercises trace + conversion wiring):
    python -m fedcrack_tpu.tools.profile_step --img 32 --steps 2 --batch 2 \
        --rounds 1 --out /tmp/profile.json
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import tempfile
import time

import jax
import numpy as np


def _aggregate_hlo_stats(xplane_paths: list[str], top_n: int) -> dict | None:
    """xprof hlo_stats -> {by_category, top_ops, total_self_time_us}.

    Returns None when xprof (an optional profiling dependency) is absent —
    the artifact then still carries the raw trace path + wall timings.
    """
    try:
        from xprof.convert import raw_to_tool_data
    except Exception:
        return None

    data, _ = raw_to_tool_data.xspace_to_tool_data(xplane_paths, "hlo_stats", {})
    table = json.loads(data)
    if not table.get("rows"):
        # CPU-backend traces carry no per-HLO device events (observed: the
        # jax profiler only populates the HLO plane on accelerator
        # backends); the artifact then records the raw trace path only.
        return None
    idx = {c["id"]: i for i, c in enumerate(table["cols"])}

    def val(row, col):
        cell = row["c"][idx[col]]
        return None if cell is None else cell.get("v")

    by_cat: dict[str, dict] = {}
    ops = []
    total_us = 0.0
    for row in table["rows"]:
        cat = str(val(row, "category") or "unknown")
        self_us = float(val(row, "total_self_time") or 0.0)
        total_us += self_us
        agg = by_cat.setdefault(cat, {"self_time_us": 0.0, "occurrences": 0})
        agg["self_time_us"] += self_us
        agg["occurrences"] += int(val(row, "occurrences") or 0)
        ops.append(
            {
                "hlo_op": str(val(row, "hlo_op_name") or "")[:120],
                "category": cat,
                "self_time_us": round(self_us, 1),
                "occurrences": int(val(row, "occurrences") or 0),
                "self_time_percent": float(val(row, "total_self_time_percent") or 0.0),
                "bound_by": val(row, "bound_by"),
                "model_gflop_per_s": val(row, "model_flop_rate"),
                "measured_memory_bw_gib_s": val(row, "measured_memory_bw"),
            }
        )
    ops.sort(key=lambda o: -o["self_time_us"])
    for cat in by_cat.values():
        cat["fraction"] = round(cat["self_time_us"] / total_us, 4) if total_us else None
        cat["self_time_us"] = round(cat["self_time_us"], 1)
    return {
        "total_self_time_us": round(total_us, 1),
        "by_category": dict(
            sorted(by_cat.items(), key=lambda kv: -kv[1]["self_time_us"])
        ),
        "top_ops": ops[:top_n],
    }


def run_profile(args) -> dict:
    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.data.synthetic import synth_crack_batch
    from fedcrack_tpu.obs.flops import mfu, train_step_flops
    from fedcrack_tpu.parallel import (
        build_federated_round,
        make_mesh,
        stack_client_data,
        stage_round_data,
    )
    from fedcrack_tpu.train.local import create_train_state

    config = ModelConfig(img_size=args.img, compute_dtype=args.dtype)
    mesh = make_mesh(1, 1)
    device = jax.devices()[0]
    round_fn = build_federated_round(mesh, config, learning_rate=1e-3, local_epochs=1)
    state0 = create_train_state(jax.random.key(args.seed), config)

    imgs, msks = synth_crack_batch(args.steps * args.batch, args.img, seed=args.seed)
    images, masks = stack_client_data([(imgs, msks)], args.steps, args.batch)
    si, sm = stage_round_data(images, masks, mesh)
    active = np.ones(1, np.float32)
    n_samp = np.full(1, float(args.steps * args.batch), np.float32)

    state = {"v": state0.variables}

    def run():
        new_vars, metrics = round_fn(state["v"], si, sm, active, n_samp)
        state["v"] = new_vars
        float(np.asarray(metrics["loss"])[0])

    run()  # compile (host-pytree signature)
    run()  # committed-device-input signature the profiled rounds use

    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="fedcrack_profile_")
    walls = []
    with jax.profiler.trace(trace_dir):
        for _ in range(args.rounds):
            t0 = time.perf_counter()
            run()
            walls.append(time.perf_counter() - t0)

    xplanes = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    )
    stats = _aggregate_hlo_stats(xplanes, args.top) if xplanes else None

    flops = train_step_flops(config, args.batch)
    wall_s = float(np.median(walls))
    step_s = wall_s / args.steps
    util = mfu(step_s, flops, device)
    out = {
        "generated_by": "fedcrack_tpu.tools.profile_step",
        "hardware": {
            "platform": device.platform,
            "device_kind": getattr(device, "device_kind", "unknown"),
        },
        "workload": {
            "img_size": args.img,
            "dtype": args.dtype,
            "steps": args.steps,
            "batch": args.batch,
            "profiled_rounds": args.rounds,
        },
        "measured": {
            "round_wall_s_median": round(wall_s, 4),
            "naive_per_step_ms": round(step_s * 1e3, 3),
            "flops_per_step": flops,
            "naive_mfu": None if util is None else round(util, 4),
            "note": (
                "naive division (includes one dispatch); cross-check against "
                "the slope-fit sweep in the BENCH artifact"
            ),
        },
        "trace_dir": trace_dir,
        "xplane_files": xplanes,
        "hlo_stats": stats,
    }
    if stats is not None and stats["total_self_time_us"] > 0:
        # Device self-time per profiled round vs measured wall: >1x gaps are
        # dispatch; the per-category fractions are of device time.
        out["measured"]["profiled_device_s_per_round"] = round(
            stats["total_self_time_us"] / 1e6 / args.rounds, 4
        )
    return out


def main(argv=None) -> int:
    from fedcrack_tpu.jaxcompat import enable_compilation_cache

    enable_compilation_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--out", required=True)
    p.add_argument("--img", type=int, default=256)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--steps", type=int, default=32)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--top", type=int, default=25)
    p.add_argument("--trace-dir", default="")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    artifact = run_profile(args)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
    if artifact["hlo_stats"] is not None:
        cats = {
            k: v["fraction"] for k, v in artifact["hlo_stats"]["by_category"].items()
        }
        print(json.dumps({"by_category_fraction": cats}))
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
