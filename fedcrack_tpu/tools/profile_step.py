"""What each named scope of the round program costs at one shape.

Drives the production path (``parallel.build_federated_round`` under
``parallel.run_mesh_federation``, uint8 transport, a reshuffled epoch
restaged under every round) for a few rounds, traces a slice of
``--slice-s`` seconds that straddles a round boundary through the public
``jax.profiler.start_trace``, and reduces it with ``obs/devtrace.py``:

- the per-scope table (``by_scope``: seconds a step, share of busy time and
  bytes a second for each ``jax.named_scope`` of the step, the model's
  blocks and the fold, forward and backward apart), joined to the trace's
  instruction names through the loaded executable's HLO text;
- the driver's host spans (``driver.round`` / ``dispatch`` / ``feed`` /
  ``stage`` / ``barrier`` / ``handoff``) as the same trace holds them on
  ``/host:CPU``, beside ``RoundRecord.host_s`` of every round.

A device trace exists only on an accelerator: on the CPU backend the
artifact carries the host spans and counters and ``by_scope`` is null.

On the TPU, the benchmark's two shapes:
    python -m fedcrack_tpu.tools.profile_step --img 256 --batch 32 --steps 194 \\
        --out chiprun_out/profile_256.json
    python -m fedcrack_tpu.tools.profile_step --img 512 --batch 16 --steps 48 \\
        --out chiprun_out/profile_512.json

The second family (``--family sdar_moe``: block-diffusion training of the
mixture-of-experts share at its published widths; the table's blocks are
``attn_proj``, ``blockdiff_attn``, ``router``, ``moe_dispatch``,
``moe_experts``, ``moe_combine``, ``embed``, ``lm_head``, each summed over
the layers), at the benchmark's shape:
    python -m fedcrack_tpu.tools.profile_step --family sdar_moe --seq-len 4096 \\
        --layers 4 --batch 2 --steps 16 --slice-s 3 --out chiprun_out/profile_sdar.json

The third (``--family joyai_llm_flash``: next-token training of the
latent-attention share; blocks ``mla_proj``, ``mla_attn``, ``dense_mlp``,
``router``, ``moe_dispatch``, ``moe_experts``, ``moe_combine``,
``shared_expert``, ``mtp_merge``, ``embed``, ``lm_head``), at the benchmark's
shape:
    python -m fedcrack_tpu.tools.profile_step --family joyai_llm_flash --seq-len 8192 \\
        --layers 5 --batch 1 --steps 10 --slice-s 4 --out chiprun_out/profile_joyai.json

The fourth (``--family qwen3_next``: next-token training of the hybrid
linear-attention share; blocks ``gdn_proj``, ``gdn_conv``, ``gdn_rule``,
``gattn_proj``, ``gattn``, ``router``, ``moe_dispatch``, ``moe_experts``,
``moe_combine``, ``shared_expert``, ``embed``, ``lm_head``), at the
benchmark's shape:
    python -m fedcrack_tpu.tools.profile_step --family qwen3_next --seq-len 8192 \\
        --layers 4 --batch 2 --steps 8 --slice-s 4 --out chiprun_out/profile_qwen3next.json

CPU smoke (tiny shape; exercises the trace and the join):
    python -m fedcrack_tpu.tools.profile_step --img 32 --steps 2 --batch 2 \\
        --out /tmp/profile.json
    python -m fedcrack_tpu.tools.profile_step --family sdar_moe --tiny --steps 2 \\
        --batch 2 --out /tmp/profile_sdar.json
    python -m fedcrack_tpu.tools.profile_step --family joyai_llm_flash --tiny --steps 2 \\
        --batch 2 --out /tmp/profile_joyai.json
    python -m fedcrack_tpu.tools.profile_step --family qwen3_next --tiny --steps 2 \\
        --batch 2 --out /tmp/profile_qwen3next.json
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import tempfile
import threading
import time

import jax
import numpy as np

BASE_SAMPLES = 128  # distinct synthetic samples; the epoch cycles them


def _epoch_pool(n: int, img: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """``n`` uint8 samples (the transport the deployment stages) cycled from
    ``BASE_SAMPLES`` synthetic ones: what a step costs does not depend on
    the pixels."""
    from fedcrack_tpu.data.synthetic import synth_crack_batch

    images, masks = synth_crack_batch(min(n, BASE_SAMPLES), img, seed=seed)
    images = np.clip(np.rint(images * 255.0), 0, 255).astype(np.uint8)
    idx = np.resize(np.arange(images.shape[0]), n)
    return images[idx], masks.astype(np.uint8)[idx]


def _text_config(args):
    """A text family's configuration: the published widths (the dataclass's
    defaults), or the tests' small ones under ``--tiny``."""
    from fedcrack_tpu.configs import GdnMoeConfig, MlaMoeConfig, SdarMoeConfig

    if args.family == "qwen3_next":
        if args.tiny:
            return GdnMoeConfig(
                hidden_size=64, num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=16,
                moe_intermediate_size=32, shared_expert_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
                first_expert=2, experts_held=2, vocab_held=64, seq_len=128, compute_dtype=args.dtype,
            )
        return GdnMoeConfig(seq_len=args.seq_len, num_hidden_layers=args.layers, compute_dtype=args.dtype)
    if args.family == "joyai_llm_flash":
        if args.tiny:
            return MlaMoeConfig(
                hidden_size=64, num_hidden_layers=3, num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
                n_routed_experts=8, num_experts_per_tok=2, first_expert=2, experts_held=2, vocab_held=64, seq_len=32,
                compute_dtype=args.dtype,
            )
        return MlaMoeConfig(seq_len=args.seq_len, num_hidden_layers=args.layers, compute_dtype=args.dtype)
    if args.tiny:
        return SdarMoeConfig(
            hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, first_expert=2, experts_held=2,
            vocab_held=64, block_length=4, seq_len=32, compute_dtype=args.dtype,
        )
    return SdarMoeConfig(seq_len=args.seq_len, num_hidden_layers=args.layers, compute_dtype=args.dtype)


def run_profile(args) -> dict:
    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.obs import devtrace
    from fedcrack_tpu.parallel import (
        build_federated_round,
        make_mesh,
        run_mesh_federation,
        shuffled_epoch_data,
    )

    family = getattr(args, "family", "resunet")
    text = family != "resunet"
    config = _text_config(args) if text else ModelConfig(img_size=args.img, compute_dtype=args.dtype)
    mesh = make_mesh(1, 1)
    device = jax.devices()[0]
    round_fn = build_federated_round(mesh, config, learning_rate=1e-5 if text else 1e-3, local_epochs=1)
    task = round_fn.task
    variables = task.init(jax.random.key(args.seed))
    rng = np.random.default_rng(args.seed)
    active = np.ones(1, np.float32)
    n_samples = np.full(1, float(args.steps * args.batch), np.float32)
    if text:
        from fedcrack_tpu.data.textdiff import stage_pair

        # Block diffusion keeps its last row for the mask token and draws noise;
        # the causal families have neither.
        top, block_length = (config.mask_token, config.block_length) if family == "sdar_moe" else (config.vocab_held, None)
        sequences = rng.integers(0, top, (1, args.steps * args.batch, config.seq_len), dtype=np.int32)

        def data_fn(r):
            ids, weight = stage_pair(sequences, args.steps, args.batch, block_length, rng)
            return ids, weight, active, n_samples
    else:
        pool_i, pool_m = _epoch_pool(args.steps * args.batch, args.img, args.seed)

        def data_fn(r):
            images, masks = shuffled_epoch_data(pool_i, pool_m, args.steps, args.batch, rng)
            return images, masks, active, n_samples

    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="fedcrack_profile_")
    timers: list[threading.Timer] = []
    traced = {}

    # The device's events and the program's TraceAnnotation spans; Python's
    # own tracer stays off (it records every call of every thread: minutes
    # to write, and it names idle gaps after the timer thread's wait).
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1

    def start():
        traced["start"] = time.perf_counter()
        jax.profiler.start_trace(trace_dir, profiler_options=options)

    def stop():
        traced["seconds"] = time.perf_counter() - traced["start"]
        jax.profiler.stop_trace()  # writes the file, seconds for half a second of trace
        traced["write_s"] = time.perf_counter() - traced["start"] - traced["seconds"]

    def on_round(record, _):
        # The last warm round gives the round's length: the slice opens half
        # of --slice-s before the next round's end and closes as much after.
        if record.round_idx == args.warm_rounds - 1:
            lead = max(record.wall_clock_s - args.slice_s / 2, 0.0)
            timers.extend((threading.Timer(lead, start), threading.Timer(lead + args.slice_s, stop)))
            for t in timers:
                t.start()

    _, records = run_mesh_federation(
        round_fn, variables, data_fn, args.warm_rounds + 2, mesh, on_round=on_round
    )
    for t in timers:
        t.join()
    hlo_text = devtrace.loaded_hlo_text(task.program_name)

    xplanes = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    table, gaps, spans, why = None, None, {}, None
    if xplanes:
        profile = jax.profiler.ProfileData.from_file(xplanes[-1])
        spans = devtrace.host_spans(profile)
        try:
            table = devtrace.by_scope(profile, hlo_text, task)
            gaps = devtrace.idle_gaps(profile)
        except ValueError as e:  # the CPU backend records no device plane
            why = str(e)

    last = records[-1]
    curve = np.asarray(last.metrics["step_loss"])
    return {
        "generated_by": "fedcrack_tpu.tools.profile_step",
        "hardware": {
            "platform": device.platform,
            "device_kind": getattr(device, "device_kind", "unknown"),
        },
        "workload": {
            "family": family,
            "img_size": None if text else args.img, "seq_len": config.seq_len if text else None,
            "layers": config.num_hidden_layers if text else None, "dtype": args.dtype, "steps": args.steps,
            "batch": args.batch, "warm_rounds": args.warm_rounds,
            "transport": "int32 ids, float32 weights" if text else "uint8",
        },
        "rounds": [
            {
                "round": r.round_idx, "wall_clock_s": r.wall_clock_s, "data_fn_s": r.data_fn_s,
                "staging_s": r.staging_s, "host_s": r.host_s, "stage": r.stage, "proc": r.proc,
                "device_memory": r.device_memory,
            }
            for r in records
        ],
        "step_loss": {
            "shape": list(curve.shape), "finite": bool(np.all(np.isfinite(curve))),
            "last_epoch_mean_minus_loss": float(np.max(np.abs(curve[:, -1].mean(-1) - np.asarray(last.metrics["loss"])))),
            "first_steps": curve[0, 0, :8].tolist(),
        },
        "slice": {
            "asked_s": args.slice_s, "seconds": traced.get("seconds"), "write_s": traced.get("write_s"),
            "xplane": xplanes[-1] if xplanes else None,
        },
        "host_spans": spans,
        "by_scope": table,
        "by_scope_missing": why,
        "idle_gaps": gaps,
    }


def format_table(artifact: dict) -> str:
    lines = []
    table = artifact["by_scope"]
    if table is not None:
        lines.append(
            f"traced slice: {table['steps']} steps, busy {table['busy_s']:.4f} s "
            f"(union {table['busy_union_s']:.4f} s), unscoped {100 * table['unscoped_share']:.2f}%"
        )
        lines.append(f"{'scope':<14}{'phase':<7}{'ms/step':>9}{'ms/slice':>10}{'% busy':>8}{'HBM GB/s':>10}")
        for row in table["rows"]:
            if row["per"] == "round":
                per_step = "a round"
            else:  # null where no operation recurs in the slice: no step to divide by
                per_step = "-" if row["seconds_per_step"] is None else f"{1e3 * row['seconds_per_step']:.3f}"
            lines.append(
                f"{row['scope'] or '(unscoped)':<14}{row['phase']:<7}{per_step:>9}{1e3 * row['seconds']:>10.3f}"
                f"{100 * row['share_of_busy']:>8.2f}{row['gbytes_per_s']:>10.1f}"
            )
        lines.append("unscoped: " + ", ".join(f"{name} {1e3 * sec:.3f} ms" for name, sec in table["unscoped_ops"]))
        lines.append("idle gaps of 0.1 ms and more: " + ", ".join(
            f"{1e3 * g['seconds']:.3f} ms under {g['host']} ({100 * g['driver_share']:.0f}% inside driver.* spans)"
            for g in artifact["idle_gaps"]
        ))
    else:
        lines.append(f"no per-scope table: {artifact['by_scope_missing']}")
    lines.append(f"{'host span':<18}{'count':>6}{'mean ms':>10}")
    for name, row in sorted(artifact["host_spans"].items()):
        lines.append(f"{name:<18}{row['count']:>6}{1e3 * row['seconds'] / row['count']:>10.3f}")
    return "\n".join(lines)


def main(argv=None) -> int:
    from fedcrack_tpu.jaxcompat import enable_compilation_cache

    enable_compilation_cache()
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--out", required=True)
    p.add_argument("--family", choices=("resunet", "sdar_moe", "joyai_llm_flash", "qwen3_next"), default="resunet",
                   help="which model family's round to profile; the task follows from it")
    p.add_argument("--img", type=int, default=256)
    p.add_argument("--seq-len", type=int, default=4096, help="text families: tokens a sequence (L; sdar_moe reads 2L)")
    p.add_argument("--layers", type=int, default=4, help="text families: layers held")
    p.add_argument("--tiny", action="store_true",
                   help="a text family at the tests' widths (hidden 64, 8 experts of which 2 held, vocabulary 64, L 32)")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--steps", type=int, default=32)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--warm-rounds", type=int, default=2, help="rounds before the traced one (the first compiles)")
    p.add_argument("--slice-s", type=float, default=0.5, help="seconds of trace, centred on a round boundary")
    p.add_argument("--trace-dir", default="")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    if args.warm_rounds < 2:
        p.error("--warm-rounds must be at least 2: the first compiles, the last one times the round")

    artifact = run_profile(args)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
    print(format_table(artifact))
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
