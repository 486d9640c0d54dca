"""What the chip really holds while a cell's round runs, by the headroom left.

    python3 benchmark/study/memory_headroom.py <workload> [<workload> ...]

The TPU allocator's ``peak_bytes_in_use`` reads a round's buffers (its two
epoch slabs and the model) and not the scratch of the running program, so a
run reports ``memory_peak_bytes`` as that reading plus the loaded
executable's scratch as XLA states it. This proves or refutes the sum. In one
process a cell: its round program runs once with nothing beside it (compile,
load); then a ballast buffer of growing size is put on the chip and a round
is run beside it. If the scratch is really taken while the round runs, the
round fails (``RESOURCE_EXHAUSTED``) as soon as ballast + buffers + scratch
pass the chip's memory, and runs below that. One JSON line an attempt goes to
``chiprun_out/memory_headroom.jsonl`` and to standard output.
"""

import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [ROOT, BENCH_DIR]

import jax
import jax.numpy as jnp

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

from lib import federated_rounds as fr
from lib.compile_log import CompileLog
from run import load_spec

STEP = 2**28  # 0.25 GiB


def main():
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", "memory_headroom.jsonl"), "a")
    compiles = CompileLog()
    for workload in sys.argv[1:]:
        spec = load_spec(workload)
        spec["traffic"] = dict(spec["traffic"], checked_rounds=1)
        used = jax.devices()[: spec["workload"]["chips"]]
        if used[0].platform != "tpu":
            raise SystemExit("memory headroom: needs the chip")
        cell = fr.Cell(spec, 7, used)

        def attempt(ballast_bytes):
            ballast = None
            row = {"workload": workload, "ballast_bytes": ballast_bytes}
            try:
                if ballast_bytes:
                    ballast = jax.device_put(jnp.zeros((ballast_bytes,), jnp.uint8), used[0])
                    ballast.block_until_ready()
                row["in_use_with_ballast"] = used[0].memory_stats().get("bytes_in_use")
                t = time.perf_counter()
                cell.drive(0.0, None, time.perf_counter(), compiles)
                row["ran"], row["seconds"] = True, time.perf_counter() - t
            except Exception as e:  # the allocator's refusal is the reading
                row["ran"], row["error"] = False, f"{type(e).__name__}: {str(e)[:300]}"
            del ballast
            stats = used[0].memory_stats() or {}
            row["stats"] = {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit", "largest_free_block_bytes", "bytes_reserved")}
            out.write(json.dumps(row) + "\n")
            out.flush()
            print(json.dumps(row), flush=True)
            return row["ran"]

        attempt(0)
        scratch = max(int(e.get_compiled_memory_stats().temp_size_in_bytes) for e in used[0].client.live_executables())
        stats = used[0].memory_stats()
        limit, buffers = int(stats["bytes_limit"]), int(stats["peak_bytes_in_use"])
        expected = limit - buffers - scratch
        print(json.dumps({"workload": workload, "bytes_limit": limit, "allocator_peak": buffers, "compiled_scratch": scratch, "expected_headroom": expected}), flush=True)
        # Around the expected headroom in quarter-GiB steps, from below; then,
        # if every one ran, on up to the whole of what the allocator has free.
        first = max(expected - 3 * STEP, STEP)
        sizes = [first + i * STEP for i in range(7)] + [limit - buffers - 2 * STEP]
        for size in sizes:
            if not attempt(int(size)):
                break
        cell.round_fn = None
        del cell


if __name__ == "__main__":
    main()
