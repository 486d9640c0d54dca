"""Rehearsal without the chip: compile each cell's round program for a
described v5e (``v5e:2x2``) at the real size and print its memory analysis.

    JAX_PLATFORMS=cpu python3 benchmark/study/compile_for_v5e.py [workload ...]

Nothing runs; sizes only. The round program's jitted function is dug out of
the closure ``build_federated_round`` returns, since the program places its
own arguments and a described device can hold none.
"""

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [ROOT, BENCH_DIR]

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedcrack_tpu.configs import ModelConfig
from fedcrack_tpu.parallel import build_federated_round


def _find_jitted(fn, depth=0):
    for cell in fn.__closure__ or ():
        v = cell.cell_contents
        if hasattr(v, "lower") and hasattr(v, "trace"):
            return v
        if callable(v) and getattr(v, "__closure__", None) and depth < 3:
            found = _find_jitted(v, depth + 1)
            if found is not None:
                return found
    return None


def main():
    jax.config.update("jax_enable_compilation_cache", False)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = sys.argv[1:] or [w["name"] for w in bench["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    from lib.federated_rounds import load_reference

    for w in (w for w in bench["workloads"] if w["name"] in names):
        entry = next(c for c in bench["configs"] if c["name"] == w["config"])
        config = json.load(open(os.path.join(ROOT, entry["file"])))
        traffic = json.load(open(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json")))
        clients, inner = traffic["mesh"]
        model, batch = config["model"], config["batch_size"]
        steps = config["train_samples"] // batch
        mesh = Mesh(np.asarray(topo.devices[: clients * inner], dtype=object).reshape(clients, inner), ("clients", "batch"))
        round_fn = build_federated_round(
            mesh, ModelConfig(**{k: tuple(v) if isinstance(v, list) else v for k, v in model.items()}),
            learning_rate=config["optimizer"]["learning_rate"], local_epochs=config["local_epochs"],
        )
        jitted = _find_jitted(round_fn)
        ref = load_reference(config)
        shapes = jax.eval_shape(lambda: ref.init_variables(jnp.zeros((2,), jnp.uint32), model))
        rep = NamedSharding(mesh, P())
        data = NamedSharding(mesh, P("clients", None, "batch"))
        per_client = NamedSharding(mesh, P("clients"))
        size = model["img_size"]
        args = (
            jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep), shapes),
            jax.ShapeDtypeStruct((clients, steps, batch, size, size, 3), jnp.uint8, sharding=data),
            jax.ShapeDtypeStruct((clients, steps, batch, size, size, 1), jnp.uint8, sharding=data),
            jax.ShapeDtypeStruct((clients,), jnp.float32, sharding=per_client),
            jax.ShapeDtypeStruct((clients,), jnp.float32, sharding=per_client),
        )
        compiled = jitted.lower(*args).compile()
        m = compiled.memory_analysis()
        text = compiled.as_text()
        print(json.dumps({
            "workload": w["name"], "temp_bytes": m.temp_size_in_bytes, "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes, "generated_code_bytes": m.generated_code_size_in_bytes,
            "all_gathers": text.count(" all-gather("), "all_reduces": text.count(" all-reduce("),
        }))


if __name__ == "__main__":
    main()
