"""Rehearsal without the chip for a ``federated_causal_lm_rounds`` cell:
compile its round program for a described v5e (``v5e:2x2``) at the real size
and print its memory analysis (``compile_for_v5e.py``'s twin for the text
shapes; nothing runs, sizes only).

    JAX_PLATFORMS=cpu python3 benchmark/study/compile_causal_lm_for_v5e.py [workload] [key=value ...]

``key=value`` overrides a key of the configuration file (``num_nextn_predict_layers=0``).
The compiler here does not hold a program to the chip's 16 GB: only the
chip's own compile says whether it fits (PERF.md section 4).
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

from fedcrack_tpu.parallel import build_federated_round

from compile_for_v5e import _find_jitted


def main():
    from lib import federated_causal_lm_rounds as fc
    from run import load_spec

    jax.config.update("jax_enable_compilation_cache", False)
    name = next((a for a in sys.argv[1:] if "=" not in a), "joyai_round_l8192_b1_1chip")
    spec = load_spec(name)
    config, traffic = spec["config"], spec["traffic"]
    config.update({k: json.loads(v) for k, v in (a.split("=", 1) for a in sys.argv[1:] if "=" in a)})
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    clients, inner = traffic["mesh"]
    batch = config["batch_size"]
    steps = config["train_samples"] // batch
    mesh = Mesh(np.asarray(topo.devices[: clients * inner], dtype=object).reshape(clients, inner), ("clients", "batch"))
    program = fc.program_config(config)
    round_fn = build_federated_round(
        mesh, program, learning_rate=config["optimizer"]["learning_rate"], local_epochs=config["local_epochs"],
    )
    # On a CPU host the model picks its dense paths: steer it to the kernels
    # here, in the script (the program has no option for it).
    object.__setattr__(round_fn.task, "kernels", "pallas")
    jitted = _find_jitted(round_fn)
    ref = fc.load_reference(config)
    shapes = jax.eval_shape(lambda: ref.init_variables(jnp.zeros((2,), jnp.uint32), fc.reference_config(config)))
    rep = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P("clients", None, "batch"))
    per_client = NamedSharding(mesh, P("clients"))
    pair = (clients, steps, batch, program.seq_len)
    args = (
        jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep), shapes),
        jax.ShapeDtypeStruct(pair, jnp.int32, sharding=data), jax.ShapeDtypeStruct(pair, jnp.float32, sharding=data),
        jax.ShapeDtypeStruct((clients,), jnp.float32, sharding=per_client),
        jax.ShapeDtypeStruct((clients,), jnp.float32, sharding=per_client),
    )
    compiled = jitted.lower(*args).compile()
    m = compiled.memory_analysis()
    text = compiled.as_text()
    print(json.dumps({
        "workload": name, "temp_bytes": m.temp_size_in_bytes, "argument_bytes": m.argument_size_in_bytes,
        "output_bytes": m.output_size_in_bytes, "alias_bytes": m.alias_size_in_bytes,
        "generated_code_bytes": m.generated_code_size_in_bytes, "kernels": text.count("tpu_custom_call"),
        "parameters": int(sum(np.prod(s.shape) for s in jax.tree_util.tree_leaves(shapes))),
    }))


if __name__ == "__main__":
    main()
