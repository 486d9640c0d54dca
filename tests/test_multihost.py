"""Multi-host bring-up (`parallel/multihost.py`, SURVEY.md §5.8).

Unit tests drive the resolution/error branches with a faked
``jax.distributed``; the slow test is the real thing — two OS processes
joined through ``jax.distributed.initialize`` over loopback (Gloo), with a
cross-process psum over a 2-device mesh spanning both.
"""

import os
import socket
import subprocess
import sys

import jax
import pytest

from fedcrack_tpu.jaxcompat import enable_compilation_cache
from fedcrack_tpu.parallel.multihost import (
    global_mesh_devices,
    initialize_if_needed,
    is_coordinator,
)


@pytest.fixture
def not_initialized(monkeypatch):
    monkeypatch.setattr(jax.distributed, "is_initialized", lambda: False, raising=False)


def test_explicit_args_must_be_complete(not_initialized):
    with pytest.raises(ValueError, match="together"):
        initialize_if_needed("10.0.0.1:9999")
    with pytest.raises(ValueError, match="together"):
        initialize_if_needed("10.0.0.1:9999", num_processes=4)
    with pytest.raises(ValueError, match="together"):
        initialize_if_needed("10.0.0.1:9999", num_processes=4, process_id=-1)


def test_env_var_resolution(not_initialized, monkeypatch):
    calls = {}
    monkeypatch.setattr(
        jax.distributed, "initialize", lambda **kw: calls.update(kw)
    )
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:9999")
    monkeypatch.setenv("JAX_NUM_PROCESSES", "4")
    monkeypatch.setenv("JAX_PROCESS_ID", "2")
    assert initialize_if_needed() is True
    assert calls == {
        "coordinator_address": "10.0.0.1:9999",
        "num_processes": 4,
        "process_id": 2,
    }


def test_env_var_incomplete_raises(not_initialized, monkeypatch):
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:9999")
    monkeypatch.delenv("JAX_NUM_PROCESSES", raising=False)
    monkeypatch.delenv("JAX_PROCESS_ID", raising=False)
    with pytest.raises(ValueError, match="together"):
        initialize_if_needed()


def test_autodetect_failure_means_single_host(not_initialized, monkeypatch):
    def raise_value_error():
        raise ValueError("no cluster metadata")

    monkeypatch.setattr(jax.distributed, "initialize", raise_value_error)
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    assert initialize_if_needed() is False


def test_already_initialized_short_circuits(monkeypatch):
    monkeypatch.setattr(jax.distributed, "is_initialized", lambda: True, raising=False)

    def boom(**kw):
        raise AssertionError("initialize must not be called again")

    monkeypatch.setattr(jax.distributed, "initialize", boom)
    assert initialize_if_needed() is True
    # and it must NOT touch jax.process_count() before deciding: doing so
    # initializes the XLA backend, after which a real initialize() raises
    # ("must be called before any JAX calls") — the bug that kept this
    # module from ever running multi-process.


def test_helpers_single_process():
    assert is_coordinator()  # process 0 by convention
    devs = global_mesh_devices()
    assert devs == sorted(devs, key=lambda d: (d.process_index, d.id))
    assert len(devs) == jax.device_count()


_WORKER = """
import sys
import jax
jax.config.update("jax_platforms", "cpu")
pid, n, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
sys.path.insert(0, {repo!r})
from fedcrack_tpu.parallel.multihost import (
    global_mesh_devices, initialize_if_needed, is_coordinator,
)
assert initialize_if_needed(f"127.0.0.1:{{port}}", n, pid)
assert jax.process_count() == n, jax.process_count()
assert is_coordinator() == (pid == 0)
devs = global_mesh_devices()
assert len(devs) == n, devs
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
mesh = Mesh(devs, ("clients",))
def f(v):
    return jax.lax.psum(v, "clients")
y = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(None), out_specs=P(None)))(
    jnp.ones((1,), jnp.float32)
)
total = float(np.asarray(jax.device_get(y))[0])
assert total == float(n), total
print(f"OK pid={{pid}} psum={{total}}")
"""


def _launch_two_workers(script_text: str, tmp_path, timeout: float) -> list[str]:
    """Run the worker script as 2 coordinated OS processes over a free
    loopback port; return their outputs. Launch rules: strip every
    JAX_/XLA_/PYTHONPATH env var (the workers pin their own platform and
    device count), share the suite's compilation cache, and never orphan a
    worker blocked in jax.distributed.initialize()."""
    script = tmp_path / "worker.py"
    script.write_text(script_text)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = {
        k: v
        for k, v in os.environ.items()
        if not k.startswith(("JAX_", "XLA_", "PYTHONPATH"))
    }
    env["JAX_COMPILATION_CACHE_DIR"] = enable_compilation_cache()
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(pid), "2", str(port)],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out
    return outs


_ROUND_WORKER = """
import sys
sys.path.insert(0, {repo!r})
import jax
from fedcrack_tpu.jaxcompat import ensure_cpu_devices
ensure_cpu_devices(4)
pid, n, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from fedcrack_tpu.configs import ModelConfig
from fedcrack_tpu.data.synthetic import synth_crack_batch
from fedcrack_tpu.parallel import build_federated_round, stack_client_data
from fedcrack_tpu.parallel.multihost import global_mesh_devices, initialize_if_needed
from fedcrack_tpu.train.local import create_train_state

assert initialize_if_needed(f"127.0.0.1:{{port}}", n, pid)
assert jax.device_count() == 4 * n
devs = global_mesh_devices()
mesh = Mesh(np.asarray(devs, dtype=object).reshape(2 * n, 2), ("clients", "batch"))
tiny = ModelConfig(img_size=16, stem_features=4, encoder_features=(8,),
                   decoder_features=(8, 4))
steps, batch = 2, 4
# Each process synthesizes only ITS clients' shards (client index = global).
local = [synth_crack_batch(steps * batch, img_size=16, seed=c)
         for c in (2 * pid, 2 * pid + 1)]
li, lm = stack_client_data(local, steps, batch)
data_sharding = NamedSharding(mesh, P("clients", None, "batch"))
images = jax.make_array_from_process_local_data(data_sharding, li)
masks = jax.make_array_from_process_local_data(data_sharding, lm)
variables = jax.device_put(create_train_state(jax.random.key(0), tiny).variables,
                           NamedSharding(mesh, P()))
cshard = NamedSharding(mesh, P("clients"))
active = jax.device_put(np.ones(2 * n, np.float32), cshard)
n_samples = jax.device_put(np.full(2 * n, float(steps * batch), np.float32), cshard)
round_fn = build_federated_round(mesh, tiny, learning_rate=1e-3, local_epochs=1)
new_vars, metrics = round_fn(variables, images, masks, active, n_samples)
jax.block_until_ready(new_vars)
local_losses = np.asarray(metrics["loss"].addressable_shards[0].data)
assert np.all(np.isfinite(local_losses)), local_losses
leaf = jax.tree_util.tree_leaves(new_vars["params"])[1]
leafsum = float(np.asarray(leaf.addressable_shards[0].data, np.float64).sum())
print(f"OK pid={{pid}} leafsum={{leafsum:.9e}}")
"""


@pytest.mark.slow
def test_two_process_federated_round(tmp_path):
    """The full §5.8 capability: ONE federated round (4 clients x 2-way
    intra-client DP over 8 devices) spanning TWO OS processes — the FedAvg
    psum crosses the process boundary, each process stages only its own
    clients' data, and the resulting global model is identical on every
    process AND identical to the same round run single-process."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    outs = _launch_two_workers(_ROUND_WORKER.format(repo=repo), tmp_path, timeout=300)
    sums = {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("OK pid="):
                pid = int(line.split("pid=")[1].split()[0])
                sums[pid] = float(line.split("leafsum=")[1])
    assert set(sums) == {0, 1}, outs
    # psum-FedAvg must leave every process with the identical global model.
    assert sums[0] == sums[1], sums

    # Golden cross-check: the same round on this process's own 8-device mesh.
    import numpy as np

    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.data.synthetic import synth_crack_batch
    from fedcrack_tpu.parallel import build_federated_round, make_mesh, stack_client_data
    from fedcrack_tpu.train.local import create_train_state

    tiny = ModelConfig(
        img_size=16, stem_features=4, encoder_features=(8,), decoder_features=(8, 4)
    )
    steps, batch = 2, 4
    per_client = [synth_crack_batch(steps * batch, img_size=16, seed=c) for c in range(4)]
    images, masks = stack_client_data(per_client, steps, batch)
    variables = create_train_state(jax.random.key(0), tiny).variables
    round_fn = build_federated_round(make_mesh(4, 2), tiny, learning_rate=1e-3, local_epochs=1)
    new_vars, _ = round_fn(
        variables, images, masks, np.ones(4, np.float32),
        np.full(4, float(steps * batch), np.float32),
    )
    leaf = jax.tree_util.tree_leaves(new_vars["params"])[1]
    golden = float(np.asarray(leaf, np.float64).sum())
    assert sums[0] == pytest.approx(golden, rel=1e-5)


@pytest.mark.slow
def test_two_process_distributed_smoke(tmp_path):
    """The real §5.8 capability check: 2 OS processes form one logical JAX
    job (process_count()==2) and a psum crosses the process boundary."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    outs = _launch_two_workers(_WORKER.format(repo=repo), tmp_path, timeout=180)
    assert any("OK pid=0 psum=2.0" in o for o in outs), outs
    assert any("OK pid=1 psum=2.0" in o for o in outs), outs
