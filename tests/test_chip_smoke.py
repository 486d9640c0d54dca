"""chip_smoke.py's contract off the chip, and the process configuration it
relies on (PR 21 bring-up): no accelerator -> non-zero and no result line; a
failed phase -> non-zero and no result line; the compile-cache rule; the
coordinator pinned to the CPU backend.

What the smoke proves ON the chip cannot run here; the slow-marked rehearsal
runs the same phases at a tiny size under the Pallas interpreter.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, env_over, timeout):
    env = {k: v for k, v in os.environ.items() if not k.startswith("FEDCRACK_")}
    env.update(env_over)
    return subprocess.run(
        [sys.executable, *args],
        cwd=ROOT,
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def test_no_accelerator_exits_nonzero_without_result():
    """Seconds, no compile: the check must fail where JAX finds no chip."""
    proc = _run([SMOKE], {"JAX_PLATFORMS": "cpu"}, timeout=120)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.fixture
def smoke(monkeypatch):
    """chip_smoke as a module, its phases replaced by instant stand-ins and
    its process-global JAX listeners not installed."""
    monkeypatch.syspath_prepend(ROOT)
    import chip_smoke

    class _NoCompileLog:
        def summary(self):
            return {}

    monkeypatch.setattr(chip_smoke, "CompileLog", _NoCompileLog)
    for name in chip_smoke.PHASES:
        monkeypatch.setattr(chip_smoke, f"phase_{name}", lambda *a: {})
    return chip_smoke


def test_all_phases_passing_prints_result_last(smoke, capsys):
    assert smoke.main(["--rehearse-cpu"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert result["ok"] is True and result["rehearsal"] is True
    assert result["device"] == {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }
    for name in smoke.PHASES:
        assert any(line.startswith(f"[pass] {name} ") for line in out)


@pytest.mark.parametrize("broken", ["train", "federate", "serve", "kernels"])
def test_failure_in_any_phase_is_nonzero_and_later_phases_still_run(
    smoke, monkeypatch, capsys, broken
):
    def boom(*a):
        raise RuntimeError("injected")

    monkeypatch.setattr(smoke, f"phase_{broken}", boom)
    assert smoke.main(["--rehearse-cpu"]) != 0
    captured = capsys.readouterr()
    assert f"[FAIL] {broken} " in captured.out
    assert "injected" in captured.err  # the traceback is reported, not eaten
    assert '"ok"' not in captured.out
    for name in smoke.PHASES:
        if name != broken:
            assert f"[pass] {name} " in captured.out


@pytest.mark.slow
def test_cpu_rehearsal_passes_every_phase():
    proc = _run([SMOKE, "--rehearse-cpu"], {"JAX_PLATFORMS": "cpu"}, timeout=900)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1])["rehearsal"] is True
    for name in ("train", "federate", "serve", "kernels"):
        assert any(line.startswith(f"[pass] {name} ") for line in lines)


# ---- the compile-cache rule ----


def test_cache_rule_placed_from_outside_sets_no_directory(monkeypatch):
    from fedcrack_tpu import jaxcompat

    updates = []
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/by/the/caller")
    assert jaxcompat.enable_compilation_cache() == "/placed/by/the/caller"
    assert updates == []


def test_cache_rule_default_is_the_fixed_gitignored_checkout_path(monkeypatch):
    from fedcrack_tpu import jaxcompat

    updates = []
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert jaxcompat.enable_compilation_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_only_the_helper_names_the_cache_directory():
    """No second code path may set a directory behind the rule's back."""
    offenders = []
    for top in ("fedcrack_tpu", "chip_smoke.py", "conftest.py",
                "__graft_entry__.py"):
        path = os.path.join(ROOT, top)
        files = (
            [os.path.join(d, f) for d, _, fs in os.walk(path) for f in fs]
            if os.path.isdir(path)
            else [path]
        )
        for f in files:
            if not f.endswith(".py") or f.endswith("jaxcompat.py"):
                continue
            with open(f) as fh:
                if "compilation_cache_dir" in fh.read():
                    offenders.append(os.path.relpath(f, ROOT))
    assert offenders == []


# ---- one process for each chip ----


_COORDINATOR = """
import sys
sys.path.insert(0, {root!r})
from fedcrack_tpu import server

class _Final:
    history, cohort = [], set()

class _StubServer:
    eval_history = []
    def __init__(self, *a, **k): pass
    async def serve_until_finished(self): return _Final()

class _State:
    variables = {{}}

server.FedServer = _StubServer
server.create_train_state = lambda *a, **k: _State()
assert server.main(["--rounds", "1"]) == 0
import jax
print("BACKEND", jax.config.jax_platforms, jax.default_backend())
"""


def test_coordinator_process_is_pinned_to_the_cpu_backend(tmp_path):
    """The environment asks for the accelerator; the coordinator must not
    take it. (Were the pin missing, this sandbox has no TPU to give and
    default_backend() would raise instead.)"""
    script = tmp_path / "coordinator.py"
    script.write_text(_COORDINATOR.format(root=ROOT))
    proc = _run([str(script)], {"JAX_PLATFORMS": "tpu"}, timeout=120)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "BACKEND cpu cpu" in proc.stdout
