"""How this program configures the one JAX it runs on (0.9.0).

Not a version shim: callers use ``jax.shard_map``, ``lax.pcast`` and
``jax.typeof`` directly. What lives here is process-level configuration that
several entry points share and that must happen BEFORE first backend use:

- :func:`ensure_cpu_devices` — pin this process to the CPU backend (with
  ``n`` virtual devices for the mesh tests). The gRPC coordinator and the
  load generator use it so they never claim the accelerator: a chip belongs
  to one process, and theirs is not the one that computes.
- :func:`enable_compilation_cache` — the one rule for where compiled
  programs persist.
- :func:`describe_devices` — the start-up line naming platform,
  ``device_kind`` and device count, so a run that landed on the CPU says so
  first.
- :func:`fp8_supported` — whether fp8 (e4m3) codes round-trip on this
  backend (the serve plane's ``kernel_plane="fp8"`` resolves through it,
  visibly — serve/engine.py).
"""

from __future__ import annotations

import logging
import os

import jax

# The in-checkout compile cache: fixed (the path is part of the cache key's
# neighbourhood — a directory that moves never hits), gitignored, and inside
# the tree so the chip tool's copy of the tree carries nothing stale from
# /tmp and nothing leaks outside the repo.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def ensure_cpu_devices(n: int | None = None) -> None:
    """Route this process onto the CPU host platform with ``n`` virtual
    devices (``n=None`` leaves the device count alone).

    Must run before first backend use; once backends are initialized the
    config updates raise RuntimeError and this is a no-op (callers that need
    a hard guarantee check ``jax.default_backend()`` afterwards — which
    itself initializes the backend, so only do that LAST). Note that
    ``jax.devices("cpu")`` is NOT a substitute: it initializes every
    backend, the accelerator included.
    """
    try:
        if n is not None:
            # Count first: it is the update that raises RuntimeError once
            # backends are initialized, leaving jax_platforms untouched.
            jax.config.update("jax_num_cpu_devices", n)
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass  # backends already initialized; run where we are


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    One rule: if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read
    it and this sets NO directory in code — whoever launched the process
    placed the cache. Otherwise the cache goes to :data:`COMPILE_CACHE_DIR`.
    Call at entry-point start, before anything compiles (the cache latches
    its directory at the first compile). JAX's own floors stay as they are:
    programs that compile in under a second are not worth a file.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def describe_devices() -> str:
    """``platform=… device_kind=… count=…`` as JAX reports it. Entry points
    that compute log or print this once at start, so a run that landed on
    the CPU says so in its first lines. Initializes the backend — call where
    the process is about to compute anyway."""
    devices = jax.devices()
    return (
        f"platform={devices[0].platform} "
        f"device_kind={devices[0].device_kind} count={len(devices)}"
    )


_FP8_PROBE: bool | None = None


def fp8_supported() -> bool:
    """Whether fp8 codes actually round-trip on this backend (a tiny cast
    runs and comes back finite) — probed once, cached. The engine resolves
    ``kernel_plane="fp8"`` through this and reports the outcome
    (``effective_kernel_plane``, a WARNING on degrade). Tests monkeypatch
    this function to pin the degraded path, so callers must resolve it
    DYNAMICALLY (``jaxcompat.fp8_supported()``, never a cached import)."""
    global _FP8_PROBE
    if _FP8_PROBE is None:
        import jax.numpy as jnp
        import numpy as np

        try:
            got = np.asarray(
                jnp.asarray([1.0, -2.5], jnp.float32)
                .astype(jnp.float8_e4m3fn)
                .astype(jnp.float32)
            )
        except jax.errors.JaxRuntimeError as e:
            # The backend's compiler refused the cast: that is the answer,
            # and the caller must be able to see why.
            logging.getLogger(__name__).warning(
                "fp8 probe refused by backend %s: %s", jax.default_backend(), e
            )
            _FP8_PROBE = False
        else:
            _FP8_PROBE = bool(np.all(np.isfinite(got)))
    return _FP8_PROBE
