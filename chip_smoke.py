"""Chip smoke: the main path of tpu-fedcrack, once, on the accelerator.

    python chip_smoke.py            # on a machine with one or more TPU chips

One process (a chip belongs to one process), no arguments, no network, data
generated in memory from a seed. Drives the entry points a user would call at
the full width of the one model the repo supports — ``ModelConfig()``: 128 px,
stem 32, encoder (64, 128, 256), decoder (256, 128, 64, 32), 2.06 M float32
parameters, batch 16, bfloat16 compute — with step counts in single digits
and random weights. Four phases, each checked by the repo's own means:

- train     the one-program mesh round (``build_federated_round`` driven by
            ``run_mesh_federation``, uint8 staging) over every visible chip;
- federate  the gRPC plane in-process: ``FedServer`` + two ``FedClient``s
            whose trainer is the per-step ``local_fit`` the client CLI runs;
- serve     ``InferenceEngine`` + ``MicroBatcher`` behind the gRPC front
            door, driven by ``tools.load_gen.run_load``, checked against a
            float32 ``model.apply`` on the same device;
- kernels   every ``pallas_call`` in the tree compiled (``interpret=False``)
            against its reference twin.

Exit code 0 and, as the LAST stdout line, one JSON object
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`` only
when JAX reports a TPU and every phase passed. No accelerator, a failed
phase, or a directory that holds nothing else of the repo: non-zero exit, no
result line.

``--rehearse-cpu`` runs the same phases at a tiny size with the Pallas
interpreter on whatever backend is present — for debugging the script in a
sandbox before spending chip time. Its result line says ``"rehearsal": true``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import threading
import time
import traceback

SEED = 21
PHASES = ("train", "federate", "serve", "kernels")
# Serve check: served (bf16) probabilities against the float32 model's. bf16
# compute moves them by ~3e-3 on this model (v5e, PR 21); ~7x margin.
SERVE_PROB_TOL = 0.02
# Kernels check: a fused plane's probabilities against its plain-XLA twin over
# the same codes. The twins differ by bf16 compute (the fused forward
# accumulates in f32 throughout) — ~2e-3 on this model; 5x margin.
PLANE_PROB_TOL = 0.01


@dataclasses.dataclass(frozen=True)
class Size:
    """What one run is sized to. ``full`` is the contract; ``tiny`` is the
    CPU rehearsal."""

    model_kw: dict
    batch: int
    steps: int
    fed_samples: int
    buckets: tuple
    tile_overlap: int
    oversize: int
    max_batch: int
    # "pallas" (compiled) or "interpret": how every pallas_call here runs.
    pallas_impl: str


FULL = Size(
    model_kw={}, batch=16, steps=4, fed_samples=64, buckets=(128, 256),
    tile_overlap=32, oversize=320, max_batch=8,
    pallas_impl="pallas",
)
TINY = Size(
    model_kw=dict(
        img_size=32, stem_features=4, encoder_features=(8,),
        decoder_features=(8, 4),
    ),
    batch=4, steps=2, fed_samples=8, buckets=(32, 64), tile_overlap=8,
    oversize=80, max_batch=4, pallas_impl="interpret",
)


class CompileLog:
    """Every backend compile this process performs, from JAX's own monitoring
    events: ``(program name, seconds, persistent-cache hit?)`` in order. A
    compile request is followed on the same thread by an optional cache-hit
    event and then by its duration event, which is what pairs them up."""

    def __init__(self):
        import jax

        self.events: list[tuple[str, float, bool]] = []
        self._local = threading.local()
        self.floor_s = float(jax.config.jax_persistent_cache_min_compile_time_secs)
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self._local.hit = False
        elif event == "/jax/compilation_cache/cache_hits":
            self._local.hit = True

    def _on_duration(self, event: str, duration: float, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            hit = getattr(self._local, "hit", False)
            self._local.hit = False
            self.events.append((str(kw.get("fun_name")), float(duration), hit))

    def mark(self) -> int:
        return len(self.events)

    def summary(self) -> dict:
        """``fresh_big`` are the programs actually compiled (not read from
        the persistent cache) that took at least twice the cache's own floor
        — the ones a warm cache must make disappear. (A program right at the
        floor is written on one run and not the next.)"""
        fresh_big = [
            (name, round(s, 1))
            for name, s, hit in self.events
            if not hit and s >= 2 * self.floor_s
        ]
        return {
            "compiles": len(self.events),
            "cache_hits": sum(1 for e in self.events if e[2]),
            "fresh_big": fresh_big,
        }


def _shard_devices(x) -> list:
    """Device of each leading-axis block of a ``P('clients', ...)`` array, in
    client order; raises unless every client's block sits on ONE device of
    its own (inner-axis shards of a client may share none with another's)."""
    owners: dict[int, set] = {}
    for s in x.addressable_shards:
        lead = s.index[0]
        start = 0 if lead.start is None else lead.start
        stop = x.shape[0] if lead.stop is None else lead.stop
        if stop - start != 1:
            raise AssertionError(f"shard spans clients [{start}, {stop})")
        owners.setdefault(start, set()).add(s.device)
    seen: set = set()
    for c, devs in sorted(owners.items()):
        if seen & devs:
            raise AssertionError(f"client {c} shares a device: {devs}")
        seen |= devs
    if len(owners) != x.shape[0]:
        raise AssertionError(f"{len(owners)} of {x.shape[0]} clients placed")
    return [sorted(d.id for d in owners[c]) for c in sorted(owners)]


def _tree_max_abs_diff(a, b) -> float:
    import jax
    import numpy as np

    return max(
        float(np.max(np.abs(np.asarray(x, np.float32) - np.asarray(y, np.float32))))
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    )


def _all_finite(tree) -> bool:
    import jax
    import numpy as np

    return all(
        bool(np.all(np.isfinite(np.asarray(x, np.float32))))
        for x in jax.tree_util.tree_leaves(tree)
    )


# ---- phases: each returns a small JSON-safe dict, or raises ----


def phase_train(size: Size, model_config, compiles: CompileLog) -> dict:
    import jax
    import numpy as np

    from fedcrack_tpu.data.pipeline import to_uint8_transport
    from fedcrack_tpu.data.synthetic import synth_crack_batch
    from fedcrack_tpu.parallel import (
        build_federated_round,
        build_federated_round_segments,
        make_mesh,
        run_mesh_federation,
        shuffled_epoch_data,
    )
    from fedcrack_tpu.parallel.driver import stage_round_data
    from fedcrack_tpu.train.local import create_train_state

    n = len(jax.devices())
    need = size.steps * size.batch
    pools = [
        to_uint8_transport(
            *synth_crack_batch(need, img_size=model_config.img_size, seed=SEED + c)
        )
        for c in range(n)
    ]
    init = create_train_state(jax.random.key(SEED), model_config).variables

    def run(n_clients: int, n_batch: int) -> dict:
        mesh = make_mesh(n_clients, n_batch)
        active = np.ones(n_clients, np.float32)
        n_samples = np.full(n_clients, float(need), np.float32)
        rngs = [np.random.default_rng(SEED + c) for c in range(n_clients)]

        def data_fn(r):
            parts = [
                shuffled_epoch_data(pi, pm, size.steps, size.batch, rng)
                for (pi, pm), rng in zip(pools, rngs)
            ]
            return (
                np.concatenate([p[0] for p in parts]),
                np.concatenate([p[1] for p in parts]),
                active,
                n_samples,
            )

        # Where the driver's own staging puts each client's slab, and where
        # the segmented round's init program puts each client's carry.
        si, sm = stage_round_data(*data_fn(0)[:2], mesh)
        if si.dtype != np.uint8:
            raise AssertionError(f"staged dtype {si.dtype}, wanted uint8")
        slab_devices = _shard_devices(si)
        del si, sm
        seg = build_federated_round_segments(
            mesh, model_config, learning_rate=1e-3, local_epochs=1, segments=1
        )
        carry_leaf = jax.tree_util.tree_leaves(seg.init_fn(init))[0]
        carry_devices = _shard_devices(carry_leaf)

        round_fn = build_federated_round(
            mesh, model_config, learning_rate=1e-3, local_epochs=1
        )
        marks: list[int] = []
        final, records = run_mesh_federation(
            round_fn, init, data_fn, 2, mesh,
            on_round=lambda rec, v: marks.append(compiles.mark()),
        )
        jax.block_until_ready(final)
        losses = np.concatenate([np.asarray(r.metrics["loss"]) for r in records])
        if not np.all(np.isfinite(losses)):
            raise AssertionError(f"non-finite round losses {losses}")
        if not _all_finite(final):
            raise AssertionError("non-finite weights after two rounds")
        moved = _tree_max_abs_diff(final["params"], init["params"])
        if not moved > 0.0:
            raise AssertionError("weights did not move")
        round2 = compiles.events[marks[0] : marks[1]]
        if round2:
            raise AssertionError(f"round 2 compiled {round2}")
        leaf = jax.tree_util.tree_leaves(final)[0]
        if not (leaf.sharding.is_fully_replicated and len(leaf.devices()) == n):
            raise AssertionError(f"weights not replicated: {leaf.sharding}")
        return {
            "mesh": [n_clients, n_batch],
            "loss": [round(float(x), 4) for x in losses],
            "weights_moved_max_abs": round(moved, 6),
            "round2_compiles": len(round2),
            "slab_devices": slab_devices,
            "carry_devices": carry_devices,
            "staged_bytes": [int(r.staged_bytes) for r in records],
        }

    out = {"clients_x_batch": run(n, 1)}
    if n >= 2 and n % 2 == 0:
        # The sync-BN inner axis: two chips per client.
        out["inner_axis"] = run(n // 2, 2)
    return out


def phase_federate(size: Size, model_config, compiles: CompileLog) -> dict:
    import jax
    import numpy as np

    from fedcrack_tpu.configs import DataConfig, FedConfig
    from fedcrack_tpu.data.pipeline import dataset_from_source
    from fedcrack_tpu.fed.serialization import tree_from_bytes
    from fedcrack_tpu.train.federated import make_train_fn
    from fedcrack_tpu.train.local import create_train_state
    from fedcrack_tpu.transport import FedClient, FedServer
    from fedcrack_tpu.transport.service import ServerThread

    cfg = FedConfig(
        max_rounds=1,
        cohort_size=2,
        local_epochs=1,
        registration_window_s=30.0,
        poll_period_s=0.05,
        host="127.0.0.1",
        port=0,
        model=model_config,
        data=DataConfig(img_size=model_config.img_size, batch_size=size.batch),
    )
    template = create_train_state(jax.random.key(SEED), model_config).variables
    server = FedServer(cfg, template, tick_period_s=0.05)
    initial_blob = server.state.global_blob
    results: dict = {}
    errors: list = []

    def run(name: str, c: int, port: int) -> None:
        try:
            # What `python -m fedcrack_tpu.client --synthetic N` builds.
            dataset = dataset_from_source(
                size.fed_samples, None, None,
                img_size=model_config.img_size, batch_size=size.batch,
                seed=SEED + c,
            )
            train_fn, _ = make_train_fn(cfg, dataset, size.batch, seed=SEED)
            client = FedClient(
                dataclasses.replace(cfg, port=port), train_fn, cname=name
            )
            results[name] = client.run_session()
        except Exception as e:  # re-raised on the main thread below
            errors.append((name, e, traceback.format_exc()))

    with ServerThread(server) as st:
        threads = [
            threading.Thread(
                target=run, args=(f"smoke-{c}", c, st.port), daemon=True
            )
            for c in range(2)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        alive = [t.name for t in threads if t.is_alive()]
    if errors:
        raise AssertionError(f"client {errors[0][0]} failed:\n{errors[0][2]}")
    if alive:
        raise AssertionError(f"client threads still running: {alive}")
    for name, res in sorted(results.items()):
        if not (res.enrolled and res.rounds_completed == cfg.max_rounds):
            raise AssertionError(
                f"{name}: enrolled={res.enrolled} rounds={res.rounds_completed}"
            )
        if not all(np.isfinite(h["loss"]) for h in res.history):
            raise AssertionError(f"{name}: non-finite loss {res.history}")
    if len(results) != 2:
        raise AssertionError(f"{len(results)}/2 clients returned")
    final_blob = server.state.global_blob
    if final_blob == initial_blob:
        raise AssertionError("averaged blob equals the initial blob")
    averaged = tree_from_bytes(final_blob, template=template)
    if not _all_finite(averaged):
        raise AssertionError("non-finite averaged weights")
    return {
        "rounds_closed": len(server.state.history),
        "clients": sorted(results),
        "client_loss": {
            n: round(float(r.history[-1]["loss"]), 4) for n, r in results.items()
        },
        "blob_bytes": len(final_blob),
        "averaged_moved_max_abs": round(
            _tree_max_abs_diff(averaged["params"], template["params"]), 6
        ),
    }


def phase_serve(size: Size, model_config, compiles: CompileLog) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedcrack_tpu.configs import ServeConfig
    from fedcrack_tpu.data.pipeline import normalize_images
    from fedcrack_tpu.models import ResUNet
    from fedcrack_tpu.models.resunet import init_variables
    from fedcrack_tpu.serve.batcher import MicroBatcher
    from fedcrack_tpu.serve.engine import InferenceEngine
    from fedcrack_tpu.serve.hot_swap import ModelVersionManager
    from fedcrack_tpu.serve.service import ServeServer, ServeServerThread, ServeService
    from fedcrack_tpu.tools.load_gen import make_images, run_load

    serve_config = ServeConfig(
        bucket_sizes=size.buckets,
        max_batch=size.max_batch,
        tile_overlap=size.tile_overlap,
        compute_dtype="bfloat16",
    )
    variables = init_variables(jax.random.key(SEED), model_config)
    engine = InferenceEngine(model_config, serve_config)
    manager = ModelVersionManager(engine, variables, initial_version=0)
    _, placed = manager.snapshot()
    engine.warmup(placed)
    weight_devices = sorted(
        d.id for d in jax.tree_util.tree_leaves(placed)[0].devices()
    )
    mark = compiles.mark()

    sizes = (*size.buckets, size.oversize)
    n_requests = 2 * len(sizes)
    batcher = MicroBatcher(engine, manager)
    service = ServeService(engine, batcher, manager)
    try:
        with ServeServerThread(ServeServer(service, port=0)) as thread:
            summary = run_load(
                f"127.0.0.1:{thread.port}",
                n_requests=n_requests,
                concurrency=2,
                sizes=sizes,
                seed=SEED,
                keep_masks=True,
                timeout_s=300.0,
            )
    finally:
        batcher.close()
        manager.stop()
    bad = {k: summary[k] for k in ("rejected", "shed", "dropped") if summary[k]}
    if summary["completed"] != n_requests or bad:
        raise AssertionError(f"{summary['completed']}/{n_requests} completed, {bad}")
    if service.tiled_served != n_requests // len(sizes):
        raise AssertionError(f"tile planner served {service.tiled_served} requests")
    during_traffic = compiles.events[mark:]
    if any(name == "jit(_predict)" for name, _, _ in during_traffic):
        raise AssertionError(f"predict program compiled under traffic: {during_traffic}")

    # One response per bucket, in two steps that cannot pass vacuously: the
    # mask that came over the wire (front door, batcher, whatever lanes it
    # shared) is bit-for-bit the mask of the engine's own probabilities for
    # that image, and those probabilities agree with the float32 model on
    # this device at full f32 matmul precision (the device's default rounds
    # f32 operands to bfloat16).
    f32_model = ResUNet(
        config=dataclasses.replace(model_config, compute_dtype="float32")
    )

    @jax.jit
    def f32_probs(v, image_u8):
        with jax.default_matmul_precision("highest"):
            logits = f32_model.apply(v, normalize_images(image_u8[None]), train=False)
        return jax.nn.sigmoid(logits.astype(jnp.float32))[0]

    images = make_images(n_requests, sizes, SEED)
    masks = {rid: (h, w, m) for rid, h, w, m in summary["masks"]}
    agreement = {}
    for rid in range(len(size.buckets)):
        h, w, mask_bytes = masks[rid]
        wire = np.frombuffer(mask_bytes, np.uint8).reshape(h, w) > 0
        served = engine.predict_image(placed, images[rid])
        if not np.array_equal(wire, served[..., 0] > 0.5):
            raise AssertionError(
                f"{h}px: the response's mask differs from the engine's own on "
                f"{int(np.sum(wire != (served[..., 0] > 0.5)))} pixels"
            )
        diff = float(np.max(np.abs(served - np.asarray(f32_probs(variables, images[rid])))))
        if not diff <= SERVE_PROB_TOL:
            raise AssertionError(
                f"{h}px: served probabilities are {diff:.4f} from the float32 "
                f"model, tolerance {SERVE_PROB_TOL}"
            )
        agreement[str(h)] = {
            "max_prob_diff_vs_f32": round(diff, 5),
            "mask_fraction_set": round(float(wire.mean()), 4),
        }
    return {
        "completed": summary["completed"],
        "per_size": summary["per_size"],
        "tiled": service.tiled_served,
        "weight_devices": weight_devices,
        "vs_f32_model_apply": agreement,
        "prob_tolerance": SERVE_PROB_TOL,
    }


def phase_kernels(size: Size, model_config, compiles: CompileLog) -> dict:
    import functools

    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from fedcrack_tpu.configs import ServeConfig
    from fedcrack_tpu.kernels.dequant import dequant_codes, dequant_matmul
    from fedcrack_tpu.models.resunet import init_variables
    from fedcrack_tpu.ops.pallas_bce import bce_sums
    from fedcrack_tpu.parallel import make_mesh
    from fedcrack_tpu.serve import quant
    from fedcrack_tpu.serve.engine import InferenceEngine

    rng = np.random.default_rng(SEED)
    impl = size.pallas_impl
    flavors = (
        ("int8", quant.quantize_leaf, quant.QKEY),
        ("fp8", quant.quantize_leaf_fp8, quant.QKEY_FP8),
    )
    out: dict = {}

    # The matmul shapes the fused forward hands the kernel at this width:
    # im2col stem (K = 27), the widest decoder 3x3 (K = 9 * C) and a 1x1.
    img, mb = size.buckets[0], size.max_batch
    feats = model_config.encoder_features[-1]
    low = img // (2 ** (1 + len(model_config.encoder_features)))
    shapes = [
        (mb * (img // 2) ** 2, 27, model_config.stem_features),
        (mb * low * low, 9 * feats, model_config.decoder_features[0]),
        (mb * low * low, feats, model_config.decoder_features[0]),
    ]

    @functools.partial(jax.jit, static_argnames="which")
    def mm(x, q, s, which):
        # The twin at full f32 precision: on the chip the default rounds f32
        # operands to bfloat16, which the kernel (by contract) does not.
        with jax.default_matmul_precision("highest"):
            return dequant_matmul(x, q, s, impl=which)

    for flavor, quantize, qkey in flavors:
        worst = 0.0
        for m, k, n in shapes:
            x = rng.normal(0, 1.0, (m, k)).astype(np.float32)
            leaf = quantize(rng.normal(0, 0.1, (k, n)).astype(np.float32))
            q, scale = leaf[qkey], leaf[quant.SKEY]
            got = np.asarray(mm(x, q, scale, impl))
            ref = np.asarray(mm(x, q, scale, "reference"))
            err = np.abs(got - ref)
            # The repo's pinned bound (tests/test_kernels.py): per entry
            # within one per-channel scale of the reference.
            if not np.all(err <= scale[None, :] + 1e-6):
                raise AssertionError(
                    f"dequant_matmul[{flavor}] {(m, k, n)}: max err "
                    f"{err.max():.3e} exceeds the per-channel scale "
                    f"{scale.min():.3e}"
                )
            worst = max(worst, float(err.max()))
        out[f"dequant_matmul_{flavor}"] = {
            "shapes": shapes, "max_abs_err_vs_reference": worst,
        }

    w = rng.normal(0, 0.1, (3, 3, feats, feats)).astype(np.float32)
    for flavor, quantize, qkey in flavors:
        leaf = quantize(w)
        got = np.asarray(dequant_codes(leaf[qkey], leaf[quant.SKEY], impl=impl))
        ref = np.asarray(dequant_codes(leaf[qkey], leaf[quant.SKEY], impl="reference"))
        np.testing.assert_allclose(got, ref, rtol=0, atol=1e-7)
    out["dequant_codes"] = {"shape": list(w.shape), "flavors": ["int8", "fp8"]}

    # BCE + statistics at the training shape, value and gradient, alone and
    # inside a shard_map over every chip (how the round program would call
    # it). The interpreter cannot propagate vma onto kernel-internal
    # constants, so the rehearsal alone turns the check off.
    px = model_config.img_size
    logits = rng.normal(0, 2.0, (size.batch, px, px, 1)).astype(np.float32)
    labels = (rng.random((size.batch, px, px, 1)) > 0.9).astype(np.float32)
    got = np.asarray(jax.jit(lambda a, b: bce_sums(a, b, impl))(logits, labels))
    ref = np.asarray(jax.jit(lambda a, b: bce_sums(a, b, "jnp"))(logits, labels))
    np.testing.assert_allclose(got, ref, rtol=1e-5)
    g_got = jax.jit(jax.grad(lambda a: bce_sums(a, labels, impl)[0]))(logits)
    g_ref = jax.jit(jax.grad(lambda a: bce_sums(a, labels, "jnp")[0]))(logits)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), rtol=1e-5, atol=1e-6)
    n = len(jax.devices())
    mesh = make_mesh(n, 1)
    sharded = jax.jit(
        jax.shard_map(
            lambda a, b: bce_sums(a[0], b[0], impl)[None],
            mesh=mesh,
            in_specs=(P("clients"), P("clients")),
            out_specs=P("clients"),
            check_vma=impl == "pallas",
        )
    )
    per_chip = np.asarray(sharded(np.stack([logits] * n), np.stack([labels] * n)))
    np.testing.assert_allclose(per_chip, np.stack([ref] * n), rtol=1e-5)
    out["bce_sums"] = {
        "shape": list(logits.shape),
        "max_rel_err": float(np.max(np.abs(got - ref) / np.maximum(np.abs(ref), 1.0))),
        "shard_map_chips": n,
    }

    # One predict per quantized plane through the engine: the plane asked
    # for is the plane built, on the compiled kernels, and its probabilities
    # agree with the plain-XLA program over the same codes — the reference
    # plane for int8, the dequantized-weights oracle for fp8 (e4m3 rounding
    # is the model's delta, not the kernel's; tests/test_kernels.py).
    variables = jax.device_get(init_variables(jax.random.key(SEED), model_config))
    images = quant.probe_images(size.buckets[0], size.max_batch, SEED)
    diffs = {}
    for plane in ("reference", "fused_int8", "fp8"):
        engine = InferenceEngine(
            model_config,
            ServeConfig(
                bucket_sizes=size.buckets[:1], max_batch=size.max_batch,
                tile_overlap=size.tile_overlap, compute_dtype="bfloat16",
                quant="int8", kernel_plane=plane,
            ),
        )
        if engine.effective_kernel_plane != plane:
            raise AssertionError(
                f"kernel_plane={plane!r} resolved to "
                f"{engine.effective_kernel_plane!r}"
            )
        if plane != "reference" and engine.kernel_impl != impl:
            raise AssertionError(
                f"{plane} runs the {engine.kernel_impl!r} kernels, wanted {impl!r}"
            )
        qv = quant.quantize_for_plane(variables, plane)
        got = engine.predict_bucket(engine.prepare(qv), images)
        if plane == "reference":
            int8_reference = got
            continue
        want = (
            int8_reference
            if plane == "fused_int8"
            else engine.predict_bucket(
                engine.prepare(quant.dequantize_variables(qv.tree)), images
            )
        )
        diffs[plane] = float(np.max(np.abs(got - want)))
        if not diffs[plane] <= PLANE_PROB_TOL:
            raise AssertionError(
                f"{plane} plane: max probability diff {diffs[plane]:.4f} against "
                f"its plain-XLA twin exceeds {PLANE_PROB_TOL}"
            )
    out["engine_planes"] = {
        "bucket": size.buckets[0],
        "max_prob_diff_vs_xla_twin": diffs,
        "tolerance": PLANE_PROB_TOL,
    }
    return out


def run_phases(phases: dict, *args) -> bool:
    """Run every phase in order; a phase that raises is reported with its
    traceback and the rest still run (one chip call should say everything it
    can), but the return value — and with it the exit code — is then False."""
    ok = True
    for name, fn in phases.items():
        t0 = time.monotonic()
        try:
            detail = fn(*args)
        except Exception:
            ok = False
            traceback.print_exc()
            print(f"[FAIL] {name} ({time.monotonic() - t0:.1f}s)", flush=True)
        else:
            print(
                f"[pass] {name} ({time.monotonic() - t0:.1f}s) "
                f"{json.dumps(detail, sort_keys=True)}",
                flush=True,
            )
    return ok


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument(
        "--rehearse-cpu",
        action="store_true",
        help="tiny sizes and the Pallas interpreter on any backend (debugging "
        "aid; never what the chip check runs)",
    )
    args = p.parse_args(argv)
    t_start = time.monotonic()

    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu" and not args.rehearse_cpu:
        print(
            f"chip_smoke: no accelerator — JAX reports platform="
            f"{device['platform']!r}; this check runs on a TPU only",
            file=sys.stderr,
        )
        return 2

    from fedcrack_tpu import jaxcompat
    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.kernels import dequant
    from fedcrack_tpu.models import resunet
    from fedcrack_tpu.ops import pallas_bce, pooling

    cache_dir = jaxcompat.enable_compilation_cache()
    compiles = CompileLog()
    size = TINY if args.rehearse_cpu else FULL
    model_config = ModelConfig(compute_dtype="bfloat16", **size.model_kw)

    print(f"platform: {device['platform']}")
    print(f"device_kind: {device['kind']}")
    print(f"device count: {device['count']}")
    print(f"jax {jax.__version__}, compile cache: {cache_dir}")
    print(
        f"dequant kernels: {dequant.default_impl()} | "
        f"BCE: {pallas_bce.default_impl()} | "
        f"pool: {'custom VJP' if resunet._USE_CUSTOM_POOL else 'XLA'} up to "
        f"{pooling._CUSTOM_MAX_GRID} px grids | fp8: {jaxcompat.fp8_supported()}"
    )
    print(f"model: {model_config}", flush=True)

    phases = {name: globals()[f"phase_{name}"] for name in PHASES}
    ok = run_phases(phases, size, model_config, compiles)
    print(
        f"compiles: {json.dumps(compiles.summary())} "
        f"wall: {time.monotonic() - t_start:.1f}s",
        flush=True,
    )
    if not ok:
        return 1
    result = {"ok": True, "device": device}
    if args.rehearse_cpu:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
