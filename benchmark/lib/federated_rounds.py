"""One run of a cell whose traffic is ``federated_rounds``.

Set-up and window are ONE call of the program's entry,
``run_mesh_federation`` over one ``build_federated_round`` program: the
first ``checked_rounds`` rounds compile and warm it and are the rounds the
reference follows (round 0 from the seed, each later one from the state the
program handed on to it); the window opens when the last of them has been read back
and closes at the end of the first round that ends ``seconds`` or more later.
Rounds run back to back with the driver's own overlapped staging; the feed
is ``datagen.RoundFeed``. The reference runs once the window has closed,
the peak memory has been read and the program's state is dropped.
"""

from __future__ import annotations

import concurrent.futures
import gc
import importlib.util
import json
import os
import sys
import threading
import time

import numpy as np

import jax

from . import check, datagen, flops
from .compile_log import CompileLog

# The system under test.
from fedcrack_tpu.configs import ModelConfig
from fedcrack_tpu.parallel import build_federated_round, make_mesh, run_mesh_federation

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class GcWatch:
    """Times the collector's pauses (``gc.callbacks``), to tell a window that
    one of them stalled from a stall of the program's own. Changes nothing."""

    def __init__(self):
        self.pauses, self._began = [], None

    def __call__(self, phase, info):
        if phase == "start":
            self._began = time.perf_counter()
        elif self._began is not None:
            self.pauses.append((self._began, time.perf_counter() - self._began, info["generation"]))

    def within(self, t0: float, t1: float) -> dict:
        inside = [(s, g) for began, s, g in self.pauses if t0 <= began <= t1]
        return {
            "pauses": len(inside), "seconds": sum(s for s, _ in inside),
            "longest_s": max((s for s, _ in inside), default=0.0), "oldest_generation": max((g for _, g in inside), default=None),
        }


class _WindowClosed(Exception):
    """Raised from the driver's per-round hook to end the one call."""


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reference(config: dict):
    return _load_module(os.path.join(BENCH_DIR, "reference", config["reference"] + ".py"), "bench_reference")


def device_report(devices, chips: int, require_chip: bool) -> tuple[dict, dict | None]:
    """What JAX reports, and the chip's row of the peaks table. No chip, too
    few chips or a chip the table lacks ends the run without a result."""
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if not require_chip:
        return info, None
    if info["platform"] != "tpu" or len(devices) < chips:
        print(f"benchmark: needs {chips} TPU chip(s), JAX reports {info}", file=sys.stderr)
        raise SystemExit(3)
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    if info["kind"] not in peaks:
        print(f"benchmark: no peaks for device kind {info['kind']!r} in peaks.json", file=sys.stderr)
        raise SystemExit(3)
    return info, peaks[info["kind"]]


def reference_rounds(ref, starts, feed, model, lr, weights, devices, *, operands=None, fault=None):
    """The reference over the first rounds, one client a device at a time
    (clients beyond the devices queue behind the first). Round ``k`` starts
    from ``starts[k]`` and is skipped where that is ``None``. Returns per
    round the global variables, each client's loss and accuracy and the mean
    gradient norm of every leaf. ``fault`` plants a fault into the reference
    put in the program's place: ``half_batch`` (the reference's own),
    ``no_exchange`` (the first client's model for the average) and
    ``stale_slab`` (round 0's data again in every later round)."""
    out = []
    for k, variables in enumerate(starts):
        if variables is None:
            out.append(None)
            continue
        images, masks = feed(0 if fault == "stale_slab" else k)
        clients = images.shape[0]

        def fit_on(d):
            """One device's clients, one after another, fed step by step."""
            return {
                c: ref.client_round(
                    variables, images[c], masks[c], model, lr, operands=operands,
                    fault=fault if fault == "half_batch" else None, device=devices[d],
                )
                for c in range(d, clients, len(devices))
            }

        # The devices side by side, a thread each.
        n_used = min(clients, len(devices))
        with concurrent.futures.ThreadPoolExecutor(n_used) as pool:
            done = {c: r for part in pool.map(fit_on, range(n_used)) for c, r in part.items()}
        results = jax.device_get([done[c] for c in range(clients)])
        client_vars = [r[0] for r in results]
        out.append({
            "variables": client_vars[0] if fault == "no_exchange" else ref.weighted_average(client_vars, list(weights)),
            "loss": [float(r[1]["loss"]) for r in results],
            "pixel_acc": [float(r[1]["pixel_acc"]) for r in results],
            "grad_norms": jax.tree_util.tree_map(lambda *g: float(np.mean(g)), *[r[1]["grad_norms"] for r in results]),
        })
    return out


class SliceTrace:
    """Traces the last ``lead_s`` of one round and the start of the next, with
    the boundary between them inside, and keeps the trace in memory. The
    session is JAX's own (``jax.profiler.start_trace`` wraps the same one and
    can only write files); Python's tracer is off and the host's is at its
    lowest level, which still records ``TraceAnnotation`` spans."""

    def __init__(self, lead_s: float):
        self.lead_s = lead_s
        self.lock = threading.Lock()
        self.timer = None
        self.session = None
        self.started_at = None
        self.profile = None
        self.span_s = None
        self.round_s = None

    def arm(self, expected_round_s: float) -> None:
        """Call as a round is dispatched: the trace starts ``lead_s`` before
        the round is expected to end."""
        self.timer = threading.Timer(max(expected_round_s - self.lead_s, 0.0), self._start)
        self.timer.daemon = True
        self.timer.start()

    def _start(self) -> None:
        from jax._src.lib import _profiler

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        options.enable_hlo_proto = False
        with self.lock:
            self.started_at = time.perf_counter()
            self.session = _profiler.ProfilerSession(options)

    def round_ended(self, round_s: float) -> bool:
        """Call at a round's end: true where the trace is running and the
        boundary is about to pass."""
        if self.timer is not None:
            self.timer.cancel()
            self.timer.join()
            self.timer = None
        with self.lock:
            if self.session is not None:
                self.round_s = round_s
        return self.session is not None

    def collect(self) -> float:
        """Stop the trace; returns the seconds that took."""
        now = time.perf_counter()
        with self.lock:
            session, self.session = self.session, None
        self.span_s = now - self.started_at
        self.profile = session.stop_and_get_profile_data()
        return time.perf_counter() - now


class Cell:
    """One seed's weights, data, mesh and round program for a cell."""

    def __init__(self, spec: dict, seed: int, used, **round_options):
        self.spec, self.used = spec, used
        config, traffic = spec["config"], spec["traffic"]
        self.model = config["model"]
        self.batch = config["batch_size"]
        self.steps = config["train_samples"] // self.batch
        self.lr = config["optimizer"]["learning_rate"]
        self.clients, inner = traffic["mesh"]
        self.checked = int(traffic["checked_rounds"])
        self.ref = load_reference(config)
        self.mesh = make_mesh(self.clients, inner, used)
        self.round_fn = self.build_round(**round_options)
        self.start = jax.device_get(self.ref.make_variables(seed, self.model))
        size = self.model["img_size"]
        # One thread a client: the pools are block copies, which leave the lock.
        with concurrent.futures.ThreadPoolExecutor(self.clients) as pool:
            pools = list(pool.map(
                lambda c: datagen.client_pool(seed, c, self.steps * self.batch, size, traffic), range(self.clients)
            ))
        self.feed = datagen.RoundFeed(pools, seed, self.steps, self.batch)
        self.n_samples = np.full(self.clients, float(self.steps * self.batch), np.float32)

    def build_round(self, **round_options):
        """The program's round for this cell's mesh and configuration."""
        model_config = ModelConfig(**{k: tuple(v) if isinstance(v, list) else v for k, v in self.model.items()})
        return build_federated_round(
            self.mesh, model_config, learning_rate=self.lr,
            local_epochs=self.spec["config"]["local_epochs"], **round_options,
        )

    def drive(self, seconds: float, tracer: SliceTrace | None, t_start: float, compiles: CompileLog) -> dict:
        """The one call of the program's entry: checked rounds, then the
        window. With a ``tracer`` the window's first round gives the round's
        length, the second is traced near its end, the trace is collected as
        the third starts, and the window's clock and records start afresh
        after the third: what the host counters report is untouched by the
        tracing."""
        traffic = self.spec["traffic"]
        active = np.ones(self.clients, np.float32)
        state = {"boundary": False, "traced": tracer is None}
        program_rounds: list = []
        records: list = []

        def data_fn(r):
            if state["boundary"]:
                # The next round has just been dispatched: the boundary is in.
                state["boundary"] = False
                state["collect_s"] = tracer.collect()
                state["restart_after"] = r - 1
            with jax.profiler.TraceAnnotation("bench.data_fn"):
                images, masks = self.feed(r)
            return images, masks, active, self.n_samples

        def on_round(record, variables):
            with jax.profiler.TraceAnnotation("bench.on_round"):
                now = time.perf_counter()
                if record.round_idx < self.checked:
                    program_rounds.append({
                        "variables": jax.device_get(variables),
                        "loss": np.asarray(record.metrics["loss"]).tolist(),
                        "pixel_acc": np.asarray(record.metrics["pixel_acc"]).tolist(),
                    })
                    if record.round_idx == self.checked - 1:
                        state["window_mark"] = compiles.mark()
                        state["t0"] = time.perf_counter()
                        state["setup_s"] = state["t0"] - t_start
                    return
                records.append(record)
                if not state["traced"]:
                    if tracer.round_ended(record.wall_clock_s):
                        state["boundary"] = state["traced"] = True
                    else:
                        tracer.arm(record.wall_clock_s)
                    return
                if state.get("restart_after") == record.round_idx:
                    state.pop("restart_after")
                    records.clear()
                    state["t0"] = time.perf_counter()
                    return
                if now - state["t0"] >= seconds:
                    state["elapsed_s"] = now - state["t0"]
                    raise _WindowClosed

        try:
            run_mesh_federation(
                self.round_fn, self.start, data_fn, 10**9, self.mesh,
                overlap_staging=bool(traffic["overlap_staging"]), on_round=on_round,
            )
        except _WindowClosed:
            pass
        return {
            "program_rounds": program_rounds, "records": records, "elapsed_s": state["elapsed_s"],
            "setup_s": state["setup_s"], "collect_s": state.get("collect_s"), "window_t0": state["t0"],
            "window_compiles": compiles.summary(state["window_mark"]),
            "setup_compiles": compiles.summary(0, state["window_mark"]),
        }

    def starts(self, program_rounds: list) -> list:
        """Where each checked round starts: round 0 from the seed's weights,
        a later round from what the program handed on to it, its own result
        of the round before, which that round's comparison holds to the
        reference. (From the seed through two rounds the program's own bf16
        trajectory parts from the float32 one by as much as a lower precision
        does: PERF.md, section 4.)"""
        return [self.start] + [r["variables"] for r in program_rounds[: self.checked - 1]]

    def reference(self, starts: list, **variant) -> list:
        """The reference over the rounds whose start is given. The fault
        ``lost_carry`` starts every later round from the seed's weights again."""
        if variant.get("fault") == "lost_carry":
            starts = [s if s is None or k == 0 else self.start for k, s in enumerate(starts)]
            variant = dict(variant, fault=None)
        return reference_rounds(self.ref, starts, self.feed, self.model, self.lr, self.n_samples, self.used, **variant)


def run(spec: dict, seed: int, seconds: float, trace: bool, t_start: float, *, require_chip: bool = True) -> dict:
    """Run the cell once; returns the result object that ``run.py`` prints."""
    workload, traffic = spec["workload"], spec["traffic"]
    compiles = CompileLog()
    chips = traffic["mesh"][0] * traffic["mesh"][1]
    if chips != workload["chips"]:
        raise ValueError(f"traffic mesh {traffic['mesh']} does not fill {workload['chips']} chip(s)")
    devices = jax.devices()
    device, peaks = device_report(devices, chips, require_chip)
    used = devices[:chips]

    t_build = time.perf_counter()
    cell = Cell(spec, seed, used)
    build_s = time.perf_counter() - t_build
    tracer = SliceTrace(float(traffic["trace_lead_s"])) if trace else None
    gc_watch = GcWatch()
    gc.callbacks.append(gc_watch)
    driven = cell.drive(seconds, tracer, t_start, compiles)
    gc.callbacks.remove(gc_watch)
    records, elapsed = driven["records"], driven["elapsed_s"]
    rounds = len(records)

    # The TPU allocator's peak counts buffers; a loaded program's scratch
    # is reserved apart from them ("at the bottom of memory") and read as
    # ``bytes_reserved`` while the program is loaded, as it still is here.
    # Both are the chip's own readings and both are held at once while a round
    # runs (benchmark/study/memory_headroom.py, PERF.md section 4).
    def held(d):
        stats = d.memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0)), int(stats.get("bytes_reserved", 0))

    allocator_peak, reserved = max((held(d) for d in used), key=sum)
    memory_peak = allocator_peak + reserved
    # What XLA states as the loaded round program's scratch, to set beside it.
    scratch = max(
        (int(e.get_compiled_memory_stats().temp_size_in_bytes) for e in used[0].client.live_executables()),
        default=0,
    )
    failed = sum(
        1 for rec in records
        if not all(np.all(np.isfinite(np.asarray(v))) for v in rec.metrics.values())
    )
    # The program's state goes before the reference comes.
    cell.round_fn = None
    gc.collect()

    t_ref = time.perf_counter()
    starts = cell.starts(driven["program_rounds"])
    numbers = check.compare(starts, driven["program_rounds"], cell.reference(starts))
    numbers["window_compiles"] = float(driven["window_compiles"]["compiles"])
    numbers["failed_rounds"] = float(failed)
    correct, compared = check.judge(numbers, spec["limits"])
    reference_s = time.perf_counter() - t_ref

    result = {"correct": bool(correct), "attempted": rounds, "failed": failed}
    breakdown = None
    if trace:
        reducer = _load_module(os.path.join(BENCH_DIR, "trace", "reduce.py"), "bench_trace_reduce")
        reduced = reducer.reduce_profile(tracer.profile, chips, tracer.span_s)
        reduced["idle_share_of_round"] = reducer.idle_share_of_round(reduced, tracer.round_s)
        tracer.profile = None
        context = {
            "records": records, "rounds": rounds, "elapsed_s": elapsed, "steps": cell.steps,
            "clients": cell.clients, "chips": chips, "peaks": peaks, "trace": reduced,
            "step_flops": flops.train_step_flops(cell.model, cell.batch),
        }
        metrics = {}
        for m in spec["per_layer"]:
            reader = _load_module(os.path.join(BENCH_DIR, "metrics", m["name"] + ".py"), "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(context)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        breakdown = reduced["breakdown"]
    else:
        values = {"round_s": elapsed / rounds, "setup_s": driven["setup_s"]}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in spec["end_to_end"]}
    device["memory_peak_bytes"] = memory_peak
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["info"] = {
        "rounds": rounds, "window_s": elapsed, "round_wall_s": [r.wall_clock_s for r in records],
        "window_gc": gc_watch.within(driven["window_t0"], driven["window_t0"] + elapsed), "reference_s": reference_s, "trace_collect_s": driven["collect_s"],
        "setup_parts_s": {"before_build": t_build - t_start, "build": build_s,
                          "checked_rounds": driven["setup_s"] - (t_build - t_start) - build_s},
        "allocator_peak_bytes": allocator_peak, "program_reserved_bytes": reserved, "compiled_scratch_bytes": scratch,
        "setup_compiles": driven["setup_compiles"], "window_compiles": driven["window_compiles"],
        "numbers": numbers,
    }
    result["compared"] = compared
    return result
