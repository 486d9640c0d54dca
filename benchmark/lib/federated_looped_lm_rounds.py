"""One run of a cell whose traffic is ``federated_looped_lm_rounds``: federated
rounds of next-token training of a looped language model's share (one stack
of layers run several times a token with the same weights, an exit after
every pass).

The run is ``federated_causal_lm_rounds.py``'s, called and not copied, as
``federated_hybrid_lm_rounds.py`` calls it: this module loads an instance of
that file for itself and binds in it the names that differ here
(``reference_config``, ``program_config``, ``Cell``, ``compare``,
``kernel_work``, ``KERNEL_SCOPES``, ``MODULE_SCOPES``, ``PROGRAM_METRICS``,
``flops_joyai``: the operation count, here ``flops_ouro.py``). The accepted
cell's own instance is untouched.

One thing more differs, and is bound the same way, through the driver's
loader: how the trace readers find a step. ``trace/reduce.py`` marks a step by
the recurring instruction with the most time in the slice, and
``trace/scopes.py`` counts the steps by the median of the instructions' event
counts. Here every exit's head runs as a loop of chunks (``token_losses``: 8
of 1,024 positions, about 1.2 ms each, four exits, forward and backward), so
the heaviest recurring instruction may be a chunk that runs dozens of times a
step: the period would read as the gap between chunks and the boundary as the
gap between two steps. Here the marker is the instruction with the most time
among those whose event count is the SMALLEST count of four or more in the
slice: an instruction that runs once a step. Both readers take the steps
from it.

And one thing differs in the traced run (``Cell.drive``): the slice holds
some 43,000 device events, and collecting it can outlast a round (4.9 s):
7.7 s in one run, and the round dispatched after it then read 8.0 s. Where
the collection took longer than the traced round, the window's clock and
records restart one round later than the accepted cell's.

``correct`` compares, for each checked round, ``direction_r<k>``,
``total_change_r<k>`` and ``step_loss_r<k>`` as the accepted causal cell
does, ``loss_r<k>`` (the worst client's gap of the round's mean step loss,
as a share of the reference's), ``exit_r<k>`` (the worst exit's gap of
``exit_mass``, the round's mean exit distribution, as a share of the
reference's), ``loop_nll_r<k>`` (the worst exit's gap of its mean
cross-entropy, as a share of the reference's), and the exact
``window_compiles`` and ``failed_rounds``. ``next_acc_r<k>`` is reported and
held to no limit.
"""

from __future__ import annotations

import os
import time

import numpy as np

import jax

from . import check, flops_ouro
from .federated_rounds import _load_module, _WindowClosed

# The system under test. (A program without this configuration class cannot
# run the cell, and says so here, at once.)
from fedcrack_tpu.configs import LoopedLmConfig

# The accepted causal driver, an instance of our own (see above).
_driver = _load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "federated_causal_lm_rounds.py"),
    __package__ + "._causal_driver_of_looped",
)

# The kinds of block, summed over the passes and layers that hold them.
KERNEL_SCOPES = (
    "embed", "loop_attn_proj", "loop_attn", "loop_mlp", "loop_exit",
    "unpack", "loss", "grad_scale", "optimizer", "step_metrics", "round_init", "fold", "round_metrics",
)
# Each pass whole: its layers and its exit.
MODULE_SCOPES = ("loop0", "loop1", "loop2", "loop3")
# What a checked round keeps of the program's own report.
PROGRAM_METRICS = ("loss", "step_loss", "next_loss", "tokens", "next_acc", "exit_mass", "loop_nll", "exit_entropy")
PUBLISHED_KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim",
    "intermediate_size", "rms_norm_eps", "vocab_size", "total_ut_steps",
)


def reference_config(config: dict) -> dict:
    """The reference's plain ``cfg`` from the configuration file: the
    published keys, with the training's beside them, and ``vocab_held``: the
    rows the causal driver's feed draws ids over, here every row."""
    training = config["training"]
    full = config["layer_types"] == ["full_attention"] * config["num_hidden_layers"]
    if not full or config["use_sliding_window"] or config["rope_scaling"] is not None or config["tie_word_embeddings"]:
        raise ValueError("the reference and the program have full attention in every layer, plain rotary angles and an untied head")
    return dict(
        {k: config[k] for k in PUBLISHED_KEYS}, rope_theta=float(config["rope_theta"]),
        exit_entropy_beta=training["exit_entropy_beta"], seq_len=training["seq_len"], vocab_held=config["vocab_size"],
    )


def program_config(config: dict) -> LoopedLmConfig:
    """The program's model configuration for the same file."""
    cfg = reference_config(config)
    del cfg["vocab_held"]
    return LoopedLmConfig(**cfg, compute_dtype=config["compute_dtype"], param_dtype=config["param_dtype"])


class Cell(_driver.Cell):
    """One seed's weights, data, mesh and round program for a cell: the
    accepted causal cell's (its ``__init__`` and ``starts``) but for what the
    reference reports and where a traced window restarts."""

    def drive(self, seconds: float, tracer, t_start: float, compiles) -> dict:
        """The accepted causal cell's ``drive``, but for where the window
        restarts after the trace is collected: after the round under which
        it was collected, as there, or one round later where the collection
        took longer than the traced round, so that no round the collection
        held up is in the window."""
        traffic = self.spec["traffic"]
        active = np.ones(self.clients, np.float32)
        state = {"boundary": False, "traced": tracer is None}
        program_rounds: list = []
        records: list = []

        def data_fn(r):
            if state["boundary"]:
                state["boundary"] = False
                state["collect_s"] = tracer.collect()
                state["restart_after"] = r - 1 if state["collect_s"] <= tracer.round_s else r
            with jax.profiler.TraceAnnotation("bench.data_fn"):
                ids, weight = self.feed(r)
            return ids, weight, active, self.n_samples

        def on_round(record, variables):
            with jax.profiler.TraceAnnotation("bench.on_round"):
                now = time.perf_counter()
                if record.round_idx < self.checked:
                    program_rounds.append({
                        "variables": jax.device_get(variables),
                        **{k: np.asarray(record.metrics[k]).tolist() for k in PROGRAM_METRICS},
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
                if "restart_after" in state:
                    if state["restart_after"] == record.round_idx:
                        del state["restart_after"]
                        records.clear()
                        state["t0"] = time.perf_counter()
                    return
                if now - state["t0"] >= seconds:
                    state["elapsed_s"] = now - state["t0"]
                    raise _WindowClosed

        try:
            _driver.run_mesh_federation(
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

    def reference(self, starts: list, *, operands=None, fault=None) -> list:
        """The reference over the rounds whose start is given, one client
        after another on the first device. ``fault``: the reference's own
        (``reference/ouro_looped_lm.py``), or ``stale_slab`` (round 0's data
        again in every later round)."""
        out = []
        for k, variables in enumerate(starts):
            if variables is None:
                out.append(None)
                continue
            ids, weight = self.feed(0 if fault == "stale_slab" else k)
            results = [
                jax.device_get(self.ref.client_round(
                    variables, ids[c], weight[c], self.model, self.lr, operands=operands,
                    fault=None if fault == "stale_slab" else fault, device=self.used[0],
                ))
                for c in range(self.clients)
            ]
            out.append({
                "variables": self.ref.weighted_average([r[0] for r in results], list(self.n_samples)),
                "loss": [float(r[1]["loss"]) for r in results],
                "step_loss": [np.asarray(r[1]["step_loss"]).tolist() for r in results],
                "next_acc": [float(r[1]["next_hits"]) / max(float(r[1]["tokens"]), 1.0) for r in results],
                **{name: [np.asarray(r[1][name]).tolist() for r in results] for name in ("exit_mass", "loop_nll")},
                "grad_norms": jax.tree_util.tree_map(lambda *g: float(np.mean(g)), *[r[1]["grad_norms"] for r in results]),
            })
        return out


def _worst_exit_gap(program, reference) -> float:
    """The worst exit's gap, as a share of the reference's value; a side with
    fewer exits reads 0 at the exits it lacks."""
    p, r = (np.atleast_2d(np.asarray(x, np.float64)) for x in (program, reference))
    exits = max(p.shape[-1], r.shape[-1])
    p, r = (np.pad(x, ((0, 0), (0, exits - x.shape[-1]))) for x in (p, r))
    return float(np.max(np.abs(p - r) / np.maximum(np.abs(r), 1e-30)))


def compare(starts: list, program_rounds: list, reference_rounds: list) -> dict:
    """Every number, by name; 0 where program and reference agree."""
    out = {}
    for k, (start, prog, ref) in enumerate(zip(starts, program_rounds, reference_rounds)):
        if ref is None:
            continue
        moving = check.moving_leaves(ref["grad_norms"])
        s, p, r = (check._flatten(t["params"], "params") for t in (start, prog["variables"], ref["variables"]))
        if set(p) != set(r):
            raise ValueError("program and reference hold different leaves")
        dot = pp = rr = 0.0
        for name in sorted(moving & set(r)):
            dp = (p[name] - s[name]).ravel().astype(np.float64)
            dr = (r[name] - s[name]).ravel().astype(np.float64)
            dot, pp, rr = dot + float(dp @ dr), pp + float(dp @ dp), rr + float(dr @ dr)
        out[f"direction_r{k}"] = float(1.0 - dot / np.sqrt(pp * rr)) if pp > 0 and rr > 0 else 1.0
        out[f"total_change_r{k}"] = float(abs(np.sqrt(pp) - np.sqrt(rr)) / np.sqrt(rr)) if rr > 0 else 1.0
        lp = np.asarray(prog["step_loss"], np.float64).reshape(len(ref["step_loss"]), -1)
        lr = np.asarray(ref["step_loss"], np.float64).reshape(lp.shape)
        out[f"step_loss_r{k}"] = float(np.max(np.abs(lp - lr) / np.abs(lr)))
        out[f"loss_r{k}"] = float(np.max(np.abs(lp.mean(axis=1) - lr.mean(axis=1)) / np.abs(lr.mean(axis=1))))
        out[f"next_acc_r{k}"] = float(np.max(np.abs(np.asarray(prog["next_acc"]).ravel() - np.asarray(ref["next_acc"]))))
        out[f"exit_r{k}"] = _worst_exit_gap(prog["exit_mass"], ref["exit_mass"])
        out[f"loop_nll_r{k}"] = _worst_exit_gap(prog["loop_nll"], ref["loop_nll"])
    for name, v in out.items():
        if not np.isfinite(v):
            out[name] = 1e30
    return out


def kernel_work(model: dict, batch: int, records: list, steps: int) -> dict:
    """(operations, bytes) a step of each kernel whose roofline is reported;
    no expert layer, so no pairs."""
    del records, steps
    return {
        "held_pairs_a_layer": 0.0,
        "loop_attn": flops_ouro.attention_step(model, batch),
        "loop_exit": flops_ouro.exit_step(model, batch),
    }


def marker(total: dict, count: dict, collective) -> str | None:
    """The instruction that marks a step: of those that are no
    ``collective`` (``trace/reduce.py``'s pattern) and recur four times or
    more, the ones with the fewest events (once a step), and of them the one
    with the most time."""
    recurring = [n for n in total if count[n] >= 4 and not collective.search(n)]
    if not recurring:
        return None
    fewest = min(count[n] for n in recurring)
    return max((n for n in recurring if count[n] == fewest), key=total.get)


def steps(accepted, events):
    """``trace/reduce.py:_steps`` (``accepted`` is that module) with the step
    marked by ``marker``: the accepted reading over the marker's events alone."""
    total: dict[str, float] = {}
    count: dict[str, int] = {}
    for start, end, name in events:
        total[name] = total.get(name, 0.0) + end - start
        count[name] = count.get(name, 0) + 1
    name = marker(total, count, accepted.COLLECTIVE)
    return None if name is None else accepted.accepted_steps([e for e in events if e[2] == name])


def seconds_a_step(accepted, profile, hlo_text: str, scopes, chips: int = 1) -> dict[str, float]:
    """``trace/scopes.py:seconds_a_step`` (``accepted`` is that module) with
    the slice's steps counted by the marker's events."""
    names = accepted.scope_map(hlo_text, scopes)
    planes = sorted((p for p in profile.planes if p.name.startswith("/device:TPU:")), key=lambda p: p.name)[:chips]
    out: dict[str, float] = {}
    for plane in planes:
        total: dict[str, float] = {}
        count: dict[str, int] = {}
        for line in plane.lines:
            if line.name != accepted.OPS_LINE:
                continue
            for e in line.events:
                name = e.name.split(" = ", 1)[0].lstrip("%")
                if accepted.ENCLOSING.match(name):
                    continue
                total[name] = total.get(name, 0.0) + e.duration_ns * 1e-9
                count[name] = count.get(name, 0) + 1
        scoped = [n for n in total if names.get(n) is not None]
        mark = marker(total, count, accepted.COLLECTIVE)
        if not scoped or mark is None:
            continue
        for n in scoped:
            # A cut step adds one event to some instructions: 7 of 6 is once.
            times = max(1, int(count[n] / count[mark] + 0.25))
            out[names[n]] = out.get(names[n], 0.0) + total[n] / count[n] * times / len(planes)
    return out


def _load_trace_module(path: str, name: str):
    """The accepted driver's loader, with this kind's step marker in
    ``trace/scopes.py`` and ``trace/reduce.py``."""
    module = _load_module(path, name)
    if os.path.basename(path) == "scopes.py":
        module.COLLECTIVE = _load_module(os.path.join(os.path.dirname(path), "reduce.py"), name + "_reduce").COLLECTIVE
        module.accepted_seconds_a_step = module.seconds_a_step
        module.seconds_a_step = lambda *args, **kwargs: seconds_a_step(module, *args, **kwargs)
    elif os.path.basename(path) == "reduce.py":
        module.accepted_steps = module._steps
        module._steps = lambda events: steps(module, events)
    return module


# What the accepted driver's ``run``, ``Cell.__init__`` and ``Cell.drive`` read by name.
_driver._load_module = _load_trace_module
_driver.reference_config, _driver.program_config = reference_config, program_config
_driver.Cell, _driver.compare, _driver.kernel_work = Cell, compare, kernel_work
_driver.KERNEL_SCOPES, _driver.MODULE_SCOPES, _driver.PROGRAM_METRICS = KERNEL_SCOPES, MODULE_SCOPES, PROGRAM_METRICS
_driver.flops_joyai = flops_ouro  # its ``train_step_flops(model, batch, held pairs a layer)``
run = _driver.run
