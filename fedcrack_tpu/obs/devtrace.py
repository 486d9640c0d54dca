"""From a device trace to what each named scope of the round program costs.

The round program names its parts with ``jax.named_scope``: the phases of a
step in ``parallel/fedavg_mesh.py`` (:data:`STEP_SCOPES`), the parts of a
round around the scan (:data:`ROUND_SCOPES`) and one scope a block of the
model. Which names are the model's blocks is the task's to say
(``tasks.py``: ``block_scope``, ``model_scope``, ``program_name``);
:data:`BLOCK` and :data:`MODEL` are the crack U-Net's, the default. XLA keeps the scope path as ``op_name`` metadata on
every instruction, but a device trace taken without the HLO proto carries
only the instruction's name (``%fusion.1491``). The loaded executable's HLO
text is the join: :func:`scope_map` reads it into ``{instruction: (scope,
phase)}``, :func:`instruction_bytes` into ``{instruction: bytes}``, and
:func:`by_scope` sums a trace's ``XLA Ops`` events over them. Read with
``jax.profiler.ProfileData`` and ``re`` alone; no ``xprof``.

The HLO text comes from the loaded executable (:func:`loaded_hlo_text`), so
a compile-cache hit serves as well as a compile.
"""

from __future__ import annotations

import re
from typing import Any

STEP_SCOPES = (
    "unpack", "gather", "lowp", "loss", "grad_scale", "dp", "bn_sync",
    "optimizer", "step_metrics",
)
ROUND_SCOPES = ("round_init", "codec", "fold", "round_metrics")
# Not a ``named_scope``: what the compiler does to the program's arguments
# before the first step (the staged slab's relayout). Such an instruction's
# ``op_name`` is the bare argument's name.
ARGUMENTS = "arguments"
# ``models/resunet.py``: one scope a block (``SegmentationTask.block_scope``
# and ``.model_scope`` say the same).
BLOCK = re.compile(r"^(stem|enc[0-9]+|dec[0-9]+|head)$")
MODEL = "ResUNet"  # flax's own scope around the module's ``__call__``
_NAMED = frozenset(STEP_SCOPES + ROUND_SCOPES)

# Events that only enclose their body's events (``benchmark/trace/reduce.py``
# leaves the same ones out).
ENCLOSING = re.compile(r"^(while|conditional|call)(\.[0-9]+)?$")
COLLECTIVE = re.compile(r"all-gather|all-reduce|all-to-all|collective-permute|reduce-scatter|collective-broadcast")
OPS_LINE = "XLA Ops"

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<shape>\(.*?\)|\S+)\s+(?P<opcode>[\w\-]+)\("
)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SHAPE = re.compile(r"\b(pred|token|[a-z]+[0-9]+[a-z0-9]*)\[([0-9,]*)\](\{[^{}]*\})?")
_WRAPPER = re.compile(r"^(?:[a-z_]+\()*([^()]*)\)*$")


def _dtype_bytes(dtype: str) -> float:
    """``bf16`` 2, ``f8e4m3fn`` 1, ``s4`` a half, ``c64`` 8, ``pred`` 1, ``token`` 0."""
    if dtype == "pred":
        return 1.0
    bits = re.search(r"[0-9]+", dtype)
    return int(bits.group()) / 8.0 if bits else 0.0


def _shape_bytes(text: str) -> float:
    """HBM bytes of every array shape printed in ``text`` (a tuple's parts
    add up). Of the layout only the memory space is read: an array the
    compiler placed on the chip (``S(1)`` and up in its layout) moves
    nothing through HBM and counts 0. A tiled layout's padding is NOT
    counted: these are the bytes the algorithm moves, not what the chip's
    tiling makes of them."""
    total = 0.0
    for dtype, dims, layout in _SHAPE.findall(text):
        if "S(" in layout:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _dtype_bytes(dtype)
    return total


def _blocks_of(task) -> tuple[re.Pattern, str | None]:
    """The block pattern and the scope that encloses the model's blocks
    (``None``: a block counts wherever it stands), from the task or, without
    one, the crack U-Net's."""
    if task is None:
        return BLOCK, MODEL
    return re.compile(task.block_scope), task.model_scope


def _resolve(op_name: str, block: re.Pattern = BLOCK, model: str | None = MODEL) -> tuple[str | None, str]:
    """``(scope, phase)`` of one ``op_name`` path. The scope is the innermost
    named one; ``phase`` is ``bwd`` under a ``transpose(...)``, ``fwd``
    under a ``jvp(...)`` alone, else ``other``."""
    parts = re.sub(r"\[[^\]]*\]", "", op_name).split("/")
    if len(parts) == 1 and "(" not in op_name:
        return ARGUMENTS, "other"
    phase = "other"
    if any(p.startswith("transpose(") for p in parts):
        phase = "bwd"
    elif any("jvp(" in p for p in parts):
        phase = "fwd"
    scope = None
    in_model = model is None
    for part in parts:
        bare = _WRAPPER.match(part)
        name = bare.group(1) if bare else part
        if name == model:
            in_model = True
        elif name in _NAMED or (in_model and block.match(name)):
            scope = name
    return scope, phase


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
# Opcodes that read only as much of their operand as they return.
_SLICING = ("slice", "dynamic-slice", "gather")
# Opcodes that move nothing of their own.
_FREE = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast", "copy-done", "slice-done")


def _computations(hlo_text: str) -> dict[str, list[tuple[str, str, str, str]]]:
    """``{computation: [(name, result shape, opcode, rest of the line)]}``."""
    out: dict[str, list] = {}
    current = out.setdefault("", [])
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = out.setdefault(head.group(1), [])
            continue
        m = _INSTRUCTION.match(line)
        if m:
            current.append((m.group("name"), m.group("shape"), m.group("opcode"), line[m.end():]))
        elif current and line.strip() not in ("", "}") and not line.startswith("HloModule"):
            # A custom call's attributes can hold line breaks (a Pallas
            # kernel's metadata); its ``metadata=`` then stands further down.
            name, shape, opcode, rest = current[-1]
            current[-1] = (name, shape, opcode, rest + " " + line.strip())
    return out


def _operands(rest: str) -> list[str]:
    """Names of the operands in ``rest``, the line after ``opcode(``."""
    depth, end = 1, 0
    for end, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0:
                break
    return re.findall(r"%([\w.\-]+)", rest[:end])


def scope_map(hlo_text: str, task=None) -> dict[str, tuple[str | None, str]]:
    """``{instruction name: (scope, phase)}``, names without the leading
    ``%``; ``task`` names the model's blocks (:func:`_blocks_of`). An instruction resolves through its own ``op_name`` metadata (a
    fusion takes its own, not its body's); ``scope`` is ``None`` where the
    path holds no named scope. The compiler's own data movement carries no
    metadata (``copy``, the ``copy-start``/``slice-start`` prefetches and
    their ``-done``s, ``bitcast``): such an instruction takes the scope of
    the first instruction that consumes it, followed until one has
    metadata, since it moves that consumer's operand."""
    out: dict[str, tuple[str | None, str]] = {}
    block, model = _blocks_of(task)
    for instructions in _computations(hlo_text).values():
        first_user: dict[str, str] = {}
        bare = []
        for name, _, _, rest in instructions:
            m = _OP_NAME.search(rest)
            if m:
                out[name] = _resolve(m.group(1), block, model)
            else:
                bare.append(name)
            for op in _operands(rest):
                first_user.setdefault(op, name)
        for name in bare:
            user = first_user.get(name)
            for _ in range(8):
                if user is None or user in out:
                    break
                user = first_user.get(user)
            if user in out:
                out[name] = out[user]
    return out


def instruction_bytes(hlo_text: str) -> dict[str, float]:
    """``{instruction name: HBM bytes it reads plus HBM bytes it writes}``
    from the printed shapes: operands plus result, arrays held on the chip
    and layouts' padding left out (see :func:`_shape_bytes`). For a fusion these are its own operands and
    result, which is what it moves through HBM, not its body's. A slice (or
    an operand of a fusion that its body only slices) counts as the bytes
    taken, and a ``dynamic-update-slice`` (alone or as a fusion's in-place
    update of an operand) as the bytes put; an async ``*-start`` counts its
    whole transfer and its ``*-done`` nothing. Enclosing instructions
    (``while``, ...) are left out."""
    computations = _computations(hlo_text)
    result = {
        name: _shape_bytes(shape) for body in computations.values() for name, shape, _, _ in body
    }

    def reads(callee: str, index: int, whole: float) -> float:
        """Bytes a fused computation reads of its operand ``index``: what its
        slices take (through nested fusions too), ``whole`` on any other use."""
        body = computations[callee]
        param = next(
            (n for n, _, op, rest in body if op == "parameter" and int(re.match(r"\s*([0-9]+)", rest).group(1)) == index),
            None,
        )
        total = 0.0
        for n, _, op, rest in body:
            ops = _operands(rest)
            if param not in ops:
                continue
            nested = _CALLS.search(rest) if op == "fusion" else None
            if op in _SLICING and ops[0] == param and param not in ops[1:]:
                total += result[n]
            elif op == "dynamic-update-slice" and ops[0] == param and param not in ops[1:]:
                pass  # updated in place: counted with the result
            elif nested and nested.group(1) in computations:
                total += sum(reads(nested.group(1), i, whole) for i, o in enumerate(ops) if o == param)
            else:
                return whole
        return min(total, whole)

    def fusion_bytes(name: str, operands: list[str], callee: str) -> float:
        total = result[name]
        params = {n for n, _, op, _ in computations[callee] if op == "parameter"}
        for n, _, op, rest in computations[callee]:
            ops = _operands(rest)
            if op == "dynamic-update-slice" and ops[0] in params:
                # In place: the buffer is not written whole, the update is.
                total += result.get(ops[1], 0.0) - result[n]
        total += sum(reads(callee, i, result.get(o, 0.0)) for i, o in enumerate(operands))
        return max(total, 0.0)

    out = {}
    for body in computations.values():
        for name, _, opcode, rest in body:
            if ENCLOSING.match(opcode):
                continue
            operands = _operands(rest)
            callee = _CALLS.search(rest)
            if opcode in _FREE:
                out[name] = 0.0
            elif opcode in _SLICING:
                out[name] = 2 * result[name]
            elif opcode.endswith("-start"):
                out[name] = result[name]
            elif opcode == "dynamic-update-slice":
                out[name] = 2 * result.get(operands[1], 0.0)
            elif opcode == "fusion" and callee and callee.group(1) in computations:
                out[name] = fusion_bytes(name, operands, callee.group(1))
            else:
                out[name] = result[name] + sum(result.get(op, 0.0) for op in operands)
    return out


def loaded_hlo_text(module: str = "client_fit") -> str:
    """The optimized HLO text of the loaded executable whose module name
    holds ``module`` (the round program is ``jit_client_fit``), the largest
    where several do. Raises where none is loaded: run a round first."""
    import jax

    texts = [
        m.to_string()
        for e in jax.devices()[0].client.live_executables()
        for m in e.hlo_modules()[:1]
        if module in m.name
    ]
    if not texts:
        raise LookupError(f"no loaded executable whose module name holds {module!r}")
    return max(texts, key=len)


def _device_planes(profile: Any) -> list:
    return sorted(
        (p for p in profile.planes
         if p.name.startswith("/device:") and any(ln.name == OPS_LINE for ln in p.lines)),
        key=lambda p: p.name,
    )


def by_scope(profile: Any, hlo_text: str, task=None) -> dict:
    """What each scope costs in a traced slice (``task`` names the model's
    blocks; without one they are the crack U-Net's). Returns ``{"rows", "busy_s",
    "busy_union_s", "steps", "unscoped_share", "unscoped_ops", "top_ops"}``;
    a row is ``{scope, phase, per, seconds, seconds_per_step, share_of_busy,
    gbytes_per_s}``, largest first; ``per`` says whether the scope runs every
    ``step`` or once a ``round`` (then ``seconds_per_step`` is null). Times are means over the device planes.
    ``busy_s`` is the sum of the leaf events' durations and the rows'
    ``seconds`` sum to it; ``busy_union_s`` is the union of their intervals
    (what ``benchmark/trace/reduce.py`` calls busy), smaller where events
    overlap. Enclosing ``while``/``conditional``/``call`` events are left
    out. ``steps`` is how often the marking operation ran, by
    ``benchmark/trace/reduce.py:_steps``'s own rule: of the instructions that
    recur (four events or more; collectives apart) the one that takes the
    most time runs once a step, whatever loops the step holds inside it (the
    most frequent instruction runs once an iteration of the innermost). 0,
    and every ``seconds_per_step`` null, where nothing recurs. A ``top_ops`` row holds
    the instruction's ``events`` and ``seconds`` in the slice too: a slice
    cuts its steps, so what a call costs is ``seconds / events``."""
    scopes = scope_map(hlo_text, task)
    nbytes = instruction_bytes(hlo_text)
    planes = _device_planes(profile)
    if not planes:
        raise ValueError(f"no device plane with an {OPS_LINE!r} line; planes: {[p.name for p in profile.planes]}")
    seconds: dict[tuple, float] = {}
    moved: dict[tuple, float] = {}
    per_op: dict[str, list] = {}
    union = 0.0
    for plane in planes:
        intervals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                name = e.name.split(" = ", 1)[0].lstrip("%")
                if ENCLOSING.match(name):
                    continue
                intervals.append((e.start_ns, e.start_ns + e.duration_ns))
                dur = e.duration_ns * 1e-9 / len(planes)
                key = scopes.get(name, (None, "other"))  # not in this program's text
                seconds[key] = seconds.get(key, 0.0) + dur
                moved[key] = moved.get(key, 0.0) + nbytes.get(name, 0.0) / len(planes)
                row = per_op.setdefault(name, [0.0, 0.0])
                row[0] += dur
                row[1] += 1.0 / len(planes)
        end = None
        for lo, hi in sorted(intervals):
            if end is None or lo > end:
                union += (hi - lo) * 1e-9 / len(planes)
                end = hi
            elif hi > end:
                union += (hi - end) * 1e-9 / len(planes)
                end = hi
    busy = sum(seconds.values())
    recurring = [(sec, n) for name, (sec, n) in per_op.items() if n >= 4 and not COLLECTIVE.search(name)]
    steps = round(max(recurring)[1]) if recurring else 0
    per_round = ROUND_SCOPES + (ARGUMENTS,)
    rows = [
        {
            "scope": scope, "phase": phase, "per": "round" if scope in per_round else "step", "seconds": s,
            "seconds_per_step": s / steps if steps and scope not in per_round else None,
            "share_of_busy": s / busy if busy else None,
            "gbytes_per_s": moved[(scope, phase)] / s / 1e9 if s > 0 else None,
        }
        for (scope, phase), s in sorted(seconds.items(), key=lambda kv: -kv[1])
    ]
    by_time = sorted(per_op.items(), key=lambda kv: -kv[1][0])
    unscoped = (None, "other")
    return {
        "rows": rows,
        "busy_s": busy,
        "busy_union_s": union,
        "steps": steps,
        "unscoped_share": sum(s for (scope, _), s in seconds.items() if scope is None) / busy if busy else None,
        "unscoped_ops": [[name, sec] for name, (sec, _) in by_time if scopes.get(name, unscoped)[0] is None][:10],
        "top_ops": [
            {
                "op": name, "scope": scopes.get(name, unscoped)[0], "phase": scopes.get(name, unscoped)[1],
                "events": n, "seconds": sec,
                "seconds_per_step": sec / steps if steps else None,
                "gbytes_per_s": nbytes.get(name, 0.0) * n / sec / 1e9 if sec > 0 else None,
            }
            for name, (sec, n) in by_time[:25]
        ],
    }


def host_spans(profile: Any, prefix: str = "driver.") -> dict[str, dict]:
    """The program's spans on the trace's host planes (``obs/spans.span``
    enters a ``TraceAnnotation``): ``{name: {"count", "seconds"}}``."""
    out: dict[str, dict] = {}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    row = out.setdefault(e.name, {"count": 0, "seconds": 0.0})
                    row["count"] += 1
                    row["seconds"] += e.duration_ns * 1e-9
    return out


def idle_gaps(profile: Any, min_s: float = 1e-4) -> list[dict]:
    """The device's idle gaps of at least ``min_s`` seconds on the first
    device plane, each named after the host event that covers most of it
    (the innermost on a tie, as ``benchmark/trace/reduce.py`` names them; a
    ``driver.*`` span where one was recorded, else what XLA's runtime
    recorded of itself, else ``None``), with ``driver_share``, the share of
    the gap inside any ``driver.*`` span. A span is on the host plane only if
    it began and ended inside the trace, so one longer than the slice
    (``driver.round``, ``driver.barrier``) is not there to name a gap."""
    plane = _device_planes(profile)[0]
    events = sorted(
        (e.start_ns, e.start_ns + e.duration_ns)
        for line in plane.lines if line.name == OPS_LINE
        for e in line.events if not ENCLOSING.match(e.name.split(" = ", 1)[0].lstrip("%"))
    )
    host = [
        (e.start_ns, e.start_ns + e.duration_ns, e.name)
        for p in profile.planes if p.name.startswith("/host:")
        for line in p.lines for e in line.events if e.duration_ns > 0
    ]
    gaps, end = [], None
    for lo, hi in events:
        if end is not None and lo - end >= min_s * 1e9:
            best, best_key = None, (0.0, 0.0)
            for a, b, name in host:
                overlap = min(b, lo) - max(a, end)
                if overlap > 0:
                    key = (round(overlap / (lo - end), 2), -(b - a))
                    if key > best_key:
                        best, best_key = name, key
            ours = sorted((max(a, end), min(b, lo)) for a, b, name in host if name.startswith("driver.") and min(b, lo) > max(a, end))
            inside, upto = 0, end
            for a, b in ours:
                inside += max(b - max(a, upto), 0)
                upto = max(upto, b)
            gaps.append({
                "at_s": (end - events[0][0]) * 1e-9, "seconds": (lo - end) * 1e-9, "host": best,
                "driver_share": inside / (lo - end),
            })
        end = hi if end is None else max(end, hi)
    return gaps

