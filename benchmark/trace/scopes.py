"""Seconds a step under each named scope of the round program, from a traced
slice and the loaded executable's HLO text.

The benchmark's own copy of what ``fedcrack_tpu/obs/devtrace.py`` does (kept
here so that no later PR can move the yardstick). XLA keeps the
``jax.named_scope`` path as ``op_name`` metadata on every instruction; a
device trace taken without the HLO proto carries only the instruction's
name, so the text is the join. An instruction belongs to the innermost of
the asked-for scopes on its path, whatever transformations wrap the parts
(``jvp(...)``, ``transpose(...)``, ``checkpoint``); instructions without
metadata (the compiler's own copies) take the scope of their first consumer.

Seconds a step: an instruction of the scan's body runs once a step, so its
mean duration over its events in the slice is its cost a step, however the
slice cuts the steps; an instruction of a loop inside the step (the head's
chunks) runs a whole number of times a step and counts that many times.
"""

from __future__ import annotations

import re
import statistics

ENCLOSING = re.compile(r"^(while|conditional|call)(\.[0-9]+)?$")
OPS_LINE = "XLA Ops"
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?:\(.*?\)|\S+)\s+[\w\-]+\((?P<rest>.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")


def loaded_hlo_text(module: str = "client_fit") -> str | None:
    """The optimized HLO text of the loaded executable whose module name
    holds ``module``, the largest where several do; nothing where none is."""
    import jax

    texts = [
        m.to_string()
        for e in jax.devices()[0].client.live_executables()
        for m in e.hlo_modules()[:1]
        if module in m.name
    ]
    return max(texts, key=len) if texts else None


def _scope_of(op_name: str, scopes: frozenset) -> str | None:
    found = None
    for part in re.sub(r"\[[^\]]*\]", "", op_name).split("/"):
        bare = part.strip("()")
        bare = bare.rsplit("(", 1)[-1]
        if bare in scopes:
            found = bare
    return found


def _instructions(hlo_text: str):
    """One string an instruction: a custom call's attributes can hold line
    breaks (a Pallas kernel's ``kernel_metadata``), and its ``metadata``
    then stands on a later line."""
    current = None
    for line in hlo_text.splitlines():
        starts = _INSTRUCTION.match(line) is not None
        if starts or _COMPUTATION.match(line) or line.strip() in ("", "}") or line.startswith("HloModule"):
            if current is not None:
                yield current
            current = line if starts else None
        elif current is not None:
            current += " " + line.strip()
    if current is not None:
        yield current


def scope_map(hlo_text: str, scopes) -> dict[str, str | None]:
    """``{instruction name: scope}`` over ``scopes``."""
    scopes = frozenset(scopes)
    out: dict[str, str | None] = {}
    first_user: dict[str, str] = {}
    bare: list[str] = []
    for line in _instructions(hlo_text):
        m = _INSTRUCTION.match(line)
        name, rest = m.group("name"), m.group("rest")
        meta = _OP_NAME.search(rest)
        if meta:
            out[name] = _scope_of(meta.group(1), scopes)
        else:
            bare.append(name)
        for op in re.findall(r"%([\w.\-]+)", rest.split("metadata=")[0]):
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


def seconds_a_step(profile, hlo_text: str, scopes, chips: int = 1) -> dict[str, float]:
    """``{scope: seconds a step}`` (forward and backward together), mean over
    the first ``chips`` device planes. Scopes none of whose instructions ran
    in the slice are left out."""
    names = scope_map(hlo_text, scopes)
    planes = sorted((p for p in profile.planes if p.name.startswith("/device:TPU:")), key=lambda p: p.name)[:chips]
    out: dict[str, float] = {}
    for plane in planes:
        total: dict[str, float] = {}
        count: dict[str, int] = {}
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                name = e.name.split(" = ", 1)[0].lstrip("%")
                if ENCLOSING.match(name):
                    continue
                total[name] = total.get(name, 0.0) + e.duration_ns * 1e-9
                count[name] = count.get(name, 0) + 1
        scoped = [n for n in total if names.get(n) is not None]
        if not scoped:
            continue
        steps = statistics.median(count[n] for n in scoped)
        for n in scoped:
            # A cut step adds one event to some instructions: 7 of 6 is once.
            times = max(1, int(count[n] / steps + 0.25))
            out[names[n]] = out.get(names[n], 0.0) + total[n] / count[n] * times / len(planes)
    return out
