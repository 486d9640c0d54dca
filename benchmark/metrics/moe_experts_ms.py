"""Milliseconds a step the chip spends under the ``moe_experts`` scope of
every layer, forward and backward: the grouped products of the held experts
(``trace/scopes.py``). Silent on a program without the scope."""


def read(run):
    seconds = (run.get("scope_seconds") or {}).get("moe_experts")
    return None if seconds is None else 1e3 * seconds
