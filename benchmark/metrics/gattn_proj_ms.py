"""Milliseconds a step the chip spends under the ``gattn_proj`` scope of
every gated full-attention layer, forward, rematerialised forward and
backward: the norm, the query-and-gate, key and value products, the
per-head norms, rotary over a quarter of a head, the output gate, the
kernels' layout transposes and the output product (``trace/scopes.py``).
Silent on a program without the scope."""


def read(run):
    seconds = (run.get("scope_seconds") or {}).get("gattn_proj")
    return None if seconds is None else 1e3 * seconds
