"""Milliseconds a step the chip spends under the ``blockdiff_attn`` scope of
every layer, forward and backward (``trace/scopes.py`` over the traced slice
and the loaded round program's text). Silent on a program without the scope."""


def read(run):
    seconds = (run.get("scope_seconds") or {}).get("blockdiff_attn")
    return None if seconds is None else 1e3 * seconds
