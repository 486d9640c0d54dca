"""Milliseconds a step the chip spends under the ``lfm_attn`` scope of the
attention layer, forward and backward: the causal splash-attention kernel
calls only, 4 query heads a key/value head, 64 lanes a head
(``trace/scopes.py``). Silent on a program without the scope."""


def read(run):
    seconds = (run.get("scope_seconds") or {}).get("lfm_attn")
    return None if seconds is None else 1e3 * seconds
