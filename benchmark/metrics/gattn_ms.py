"""Milliseconds a step the chip spends under the ``gattn`` scope of every
gated full-attention layer, forward and backward: the causal
splash-attention kernel calls only, 8 query heads a key/value head, 256 wide
(``trace/scopes.py``). Silent on a program without the scope."""


def read(run):
    seconds = (run.get("scope_seconds") or {}).get("gattn")
    return None if seconds is None else 1e3 * seconds
