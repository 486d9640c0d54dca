"""Milliseconds a step the chip spends under the ``mla_attn`` scope of every
layer and of the multi-token-prediction module, forward, rematerialised
forward and backward: the causal splash-attention kernel calls only
(``trace/scopes.py``). Silent on a program without the scope."""


def read(run):
    seconds = (run.get("scope_seconds") or {}).get("mla_attn")
    return None if seconds is None else 1e3 * seconds
