"""Milliseconds a step the chip spends under the ``loop_attn`` scope of every
layer application of the looped language model (four layers, each applied
once a pass, four passes), forward and backward: the causal splash-attention
kernel calls only, 16 heads of 128 (``trace/scopes.py``). Silent on a program
without the scope."""


def read(run):
    seconds = (run.get("scope_seconds") or {}).get("loop_attn")
    return None if seconds is None else 1e3 * seconds
