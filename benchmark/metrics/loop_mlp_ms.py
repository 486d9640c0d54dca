"""Milliseconds a step the chip spends under the ``loop_mlp`` scope of every
layer application of the looped language model, forward, rematerialised
forward and backward: the feed-forward block's two norms and its SwiGLU of
width 5,632 (``trace/scopes.py``). Silent on a program without the scope."""


def read(run):
    seconds = (run.get("scope_seconds") or {}).get("loop_mlp")
    return None if seconds is None else 1e3 * seconds
