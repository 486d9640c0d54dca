"""Milliseconds a step the chip spends under the ``loop_exit`` scope of the
looped language model, forward and backward: its four exits, each the final
norm, the exit gate, the head over the whole vocabulary and the cross-entropy,
and the exit distribution and loss over them (``trace/scopes.py``). Silent on
a program without the scope."""


def read(run):
    seconds = (run.get("scope_seconds") or {}).get("loop_exit")
    return None if seconds is None else 1e3 * seconds
