"""Milliseconds a step the chip spends under the ``lfm_conv`` scope of every
gated short-convolution layer, forward, rematerialised forward and backward:
the gate ``B * x~``, the three taps along the sequence and the gate ``C *``
(``trace/scopes.py``). Silent on a program without the scope."""


def read(run):
    seconds = (run.get("scope_seconds") or {}).get("lfm_conv")
    return None if seconds is None else 1e3 * seconds
