"""Milliseconds a step the chip spends under the ``lfm_conv_proj`` scope of
every gated short-convolution layer, forward, rematerialised forward and
backward: the operator's norm, ``W_in`` (2,048 -> 6,144) and ``W_out``
(``trace/scopes.py``). Silent on a program without the scope."""


def read(run):
    seconds = (run.get("scope_seconds") or {}).get("lfm_conv_proj")
    return None if seconds is None else 1e3 * seconds
