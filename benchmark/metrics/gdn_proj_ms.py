"""Milliseconds a step the chip spends under the ``gdn_proj`` and
``gdn_conv`` scopes of every Gated DeltaNet layer, forward, rematerialised
forward and backward: the norm, the fused ``[q | k | v | z]`` and ``[b | a]``
products, the depthwise causal convolution with its SiLU, the gated norm and
the output product (``trace/scopes.py``). Silent on a program with neither
scope."""


def read(run):
    scopes = run.get("scope_seconds") or {}
    seconds = [scopes[name] for name in ("gdn_proj", "gdn_conv") if name in scopes]
    return 1e3 * sum(seconds) if seconds else None
