"""Milliseconds a step the chip spends under the ``mla_proj`` scope of every
layer and of the multi-token-prediction module, forward and backward: the
norms, the four low-rank products, the latent norms, rotary, the broadcast
of the shared rotary key, the kernels' layout transposes and the output
product (``trace/scopes.py``). Silent on a program without the scope."""


def read(run):
    seconds = (run.get("scope_seconds") or {}).get("mla_proj")
    return None if seconds is None else 1e3 * seconds
