"""Milliseconds a step the chip spends under the ``mtp`` scope, forward and
backward: the whole multi-token-prediction module (the next tokens'
embedding, the merge, its layer of the sparse kind and its pass through the
head), whatever kinds of block it holds (``trace/scopes.py`` over the one
scope). Silent on a program without the module."""


def read(run):
    seconds = (run.get("module_seconds") or {}).get("mtp")
    return None if seconds is None else 1e3 * seconds
