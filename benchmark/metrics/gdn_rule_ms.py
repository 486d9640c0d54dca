"""Milliseconds a step the chip spends under the ``gdn_rule`` scope of every
Gated DeltaNet layer, forward, rematerialised forward and backward: the L2
norms of queries and keys, the decays, and the gated delta rule in whatever
form the program computes it (``trace/scopes.py``). Silent on a program
without the scope."""


def read(run):
    seconds = (run.get("scope_seconds") or {}).get("gdn_rule")
    return None if seconds is None else 1e3 * seconds
