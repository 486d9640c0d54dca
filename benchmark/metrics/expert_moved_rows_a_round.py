"""How many rows the held-expert layer's gather and per-token sum moved: a
call's ``kept`` where the row kernels ran and its row arrays' length (the
budget, or every pair on the overflow's branch) where XLA's form ran,
summed over the round's calls (``RoundRecord.metrics["moved_rows"]``,
``[clients]`` a round, summed over the clients), mean over the window's
rounds. Every pass of the layer's data movement, forward, rematerialised
and backward, scales with it. Silent on a program without the counter."""


def read(run):
    moved = [r.metrics.get("moved_rows") for r in run["records"]]
    if not moved or any(m is None for m in moved):
        return None
    return sum(float(m.sum()) for m in moved) / len(moved)
