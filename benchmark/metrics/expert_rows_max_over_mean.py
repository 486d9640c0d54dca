"""How unevenly the router loads the held experts: over the window's rounds,
the rows the busiest held expert of any layer computed over the mean over
all held experts of all layers (``RoundRecord.metrics["expert_rows"]``,
``[clients, layers, held]`` a round). 1 is an even load. Silent on a program
without the counter."""


def read(run):
    rows = [r.metrics.get("expert_rows") for r in run["records"]]
    if not rows or any(x is None for x in rows):
        return None
    total = sum(x.sum(axis=0) for x in rows)
    return float(total.max() / total.mean()) if total.mean() > 0 else None
