"""How often the expert layer's row budget did NOT bound what moved: the
calls of a round whose kept pairs overflowed the budget and took the branch
that moves a row for every (token, slot) pair
(``RoundRecord.metrics["budget_overflows"]``, ``[clients]`` a round, summed
over the clients), mean over the window's rounds. A round with more of them
is longer. Silent on a program without the counter."""


def read(run):
    calls = [r.metrics.get("budget_overflows") for r in run["records"]]
    if not calls or any(c is None for c in calls):
        return None
    return sum(float(c.sum()) for c in calls) / len(calls)
