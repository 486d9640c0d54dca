"""Share of a round's wall clock in which the driver's host thread works
rather than waits: dispatch, feed and staging (``RoundRecord.host_s``, the
``driver.dispatch`` / ``driver.feed`` / ``driver.stage`` spans) over
``wall_clock_s``, mean over the window's rounds. At 100 the barrier is
empty: the host sets the pace and ``round_s`` is feed and staging. Silent
on a program without ``host_s``."""


def read(run):
    records = run["records"]
    split = [getattr(r, "host_s", None) for r in records]
    if not split or not all(split):
        return None
    shares = [(s["dispatch"] + s["feed"] + s["stage"]) / r.wall_clock_s for s, r in zip(split, records)]
    return 100.0 * sum(shares) / len(shares)
