"""Host time the driver spent in the feed for the next round, under a round
(``RoundRecord.host_s["feed"]``, the seconds of its ``driver.feed`` span:
``data_fn(r + 1)`` and nothing else), mean over the window's rounds.
``data_fn_ms`` reads ``RoundRecord.data_fn_s``, the same clock a record
later: the window's first record then carries what ran under the round
before the window, in a traced run the harness's own ``tracer.collect()``.
This is the feed alone. Silent on a program without ``host_s``."""


def read(run):
    split = [getattr(r, "host_s", None) for r in run["records"]]
    if not split or not all(split):
        return None
    return 1e3 * sum(s["feed"] for s in split) / len(split)
