"""Host time the driver spent staging the next round's data under a round
(``RoundRecord.host_s["stage"]``, the seconds of its ``driver.stage`` span),
mean over the window's rounds. ``stage_exposed_ms`` reads 0 while this
hides; this is what it costs. Silent on a program without ``host_s``."""


def read(run):
    split = [getattr(r, "host_s", None) for r in run["records"]]
    if not split or not all(split):
        return None
    return 1e3 * sum(s["stage"] for s in split) / len(split)
