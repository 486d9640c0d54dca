"""Host time between a round's barrier and the next round's dispatch
(``RoundRecord.host_s["handoff"]``, the ``driver.handoff`` span: record,
registry, ``on_round``, checkpoint, release of the old slab), mean over the
window's rounds. The device idles through all of it. Silent on a program
without ``host_s``."""


def read(run):
    split = [getattr(r, "host_s", None) for r in run["records"]]
    if not split or not all(split):
        return None
    return 1e3 * sum(s["handoff"] for s in split) / len(split)
