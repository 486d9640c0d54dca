"""CPU time the whole process spent while a round ran, all its threads (the
driver's, the feed's, the runtime's), over the round's wall clock
(``RoundRecord.proc["cpu_s"]``, user + system seconds by ``getrusage`` at the
round's dispatch and after its barrier, over ``wall_clock_s``), mean over the
window's rounds, in percent of ONE core: it passes 100 where more than one
thread works. ``host_busy_pct`` is the driver's thread alone. Silent on a
program without ``proc``."""


def read(run):
    records = run["records"]
    spent = [getattr(r, "proc", None) for r in records]
    if not spent or not all(spent):
        return None
    shares = [p["cpu_s"] / r.wall_clock_s for p, r in zip(spent, records)]
    return 100.0 * sum(shares) / len(shares)
