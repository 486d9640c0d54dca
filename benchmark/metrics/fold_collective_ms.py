"""What the ordered fold's collectives cost a chip in a round: the seconds of
collective operations (all-gather, all-reduce, collective-permute, ...) on a
device in the traced slice, mean over the chips (``trace/reduce.py``'s
``collective_s``). The slice holds one round boundary, so one fold. Silent
where no collective ran (one chip) or on a trace without the number."""


def read(run):
    trace = run["trace"]
    if trace is None or not trace.get("collective_s"):
        return None
    return 1e3 * trace["collective_s"]
