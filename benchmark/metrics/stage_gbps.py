"""The rate at which a round's data reaches the chips: the bytes put on the
mesh's devices (``RoundRecord.stage["bytes"]``, summed) over the staging's
host seconds (``RoundRecord.host_s["stage"]``, the ``driver.stage`` span),
both summed over the window's rounds, in GB/s. Times ``stage_hidden_ms`` it
gives the bytes a round stages. Silent on a program without ``stage``."""


def read(run):
    records = run["records"]
    split = [getattr(r, "stage", None) for r in records]
    if not split or not all(split):
        return None
    seconds = sum(r.host_s["stage"] for r in records)
    return sum(sum(s["bytes"]) for s in split) / seconds / 1e9 if seconds > 0 else None
