"""Host time the feed spent on a round's data (``RoundRecord.data_fn_s``: the
shuffle of the next epoch), mean over the window's rounds."""


def read(run):
    if not run["records"]:
        return None
    return 1e3 * sum(r.data_fn_s for r in run["records"]) / len(run["records"])
