"""Host-blocking staging a round paid for its own data (``RoundRecord.staging_s``;
0 when it rode under the previous round), mean over the window's rounds."""


def read(run):
    if not run["records"]:
        return None
    return 1e3 * sum(r.staging_s for r in run["records"]) / len(run["records"])
