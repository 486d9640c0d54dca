"""How much the held experts' grouped products ran: the tiles of
``GMM_TILE_M`` rows one grouped product of a held-expert layer call ran over,
summed over the round's calls (``RoundRecord.metrics["expert_tiles"]``,
``[clients]`` a round, summed over the clients), mean over the window's
rounds. The forward's three products, their rematerialisation and the
backward's all scale with it. Silent on a program without the counter."""


def read(run):
    tiles = [r.metrics.get("expert_tiles") for r in run["records"]]
    if not tiles or any(t is None for t in tiles):
        return None
    return sum(float(t.sum()) for t in tiles) / len(tiles)
