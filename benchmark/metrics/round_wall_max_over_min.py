"""How far the window's rounds part from one another: the longest over the
shortest ``RoundRecord.wall_clock_s``. Rounds of one program on one shape
repeat to a thousandth, so a window that holds a stall reads well over 1
(and its ``round_s`` high) where a regression reads 1."""


def read(run):
    walls = [r.wall_clock_s for r in run["records"]]
    if not walls or min(walls) <= 0:
        return None
    return max(walls) / min(walls)
