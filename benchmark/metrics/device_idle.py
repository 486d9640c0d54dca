"""Share of a round in which the chip that idles most runs nothing: its idle
time inside the traced round boundary, once, plus its steady scan's idle rate
over the rest of the round (``trace/reduce.py:idle_share_of_round``)."""


def read(run):
    trace = run["trace"]
    if trace is None or trace.get("idle_share_of_round") is None:
        return None
    return 100.0 * trace["idle_share_of_round"]
