"""What one SGD step of the scan costs the chip: in the traced slice, the
median distance between two starts of the operation that marks a step (mean
over the chips). Silent where no operation recurs in the slice."""


def read(run):
    trace = run["trace"]
    if trace is None or "step_period_s" not in trace:
        return None
    return 1e3 * trace["step_period_s"]
