"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips."""
from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - tr.busy_ns(run.trace) * 1e-9 / run.window_s)
