"""Time of all-gather, reduce-scatter, all-reduce and other collective
operations during which no other operation runs on that device, over the
traced window, on the worst device."""
from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    if not any(tr.is_collective(n) for evs in run.trace.ops.values()
               for *_, n in evs):
        return None
    return 100.0 * tr.exposed_collective_ns(run.trace) * 1e-9 / run.window_s
