"""Mean device-idle gap between the end of one train step's last
operation and the start of the next step's first (loop, ``place_batch``,
the guard's rollback read)."""
from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    g = tr.step_gaps_ns(run.trace, run.program)
    return sum(g) / len(g) * 1e-6 if g else None
