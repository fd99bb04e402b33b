"""Device time of operations under the ``embed`` scope (the token
embedding's gather and, in the backward, its scatter-add gradient) over
the device's busy time."""
from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    t = tr.scope_ns(run.trace, run.scopes, "embed")
    return 100.0 * t / tr.busy_ns(run.trace) if t else None
