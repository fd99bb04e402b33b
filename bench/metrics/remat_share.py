"""Device time of the layer stack's recomputed forward (operations whose
HLO ``op_name`` holds both ``/layers/`` and ``/rematted_computation/``,
the copy ``jax.checkpoint`` runs inside the backward pass) over the
device's busy time."""
from bench import trace as tr

TAGS = ("/layers/", "/rematted_computation/")


def read(run):
    if run.trace is None:
        return None
    tot = 0.0
    for evs in run.trace.ops.values():
        tot += sum(e - s for s, e, n in tr.leaves(evs)
                   if all(t in run.scopes.get(tr.op_name(n), "")
                          for t in TAGS))
    t = tot / len(run.trace.ops)
    return 100.0 * t / tr.busy_ns(run.trace) if t else None
