"""Device time of operations under the ``mlp`` scope (the MLP sub-layer:
pre-norm, matmuls, activation and residual add, in the forward, the
backward and the recomputed forward) over the device's busy time."""
from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    t = tr.scope_ns(run.trace, run.scopes, "mlp")
    return 100.0 * t / tr.busy_ns(run.trace) if t else None
