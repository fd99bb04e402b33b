"""Device time of operations under the ``norm_psum`` scope (the SCALE
update's cross-chip sum of column sums of squares,
``kernels/dispatch._psum_ss``) over the device's busy time."""
from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    t = tr.scope_ns(run.trace, run.scopes, "norm_psum")
    return 100.0 * t / tr.busy_ns(run.trace) if t else None
