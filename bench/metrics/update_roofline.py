"""Least bytes of the SCALE update (bench/counts.py) at the chip's HBM
bandwidth, over the summed time of the ``colnorm_*`` and ``scale_head_*``
kernels, per chip."""
from bench import counts
from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    t = tr.kernel_ns(run.trace, ("colnorm_", "scale_head_")) * 1e-9
    n = tr.program_runs(run.trace, run.program)
    if not t or not n:
        return None
    need = n * counts.update_bytes(run.model) / run.chips
    return 100.0 * need / run.peak["hbm_bytes_per_s"] / t
