"""LM-head cross-entropy FLOPs the steps require (6 * hidden * published
vocabulary per labelled token) at the chip's bf16 peak, over the summed
time of the ``xent_*`` kernels, per chip."""
from bench import counts
from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    t = tr.kernel_ns(run.trace, ("xent_",)) * 1e-9
    n = tr.program_runs(run.trace, run.program)
    if not t or not n:
        return None
    need = sum(counts.xent_flops(run.model, b)
               for b in run.batches[:n]) / run.chips
    return 100.0 * need / run.peak["bf16_flops"] / t
