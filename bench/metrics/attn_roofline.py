"""Attention FLOPs the steps require (12 * heads * head_dim per
in-document causal pair per layer) at the chip's bf16 peak, over the
summed time of the ``attn_*`` kernels, per chip."""
from bench import counts
from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    t = tr.kernel_ns(run.trace, ("attn_",)) * 1e-9
    n = tr.program_runs(run.trace, run.program)
    if not t or not n:
        return None
    need = sum(counts.attention_flops(run.model, b)
               for b in run.batches[:n]) / run.chips
    return 100.0 * need / run.peak["bf16_flops"] / t
