"""Model FLOPs of the train steps in the trace over the traced window
times chips times the chip's bf16 peak (bench/counts.py: 6 per matrix
parameter per document token plus attention's in-document causal pairs;
no recompute, no embedding gather)."""
from bench import counts
from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    n = tr.program_runs(run.trace, run.program)
    if not n:
        return None
    flops = sum(counts.model_flops(run.model, b) for b in run.batches[:n])
    return 100.0 * flops / (run.window_s * run.chips
                            * run.peak["bf16_flops"])
