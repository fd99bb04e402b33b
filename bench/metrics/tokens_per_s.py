"""Loss-weighted tokens of every step in the window, summed over chips,
over the window's wall time."""
from bench.counts import labelled_tokens


def read(run):
    return sum(labelled_tokens(b) for b in run.batches) / run.window_s
