"""Traffic generator: a ring of packed training batches, made on the host.

One generator reads every traffic mix (``bench/traffic/<mix>.json``)::

    {"corpus": "pile",          # bench/corpora/<corpus>.json, see below
     "row_len": 4096,           # tokens per packed row
     "ring": 8,                 # distinct batches, cycled
     "layout_seed": 0,          # fixes the lengths and the packing
     "bigram_prob": 0.8, "zipf_a": 1.2}    # token statistics

and a corpus file gives the document lengths as published::

    {"tokens_per_byte": 0.29335,
     "components": [[name, weight_percent, mean_doc_kib], ...]}

The number of rows per batch is the configuration's ``tokens_per_step``
over ``row_len``: the batch is counted in tokens and held fixed while the
layout changes.

Every component holds its published weight of the ring's tokens. Its
documents are drawn, until that share is filled, from an exponential
distribution with the component's published mean length (bytes times
``tokens_per_byte``; the mean is all that is published, and the
exponential is the distribution of largest entropy with a given mean), at
least one token each; the document that overfills the share is cut to it.
A document longer than a row is split at the row boundary into pieces of
at most ``row_len``. The pieces of the whole ring are packed first fit in
decreasing length (the program's first-fit packer,
``SyntheticLM._packed_batch``, given the longest piece first, so that a
long document's pieces are never left out for want of an empty row) into
its rows, what room is left is filled from the mix as a packer fed by a
stream does, and the rows are dealt to the batches in turn, so that each
batch of several rows holds long and short documents alike. The ring
starts with the batch of the most documents and then the one of the
fewest, the two steps the reference follows, so that it checks the
document masks and the longest causal spans; the other batches follow in
an order drawn from ``layout_seed``. Each row carries ``segment_ids`` 1..N in placement
order (0 on pad), within-document ``positions``, next-token ``labels``
inside the document (-1 at its last token and on pad) and
``loss_weights`` (1 where a label is, else 0).

Lengths, packing and the order of the batches come from ``layout_seed``,
never from ``--seed``, so every seed does the same work in the same order,
whatever number of steps a window holds; the run's seed draws the tokens.
Tokens come from a numpy copy of ``SyntheticLM``'s generator: the first
token of each document is Zipf(``zipf_a``), and each later one follows the
affine bigram ``(a * prev + b) % vocab`` with probability ``bigram_prob``,
else a fresh Zipf draw. ``a`` is drawn prime to ``vocab``, so the bigram is a
bijection: with a common factor (the program's generator draws any odd
``a``, and 152064 = 2^9 * 3^3 * 11) every chain falls into a short cycle,
and a few tokens fill a large share of the batch on some seeds only.
"""
from __future__ import annotations

import math

import numpy as np

KEYS = ("tokens", "labels", "segment_ids", "positions", "loss_weights")


class _Streams:
    """Each component's documents in drawn order, split into pieces of at
    most ``S``; lengths from ``rng``, at least one token each."""

    def __init__(self, traffic: dict, rng):
        corpus = traffic["corpus"]
        per_byte = float(corpus["tokens_per_byte"])
        self.S, self.rng = int(traffic["row_len"]), rng
        self.mean = {n: kib * 1024 * per_byte
                     for n, _, kib in corpus["components"]}
        self.left = {n: [] for n in self.mean}

    def next(self, name: str) -> int:
        if not self.left[name]:
            n = max(1, math.ceil(self.rng.exponential(self.mean[name])))
            self.left[name] = [self.S] * (n // self.S) + (
                [n % self.S] if n % self.S else [])
        return self.left[name].pop(0)


def doc_pieces(traffic: dict, capacity: int, rng=None, streams=None) -> dict:
    """{component: piece lengths}, each component its published weight of
    ``capacity`` tokens in expectation: whole pieces while they fit its
    share, then the piece that would overfill it with the probability
    that the share has room for, so that long documents keep their
    length."""
    corpus = traffic["corpus"]
    total = sum(w for _, w, _ in corpus["components"])
    if rng is None:
        rng = np.random.RandomState(int(traffic["layout_seed"]))
    streams = streams or _Streams(traffic, rng)
    out = {}
    for name, weight, _ in corpus["components"]:
        room = capacity * weight / total
        pieces = out.setdefault(name, [])
        while room > 0:
            n = streams.next(name)
            if n <= room or rng.random_sample() < room / n:
                pieces.append(n)
            room -= n
    return out


def first_fit(pieces: list, fill: list, S: int) -> list:
    """[(row, offset, length)] of the pieces that first fit places, in the
    order given, into rows filled to ``fill`` (updated); a piece that fits
    nowhere is left out."""
    placed = []
    for n in pieces:
        row = next((r for r in range(len(fill)) if S - fill[r] >= n), None)
        if row is not None:
            placed.append((row, fill[row], n))
            fill[row] += n
    return placed


def layouts(traffic: dict, B: int) -> list:
    """Per batch of the ring, [(row, offset, length)] of its pieces. What
    room first fit leaves is filled from the mix, a document drawn by the
    components' weights and cut at the row's end, as a packer fed by a
    stream of documents does."""
    S, R = int(traffic["row_len"]), int(traffic["ring"])
    rng = np.random.RandomState(int(traffic["layout_seed"]))
    streams = _Streams(traffic, rng)
    pieces = sorted((n for p in doc_pieces(traffic, R * B * S, rng,
                                           streams).values()
                     for n in p), reverse=True)
    fill = [0] * (R * B)
    placed = first_fit(pieces, fill, S)
    names = [n for n, _, _ in traffic["corpus"]["components"]]
    w = np.array([c[1] for c in traffic["corpus"]["components"]], float)
    for row in range(R * B):
        while fill[row] < S:
            n = min(streams.next(names[rng.choice(len(names), p=w / w.sum())]),
                    S - fill[row])
            placed.append((row, fill[row], n))
            fill[row] += n
    out = [[] for _ in range(R)]
    for row, off, n in sorted(placed):
        out[row % R].append((row // R, off, n))
    docs = [len(b) for b in out]
    first = [int(np.argmax(docs)), int(np.argmin(docs))]
    rest = [i for i in rng.permutation(R) if i not in first]
    return [out[i] for i in first + rest]


def zipf_cdf(vocab: int, a: float) -> np.ndarray:
    w = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** a
    return np.cumsum(w / w.sum())


def make_ring(traffic: dict, tokens_per_step: int, vocab: int,
              seed: int) -> list:
    """The ring of batches for one run: a list of dicts of (rows, row_len)
    numpy arrays (the program's packed-batch format)."""
    S = int(traffic["row_len"])
    if tokens_per_step % S:
        raise ValueError(f"tokens_per_step {tokens_per_step} is not a "
                         f"multiple of row_len {S}")
    B = tokens_per_step // S
    lays = layouts(traffic, B)
    R = len(lays)
    seg = np.zeros((R, B, S), np.int32)
    pos = np.zeros((R, B, S), np.int32)
    wts = np.zeros((R, B, S), np.float32)
    nseg = np.zeros((R, B), np.int32)
    for i, lay in enumerate(lays):
        for row, off, n in lay:
            nseg[i, row] += 1
            seg[i, row, off:off + n] = nseg[i, row]
            pos[i, row, off:off + n] = np.arange(n)
            wts[i, row, off:off + n - 1] = 1.0

    rng = np.random.default_rng(abs(int(seed)))
    a = 2
    while math.gcd(a, vocab) != 1:   # a bijection mod vocab: no chain
        a = int(rng.integers(3, 97)) * 2 + 1   # falls into a short cycle
    b = int(rng.integers(0, vocab))
    cdf = zipf_cdf(vocab, float(traffic["zipf_a"]))
    rows = R * B
    zipf = np.minimum(np.searchsorted(cdf, rng.random((rows, S))),
                      vocab - 1).astype(np.int64)
    coin = rng.random((rows, S)) < float(traffic["bigram_prob"])
    p2 = pos.reshape(rows, S)
    toks = np.zeros((rows, S), np.int64)
    prev = zipf[:, 0]
    toks[:, 0] = prev
    for t in range(1, S):
        nxt = np.where(coin[:, t], (a * prev + b) % vocab, zipf[:, t])
        prev = np.where(p2[:, t] == 0, zipf[:, t], nxt)
        toks[:, t] = prev
    toks = np.where(seg.reshape(rows, S) > 0, toks, 0)
    toks = toks.reshape(R, B, S).astype(np.int32)
    labels = np.full((R, B, S), -1, np.int32)
    labels[..., :-1] = np.where(wts[..., :-1] > 0, toks[..., 1:], -1)

    return [{"tokens": toks[i], "labels": labels[i],
             "segment_ids": seg[i], "positions": pos[i],
             "loss_weights": wts[i]} for i in range(R)]


def doc_lengths(batch: dict) -> list:
    """Lengths of the documents (pieces) packed in one batch."""
    seg = np.asarray(batch["segment_ids"])
    out = []
    for row in seg:
        ids, counts = np.unique(row[row > 0], return_counts=True)
        out += [int(c) for c in counts]
    return out
