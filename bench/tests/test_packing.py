"""The traffic generator: deterministic, the same work for every seed."""
import numpy as np
import pytest

from bench import counts, harness, packing
from bench.tests.tiny import TRAFFIC


def ring(seed):
    return packing.make_ring(TRAFFIC, 256, 250, seed)


def test_same_seed_same_inputs():
    a, b = ring(7), ring(7)
    for x, y in zip(a, b):
        for k in packing.KEYS:
            np.testing.assert_array_equal(x[k], y[k])


def test_every_seed_does_the_same_work():
    def work(r):
        return [(counts.causal_pairs(b), counts.labelled_tokens(b))
                for b in r]
    assert work(ring(1)) == work(ring(2**31 + 11))
    assert any(not np.array_equal(x["tokens"], y["tokens"])
               for x, y in zip(ring(1), ring(2)))


def test_packed_format():
    for b in ring(3):
        seg, pos, lab = b["segment_ids"], b["positions"], b["labels"]
        w, tok = b["loss_weights"], b["tokens"]
        assert seg.shape == (4, 64) and tok.dtype == np.int32
        assert tok.min() >= 0 and tok.max() < 250
        for r in range(4):
            for s in np.unique(seg[r][seg[r] > 0]):
                idx = np.flatnonzero(seg[r] == s)
                assert np.array_equal(idx, np.arange(idx[0], idx[-1] + 1))
                assert np.array_equal(pos[r, idx], np.arange(len(idx)))
                # labels are the next token inside the document
                assert np.array_equal(lab[r, idx[:-1]], tok[r, idx[1:]])
                assert lab[r, idx[-1]] == -1 and w[r, idx[-1]] == 0
            assert np.all(lab[r][seg[r] == 0] == -1)
        assert np.array_equal(w > 0, lab >= 0)


def test_long_documents_split_at_the_row():
    for b in ring(5):
        assert max(packing.doc_lengths(b)) <= 64
    assert any(64 in packing.doc_lengths(b) for b in ring(5))


def test_every_component_fills_its_weight():
    """Whole pieces, the last one drawn with the chance the share has room
    for: each component within one piece of its weight, and on average
    over layouts on it."""
    got = packing.doc_pieces(TRAFFIC, 1000)
    assert abs(sum(got["short"]) - 700) < 64
    assert abs(sum(got["long"]) - 300) < 64
    assert max(got["long"]) == 64 and max(got["short"]) <= 64
    mean = np.mean([sum(packing.doc_pieces(dict(TRAFFIC, layout_seed=i),
                                           1000)["long"])
                    for i in range(400)])
    assert abs(mean - 300) < 6


@pytest.mark.parametrize("mix", ["pack4k", "pack16k"])
def test_published_mixes_fill_their_rows(mix):
    """The benchmark's mixes: every component is drawn, long documents
    fill whole rows, and the ring has no pad."""
    traffic = harness.load_traffic(mix)
    S = traffic["row_len"]
    pieces = packing.doc_pieces(traffic, 8 * 16384)
    assert set(pieces) == {c[0] for c in traffic["corpus"]["components"]}
    r = packing.make_ring(traffic, 16384, 152064, 2**31 + 17)
    assert max(n for b in r for n in packing.doc_lengths(b)) == S
    assert sum(counts.doc_tokens(b) for b in r) == 8 * 16384


@pytest.mark.parametrize("vocab", [152064, 49155])
def test_bigram_is_a_bijection(vocab):
    """No seed's chain of tokens falls into a short cycle: the most
    frequent token's share is alike on every seed."""
    t = dict(TRAFFIC, row_len=512)
    top = []
    for seed in (3, 4, 7, 8, 12, 2**31 + 1):
        toks = np.concatenate([b["tokens"][b["segment_ids"] > 0]
                               for b in packing.make_ring(t, 2048, vocab,
                                                          seed)])
        top.append(np.bincount(toks).max() / toks.size)
    assert max(top) < 1.5 * min(top), top
