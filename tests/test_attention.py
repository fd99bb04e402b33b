"""Flash attention: jnp scan reference (custom VJP) vs naive oracle, the
fused Pallas kernels behind ``dispatch.flash_attention`` vs that reference,
and the shard_map'd variant on a forced-8-device (4, 2) host mesh.

Layered like the xent tests: first pin the scan reference (including the
rectangular-causal T > S support cached prefill continuation needs), then
hold the fused dispatch path — interpret oracle on CPU — to it for the
forward and dQ/dK/dV across dtypes, GQA ratios, ragged shapes, the
``kv_len`` decode bound, fully-masked rows and packed rows over many tiles,
check the kernels' fetch plan against their tile test, and finally the
sharded matrix in a subprocess mesh.
"""
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import repro_fused
from repro.kernels import dispatch
from repro.kernels.attention import attention as ak
from repro.kernels.attention import ref as aref
from repro.kernels.attention.mask import fetch_plan, mask_spec
from repro.models.layers import (_pick_block, causal_blockwise_attention,
                                 chunked_q_attention, decode_attention,
                                 flash_attention, largest_divisor)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # minimal install: only the property test skips
    HAVE_HYPOTHESIS = False


def naive(q, k, v, scale, causal=True, kv_len=None):
    """The test-scale full-softmax oracle (kernels/attention/ref.py)."""
    return aref.attention(q, k, v, scale=scale, causal=causal, kv_len=kv_len)


def _gqa(B, S, T, H, K, hd, dtype=jnp.float32, seed=0, hdv=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, T, K, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, T, K, hdv or hd),
                          jnp.float32).astype(dtype)
    dout = jax.random.normal(ks[3], (B, S, H, hdv or hd),
                             jnp.float32).astype(dtype)
    return q, k, v, dout


# ---- the jnp scan reference ------------------------------------------------

@pytest.mark.parametrize("B,S,T,H,hd,blk,causal", [
    (2, 64, 64, 4, 16, 16, True),
    (1, 128, 128, 8, 32, 32, True),
    (2, 96, 96, 2, 8, 48, True),
    (2, 64, 32, 4, 16, 16, False),
    (1, 60, 60, 2, 8, 16, True),     # non-divisible -> block fallback
    (1, 16, 48, 2, 8, 16, True),     # rectangular causal: cached prefill
    (2, 24, 60, 2, 8, 12, True),     # rectangular + block fallback
])
def test_flash_matches_naive(B, S, T, H, hd, blk, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, T, H, hd))
    v = jax.random.normal(ks[2], (B, T, H, hd))
    dout = jax.random.normal(ks[3], (B, S, H, hd))
    out = flash_attention(q, k, v, blk, hd ** -0.5, causal)
    ref = naive(q, k, v, hd ** -0.5, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    f = lambda *a: jnp.sum(flash_attention(*a, blk, hd ** -0.5, causal) * dout)
    g = lambda *a: jnp.sum(naive(*a, hd ** -0.5, causal) * dout)
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gg):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_rectangular_causal_rejects_more_queries_than_keys():
    q, k, v, _ = _gqa(1, 8, 4, 2, 2, 8)
    with pytest.raises(ValueError, match="needs T >= S"):
        flash_attention(q, k, v, 4, 0.35, True)


if HAVE_HYPOTHESIS:
    @given(seed=st.integers(0, 2**16), s_blocks=st.integers(1, 4),
           h=st.sampled_from([1, 2, 4]), hd=st.sampled_from([4, 8, 16]))
    @settings(max_examples=15, deadline=None)
    def test_flash_property(seed, s_blocks, h, hd):
        S = 16 * s_blocks
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (1, S, h, hd))
        k = jax.random.normal(ks[1], (1, S, h, hd))
        v = jax.random.normal(ks[2], (1, S, h, hd))
        out = flash_attention(q, k, v, 16, hd ** -0.5, True)
        ref = naive(q, k, v, hd ** -0.5, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5)


def test_gqa_repeat_equivalence():
    """GQA via repeated kv == grouped-head einsum oracle (reference path)."""
    B, S, H, K, hd = 2, 64, 8, 2, 16
    q, k, v, _ = _gqa(B, S, S, H, K, hd, seed=1)
    with repro_fused("off"):
        out = causal_blockwise_attention(q, k, v, 16, hd ** -0.5)
    kf = jnp.repeat(k, H // K, 2)
    vf = jnp.repeat(v, H // K, 2)
    ref = naive(q, kf, vf, hd ** -0.5, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_chunked_q_attention_kv_len_mask():
    B, S, T, H, hd = 1, 4, 32, 2, 8
    q, k, v, _ = _gqa(B, S, T, H, H, hd, seed=2)
    out = chunked_q_attention(q, k, v, 4, hd ** -0.5, kv_len=jnp.asarray(10))
    ref = naive(q, k[:, :10], v[:, :10], hd ** -0.5, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---- shared divisor helper (block fallbacks) -------------------------------

def test_largest_divisor():
    assert largest_divisor(60, 16) == 15
    assert largest_divisor(64, 64) == 64
    assert largest_divisor(17, 16) == 1
    assert largest_divisor(1, 8) == 1


def test_pick_block_common_divisor_and_warning():
    # common-divisor search replaces the silent decrement loop
    assert _pick_block(64, 64, 16) == 16
    assert _pick_block(60, 60, 16) == 15
    assert _pick_block(24, 60, 16) == 12
    with pytest.warns(UserWarning, match="tile shrinks to 1"):
        assert _pick_block(17, 17, 16) == 1  # prime S: per-position scan
    with pytest.warns(UserWarning, match="tile shrinks"):
        assert _pick_block(2 * 97, 2 * 97, 64) == 2
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # >= half the target: silent
        assert _pick_block(60, 60, 16) == 15
        assert _pick_block(32, 48, 16) == 16


# ---- fused dispatch parity -------------------------------------------------

DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    # bf16 dK/dV reduce over up to 8 group heads of bf16-rounded products
    return 6e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", [
    (2, 32, 32, 4, 4, 16, True),    # GQA ratio 1
    (2, 32, 32, 8, 2, 16, True),    # GQA ratio 4
    (1, 32, 32, 8, 1, 8, True),     # GQA ratio 8 (MQA)
    (1, 60, 124, 4, 2, 8, True),    # ragged rectangular causal T > S
    (2, 48, 20, 4, 2, 8, False),    # ragged non-causal cross attention
], ids=["gqa1", "gqa4", "gqa8", "rect_ragged", "cross_ragged"])
def test_fused_flash_matches_reference(shape, dtype):
    """dispatch.flash_attention (kernels, no kv repeat) == the plain
    float32 oracle over repeated kv, for the forward and all three
    gradients. The oracle runs on the same (possibly bf16-valued) inputs
    in f32 at "highest" matmul precision: a bf16 reference would round
    each of the G group heads' dK/dV before summing them, an error of its
    own that the kernels (one f32 accumulation) do not make."""
    B, S, T, H, K, hd, causal = shape
    _check_fused_parity(*_gqa(B, S, T, H, K, hd, dtype, seed=3), causal)


def _check_fused_parity(q, k, v, dout, causal, segments=None, block=None,
                        kv_len=None):
    H, K, hd, dtype = q.shape[2], k.shape[2], q.shape[3], q.dtype
    scale = hd ** -0.5
    assert dispatch.attn_route(q.shape, k.shape, causal)[0] == "kernel"
    f32 = lambda x: x.astype(jnp.float32)

    def f_fused(q, k, v):
        return jnp.sum(f32(dispatch.flash_attention(
            q, k, v, scale=scale, causal=causal, segments=segments,
            kv_len=kv_len, block=block)) * f32(dout))

    # grad through the repeat sums group heads back onto (B, T, K, hd)
    def terms_ref(q, k, v):
        kf, vf = jnp.repeat(k, H // K, 2), jnp.repeat(v, H // K, 2)
        return aref.attention(q, kf, vf, scale=scale, causal=causal,
                              segments=segments, kv_len=kv_len) * f32(dout)

    v1, g1 = jax.value_and_grad(f_fused, argnums=(0, 1, 2))(q, k, v)
    with jax.default_matmul_precision("highest"):
        v2, g2 = jax.value_and_grad(lambda *a: jnp.sum(terms_ref(*a)),
                                    argnums=(0, 1, 2))(f32(q), f32(k), f32(v))
        # the value is a sum of terms of both signs that can nearly cancel
        # (gqa8: -0.28 from terms whose magnitudes sum to ~640), so its
        # rounding error scales with the sum of the terms' magnitudes
        mag = float(jnp.sum(jnp.abs(terms_ref(f32(q), f32(k), f32(v)))))
    rtol = 1e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(float(v1), float(v2), rtol=0, atol=rtol * mag)
    tol = _tol(dtype)
    for a, x, b, name in zip(g1, (q, k, v), g2, ("dq", "dk", "dv")):
        assert a.shape == x.shape and a.dtype == x.dtype, name
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=tol,
                                   err_msg=name)


def _packed_ids(S, *rows):
    """(len(rows), S) int32 segment ids: each row's documents of the given
    lengths from lane 0 on, ids 1, 2, ...; the rest of the row is pad (0)."""
    seg = np.zeros((len(rows), S), np.int32)
    for b, lens in enumerate(rows):
        ends = np.cumsum(lens)
        for d, (a, e) in enumerate(zip(ends - lens, ends), 1):
            seg[b, a:e] = d
    return jnp.asarray(seg)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("lens", [
    # 200 lanes in 32-lane tiles: the last document ends on a tile edge
    # (lane 160), so the last tiles (160-191, 192-199) hold pad only
    ([30, 66, 64], [10, 118, 32]),
    # one document over five of seven tiles between two short ones
    ([5, 150, 45],),
], ids=["pad_tiles", "long_doc"])
def test_fused_packed_matches_reference(lens, dtype):
    """Packed rows over many tiles, where most tile pairs are dead and
    their steps fetch no block: the kernels still match the oracle with
    its segment mask, forward and all three gradients."""
    seg = _packed_ids(200, *lens)
    q, k, v, dout = _gqa(seg.shape[0], 200, 200, 2, 1, 16, dtype, seed=15)
    _check_fused_parity(q, k, v, dout, True, (seg, seg), block=(32, 32))


@pytest.mark.parametrize("S,T,causal,kv_len", [
    (160, 160, True, None),     # unpacked causal: dead above the diagonal
    (96, 224, True, None),      # causal offset T > S
    (8, 224, False, 77),        # decode: dead past the cache fill
    (96, 160, False, None),     # cross: every pair live
], ids=["causal", "offset", "decode", "cross"])
def test_fused_tiled_matches_reference(S, T, causal, kv_len):
    """Unpacked calls over many 32-lane tiles, each with the fetch plan its
    own mask gives, against the oracle: forward and all three gradients."""
    q, k, v, dout = _gqa(1, S, T, 2, 1, 16, seed=16)
    _check_fused_parity(q, k, v, dout, causal, block=(32, 32),
                        kv_len=None if kv_len is None else jnp.asarray(kv_len))


def _live_tiles(S, T, bq, bk, causal, kv_len=None, segments=None):
    """(B, n_q, n_k) bool: the kernels' per-step tile test, pair by pair
    over numpy ids (ranges over the lanes inside S and T)."""
    nq, nk = -(-S // bq), -(-T // bk)
    offset = T - S if causal else 0
    kl = T if kv_len is None else kv_len
    segs = (np.zeros((1, S), int), np.zeros((1, T), int)) \
        if segments is None else [np.asarray(x) for x in segments]
    live = np.zeros((segs[0].shape[0], nq, nk), bool)
    for b, i, j in np.ndindex(live.shape):
        qs = segs[0][b, i * bq:min((i + 1) * bq, S)]
        ks = segs[1][b, j * bk:min((j + 1) * bk, T)]
        live[b, i, j] = (j * bk < kl
                         and (not causal
                              or offset + (i + 1) * bq - 1 >= j * bk)
                         and qs.min() <= ks.max() and ks.min() <= qs.max())
    return live


def _random_rows(rng, B, S, tile, pad):
    """Packed rows of random documents; some end on a tile edge, and with
    ``pad`` a row may end in a pad tail."""
    rows = []
    for _ in range(B):
        lens, left = [], S - (int(rng.integers(0, S // 2)) if pad else 0)
        while left > 0:
            n = int(rng.choice([rng.integers(1, 3 * tile), tile,
                                2 * tile]))
            lens.append(min(n, left))
            left -= lens[-1]
        rows.append(lens)
    return _packed_ids(S, *rows)


@pytest.mark.parametrize("S,T,bq,bk,causal,kv_len,packed", [
    (256, 256, 32, 32, True, None, "pad"),      # pad tails, tile edges
    (200, 200, 32, 64, True, None, "pad"),      # remainder tiles
    (200, 200, 32, 32, True, None, "full"),     # rows without pad
    (96, 224, 32, 64, True, None, "full"),      # causal offset T > S
    (256, 256, 64, 32, True, None, None),       # unpacked causal
    (8, 200, 8, 32, False, 77, None),           # kv_len decode
    (64, 200, 32, 32, False, None, None),       # cross, no segments
    (128, 128, 32, 32, False, None, "pad"),     # non-causal packed
], ids=["pad", "remainder", "no_pad", "offset", "causal", "decode",
        "cross", "noncausal_packed"])
def test_fetch_plan_covers_every_live_tile(S, T, bq, bk, causal, kv_len,
                                           packed):
    """Every tile pair the kernels compute lies inside the fetch plan's
    range, in both orientations, and each range starts and ends on a live
    pair (a tile with none gets (0, 0))."""
    rng = np.random.default_rng(S + T + bq)
    B = 3
    segments = None
    if packed:
        seg = _random_rows(rng, B, S, bq, packed == "pad")
        segments = (seg, seg if S == T else
                    _random_rows(rng, B, T, bk, packed == "pad"))
        if S != T:  # the queries are the last S keys of each row
            segments = (segments[1][:, T - S:], segments[1])
    spec = mask_spec(S, T, causal=causal, segments=segments,
                     kv_len=None if kv_len is None else jnp.asarray(kv_len))
    plan = [np.asarray(x) for x in fetch_plan(
        spec, S, T, bq, bk, batch=B, segments=segments,
        kv_len=None if kv_len is None else jnp.asarray(kv_len))]
    live = np.broadcast_to(_live_tiles(S, T, bq, bk, causal, kv_len,
                                       segments), (B, -(-S // bq),
                                                   -(-T // bk)))
    assert live.any()
    for lo, hi, lv in ((plan[0], plan[1], live),
                       (plan[2], plan[3], live.transpose(0, 2, 1))):
        t = np.arange(lv.shape[2])
        inside = (t >= lo[..., None]) & (t <= hi[..., None])
        assert not (lv & ~inside).any()
        some = lv.any(2)
        pick = lambda x: np.take_along_axis(lv, x[..., None], 2)[..., 0]
        assert (pick(lo) | ~some).all() and (pick(hi) | ~some).all()
        assert (lo[~some] == 0).all() and (hi[~some] == 0).all()


def test_fetch_repeats_hand_count():
    """Skipped copies of a fixed layout, counted by hand: one 1024-lane
    row, 512-lane tiles, 4 query heads on 2 kv heads, two documents that
    meet on the tile edge (the off-diagonal tile pairs are dead) against
    one document over the whole row (only the pair above the diagonal
    is)."""
    q = jax.ShapeDtypeStruct((1, 1024, 4, 16), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, 1024, 2, 16), jnp.float32)
    two = _packed_ids(1024, [512, 512])
    # forward/dQ: each head reads kv tiles 0, 0 for q tile 0 and 1, 1 for
    # q tile 1 -> 2 repeats a head; dK/dV likewise for each (kv, group)
    assert ak.fetch_repeats(q, kv, kv, segments=(two, two)) == {
        "fwd": (8, 16), "dq": (8, 16), "dkv": (8, 16)}
    one = _packed_ids(1024, [1024])
    # forward/dQ: kv tiles 0, 0 | 0, 1 per head -> 2 repeats a head;
    # dK/dV: q tiles 0, 1 under kv tile 0 (no repeat), 1, 1 under kv
    # tile 1 for each of the 2 group heads -> 2 repeats a kv head
    assert ak.fetch_repeats(q, kv, kv, segments=(one, one)) == {
        "fwd": (8, 16), "dq": (8, 16), "dkv": (4, 16)}
    # the same unpacked causal call: the plan comes from the diagonal alone
    assert ak.fetch_repeats(q, kv, kv) == ak.fetch_repeats(
        q, kv, kv, segments=(one, one))


def test_fused_decode_over_cache_kv_len():
    """The rectangular decode shape (S=1..block vs a T cache) with the
    traced kv_len bound == chunked_q_attention == naive over k[:kv_len]."""
    B, T, H, K, hd = 2, 64, 4, 2, 8
    scale = hd ** -0.5
    for S, fill in ((1, 10), (4, 33), (8, 64)):
        q, k, v, _ = _gqa(B, S, T, H, K, hd, seed=4 + S)
        kv_len = jnp.asarray(fill)
        out = dispatch.flash_attention(q, k, v, scale=scale, causal=False,
                                       kv_len=kv_len)
        ref = chunked_q_attention(q, k, v, S, scale, kv_len=kv_len)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        kf, vf = jnp.repeat(k, H // K, 2), jnp.repeat(v, H // K, 2)
        nref = naive(q, kf[:, :fill], vf[:, :fill], scale, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(nref),
                                   atol=2e-5)
    # decode_attention routes the same call (and falls back bitwise)
    q, k, v, _ = _gqa(B, 1, T, H, K, hd, seed=9)
    out = decode_attention(q, k, v, 1, scale, kv_len=jnp.asarray(7))
    with repro_fused("off"):
        ref = decode_attention(q, k, v, 1, scale, kv_len=jnp.asarray(7))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_fused_fully_masked_rows_emit_zero():
    """kv_len=0 masks every key: the flash convention (l clamped at 1e-30)
    emits exactly 0 output and 0 gradients — where a naive softmax NaNs."""
    q, k, v, dout = _gqa(1, 4, 16, 4, 2, 8, seed=10)
    out = dispatch.flash_attention(q, k, v, scale=0.35, causal=False,
                                   kv_len=jnp.asarray(0))
    np.testing.assert_array_equal(np.asarray(out), 0.0)
    grads = jax.grad(
        lambda q, k, v: jnp.sum(dispatch.flash_attention(
            q, k, v, scale=0.35, causal=False, kv_len=jnp.asarray(0))
            * dout), argnums=(0, 1, 2))(q, k, v)
    for g in grads:
        np.testing.assert_array_equal(np.asarray(g), 0.0)


def test_attn_routing_and_fallbacks(monkeypatch):
    assert dispatch.attn_supported((2, 8, 4, 16), (2, 8, 2, 16))
    assert dispatch.attn_supported((2, 8, 4, 16), (2, 32, 2, 16))  # T > S
    assert not dispatch.attn_supported((2, 8, 4, 16), (2, 4, 2, 16))  # T < S
    assert dispatch.attn_supported((2, 8, 4, 16), (2, 4, 2, 16),
                                   causal=False)
    assert not dispatch.attn_supported((2, 8, 4, 16), (2, 8, 3, 16))  # H % K
    assert not dispatch.attn_supported((2, 8, 4, 16), (2, 8, 2, 8))  # hd
    assert not dispatch.attn_supported((2, 8, 4, 16), (1, 8, 2, 16))  # B
    assert not dispatch.attn_supported((8, 4, 16), (8, 2, 16))  # ndim
    assert dispatch.attn_route((2, 8, 4, 16), (2, 8, 2, 16))[0] == "kernel"
    # causal + kv_len has no implemented semantics on either route: the
    # entry point must refuse rather than silently pick one per route
    qe, ke, ve, _ = _gqa(1, 4, 8, 2, 2, 8, seed=14)
    with pytest.raises(ValueError, match="kv_len requires causal=False"):
        dispatch.flash_attention(qe, ke, ve, scale=0.35, causal=True,
                                 kv_len=jnp.asarray(4))
    monkeypatch.setenv("REPRO_FUSED", "off")
    assert dispatch.attn_route((2, 8, 4, 16), (2, 8, 2, 16))[0] == "ref"
    with pytest.raises(ValueError, match="kv_len requires causal=False"):
        dispatch.flash_attention(qe, ke, ve, scale=0.35, causal=True,
                                 kv_len=jnp.asarray(4))
    # the off-route still yields correct (scan-reference) values, bitwise
    q, k, v, _ = _gqa(1, 16, 16, 4, 2, 8, seed=11)
    out = dispatch.flash_attention(q, k, v, scale=0.35)
    kf, vf = jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(flash_attention(q, kf, vf, 128, 0.35,
                                                    True)))


def test_forward_fused_equals_scan_reference():
    """End-to-end: a tiny model forward + loss grads with the default
    (fused) attention == the REPRO_FUSED=off scan path."""
    from conftest import tiny_cfg
    from repro.models import init_params, loss_fn
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(12), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(13), (2, 32), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}

    def loss(p):
        return loss_fn(p, cfg, batch)[0]

    l_f, g_f = jax.value_and_grad(loss)(params)
    with repro_fused("off"):
        l_r, g_r = jax.value_and_grad(loss)(params)
    np.testing.assert_allclose(float(l_f), float(l_r), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_f),
                    jax.tree_util.tree_leaves(g_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


# ---- sharded matrix on a forced 8-device host mesh ------------------------

def test_sharded_attention_parity_under_forced_8_devices():
    """(4, 2) mesh: batch over "data", heads over "model" — each device
    runs its local (B/4, S, H/2, hd) x (B/4, T, K/2, hd) problem with no
    collectives. out/dQ/dK/dV must match the unsharded scan reference for
    f32 and bf16 across GQA ratios; inexpressible layouts fall back."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.kernels import dispatch
from repro.models.layers import flash_attention

assert len(jax.devices()) == 8
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(data=4, model=2)  # Auto axes, as the trainer's
B, S, T, H, hd = 8, 16, 16, 8, 8
scale = hd ** -0.5
qsh = NamedSharding(mesh, P("data", None, "model", None))
for dtype in (jnp.float32, jnp.bfloat16):
    for K in (8, 2):  # GQA ratios 1 and 4, kv heads TP-shard alongside q
        ks = jax.random.split(jax.random.PRNGKey(K), 4)
        q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32).astype(dtype)
        k = jax.random.normal(ks[1], (B, T, K, hd), jnp.float32).astype(dtype)
        v = jax.random.normal(ks[2], (B, T, K, hd), jnp.float32).astype(dtype)
        do = jax.random.normal(ks[3], (B, S, H, hd), jnp.float32).astype(dtype)
        route, plan = dispatch.attn_route(q.shape, k.shape, True, None,
                                          qsh, qsh)
        assert route == "kernel" and plan.batch_axes == ("data",) \
            and plan.head_axes == ("model",), (route, plan)
        q_s, k_s, v_s = (jax.device_put(x, qsh) for x in (q, k, v))

        def f_fused(q, k, v):
            return jnp.sum(dispatch.flash_attention(
                q, k, v, scale=scale, causal=True, q_sharding=qsh,
                kv_sharding=qsh).astype(jnp.float32)
                * do.astype(jnp.float32))

        def f_ref(q, k, v):
            kf, vf = jnp.repeat(k, H // K, 2), jnp.repeat(v, H // K, 2)
            return jnp.sum(flash_attention(q, kf, vf, 16, scale, True)
                           .astype(jnp.float32) * do.astype(jnp.float32))

        v1, g1 = jax.value_and_grad(f_fused, argnums=(0, 1, 2))(q_s, k_s, v_s)
        v2, g2 = jax.value_and_grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        # bf16 compares bf16-rounded outputs/grads whose sums/reductions
        # round differently between the two implementations
        tol = 1e-1 if dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(
            float(v1), float(v2),
            rtol=5e-2 if dtype == jnp.bfloat16 else 1e-5)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=tol)

# MQA: K=1 cannot shard over the 2-way head axis while q does -> the
# kernel's q_head // group indexing would misalign; must fall back
ksh1 = NamedSharding(mesh, P("data", None, None, None))
assert dispatch.attn_route((8, 16, 8, 8), (8, 16, 1, 8), True, None,
                           qsh, ksh1)[0] == "ref"
# sequence-sharded kv (the decode cache layout) -> ref
cache_sh = NamedSharding(mesh, P("data", "model", None, None))
assert dispatch.attn_route((8, 1, 8, 8), (8, 16, 8, 8), False, None,
                           NamedSharding(mesh, P("data", None, None, None)),
                           cache_sh)[0] == "ref"
# batch not divisible by its axes -> ref
assert dispatch.attn_route((6, 16, 8, 8), (6, 16, 8, 8), True, None,
                           qsh, qsh)[0] == "ref"

# end-to-end under the mesh: loss_fn(mesh=...) routes attention + xent
# through the sharded kernel plans and must match the off-mesh value
from conftest import tiny_cfg
from repro.models import init_params, loss_fn
from repro.models.sharding import Rules, tree_shardings
from repro.models import param_logical_axes, param_shapes
cfg = tiny_cfg(vocab_size=256)
params = init_params(jax.random.PRNGKey(5), cfg)
toks = jax.random.randint(jax.random.PRNGKey(6), (8, 32), 0, 256)
batch = {"tokens": toks, "labels": toks}
shardings = tree_shardings(param_logical_axes(cfg), mesh, Rules(),
                           param_shapes(cfg))
params_s = jax.tree_util.tree_map(jax.device_put, params, shardings)
l_mesh = loss_fn(params_s, cfg, batch, mesh=mesh)[0]
l_ref = loss_fn(params, cfg, batch)[0]
np.testing.assert_allclose(float(l_mesh), float(l_ref), rtol=1e-5)
print("OK")
"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_FUSED", None)
    here = os.path.dirname(__file__)
    env["PYTHONPATH"] = (os.path.join(here, "..", "src") + os.pathsep + here
                         + os.pathsep + env.get("PYTHONPATH", ""))
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout
