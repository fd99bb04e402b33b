"""Pallas TPU kernels for fused blockwise (flash) attention.

Attention is the last major hot path of a training step that still runs as
a jnp ``lax.scan`` over tile pairs (``models.layers.flash_attention``):
correct and memory-O(tile), but each scan step round-trips the f32
output/max/sum carries through HBM block slices and — for GQA — consumes a
kv tree *repeated* to the full query head count. These kernels keep the
flash algorithm and move everything tile-resident:

  * ``mha_fwd`` — grid (batch*heads, q tiles, kv tiles), kv innermost.
    Each step computes one (bq, bk) score tile on the MXU and folds it
    into the running online-softmax carries (max, sum, f32 output
    accumulator) held in VMEM scratch; the normalized output and per-row
    ``lse`` are emitted once at the last kv tile. The f32 carries never
    touch HBM, and peak score storage is one (bq, bk) VMEM tile —
    independent of S, T and the head count (the scan's einsum materializes
    the (B, H, bq, bk) tile across *all* heads at once).
  * ``mha_bwd_dq`` — same tiling; recomputes the score tile from
    (q, k, lse), forms ``ds = p * (dp - D) * scale`` in registers and
    accumulates ``ds @ k_tile`` into a (bq, hd) scratch, one dQ write per
    q tile.
  * ``mha_bwd_dkv`` — transposed grid (batch*kv_heads, kv tiles, group,
    q tiles): the (bk, hd)/(bk, hdv) dK/dV tiles stay resident in scratch
    while all q tiles *of every query head in the group* stream by — the
    GQA group reduction happens in VMEM, so dK/dV are emitted directly in
    the (B, T, K, hd) storage layout (the scan repeats kv up front and
    pays G-times the kv traffic in both directions).

GQA is native: kv BlockSpecs index the kv head as ``q_head // group``
(forward/dQ) or iterate the group on the grid (dK/dV) — the H/K repeat is
never materialized.

Masking is described by one :class:`~repro.kernels.attention.mask.MaskSpec`
(see that module): rectangular causal with the static offset ``T - S``
folded into the tile iota, the traced ``kv_len`` cache-fill bound, and —
for packed multi-document batches — per-position **segment ids**. Segment
ids ride as two int32 operands blocked alongside q and kv: the query tile
sees a (bq, 1) column and the key tile a (1, bk) row, whose broadcasted
equality intersects the causal/bound clauses elementwise. Tile pairs that
are fully masked skip their compute entirely via ``pl.when`` — above the
causal diagonal, past ``kv_len`` (decode over a mostly empty cache touches
only the filled tiles), or when the two tiles' segment-id *ranges* don't
overlap (packed documents are contiguous, so segment ids are sorted per
row and a min/max range test is exact for interior tiles).

Skipped pairs skip their copies too. Each wrapper builds one
:func:`~repro.kernels.attention.mask.fetch_plan` from its mask operands —
the same tile test over every pair: for each (row, q tile) the first and
last live kv tile, for each (row, kv tile) the first and last live q tile
— and hands it to the ``pallas_call`` as two scalar-prefetch operands.
The index maps of the streamed blocks (k, v and the kv segment ids in
forward/dQ; q, dout, lse, delta and the q segment ids in dK/dV) clamp
their tile into that range, so a dead step asks for the block the step
before it holds and the pipeline starts no copy. Such a step then holds
another tile's segment ids, so the tile test, with its segment-id reads
and reductions, runs only on a step whose clamped index is its own: a
step outside the range does no work at all. Every live step computes on
the same blocks as without the plan. :func:`fetch_repeats` counts the
steps that copy nothing.

Masking mirrors the xent kernels' conventions (out-of-bounds block regions
are undefined — NaN in interpret mode — and 0*NaN = NaN, so *both*
operands of every contraction are zeroed on padded positions):

  * remainder kv tiles (T % bk): score columns past T are masked to the
    finite ``_NEG`` stand-in and k/v rows past T are zeroed before any
    contraction that consumes them; segment-id lanes past the bounds are
    pushed out of the tile-skip min/max reductions;
  * remainder q tiles (S % bq): forward/dQ rows are independent and
    clipped on write; dK/dV zero q/dout rows and ``p``/``ds`` rows past S
    before the row contraction;
  * fully-masked rows (``kv_len`` 0, nothing valid, or — with segments —
    a pad row whose segment id appears nowhere in the keys) emit 0 output
    via the ``max(l, 1e-30)`` clamp — the same convention as the jnp scan
    — and a ~-1e30 ``lse``, which makes their backward contributions
    vanish.

Layout: the public entry points take the model's (B, S, H, hd) activation
layout and transpose to the kernels' (B, H, S, hd) so the sequence tile is
the sublane dimension (one XLA transpose each way; the grid then indexes
4-D blocks of shape (1, 1, tile, hd)). ``kv_len`` is a traced SMEM scalar;
segment ids are (B, S, 1)/(B, 1, T) int32 VMEM blocks (a zero-size dummy
pair keeps the kernel arity fixed when the spec has no segment clause).
All softmax statistics and accumulators are f32; probability tiles are
cast to the value dtype for the MXU contraction exactly like the scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..vmem import COMPILER_PARAMS, VMEM_BUDGET_BYTES, tile_bytes
from .mask import MaskSpec, fetch_plan, mask_spec

_NEG = -1e30  # finite -inf stand-in: keeps the running max NaN-free when a
#               tile (or a whole row) is entirely masked
_SEG_BIG = 2 ** 30  # out-of-bounds stand-in for segment-id min/max ranges


def _pick_tiles(S: int, T: int, hd: int, hdv: int, block=None, *,
                el_bytes: int = 4):
    """(bq, bk) tile for one kernel, clamped to the (padded) problem.

    Both tiles start at 512 and shrink until the scoped-VMEM working set
    of the largest of the three kernels fits the budget (see
    :mod:`repro.kernels.vmem`): every q/k/v/dout/output block twice
    (double-buffered, lanes padded — hd 64 costs as 128), the (bq, 1) row
    statistics, the f32 accumulators, and six f32 (bq, bk) score-sized
    temporaries (scores, p, dp, ds and the row/column iotas). bk shrinks
    first (k/v stream per q tile; a bigger bq is the HBM-reuse lever).
    Floors at 128; the clamp keeps tiny problems to a single tile.
    """
    if block is not None:
        bq, bk = block
    else:
        bq = bk = 512

        def cost(bq, bk):
            q, qv = tile_bytes(bq, hd, el_bytes), tile_bytes(bq, hdv, el_bytes)
            k, v = tile_bytes(bk, hd, el_bytes), tile_bytes(bk, hdv, el_bytes)
            vec = tile_bytes(bq, 1, 4)
            blocks = q + k + v + qv + max(q, qv) + k + v + 3 * vec
            scratch = (tile_bytes(bq, max(hd, hdv), 4) + tile_bytes(bk, hd, 4)
                       + tile_bytes(bk, hdv, 4) + 2 * vec)
            return 2 * blocks + scratch + 6 * tile_bytes(bq, bk, 4) + q + qv

        while cost(bq, bk) > VMEM_BUDGET_BYTES and bk > 128:
            bk //= 2
        while cost(bq, bk) > VMEM_BUDGET_BYTES and bq > 128:
            bq //= 2
    bq = min(bq, -(-S // 8) * 8)
    bk = min(bk, -(-T // 128) * 128)
    return bq, bk


def _run_pair(i, j, bq, bk, spec: MaskSpec, kl, qseg, kseg, s_len: int,
              t_len: int):
    """Traced predicate: does tile pair (i, j) contain any valid position?

    False above the rectangular-causal diagonal (the last query row of
    tile i, at global position ``offset + (i+1)*bq - 1``, sits before the
    first key of tile j), entirely past the ``kv_len`` fill bound, or —
    with segments — when the two tiles' segment-id ranges don't intersect
    (ids are sorted within a packed row, so range overlap is exact for
    interior tiles and conservative on remainder tiles). Skipped pairs run
    no MXU work at all.
    """
    run = j * bk < kl
    if spec.causal:
        run &= spec.offset + (i + 1) * bq - 1 >= j * bk
    if spec.has_segments:
        # lanes past the real bounds hold undefined memory: push them out
        # of the min/max before reducing so the predicate stays sound
        qrows = i * bq + jax.lax.broadcasted_iota(jnp.int32, qseg.shape, 0)
        kcols = j * bk + jax.lax.broadcasted_iota(jnp.int32, kseg.shape, 1)
        q_lo = jnp.min(jnp.where(qrows < s_len, qseg, _SEG_BIG))
        q_hi = jnp.max(jnp.where(qrows < s_len, qseg, -_SEG_BIG))
        k_lo = jnp.min(jnp.where(kcols < t_len, kseg, _SEG_BIG))
        k_hi = jnp.max(jnp.where(kcols < t_len, kseg, -_SEG_BIG))
        run &= (q_lo <= k_hi) & (k_lo <= q_hi)
    return run


def _when_live(own, i, j, bq, bk, spec: MaskSpec, kl, qs_ref, ks_ref,
               s_len: int, t_len: int):
    """Decorator: run ``body(qseg, kseg)`` where tile pair (i, j) is live.

    ``own`` says the step holds its own blocks. A step clamped onto a
    neighbour's block holds that block's segment ids, so only an own step
    reads them and runs :func:`_run_pair`; every other step does nothing.
    """
    def wrap(body):
        @pl.when(own)
        def _test():
            qseg = qs_ref[0] if spec.has_segments else None
            kseg = ks_ref[0] if spec.has_segments else None
            pl.when(_run_pair(i, j, bq, bk, spec, kl, qseg, kseg, s_len,
                              t_len))(lambda: body(qseg, kseg))
    return wrap


def _masks(i, j, bq, bk, spec: MaskSpec, kl, qseg, kseg, s_len: int,
           t_len: int):
    """(col validity, row validity) (bq, bk) masks for one score tile."""
    rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = (cols < t_len) & (cols < kl)
    if spec.causal:
        valid &= spec.offset + rows >= cols
    if spec.has_segments:
        valid &= qseg == kseg  # (bq, 1) == (1, bk) -> (bq, bk)
    return valid, rows < s_len


def _seg_blocks(spec: MaskSpec, segments, B, S, T, bq, bk, qmap, kmap):
    """(q_seg array, kv_seg array, q BlockSpec, kv BlockSpec) operand pack.

    Segment ids enter as (B, S)/(B, T) int32 and are viewed as
    (B, S, 1)/(B, 1, T) so the query tile blocks to (1, bq, 1) — a column
    the score-tile mask broadcasts against the key tile's (1, 1, bk) row;
    ``qmap``/``kmap`` are their index maps. Without segments a zero-size
    dummy pair keeps the pallas arity fixed (one int32 element of traffic,
    no reads).
    """
    if not spec.has_segments:
        dummy = jnp.zeros((1, 1, 1), jnp.int32)
        zero = pl.BlockSpec((1, 1, 1), lambda *_: (0, 0, 0))
        return dummy, dummy, zero, zero
    q_seg, kv_seg = segments
    qs = q_seg.astype(jnp.int32).reshape(B, S, 1)
    ks = kv_seg.astype(jnp.int32).reshape(B, 1, T)
    return qs, ks, pl.BlockSpec((1, bq, 1), qmap), pl.BlockSpec((1, 1, bk),
                                                                kmap)


def _clamp(t, lo, hi, row):
    """Tile index ``t`` held to the live range ``[lo[row], hi[row]]``.

    A step outside the range asks for the block the step before it
    already holds, so the pipeline copies nothing for it.
    """
    return jnp.minimum(jnp.maximum(t, lo[row]), hi[row])


def _kv_stream(H: int, G: int, n_q: int):
    """Index map of the k/v blocks on the (batch*heads, q, kv) grid, the
    kv tile clamped to the live run of the step's (row, q tile)."""
    def index(bh, i, j, lo, hi):
        return (bh // H, (bh % H) // G,
                _clamp(j, lo, hi, (bh // H) * n_q + i), 0)
    return index


def _q_stream(K: int, G: int, n_k: int):
    """Index map of the q/dout/lse/delta blocks on the dK/dV grid
    (batch*kv_heads, kv, group, q), the q tile clamped to the live run of
    the step's (row, kv tile)."""
    def index(bk_, j, g, i, lo, hi):
        return (bk_ // K, (bk_ % K) * G + g,
                _clamp(i, lo, hi, (bk_ // K) * n_k + j), 0)
    return index


def fetch_repeats(q, k, v, kv_len=None, *, causal: bool = True,
                  segments=None, block=None) -> dict:
    """{kernel: (repeats, steps)} for one call of each attention kernel.

    ``repeats`` counts the grid steps whose streamed block (k/v in the
    forward and dQ kernels, q/dout in dK/dV) has the same index as the
    step before, so the pipeline copies none; ``steps`` is the grid's size.
    Takes the operands of :func:`mha_fwd` (arrays or ``ShapeDtypeStruct``)
    and evaluates the kernels' own index maps over their grids on the host.
    """
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    spec = mask_spec(S, T, causal=causal, kv_len=kv_len, segments=segments)
    bq, bk = _pick_tiles(S, T, hd, v.shape[-1], block,
                         el_bytes=jnp.dtype(q.dtype).itemsize)
    nq, nk = -(-S // bq), -(-T // bk)
    plan = [np.asarray(x).reshape(-1) for x in fetch_plan(
        spec, S, T, bq, bk, batch=B, kv_len=kv_len, segments=segments)]

    def count(index, grid, lo, hi):
        steps = np.indices(grid).reshape(len(grid), -1)
        blocks = np.stack(np.broadcast_arrays(
            *(np.asarray(x) for x in index(*steps, lo, hi))))
        return (int(np.all(blocks[:, 1:] == blocks[:, :-1], 0).sum()),
                blocks.shape[1])

    fwd = count(_kv_stream(H, G, nq), (B * H, nq, nk), plan[0], plan[1])
    dkv = count(_q_stream(K, G, nk), (B * K, nk, G, nq), plan[2], plan[3])
    return {"fwd": fwd, "dq": fwd, "dkv": dkv}


def _resolve_spec(spec, S, T, causal, kv_len, segments):
    # A trivial kv_len operand (the dispatch layer always threads the kl
    # scalar, defaulting it to T) is fine against has_kv_len=False — the
    # kernels treat kl as a universal key bound. The other direction, and
    # any segment mismatch, means the caller built the spec for different
    # operands.
    if spec is None:
        return mask_spec(S, T, causal=causal, kv_len=kv_len,
                         segments=segments)
    if (spec.has_kv_len and kv_len is None) or \
            spec.has_segments != (segments is not None):
        raise ValueError(f"traced operands do not match {spec}")
    return spec


def _zero_invalid_rows(ref, j, bk, t_len: int):
    """k/v block with undefined rows past T zeroed (remainder kv tiles)."""
    rows = j * bk + jax.lax.broadcasted_iota(jnp.int32, ref.shape[2:], 0)
    return jnp.where(rows < t_len, ref[0, 0], 0)


def _sdot(a, b):
    """(bq, d) x (bk, d) -> (bq, bk) f32 score-style contraction."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tdot(a, b):
    """(bq, bk) x (bq, d) -> (bk, d) f32 row (token) contraction."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# forward: online softmax over kv tiles, carries in VMEM scratch
# --------------------------------------------------------------------------

def _fwd_kernel(lo_ref, hi_ref, q_ref, k_ref, v_ref, kl_ref, qs_ref, ks_ref,
                o_ref, lse_ref, m_acc, l_acc, acc, *, scale, spec, bq, bk,
                heads, n_q_tiles, n_k_tiles, s_len, t_len):
    i, j = pl.program_id(1), pl.program_id(2)
    own = _clamp(j, lo_ref, hi_ref,
                 (pl.program_id(0) // heads) * n_q_tiles + i) == j
    kl = kl_ref[0, 0]

    @pl.when(j == 0)
    def _init():
        m_acc[...] = jnp.full_like(m_acc, _NEG)
        l_acc[...] = jnp.zeros_like(l_acc)
        acc[...] = jnp.zeros_like(acc)

    @_when_live(own, i, j, bq, bk, spec, kl, qs_ref, ks_ref, s_len, t_len)
    def _compute(qseg, kseg):
        s = _sdot(q_ref[0, 0], k_ref[0, 0]) * scale
        valid, _ = _masks(i, j, bq, bk, spec, kl, qseg, kseg, s_len, t_len)
        s = jnp.where(valid, s, _NEG)
        m_new = jnp.maximum(m_acc[...], jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_acc[...] - m_new)
        # explicit mask on the exp: with everything pinned at _NEG the
        # difference is 0 and exp would contribute 1 per masked column
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_acc[...] = l_acc[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_acc[...] = m_new
        v_eff = _zero_invalid_rows(v_ref, j, bk, t_len)
        acc[...] = acc[...] * alpha + jnp.dot(
            p.astype(v_eff.dtype), v_eff, preferred_element_type=jnp.float32)

    @pl.when(j == n_k_tiles - 1)
    def _emit():
        l = jnp.maximum(l_acc[...], 1e-30)  # fully-masked rows -> 0 output
        o_ref[0, 0] = (acc[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_acc[...] + jnp.log(l)


def mha_fwd(q, k, v, kv_len=None, *, scale: float, causal: bool = True,
            segments=None, spec: MaskSpec | None = None, block=None,
            interpret: bool = True):
    """(out, lse): q (B, S, H, hd); k (B, T, K, hd), v (B, T, K, hdv).

    H % K == 0 (kv blocks are indexed by ``q_head // group`` — the repeat
    is never materialized). Masking comes from ``spec`` (built from the
    ``causal``/``kv_len``/``segments`` operands when not given).
    ``kv_len`` (traced int, default T) bounds the valid key positions; at
    this layer it simply intersects whatever causal mask is active (the
    dispatch entry rejects causal + kv_len — the anchored-at-T causal
    offset is not the causal-over-fill a caller might expect).
    ``segments`` is a ((B, S), (B, T)) int32 pair; positions with
    differing ids never attend. Returns out (B, S, H, hdv) in q's dtype
    and lse (B, H, S) f32 — the combined max+log-sum the backward kernels
    (and a future cross-shard softmax combine) consume.
    """
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    hdv = v.shape[-1]
    G = H // K
    spec = _resolve_spec(spec, S, T, causal, kv_len, segments)
    bq, bk = _pick_tiles(S, T, hd, hdv, block, el_bytes=q.dtype.itemsize)
    grid = (B * H, pl.cdiv(S, bq), pl.cdiv(T, bk))
    kl = jnp.asarray(T if kv_len is None else kv_len,
                     jnp.int32).reshape(1, 1)
    plan = fetch_plan(spec, S, T, bq, bk, batch=B, kv_len=kv_len,
                      segments=segments)
    kv_map = _kv_stream(H, G, grid[1])
    qmap = lambda bh, i, j, *_: (bh // H, bh % H, i, 0)
    qs, ks, qs_spec, ks_spec = _seg_blocks(
        spec, segments, B, S, T, bq, bk, lambda bh, i, j, *_: (bh // H, i, 0),
        lambda *a: (a[0] // H, 0, kv_map(*a)[2]))
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, spec=spec,
                          bq=bq, bk=bk, heads=H, n_q_tiles=grid[1],
                          n_k_tiles=grid[2], s_len=S, t_len=T),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, hd), qmap),
                pl.BlockSpec((1, 1, bk, hd), kv_map),
                pl.BlockSpec((1, 1, bk, hdv), kv_map),
                pl.BlockSpec((1, 1), lambda *_: (0, 0),
                             memory_space=pltpu.SMEM),
                qs_spec, ks_spec,
            ],
            out_specs=[pl.BlockSpec((1, 1, bq, hdv), qmap),
                       pl.BlockSpec((1, 1, bq, 1), qmap)],
            scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, hdv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, H, S, hdv), q.dtype),
                   jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32)],
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
        name="attn_fwd",
    )(plan.kv_lo.reshape(-1), plan.kv_hi.reshape(-1), qt, kt, vt, kl, qs, ks)
    return jnp.swapaxes(out, 1, 2), lse[..., 0]


# --------------------------------------------------------------------------
# backward dQ: recompute score tiles, dQ accumulator resident per q tile
# --------------------------------------------------------------------------

def _dq_kernel(lo_ref, hi_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
               kl_ref, qs_ref, ks_ref, dq_ref, acc, *, scale, spec, bq, bk,
               heads, n_q_tiles, n_k_tiles, s_len, t_len):
    i, j = pl.program_id(1), pl.program_id(2)
    own = _clamp(j, lo_ref, hi_ref,
                 (pl.program_id(0) // heads) * n_q_tiles + i) == j
    kl = kl_ref[0, 0]

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    @_when_live(own, i, j, bq, bk, spec, kl, qs_ref, ks_ref, s_len, t_len)
    def _compute(qseg, kseg):
        s = _sdot(q_ref[0, 0], k_ref[0, 0]) * scale
        valid, _ = _masks(i, j, bq, bk, spec, kl, qseg, kseg, s_len, t_len)
        p = jnp.where(valid, jnp.exp(s - lse_ref[0, 0]), 0.0)
        v_eff = _zero_invalid_rows(v_ref, j, bk, t_len)
        dp = _sdot(do_ref[0, 0], v_eff)
        ds = p * (dp - d_ref[0, 0]) * scale
        k_eff = _zero_invalid_rows(k_ref, j, bk, t_len)
        acc[...] += jnp.dot(ds.astype(k_eff.dtype), k_eff,
                            preferred_element_type=jnp.float32)

    @pl.when(j == n_k_tiles - 1)
    def _emit():
        dq_ref[0, 0] = acc[...].astype(dq_ref.dtype)


def mha_bwd_dq(q, k, v, dout, lse, delta, kv_len=None, *, scale: float,
               causal: bool = True, segments=None,
               spec: MaskSpec | None = None, block=None,
               interpret: bool = True):
    """dQ (B, S, H, hd) in q's dtype.

    ``lse`` (B, H, S) is the forward's log-sum-exp; ``delta`` (B, H, S)
    f32 is ``sum(dout * out, -1)`` — both row vectors stream as (bq, 1)
    blocks. Rows past S carry undefined statistics; their NaNs stay on
    independent rows and are clipped on write.
    """
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    hdv = v.shape[-1]
    G = H // K
    spec = _resolve_spec(spec, S, T, causal, kv_len, segments)
    bq, bk = _pick_tiles(S, T, hd, hdv, block, el_bytes=q.dtype.itemsize)
    grid = (B * H, pl.cdiv(S, bq), pl.cdiv(T, bk))
    kl = jnp.asarray(T if kv_len is None else kv_len,
                     jnp.int32).reshape(1, 1)
    plan = fetch_plan(spec, S, T, bq, bk, batch=B, kv_len=kv_len,
                      segments=segments)
    kv_map = _kv_stream(H, G, grid[1])
    qmap = lambda bh, i, j, *_: (bh // H, bh % H, i, 0)
    qs, ks, qs_spec, ks_spec = _seg_blocks(
        spec, segments, B, S, T, bq, bk, lambda bh, i, j, *_: (bh // H, i, 0),
        lambda *a: (a[0] // H, 0, kv_map(*a)[2]))
    row = pl.BlockSpec((1, 1, bq, 1), qmap)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, spec=spec,
                          bq=bq, bk=bk, heads=H, n_q_tiles=grid[1],
                          n_k_tiles=grid[2], s_len=S, t_len=T),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, hd), qmap),
                pl.BlockSpec((1, 1, bk, hd), kv_map),
                pl.BlockSpec((1, 1, bk, hdv), kv_map),
                pl.BlockSpec((1, 1, bq, hdv), qmap),
                row, row,
                pl.BlockSpec((1, 1), lambda *_: (0, 0),
                             memory_space=pltpu.SMEM),
                qs_spec, ks_spec,
            ],
            out_specs=pl.BlockSpec((1, 1, bq, hd), qmap),
            scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
        name="attn_bwd_dq",
    )(plan.kv_lo.reshape(-1), plan.kv_hi.reshape(-1),
      jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
      jnp.swapaxes(dout, 1, 2), lse[..., None], delta[..., None], kl, qs, ks)
    return jnp.swapaxes(dq, 1, 2)


# --------------------------------------------------------------------------
# backward dK/dV: kv tile resident while (group x q) tiles stream
# --------------------------------------------------------------------------

def _dkv_kernel(lo_ref, hi_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
                kl_ref, qs_ref, ks_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                scale, spec, bq, bk, kv_heads, n_g, n_q_tiles, n_k_tiles,
                s_len, t_len):
    j, g, i = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    own = _clamp(i, lo_ref, hi_ref,
                 (pl.program_id(0) // kv_heads) * n_k_tiles + j) == i
    kl = kl_ref[0, 0]

    @pl.when((g == 0) & (i == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @_when_live(own, i, j, bq, bk, spec, kl, qs_ref, ks_ref, s_len, t_len)
    def _compute(qseg, kseg):
        # the q (token) axis is contracted here, so — unlike forward/dQ —
        # undefined remainder *rows* must be zeroed on both operand sides
        qrows = i * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                  q_ref.shape[2:], 0)
        q_eff = jnp.where(qrows < s_len, q_ref[0, 0], 0)
        dorows = i * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                   do_ref.shape[2:], 0)
        do_eff = jnp.where(dorows < s_len, do_ref[0, 0], 0)
        s = _sdot(q_eff, k_ref[0, 0]) * scale
        valid, rowmask = _masks(i, j, bq, bk, spec, kl, qseg, kseg, s_len,
                                t_len)
        # rows past S carry undefined lse/delta: fold the row bound into
        # the mask so p/ds are exactly 0 there (0 * NaN would poison the
        # whole dK/dV accumulator, not just one row)
        valid &= rowmask
        p = jnp.where(valid, jnp.exp(s - lse_ref[0, 0]), 0.0)
        dv_acc[...] += _tdot(p.astype(do_eff.dtype), do_eff)
        v_eff = _zero_invalid_rows(v_ref, j, bk, t_len)
        dp = _sdot(do_eff, v_eff)
        ds = jnp.where(valid, p * (dp - d_ref[0, 0]) * scale, 0.0)
        dk_acc[...] += _tdot(ds.astype(q_eff.dtype), q_eff)

    @pl.when((g == n_g - 1) & (i == n_q_tiles - 1))
    def _emit():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def mha_bwd_dkv(q, k, v, dout, lse, delta, kv_len=None, *, scale: float,
                causal: bool = True, segments=None,
                spec: MaskSpec | None = None, block=None,
                interpret: bool = True):
    """(dK, dV) in kv dtypes, emitted directly in the (B, T, K, hd|hdv)
    storage layout: the grid iterates (kv tiles, group, q tiles) with the
    dK/dV accumulators resident in VMEM, so the GQA reduction over the
    ``group`` query heads of each kv head never materializes a
    (B, T, H, hd)-sized gradient.
    """
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    hdv = v.shape[-1]
    G = H // K
    spec = _resolve_spec(spec, S, T, causal, kv_len, segments)
    bq, bk = _pick_tiles(S, T, hd, hdv, block, el_bytes=q.dtype.itemsize)
    grid = (B * K, pl.cdiv(T, bk), G, pl.cdiv(S, bq))
    kl = jnp.asarray(T if kv_len is None else kv_len,
                     jnp.int32).reshape(1, 1)
    plan = fetch_plan(spec, S, T, bq, bk, batch=B, kv_len=kv_len,
                      segments=segments)
    qmap = _q_stream(K, G, grid[1])
    kvmap = lambda bk_, j, g, i, *_: (bk_ // K, bk_ % K, j, 0)
    qs, ks, qs_spec, ks_spec = _seg_blocks(
        spec, segments, B, S, T, bq, bk,
        lambda *a: (a[0] // K, qmap(*a)[2], 0),
        lambda bk_, j, g, i, *_: (bk_ // K, 0, j))
    row = pl.BlockSpec((1, 1, bq, 1), qmap)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, spec=spec,
                          bq=bq, bk=bk, kv_heads=K, n_g=G,
                          n_q_tiles=grid[3], n_k_tiles=grid[1], s_len=S,
                          t_len=T),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, hd), qmap),
                pl.BlockSpec((1, 1, bk, hd), kvmap),
                pl.BlockSpec((1, 1, bk, hdv), kvmap),
                pl.BlockSpec((1, 1, bq, hdv), qmap),
                row, row,
                pl.BlockSpec((1, 1), lambda *_: (0, 0),
                             memory_space=pltpu.SMEM),
                qs_spec, ks_spec,
            ],
            out_specs=[pl.BlockSpec((1, 1, bk, hd), kvmap),
                       pl.BlockSpec((1, 1, bk, hdv), kvmap)],
            scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                            pltpu.VMEM((bk, hdv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, K, T, hd), k.dtype),
                   jax.ShapeDtypeStruct((B, K, T, hdv), v.dtype)],
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
        name="attn_bwd_dkv",
    )(plan.q_lo.reshape(-1), plan.q_hi.reshape(-1),
      jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
      jnp.swapaxes(dout, 1, 2), lse[..., None], delta[..., None], kl, qs, ks)
    return jnp.swapaxes(dk, 1, 2), jnp.swapaxes(dv, 1, 2)
