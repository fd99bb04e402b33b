"""Pallas TPU kernels for fused LM-head cross-entropy (logits -> loss/grad).

The head path ``loss = xent(h @ w, labels)`` is the activation-memory
hot-spot of a training step: the (tokens, vocab) logit matrix is V/D times
bigger than the hidden states that produce it. The jnp path bounds it by
chunking tokens (``models.model.lm_loss``) but still materializes a
(chunk, V) f32 logit block in HBM per scan step — and the backward scan
re-materializes it and streams a (D, V) f32 dW accumulator through HBM on
*every* chunk. These kernels never let logits leave VMEM:

  * ``xent_fwd`` — grid (token tiles, vocab tiles), vocab innermost. Each
    step computes one (bn, bv) logit tile on the MXU and folds it into a
    running online-logsumexp (max + scaled sum, flash-attention style) and
    the label-logit accumulator held in VMEM scratch; per-token ``lse`` and
    ``ll`` (each (N, 1) f32 — noise next to the matrices) are emitted once
    at the last vocab tile. Peak logit storage is one (bn, bv) VMEM tile,
    independent of V and S.
  * ``xent_bwd`` — transposed grid (vocab tiles outer, token tiles
    inner). Each step forms one logit tile and ``dlogits = (softmax -
    onehot(label)) * g`` once, in VMEM, and feeds both gradients from it:
    ``h_tile^T @ dlogits`` into the (D, bv) dW tile, resident in scratch
    while all token tiles stream by and written once per vocab tile, and
    ``dlogits @ w_tile^T`` into the token tile's (bn, D) f32 dH partial
    sum, which lives in HBM between vocab tiles (read and written by the
    kernel's own DMAs, overlapped with the contractions). Forward and
    backward form each logit twice in all: 8·D·V FLOPs a token against the
    6·D·V that the loss and both gradients need (a backward kernel per
    gradient would form it three times, 10·D·V).

Masking folds three boundaries into the tile iota, mirroring the colnorm
kernels' remainder handling (out-of-bounds block regions are undefined —
NaN in interpret mode — and 0*NaN = NaN, so *both* operands of every
contraction are zeroed on padded positions):

  * padded vocab: global column id ``col_offset + j*bv + iota`` >=
    ``vocab_size`` contributes neither to the logsumexp nor to either
    gradient (its dlogits are exactly 0);
  * remainder vocab tiles (local V % bv): lanes past the local w width are
    undefined memory whose *global* ids can still be < ``vocab_size`` on a
    non-last vocab shard, so validity is the conjunction of the local
    bound and the global one (see ``_col_masks``) — and the label one-hot
    uses the same mask so a label owned by another shard cannot match an
    undefined local lane carrying its global id;
  * remainder token tiles (N % bn): forward rows are independent and
    clipped on write, backward dH rows land in padding the wrapper drops;
    the backward zeroes h rows and dlogits rows past N before the token
    contraction.

The backward zeroes undefined operand lanes in the operand's own dtype and
only in the variant compiled for a remainder tile; full tiles run unmasked
operands.

``col_offset`` is a traced SMEM scalar: under a vocab-sharded mesh the
dispatch layer passes ``shard_index * local_V`` so labels (global ids)
resolve against the local w shard; the per-shard (lse, ll) pair is then
combined with ``pmax``/``psum`` outside (see ``dispatch.xent_loss``).

Masked labels (-1) hit no column (col >= 0 always), so ``ll`` is 0 and the
wrapper's validity mask is the only special-casing they need. D is carried
whole per block (blocks are exact on D, never padded); ``_pick_blocks``
shrinks the token/vocab tile instead when bn*D or D*bv would crowd VMEM.

Transposed-w variants (``transposed=True`` on every entry point): the head
is a **tied embedding** stored (V, D) instead of the use layout (D, V).
Blocks then index ``w[vocab_tile, d]`` — the logit tile is the same
(bn, bv) MXU contraction with w's dims swapped, the column masks apply to
w's *rows*, and ``xent_bwd`` emits dW in (V, D) layout so the gradient
lands directly on the embedding without a transpose pass. Tile sizes,
masking and the online-logsumexp recurrence are identical (one code path,
the ``wt`` static flag only swaps the w-side indexing).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..vmem import (COMPILER_PARAMS, VMEM_BUDGET_BYTES,
                    WIDE_COMPILER_PARAMS, WIDE_VMEM_BUDGET_BYTES, tile_bytes)

_NEG = -1e30  # finite -inf stand-in: keeps the running max
#                            NaN-free when a tile (or a whole vocab shard)
#                            is entirely padding


def _vmem_cost(bn: int, bv: int, d: int, el: int, out_el: int,
               kernel: str) -> int:
    """Scoped-VMEM bytes of one ``kernel`` ("fwd" | "bwd") step.

    Blocks count twice (double-buffered in and out), scratch once, and the
    f32 logit tile four times (it, its iota/mask and the exp/dlogits
    temporaries). The backward adds its resident f32 dW tile, the two f32
    dH staging slots and the dH contraction's result, and the w block
    transposed for that contraction. Compiled for v5e at the tiles of the
    Qwen2-7B and Granite-3.0-8B heads, the kernel takes 4–5 MiB less than
    this count.
    """
    h, w = tile_bytes(bn, d, el), tile_bytes(d, bv, el)
    vec = tile_bytes(bn, 1, 4)          # labels / lse / ll / gl row vectors
    logits = 4 * tile_bytes(bn, bv, 4)
    if kernel == "fwd":
        return 2 * (h + w + 3 * vec) + 3 * vec + logits
    return (2 * (h + w + 3 * vec + tile_bytes(d, bv, out_el))
            + tile_bytes(d, bv, 4) + 3 * tile_bytes(bn, d, 4) + w + logits)


_BUDGET = {"fwd": VMEM_BUDGET_BYTES, "bwd": WIDE_VMEM_BUDGET_BYTES}


def _pick_blocks(n: int, d: int, v: int, block=None, *, el_bytes: int = 4,
                 out_bytes: int = 4, kernel: str = "fwd"):
    """(bn, bv) tile for one kernel, clamped to the (padded) problem.

    Tiles start at 2048 x 2048 and shrink until :func:`_vmem_cost` fits
    the kernel's scoped-VMEM budget, the HBM-reuse lever last. The
    forward streams w once per token tile, so bv shrinks first, down to
    512, then bn down to 256. The backward streams h and the f32 dH once
    per vocab tile, so bn shrinks first, down to 256, then bv down to 512
    (a v5e step of 6·bn·bv·D FLOPs over ~10·bn·D bytes stays
    compute-bound from bv = 512). Then whichever is larger shrinks, to the
    (32, 128) hardware tiling floor.
    """
    if block is not None:
        bn, bv = block
    else:
        bn = min(2048, -(-n // 32) * 32)
        bv = min(2048, -(-v // 128) * 128)
        fits = lambda bn, bv: _vmem_cost(bn, bv, d, el_bytes, out_bytes,
                                         kernel) <= _BUDGET[kernel]

        def shrink_bv():
            if kernel == "bwd" and bn > 256:
                return False
            if bv > 512:
                return True
            if bn > 256:
                return False
            return bv > 128 and bv >= bn

        while not fits(bn, bv) and (bn > 32 or bv > 128):
            if shrink_bv():
                bv //= 2
            else:
                bn = max(32, bn // 2 // 32 * 32)
    bn = min(bn, -(-n // 32) * 32)
    bv = min(bv, -(-v // 128) * 128)
    return bn, bv


def _logit_tile(h, w, wt: bool):
    """(bn, bv) f32 logit tile; ``wt`` statically selects the w layout.

    Untied: w block (d, bv), plain ``h @ w``. Transposed (tied): w block
    (bv, d), contraction over each side's d dim — the same MXU shape, the
    systolic array just streams w row-major.
    """
    if wt:
        return jax.lax.dot_general(h, w, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    return jnp.dot(h, w, preferred_element_type=jnp.float32)


def _w_spec(d, bv, wt: bool, transpose_grid: bool = False):
    """BlockSpec for the w operand: (d, bv) tiles or (bv, d) when ``wt``."""
    if transpose_grid:  # backward grid is (vocab, token): j is program_id(0)
        if wt:
            return pl.BlockSpec((bv, d), lambda j, i: (j, 0))
        return pl.BlockSpec((d, bv), lambda j, i: (0, j))
    if wt:
        return pl.BlockSpec((bv, d), lambda i, j: (j, 0))
    return pl.BlockSpec((d, bv), lambda i, j: (0, j))


def _col_masks(off, j, bv, v_local, vocab_size, shape, axis):
    """(global col ids, validity mask) for one vocab tile.

    A lane is valid only if it is inside the **local** w (lcol < v_local —
    remainder-tile lanes past it are undefined memory whose *global* ids
    can still be < vocab_size on any non-last vocab shard) AND its global
    id is a real vocab entry (col < vocab_size — padded-vocab columns).
    The mask guards the logsumexp/softmax contributions and the label
    one-hot (a label owned by another shard must not match an undefined
    local lane that happens to carry its global id).
    """
    lcol = jax.lax.broadcasted_iota(jnp.int32, shape, axis) + j * bv
    col = off + lcol
    return col, (lcol < v_local) & (col < vocab_size)


# --------------------------------------------------------------------------
# forward: blockwise logits -> online logsumexp + label logit
# --------------------------------------------------------------------------

def _fwd_kernel(h_ref, w_ref, lab_ref, off_ref, lse_ref, ll_ref,
                m_acc, s_acc, ll_acc, *, n_v_tiles, bv, v_local, vocab_size,
                wt):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_acc[...] = jnp.full_like(m_acc, _NEG)
        s_acc[...] = jnp.zeros_like(s_acc)
        ll_acc[...] = jnp.zeros_like(ll_acc)

    logits = _logit_tile(h_ref[...], w_ref[...], wt)
    col, vmask = _col_masks(off_ref[0, 0], j, bv, v_local, vocab_size,
                            logits.shape, 1)
    logits = jnp.where(vmask, logits, _NEG)
    m_new = jnp.maximum(m_acc[...], jnp.max(logits, axis=1, keepdims=True))
    # explicit mask on the exp: with everything pinned at _NEG the
    # difference is 0 and exp would contribute 1 per padded column
    e = jnp.where(vmask, jnp.exp(logits - m_new), 0.0)
    s_acc[...] = (s_acc[...] * jnp.exp(m_acc[...] - m_new)
                  + jnp.sum(e, axis=1, keepdims=True))
    m_acc[...] = m_new
    ll_acc[...] += jnp.sum(
        jnp.where((col == lab_ref[...]) & vmask, logits, 0.0),
        axis=1, keepdims=True)

    @pl.when(j == n_v_tiles - 1)
    def _emit():
        lse_ref[...] = m_acc[...] + jnp.log(s_acc[...])
        ll_ref[...] = ll_acc[...]


def xent_fwd(h, w, labels, *, vocab_size: int, col_offset=0, block=None,
             interpret: bool = True, transposed: bool = False):
    """Per-token (lse, ll): h (N, D), w (D, V) — or (V, D) when
    ``transposed`` (tied embedding head) — labels (N,) int32.

    Returns two (N,) f32 vectors: the logsumexp over valid columns and the
    logit at the label (0 for labels outside [col_offset, col_offset+V) or
    masked -1 labels). ``loss = lse - ll`` for valid tokens.
    """
    n, d = h.shape
    v = w.shape[0] if transposed else w.shape[1]
    bn, bv = _pick_blocks(n, d, v, block, el_bytes=h.dtype.itemsize)
    grid = (pl.cdiv(n, bn), pl.cdiv(v, bv))
    off = jnp.asarray(col_offset, jnp.int32).reshape(1, 1)
    tok = pl.BlockSpec((bn, 1), lambda i, j: (i, 0))
    lse, ll = pl.pallas_call(
        functools.partial(_fwd_kernel, n_v_tiles=grid[1], bv=bv, v_local=v,
                          vocab_size=vocab_size, wt=transposed),
        grid=grid,
        in_specs=[pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
                  _w_spec(d, bv, transposed),
                  tok,
                  pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                               memory_space=pltpu.SMEM)],
        out_specs=[tok, tok],
        out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.float32),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bn, 1), jnp.float32)] * 3,
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
        name="xent_fwd",
    )(h, w, labels.reshape(n, 1), off)
    return lse[:, 0], ll[:, 0]


# --------------------------------------------------------------------------
# backward: one logit tile -> dlogits -> both dW (resident) and dH (in HBM)
# --------------------------------------------------------------------------

def _bwd_kernel(w_ref, h_ref, lab_ref, lse_ref, gl_ref, off_ref, dw_ref,
                dh_hbm, acc_ref, dh_buf, sem, *, n_v_tiles, n_t_tiles, bn,
                bv, v_local, n_tokens, vocab_size, wt):
    j, i = pl.program_id(0), pl.program_id(1)
    step = j * n_t_tiles + i
    slot = step % 2
    rows = pl.ds(pl.multiple_of(i * bn, bn), bn)

    def copy(k, out):  # dH staging slot k <-> this token tile's HBM rows
        if out:
            return pltpu.make_async_copy(dh_buf.at[k], dh_hbm.at[rows],
                                         sem.at[1, k])
        return pltpu.make_async_copy(dh_hbm.at[rows], dh_buf.at[k],
                                     sem.at[0, k])

    def wait_prev_write():  # same byte count as the previous step's write
        copy(1 - slot, True).wait()

    if n_t_tiles == 1:  # this step re-reads the rows the last one wrote
        pl.when(step > 0)(wait_prev_write)

    @pl.when(j > 0)
    def _fetch():
        copy(slot, False).start()

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # A remainder tile's lanes past w's width and rows past N are undefined
    # memory (0 * NaN = NaN in either contraction): zero them in the block
    # buffers, in the operands' own dtype, on those tiles alone. Zeroed h,
    # lse and gl rows then give dlogits rows of exactly 0.
    v_rem, n_rem = v_local % bv, n_tokens % bn
    if v_rem:
        @pl.when(j == n_v_tiles - 1)
        def _clear_lanes():
            if wt:
                w_ref[v_rem:, :] = jnp.zeros((bv - v_rem, w_ref.shape[1]),
                                             w_ref.dtype)
            else:
                w_ref[:, v_rem:] = jnp.zeros((w_ref.shape[0], bv - v_rem),
                                             w_ref.dtype)
    if n_rem:
        @pl.when(i == n_t_tiles - 1)
        def _clear_rows():
            for ref in (h_ref, lse_ref, gl_ref):
                ref[n_rem:, :] = jnp.zeros((bn - n_rem, ref.shape[1]),
                                           ref.dtype)

    h, w = h_ref[...], w_ref[...]
    logits = _logit_tile(h, w, wt)
    col, vmask = _col_masks(off_ref[0, 0], j, bv, v_local, vocab_size,
                            logits.shape, 1)
    p = jnp.where(vmask, jnp.exp(logits - lse_ref[...]), 0.0)
    dlog = (p - jnp.where((col == lab_ref[...]) & vmask, 1.0, 0.0)) \
        * gl_ref[...]
    # dlogits enter both contractions in f32 beside the operands' dtype: on
    # v5e that runs at the rate of bf16 dlogits and rounds nothing more
    tok = (((0,), (0,)), ((), ()))
    if wt:  # w and dW (bv, D): dW[v, :] = sum_n dlog[n, v] h[n, :]
        acc_ref[...] += jax.lax.dot_general(
            dlog, h, tok, preferred_element_type=jnp.float32)
        dh = jax.lax.dot_general(dlog, w, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    else:
        acc_ref[...] += jax.lax.dot_general(
            h, dlog, tok, preferred_element_type=jnp.float32)
        dh = jax.lax.dot_general(dlog, w, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _first():
        dh_buf[slot] = dh

    @pl.when(j > 0)
    def _add():
        copy(slot, False).wait()
        dh_buf[slot] += dh

    copy(slot, True).start()
    if n_t_tiles > 1:  # the next step stages into that write's slot
        pl.when(step > 0)(wait_prev_write)

    @pl.when(step == n_v_tiles * n_t_tiles - 1)
    def _drain():
        copy(slot, True).wait()

    @pl.when(i == n_t_tiles - 1)
    def _emit():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def xent_bwd(h, w, labels, lse, gl, *, vocab_size: int, col_offset=0,
             block=None, interpret: bool = True, dw_dtype=jnp.float32,
             transposed: bool = False):
    """(dH, dW) of the gl-weighted cross-entropy, from one logit pass.

    ``gl`` (N,) f32 is the per-token upstream cotangent (already 0 for
    masked labels); ``lse`` the forward's (globally combined) logsumexp.
    The grid runs vocab tiles outer and token tiles inner: each step forms
    one (bn, bv) logit tile and its dlogits, accumulates ``h^T @ dlogits``
    into the vocab tile's dW, resident in VMEM until the last token tile
    (then written once in w's layout, in ``dw_dtype``), and adds
    ``dlogits @ w^T`` to the token tile's f32 dH rows in HBM, which the
    kernel streams itself through two VMEM slots (the read overlaps the
    step's contractions, the write the next step's).

    dH comes back (N, D) f32; under vocab sharding it is a partial sum
    over local columns and dW one over local tokens — the caller psums
    them over the mesh axes and rounds. ``transposed``: w (and dW) is the
    tied (V, D) embedding.
    """
    n, d = h.shape
    v = w.shape[0] if transposed else w.shape[1]
    bn, bv = _pick_blocks(n, d, v, block, el_bytes=h.dtype.itemsize,
                          out_bytes=jnp.dtype(dw_dtype).itemsize,
                          kernel="bwd")
    grid = (pl.cdiv(v, bv), pl.cdiv(n, bn))
    off = jnp.asarray(col_offset, jnp.int32).reshape(1, 1)
    tok = pl.BlockSpec((bn, 1), lambda j, i: (i, 0))
    wspec = _w_spec(d, bv, transposed, transpose_grid=True)
    dw, dh = pl.pallas_call(
        functools.partial(_bwd_kernel, n_v_tiles=grid[0],
                          n_t_tiles=grid[1], bn=bn, bv=bv, v_local=v,
                          n_tokens=n, vocab_size=vocab_size,
                          wt=transposed),
        grid=grid,
        in_specs=[wspec,
                  pl.BlockSpec((bn, d), lambda j, i: (i, 0)),
                  tok, tok, tok,
                  pl.BlockSpec((1, 1), lambda j, i: (0, 0),
                               memory_space=pltpu.SMEM)],
        out_specs=[wspec, pl.BlockSpec(memory_space=pl.ANY)],
        # dH rows padded to whole token tiles: the kernel's DMAs are static
        out_shape=[jax.ShapeDtypeStruct((v, d) if transposed else (d, v),
                                        dw_dtype),
                   jax.ShapeDtypeStruct((grid[1] * bn, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bv, d) if transposed else (d, bv),
                                   jnp.float32),
                        pltpu.VMEM((2, bn, d), jnp.float32),
                        pltpu.SemaphoreType.DMA((2, 2))],
        interpret=interpret,
        compiler_params=WIDE_COMPILER_PARAMS,
        name="xent_bwd",
    )(w, h, labels.reshape(n, 1), lse.reshape(n, 1), gl.reshape(n, 1), off)
    return dh[:n], dw
