"""Pallas TPU kernels for row/column gradient normalization and the fused
SCALE parameter update.

The SCALE optimizer step is HBM-bandwidth-bound: every parameter matrix and
its gradient stream through HBM once per step, and the arithmetic per element
is tiny. These kernels minimize HBM passes; :mod:`repro.kernels.dispatch`
decides when they run compiled vs. in interpret mode.

Coverage matrix (see ``dispatch.supported``):

  ndim   norm kind          dtype            handling
  -----  -----------------  ---------------  -------------------------------
  2      col / row          f32 / bf16       single grid cell per (j, i) tile
  3      col / row          f32 / bf16       leading grid axis over layers /
                                             experts (stacked scan params)
  any    larger             f32 / bf16       resolved to col/row per shape at
                                             dispatch (shapes are static)
  any    sign / ns / svd    --               jnp reference (not fused)

Arbitrary (non-tile-divisible) shapes are supported: grids use ``pl.cdiv``
and kernels mask the remainder rows/cols of the reduction axis with a
``broadcasted_iota`` predicate, so vocab-size 50257 heads and odd MLP dims
take the fused path instead of falling back to jnp.

Kernels:

  * ``norm_sumsq``    — tiled sum-of-squares reduction along the reduce axis
    (rows for ``col``, columns for ``row``), f32 accumulator in VMEM scratch.
    The reduce axis is the innermost grid dimension, exploiting Pallas TPU's
    sequential grid execution to accumulate across tiles and emit once per
    output tile.
  * ``norm_apply``    — element-wise tiles consuming the sums-of-squares;
    out = g / (||axis||+eps). One read of g, one write of the output.
  * ``update_apply``  — fuses the SGD subtraction: theta' = theta -
    lr * g/(||axis||+eps). theta and g are read once and theta written once;
    theta is aliased to the output (``input_output_aliases``) so under
    buffer donation the write is truly in-place — no fresh theta allocation.

Every kernel takes a ``gscale`` scalar (SMEM) applied to the gradient at
read time (``g_eff = gscale * f32(g)``). This is how the trainer folds the
global-norm clip factor into the fused step: the clipped gradient never
materializes, saving the separate full grad read+write a tree-level
``g * scale`` would cost (XLA cannot fuse element-wise prologues into a
``pallas_call``). ``gscale`` participates in both the sum-of-squares and
the apply, so the result is exactly clip-then-update.

HBM-pass accounting per matrix parameter: one pass = one full-matrix read
or write (the per-slice norm vector is ~1/256 of a matrix — noise). For the
stateless update theta' = theta - lr * g/||g||:

  unfused jnp sequence:   g r (sumsq); g r, gn w (scale);
                          theta r, gn r, theta' w (apply)        = 6 passes
  fused (sumsq + update_apply):
                          g r (sumsq); theta r, g r, theta' w    = 4 passes

i.e. the bandwidth-dominant apply stage touches each matrix exactly 3x
(theta read, grad read, theta write); the preceding norm reduction re-reads
g once — a hard floor for col/row norms, which need the full column/row
sums before any element can be scaled.

Tile sizes default to (256, 256): 256x256xf32 = 256 KiB per operand tile,
three operands + scratch < 2 MiB, comfortably inside a v5e core's 16 MiB
VMEM while keeping both dims multiples of the (8, 128) f32 / (16, 128) bf16
tiling and the 128-lane VPU width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = (256, 256)


def _canon3(x: jnp.ndarray) -> jnp.ndarray:
    """Canonicalize to (L, m, n); 2-D inputs get a unit layer axis."""
    if x.ndim == 2:
        return x[None]
    if x.ndim == 3:
        return x
    raise ValueError(f"fused kernels take 2-D/3-D arrays, got {x.shape}")


def _blocks(m: int, n: int, block=DEFAULT_BLOCK):
    """Clamp the default block to the (padded) array size.

    Sublane dim rounds to 32 (covers f32/bf16/int8 tiling), lane dim to 128,
    so a single-tile grid over a small or ragged array stays hardware-aligned.
    """
    bm = min(block[0], -(-m // 32) * 32)
    bn = min(block[1], -(-n // 128) * 128)
    return bm, bn


def _red_mask(shape, tile_idx, block_sz, dim, axis_in_tile):
    """True for positions whose global index along the reduce axis is < dim.

    Remainder tiles are zero-padded via this mask before squaring — Pallas
    pads out-of-bounds block regions with undefined values (NaN in interpret
    mode), which would otherwise poison the accumulator.
    """
    idx = jax.lax.broadcasted_iota(jnp.int32, shape, axis_in_tile)
    return tile_idx * block_sz + idx < dim


# --------------------------------------------------------------------------
# norm_sumsq: sum of squares along the reduce axis
# --------------------------------------------------------------------------

def _sumsq_kernel(g_ref, gs_ref, out_ref, acc_ref, *, n_red_tiles, red_dim,
                  red_block, red_axis):
    i = pl.program_id(2)  # reduce-axis tile (innermost)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    gf = g_ref[0].astype(jnp.float32) * gs_ref[0, 0]
    gf = jnp.where(_red_mask(gf.shape, i, red_block, red_dim, red_axis),
                   gf, 0.0)
    acc_ref[...] += jnp.sum(gf * gf, axis=red_axis, keepdims=True)

    @pl.when(i == n_red_tiles - 1)
    def _emit():
        out_ref[0] = acc_ref[...]


def norm_sumsq(g: jnp.ndarray, axis: str = "col", block=DEFAULT_BLOCK,
               interpret: bool = True, gscale=1.0) -> jnp.ndarray:
    """Per-column (axis="col") or per-row (axis="row") sum of squares of
    gscale * g.

    g (L, m, n) -> (L, 1, n) for col, (L, m, 1) for row; f32.
    """
    L, m, n = g.shape
    bm, bn = _blocks(m, n, block)
    if axis == "col":  # reduce over rows
        grid = (L, pl.cdiv(n, bn), pl.cdiv(m, bm))
        g_map = lambda l, j, i: (l, i, j)
        out_spec = pl.BlockSpec((1, 1, bn), lambda l, j, i: (l, 0, j))
        out_shape = jax.ShapeDtypeStruct((L, 1, n), jnp.float32)
        scratch = pltpu.VMEM((1, bn), jnp.float32)
        red_dim, red_block, red_axis = m, bm, 0
    elif axis == "row":  # reduce over columns
        grid = (L, pl.cdiv(m, bm), pl.cdiv(n, bn))
        g_map = lambda l, j, i: (l, j, i)
        out_spec = pl.BlockSpec((1, bm, 1), lambda l, j, i: (l, j, 0))
        out_shape = jax.ShapeDtypeStruct((L, m, 1), jnp.float32)
        scratch = pltpu.VMEM((bm, 1), jnp.float32)
        red_dim, red_block, red_axis = n, bn, 1
    else:
        raise ValueError(f"axis must be 'col' or 'row', got {axis!r}")
    gs_arr = jnp.asarray(gscale, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        functools.partial(_sumsq_kernel, n_red_tiles=grid[2],
                          red_dim=red_dim, red_block=red_block,
                          red_axis=red_axis),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bm, bn), g_map),
                  pl.BlockSpec((1, 1), lambda l, j, i: (0, 0),
                               memory_space=pltpu.SMEM)],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[scratch],
        interpret=interpret,
        name="colnorm_sumsq",
    )(g, gs_arr)


# --------------------------------------------------------------------------
# norm_apply / update_apply: element-wise consumers of the sums-of-squares
# --------------------------------------------------------------------------

def _norm_apply_kernel(g_ref, ss_ref, gs_ref, out_ref, *, eps: float):
    # ss block is (1, 1, bn) or (1, bm, 1); broadcasting covers both axes.
    norm = jnp.sqrt(ss_ref[0]) + eps
    gf = g_ref[0].astype(jnp.float32) * gs_ref[0, 0]
    out_ref[0] = (gf / norm).astype(out_ref.dtype)


def _ew_specs(L, m, n, bm, bn, axis):
    """Grid + block specs shared by the element-wise kernels."""
    grid = (L, pl.cdiv(n, bn), pl.cdiv(m, bm))
    tile = pl.BlockSpec((1, bm, bn), lambda l, j, i: (l, i, j))
    if axis == "col":
        ss = pl.BlockSpec((1, 1, bn), lambda l, j, i: (l, 0, j))
    else:
        ss = pl.BlockSpec((1, bm, 1), lambda l, j, i: (l, i, 0))
    smem = pl.BlockSpec((1, 1), lambda l, j, i: (0, 0),
                        memory_space=pltpu.SMEM)
    return grid, tile, ss, smem


def norm_apply(g, ss, axis: str = "col", block=DEFAULT_BLOCK,
               eps: float = 1e-8, interpret: bool = True, gscale=1.0,
               out_dtype=None):
    """gscale * g / (sqrt(ss)+eps) with ss broadcast along the reduce axis.

    ``out_dtype`` overrides the output dtype (math is f32 regardless) —
    used when g is a reduced-precision momentum buffer but the normalized
    direction must stay f32.
    """
    L, m, n = g.shape
    bm, bn = _blocks(m, n, block)
    grid, tile, ss_spec, smem = _ew_specs(L, m, n, bm, bn, axis)
    gs_arr = jnp.asarray(gscale, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        functools.partial(_norm_apply_kernel, eps=eps),
        grid=grid,
        in_specs=[tile, ss_spec, smem],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((L, m, n), out_dtype or g.dtype),
        interpret=interpret,
        name="colnorm_apply",
    )(g, ss, gs_arr)


def _update_apply_kernel(theta_ref, g_ref, ss_ref, lr_ref, gs_ref, out_ref,
                         *, eps: float):
    norm = jnp.sqrt(ss_ref[0]) + eps
    gf = g_ref[0].astype(jnp.float32) * gs_ref[0, 0]
    upd = theta_ref[0].astype(jnp.float32) - lr_ref[0, 0] * gf / norm
    out_ref[0] = upd.astype(out_ref.dtype)


def update_apply(theta, g, ss, lr, axis: str = "col", block=DEFAULT_BLOCK,
                 eps: float = 1e-8, interpret: bool = True, gscale=1.0):
    """theta - lr * gscale*g/(sqrt(ss)+eps): the fused SCALE parameter write.

    theta is aliased to the output buffer (``input_output_aliases={0: 0}``):
    when the caller donates theta (``donate_argnums`` on the train step) the
    update happens in-place and no fresh theta-sized buffer is allocated.
    """
    L, m, n = theta.shape
    bm, bn = _blocks(m, n, block)
    grid, tile, ss_spec, smem = _ew_specs(L, m, n, bm, bn, axis)
    lr_arr = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    gs_arr = jnp.asarray(gscale, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        functools.partial(_update_apply_kernel, eps=eps),
        grid=grid,
        in_specs=[tile, tile, ss_spec, smem, smem],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((L, m, n), theta.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
        name="colnorm_update",
    )(theta, g, ss, lr_arr, gs_arr)


# --------------------------------------------------------------------------
# 2-D convenience wrappers (legacy call sites and tests)
# --------------------------------------------------------------------------

def col_sumsq(g: jnp.ndarray, block=DEFAULT_BLOCK, interpret: bool = True):
    """Sum of squares per column. g (m, n) -> (1, n), f32."""
    return norm_sumsq(_canon3(g), "col", block, interpret)[0]


def colnorm_apply(g, ss, block=DEFAULT_BLOCK, eps: float = 1e-8,
                  interpret: bool = True):
    return norm_apply(_canon3(g), _canon3(ss), "col", block, eps, interpret)[0]
