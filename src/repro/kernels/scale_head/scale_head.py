"""Pallas TPU kernels for the fused SCALE momentum (LM-head) update.

Momentum-carrying matrices (by default only the LM head) are the stateful
part of SCALE. The naive sequence (EMA, norm, axpy) makes ~7 HBM passes over
theta/m/g; fused here into two kernels:

  * ``momentum_sumsq`` — writes m' = beta*m + (1-beta)*g tile-by-tile while
    accumulating sum(m'^2) along the reduce axis in VMEM scratch (reduce
    axis innermost in the grid -> sequential accumulation), emitting the
    sums-of-squares once per output tile. One read of m and g, one write
    of m'.
  * the apply step reuses :func:`repro.kernels.colnorm.colnorm.update_apply`
    (theta' = theta - lr * m'/(||m'||+eps)): one read of theta and m', one
    write of theta'.

Same coverage as the colnorm kernels: 2-D or stacked 3-D params, ``col`` or
``row`` reduce axis, arbitrary (non-tile-divisible) shapes via cdiv grids +
iota remainder masks. Remainder masking matters twice here: padded lanes of
m'/g are undefined, so they are excluded from the accumulator (the m' write
itself is clipped by Pallas).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..colnorm.colnorm import DEFAULT_BLOCK, _blocks, _red_mask, update_apply

__all__ = ["DEFAULT_BLOCK", "momentum_sumsq", "head_update_apply"]


def _momentum_sumsq_kernel(m_ref, g_ref, beta_ref, gs_ref, m_out_ref, ss_ref,
                           acc_ref, *, n_red_tiles, red_dim, red_block,
                           red_axis):
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    beta = beta_ref[0, 0]
    m_new = beta * m_ref[0].astype(jnp.float32) + \
        (1.0 - beta) * (g_ref[0].astype(jnp.float32) * gs_ref[0, 0])
    m_out_ref[0] = m_new.astype(m_out_ref.dtype)
    masked = jnp.where(
        _red_mask(m_new.shape, i, red_block, red_dim, red_axis), m_new, 0.0)
    acc_ref[...] += jnp.sum(masked * masked, axis=red_axis, keepdims=True)

    @pl.when(i == n_red_tiles - 1)
    def _emit():
        ss_ref[0] = acc_ref[...]


def momentum_sumsq(m, g, beta, axis: str = "col", block=DEFAULT_BLOCK,
                   interpret: bool = True, gscale=1.0):
    """(m', ss): m' = beta*m + (1-beta)*gscale*g, ss = sumsq(m') along axis.

    m, g: (L, mm, n). Returns m' (L, mm, n) in **m's dtype** (the momentum
    storage dtype — bf16 under ``scale(momentum_dtype="bfloat16")``) and ss
    (L, 1, n) for col / (L, mm, 1) for row, f32. The EMA and the
    sums-of-squares are computed in f32; only the emitted m' is rounded
    (cast-on-write). ``gscale`` folds the trainer's grad-clip factor into
    the EMA read (see colnorm kernel docs). m is aliased to m' so the
    momentum write is in-place under buffer donation.
    """
    L, mm, n = m.shape
    bm, bn = _blocks(mm, n, block)
    tile = pl.BlockSpec((1, bm, bn), lambda l, j, i: (l, i, j))
    if axis == "col":
        grid = (L, pl.cdiv(n, bn), pl.cdiv(mm, bm))
        ss_spec = pl.BlockSpec((1, 1, bn), lambda l, j, i: (l, 0, j))
        ss_shape = jax.ShapeDtypeStruct((L, 1, n), jnp.float32)
        scratch = pltpu.VMEM((1, bn), jnp.float32)
        red_dim, red_block, red_axis = mm, bm, 0
    elif axis == "row":
        grid = (L, pl.cdiv(mm, bm), pl.cdiv(n, bn))
        tile = pl.BlockSpec((1, bm, bn), lambda l, j, i: (l, j, i))
        ss_spec = pl.BlockSpec((1, bm, 1), lambda l, j, i: (l, j, 0))
        ss_shape = jax.ShapeDtypeStruct((L, mm, 1), jnp.float32)
        scratch = pltpu.VMEM((bm, 1), jnp.float32)
        red_dim, red_block, red_axis = n, bn, 1
    else:
        raise ValueError(f"axis must be 'col' or 'row', got {axis!r}")
    beta_arr = jnp.asarray(beta, jnp.float32).reshape(1, 1)
    gs_arr = jnp.asarray(gscale, jnp.float32).reshape(1, 1)
    smem = pl.BlockSpec((1, 1), lambda l, j, i: (0, 0),
                        memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_momentum_sumsq_kernel, n_red_tiles=grid[2],
                          red_dim=red_dim, red_block=red_block,
                          red_axis=red_axis),
        grid=grid,
        in_specs=[tile, tile, smem, smem],
        out_specs=[tile, ss_spec],
        out_shape=[jax.ShapeDtypeStruct((L, mm, n), m.dtype), ss_shape],
        input_output_aliases={0: 0},
        scratch_shapes=[scratch],
        interpret=interpret,
        name="scale_head_momentum",
    )(m, g, beta_arr, gs_arr)


def head_update_apply(theta, m_new, ss, lr, axis: str = "col",
                      block=DEFAULT_BLOCK, eps: float = 1e-8,
                      interpret: bool = True):
    """theta - lr * m'/(sqrt(ss)+eps); shares the colnorm apply kernel
    (theta aliased in-place, no gscale — the clip factor already entered
    through the momentum EMA)."""
    return update_apply(theta, m_new, ss, lr, axis, block, eps, interpret)
