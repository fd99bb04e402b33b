"""Model building blocks, purely functional.

Every module exposes
  * ``<mod>_spec(cfg) -> {name: Spec}``  — single source of truth for shapes,
    logical sharding axes and initializers;
  * ``apply_<mod>(params, cfg, ...)``    — forward.

Attention uses a *triangular blockwise* (flash-style) causal algorithm: a
``lax.scan`` over the lower-triangle (q-block, kv-block) tile pairs with an
online-softmax carry, so peak memory is O(tile) and compiled FLOPs are
~S^2/2 rather than S^2. TPU adaptation: tiles are MXU-aligned multiples of
128 and the softmax statistics stay in f32 VREGs.
"""
from __future__ import annotations

import functools
import math
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.attention.mask import MaskSpec, mask_array, mask_spec

from .config import ModelConfig
from .sharding import Rules, shard


class Spec(NamedTuple):
    shape: tuple
    axes: tuple           # logical axis names (len == ndim)
    init: str = "normal"  # normal | zeros | ones | alog | dtbias | small


def init_param(key, spec: Spec, dtype) -> jnp.ndarray:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "alog":  # mamba A in [1, 16): store log
        u = jax.random.uniform(key, spec.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(dtype)
    if spec.init == "dtbias":  # inverse softplus of dt ~ U[1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, spec.shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    scale = 0.006 if spec.init == "small" else 0.02
    return (scale * jax.random.normal(key, spec.shape, jnp.float32)).astype(dtype)


def init_from_spec(key, spec_tree: dict, dtype) -> dict:
    leaves, treedef = jax.tree_util.tree_flatten(
        spec_tree, is_leaf=lambda x: isinstance(x, Spec))
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(
        treedef, [init_param(k, s, dtype) for k, s in zip(keys, leaves)])


def axes_from_spec(spec_tree: dict) -> dict:
    return jax.tree_util.tree_map(
        lambda s: s.axes, spec_tree, is_leaf=lambda x: isinstance(x, Spec))


def shapes_from_spec(spec_tree: dict) -> dict:
    return jax.tree_util.tree_map(
        lambda s: tuple(s.shape), spec_tree, is_leaf=lambda x: isinstance(x, Spec))


# ---------------------------------------------------------------- norms/rope

def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def rope_tables(positions: jnp.ndarray, dim: int, theta: float):
    """cos/sin tables for ``positions`` (any shape), last dim ``dim // 2``."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x (..., S, n_heads, dim); cos/sin (..., S, dim/2)."""
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    c = cos[..., None, :]
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1).astype(x.dtype)


# ------------------------------------------------------- blockwise attention
#
# Flash-style blockwise attention with a custom VJP: the backward pass
# recomputes score tiles from (q, k, v, out, lse) instead of saving O(S^2)
# intermediates through the scan's autodiff (which would otherwise stack
# per-tile scores for every pair — the dominant HBM term at 4k+ contexts).
# All inputs are full-head (B, S, H, hd): GQA repeats kv before the call so
# the head axis shards cleanly over the TP mesh axis. This scan is the
# **bitwise jnp reference** for the fused Pallas kernels behind
# ``repro.kernels.dispatch.flash_attention`` (``REPRO_FUSED=off`` or
# uncovered shapes route back here); the public wrappers below
# (``causal_blockwise_attention`` / ``cross_blockwise_attention`` /
# ``decode_attention``) own that routing.

def largest_divisor(n: int, target: int) -> int:
    """Largest divisor of ``n`` that is <= ``target`` (>= 1; O(sqrt n)).

    The shared divisor search behind every blockwise fallback
    (``_pick_block`` here, ``model._pick_chunk`` for the loss scan):
    computed directly over the divisor pairs instead of decrementing from
    ``target``, which silently walked prime sizes down to 1.
    """
    target = min(target, n)
    best, d = 1, 1
    while d * d <= n:
        if n % d == 0:
            for c in (d, n // d):
                if best < c <= target:
                    best = c
        d += 1
    return best


def _pick_block(S: int, T: int, block: int) -> int:
    """Largest common divisor block of (S, T) that is <= ``block`` — and
    *audibly*: a prime or awkward length used to silently degrade to
    block=1 (the same failure mode as the pre-PR-3 ``chunk -= 1``),
    turning the tile scan into a per-position loop. Warns whenever the
    usable block falls below half the requested size.
    """
    target = max(min(block, S, T), 1)
    best = largest_divisor(math.gcd(S, T), target)
    if best * 2 < target:
        warnings.warn(
            f"blockwise attention: (S={S}, T={T}) share no divisor in "
            f"({target // 2}, {target}]; the tile shrinks to {best} "
            f"({(S // best) * (T // best)} candidate tile pairs). Pick "
            f"lengths with a common divisor near block={target} to keep "
            f"the scan short.", stacklevel=3)
    return best


def _tile_pairs(nq: int, nk: int, causal: bool, block: int = 1,
                offset: int = 0) -> np.ndarray:
    """(q tile, kv tile) index pairs; causal drops fully-masked pairs.

    Causal is *rectangular*: with ``offset = T - S >= 0`` query ``i``
    attends keys ``j <= offset + i`` (a cached-prefill continuation whose
    query block sits at the end of the key range; ``offset = 0`` is
    ordinary causal, where this reduces to the lower triangle). A pair
    survives iff its last query position reaches its first key position.
    """
    if causal:
        return np.array(
            [(qi, ki) for qi in range(nq) for ki in range(nk)
             if ki * block <= offset + (qi + 1) * block - 1],
            dtype=np.int32)
    return np.array([(qi, ki) for qi in range(nq) for ki in range(nk)],
                    dtype=np.int32)


_FLASH_RULES = Rules()


def _shard_flash(x, axes):
    """Head-shard the f32 flash-attention carries (they would otherwise sit
    replicated over the TP axis: 1-2 GB per layer for 128-head models)."""
    return shard(x, axes, _FLASH_RULES)


_NEG = -1e30  # finite -inf stand-in, same value as the fused kernels':
#               masked score entries underflow exp() to exactly +0.0, and a
#               row with no valid position keeps a NaN-free running max
#               (with -inf masking a fully-masked first tile made
#               ``exp(m_old - m_new)`` = exp(-inf - -inf) = NaN — reachable
#               once segment masking can blank a tile below the causal
#               diagonal)


def _tile_valid(spec: MaskSpec, qs, ks, block: int, q_seg, kv_seg):
    """Validity mask for one (q, kv) tile pair of the scan.

    Returns None when the spec masks nothing here (the non-causal
    no-segment path stays mask-free), a (block, block) bool for pure
    causal, or (B, 1, block, block) once segment ids participate —
    broadcastable against the (B, H, block, block) score tile either way.
    """
    valid = None
    if spec.causal:
        qpos = spec.offset + qs + jnp.arange(block)
        kpos = ks + jnp.arange(block)
        valid = qpos[:, None] >= kpos[None, :]
    if spec.has_segments:
        qsegb = jax.lax.dynamic_slice_in_dim(q_seg, qs, block, 1)
        ksegb = jax.lax.dynamic_slice_in_dim(kv_seg, ks, block, 1)
        seg = (qsegb[:, :, None] == ksegb[:, None, :])[:, None]
        valid = seg if valid is None else valid & seg
    return valid


def _flash_forward(q, k, v, q_seg, kv_seg, block: int, scale: float,
                   spec: MaskSpec):
    B, S, H, hd = q.shape
    T = k.shape[1]
    hdv = v.shape[-1]
    offset = spec.offset
    block = _pick_block(S, T, block)
    pairs = _tile_pairs(S // block, T // block, spec.causal, block, offset)

    acc0 = _shard_flash(jnp.zeros((B, S, H, hdv), jnp.float32),
                        ("act_batch", None, "act_heads", None))
    m0 = _shard_flash(jnp.full((B, S, H), -jnp.inf, jnp.float32),
                      ("act_batch", None, "act_heads"))
    l0 = _shard_flash(jnp.zeros((B, S, H), jnp.float32),
                      ("act_batch", None, "act_heads"))

    def body(carry, pair):
        acc, m, l = carry
        qs, ks = pair[0] * block, pair[1] * block
        qb = jax.lax.dynamic_slice_in_dim(q, qs, block, 1)
        kb = jax.lax.dynamic_slice_in_dim(k, ks, block, 1)
        vb = jax.lax.dynamic_slice_in_dim(v, ks, block, 1)
        s = jnp.einsum("bqhd,bshd->bhqs", qb, kb).astype(jnp.float32) * scale
        valid = _tile_valid(spec, qs, ks, block, q_seg, kv_seg)
        if valid is not None:
            s = jnp.where(valid, s, _NEG)
        accb = jnp.swapaxes(jax.lax.dynamic_slice_in_dim(acc, qs, block, 1), 1, 2)
        mb = jnp.swapaxes(jax.lax.dynamic_slice_in_dim(m, qs, block, 1), 1, 2)
        lb = jnp.swapaxes(jax.lax.dynamic_slice_in_dim(l, qs, block, 1), 1, 2)
        m_new = jnp.maximum(mb, jnp.max(s, axis=-1))
        alpha = jnp.exp(mb - m_new)
        # explicit mask on the exp (bitwise = the old -inf masking where
        # any position is valid: masked entries underflow to +0.0 either
        # way, and the running max only ever sees real scores)
        if valid is not None:
            p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
        else:
            p = jnp.exp(s - m_new[..., None])
        lb = lb * alpha + jnp.sum(p, axis=-1)
        accb = accb * alpha[..., None] + jnp.einsum(
            "bhqs,bshd->bhqd", p.astype(v.dtype),
            vb).astype(jnp.float32)
        acc = jax.lax.dynamic_update_slice_in_dim(
            acc, jnp.swapaxes(accb, 1, 2), qs, 1)
        m = jax.lax.dynamic_update_slice_in_dim(
            m, jnp.swapaxes(m_new, 1, 2), qs, 1)
        l = jax.lax.dynamic_update_slice_in_dim(
            l, jnp.swapaxes(lb, 1, 2), qs, 1)
        return (acc, m, l), None

    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), jnp.asarray(pairs))
    l = jnp.maximum(l, 1e-30)
    out = (acc / l[..., None]).astype(q.dtype)
    lse = m + jnp.log(l)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def masked_flash_attention(q, k, v, q_seg, kv_seg, block: int, scale: float,
                           spec: MaskSpec):
    """Memory-O(S*d) blockwise attention under a :class:`MaskSpec`.

    q,k,v (B,S,H,hd) / (B,T,H,hd); ``q_seg``/``kv_seg`` are (B, S)/(B, T)
    int32 segment ids, read only when ``spec.has_segments`` (pass
    zero-size (B, 0) arrays otherwise — :func:`flash_attention` does).
    The spec is a nondiff hashable; segment ids are traced operands whose
    cotangents are float0. This jnp scan is the bitwise reference path for
    the fused kernels (see the section comment above).
    """
    return _flash_forward(q, k, v, q_seg, kv_seg, block, scale, spec)[0]


def flash_attention(q, k, v, block: int, scale: float, causal: bool):
    """Blockwise attention with only the causal clause (pre-packing API).

    ``causal`` masks rectangularly when T > S (query ``i`` sees keys
    ``j <= (T - S) + i`` — a cached-prefill continuation); T == S is
    ordinary causal. Thin wrapper: builds the equivalent
    :class:`MaskSpec` and runs :func:`masked_flash_attention` with no
    segment operands — bitwise the pre-MaskSpec scan.
    """
    spec = mask_spec(q.shape[1], k.shape[1], causal=causal)
    z = jnp.zeros((q.shape[0], 0), jnp.int32)
    return masked_flash_attention(q, k, v, z, z, block, scale, spec)


def _flash_fwd_rule(q, k, v, q_seg, kv_seg, block, scale, spec):
    out, lse = _flash_forward(q, k, v, q_seg, kv_seg, block, scale, spec)
    return out, (q, k, v, q_seg, kv_seg, out, lse)


def _flash_bwd_rule(block, scale, spec, res, dout):
    q, k, v, q_seg, kv_seg, out, lse = res
    B, S, H, hd = q.shape
    T = k.shape[1]
    offset = spec.offset
    block_ = _pick_block(S, T, block)
    pairs = _tile_pairs(S // block_, T // block_, spec.causal, block_,
                        offset)
    # D_i = sum_d dout_i * out_i  (B,S,H)
    Dsum = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), -1)

    dq0 = _shard_flash(jnp.zeros(q.shape, jnp.float32),
                       ("act_batch", None, "act_heads", None))
    dk0 = _shard_flash(jnp.zeros(k.shape, jnp.float32),
                       ("act_batch", None, "act_heads", None))
    dv0 = _shard_flash(jnp.zeros(v.shape, jnp.float32),
                       ("act_batch", None, "act_heads", None))

    def body(carry, pair):
        dq, dk, dv = carry
        qs, ks = pair[0] * block_, pair[1] * block_
        qb = jax.lax.dynamic_slice_in_dim(q, qs, block_, 1)
        kb = jax.lax.dynamic_slice_in_dim(k, ks, block_, 1)
        vb = jax.lax.dynamic_slice_in_dim(v, ks, block_, 1)
        dob = jax.lax.dynamic_slice_in_dim(dout, qs, block_, 1)
        lseb = jnp.swapaxes(
            jax.lax.dynamic_slice_in_dim(lse, qs, block_, 1), 1, 2)
        Db = jnp.swapaxes(
            jax.lax.dynamic_slice_in_dim(Dsum, qs, block_, 1), 1, 2)
        s = jnp.einsum("bqhd,bshd->bhqs", qb, kb).astype(jnp.float32) * scale
        valid = _tile_valid(spec, qs, ks, block_, q_seg, kv_seg)
        if valid is not None:
            # explicit zero (not exp of a masked score): a fully-masked
            # row's lse is ~_NEG and exp(_NEG - lse) would be exp(~0) = 1
            p = jnp.where(valid, jnp.exp(s - lseb[..., None]), 0.0)
        else:
            p = jnp.exp(s - lseb[..., None])                 # (B,H,q,s)
        pb = p.astype(v.dtype)
        dvb = jnp.einsum("bhqs,bqhd->bshd", pb, dob)
        dp = jnp.einsum("bqhd,bshd->bhqs", dob, vb).astype(jnp.float32)
        ds = p * (dp - Db[..., None]) * scale
        dsb = ds.astype(q.dtype)
        dqb = jnp.einsum("bhqs,bshd->bqhd", dsb, kb)
        dkb = jnp.einsum("bhqs,bqhd->bshd", dsb, qb)
        dq = jax.lax.dynamic_update_slice_in_dim(
            dq, jax.lax.dynamic_slice_in_dim(dq, qs, block_, 1)
            + dqb.astype(jnp.float32), qs, 1)
        dk = jax.lax.dynamic_update_slice_in_dim(
            dk, jax.lax.dynamic_slice_in_dim(dk, ks, block_, 1)
            + dkb.astype(jnp.float32), ks, 1)
        dv = jax.lax.dynamic_update_slice_in_dim(
            dv, jax.lax.dynamic_slice_in_dim(dv, ks, block_, 1)
            + dvb.astype(jnp.float32), ks, 1)
        return (dq, dk, dv), None

    (dq, dk, dv), _ = jax.lax.scan(body, (dq0, dk0, dv0), jnp.asarray(pairs))
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            _int_cotangent(q_seg), _int_cotangent(kv_seg))


def _int_cotangent(x):
    """float0 cotangent for an integer operand (segment ids)."""
    return np.zeros(x.shape, jax.dtypes.float0)


masked_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _route_attention(q, k, v, scale: float, *, causal: bool, kv_len=None,
                     segments=None, rules: Optional[Rules] = None, mesh=None,
                     kv_axes=("act_batch", None, "act_heads", None)):
    """Fused-kernel route for one attention call (None -> caller's jnp path).

    Mirrors ``model.lm_loss``'s xent routing: derive the q/kv
    NamedShardings from the logical rules when a mesh is given
    (``kv_axes`` lets the decode path describe its cache layout), and
    only call the dispatch entry point when it will actually run the
    kernels — the callers keep their own scan/chunked reference paths.
    """
    from repro.kernels import dispatch as _kd  # lazy: optional kernel layer
    q_sh = kv_sh = None
    if mesh is not None and rules is not None:
        q_sh = rules.sharding(("act_batch", None, "act_heads", None), mesh,
                              q.shape)
        kv_sh = rules.sharding(kv_axes, mesh, k.shape)
    if _kd.attn_route(q.shape, k.shape, causal, None, q_sh, kv_sh,
                      v.shape)[0] != "kernel":
        return None
    return _kd.flash_attention(q, k, v, scale=scale, causal=causal,
                               kv_len=kv_len, segments=segments,
                               q_sharding=q_sh, kv_sharding=kv_sh)


def causal_blockwise_attention(q, k, v, block: int, scale: float, *,
                               rules: Optional[Rules] = None, mesh=None,
                               segments=None) -> jnp.ndarray:
    """Causal flash attention; kv may have fewer heads (GQA).

    Fused route (default where covered): the Pallas kernels behind
    ``dispatch.flash_attention`` index the kv block by ``q_head // group``
    natively — the H/K repeat is never materialized, and under ``mesh``
    the kernels shard_map over the activation batch/head axes. Reference
    route (``REPRO_FUSED=off`` / uncovered): repeat kv to full heads (so
    the head axis TP-shards cleanly) and run the jnp scan — the bitwise
    pre-kernel path. ``segments`` — a ((B, S), (B, T)) int32 pair —
    additionally forbids attention across packed-document boundaries.
    """
    return _blockwise_attention(q, k, v, block, scale, causal=True,
                                rules=rules, mesh=mesh, segments=segments)


def cross_blockwise_attention(q, k, v, block: int, scale: float, *,
                              rules: Optional[Rules] = None, mesh=None,
                              segments=None) -> jnp.ndarray:
    """Non-causal flash attention (cross-attention over image tokens).

    Routed like :func:`causal_blockwise_attention` (kernels where
    covered, repeated-kv jnp scan otherwise).
    """
    return _blockwise_attention(q, k, v, block, scale, causal=False,
                                rules=rules, mesh=mesh, segments=segments)


def _blockwise_attention(q, k, v, block, scale, *, causal, rules, mesh,
                         segments):
    out = _route_attention(q, k, v, scale, causal=causal, segments=segments,
                           rules=rules, mesh=mesh)
    if out is not None:
        return out
    H, K = q.shape[2], k.shape[2]
    if K != H:
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
        if rules is not None:
            k = shard(k, ("act_batch", None, "act_heads", None), rules)
            v = shard(v, ("act_batch", None, "act_heads", None), rules)
    if segments is not None:
        spec = mask_spec(q.shape[1], k.shape[1], causal=causal,
                         segments=segments)
        return masked_flash_attention(q, k, v, segments[0], segments[1],
                                      block, scale, spec)
    return flash_attention(q, k, v, block, scale, causal)


def decode_attention(q, k, v, q_block: int, scale: float, kv_len=None, *,
                     rules: Optional[Rules] = None, mesh=None,
                     kv_axes=("cache_batch", "cache_seq", None,
                              "cache_kv")) -> jnp.ndarray:
    """Attention over a T-length cache (decode / single-query cross-attn).

    Kernel route: the flash kernels run the rectangular (S=1..block, T)
    problem with the traced ``kv_len`` bound folded into the tile masks —
    tiles past the cache fill skip their compute entirely. The
    sequence-sharded decode cache (``cache_seq -> "model"``) is not
    expressible as a batch/head shard_map plan, so under such a mesh this
    falls back to :func:`chunked_q_attention`, which GSPMD partitions
    over the sharded T with small lse all-reduces.
    """
    out = _route_attention(q, k, v, scale, causal=False, kv_len=kv_len,
                           rules=rules, mesh=mesh, kv_axes=kv_axes)
    if out is not None:
        return out
    return chunked_q_attention(q, k, v, q_block, scale, kv_len=kv_len)


def chunked_q_attention(q, k, v, q_block: int, scale: float,
                        kv_len: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Non-causal attention chunked over q (cross-attn / decode-over-cache).

    q (B,S,H,hd); k,v (B,T,K,hd). ``kv_len`` masks positions >= kv_len —
    densified through the shared :func:`~repro.kernels.attention.mask
    .mask_array` so decode consumes the same MaskSpec clause as the
    kernels (decode serves one document per row, so the segment clause is
    never live here — ``mask_spec`` rejects segments + kv_len outright).
    """
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    q_block = min(q_block, S)
    assert S % q_block == 0
    nq = S // q_block
    qg = q.reshape(B, nq, q_block, K, G, hd)

    spec = mask_spec(S, T, causal=False, kv_len=kv_len)
    kmask = None
    if spec.has_kv_len:
        # (T,) row of the dense (1, S, T) mask: non-causal + kv_len is
        # query-invariant, bitwise what `arange(T) < kv_len` produced
        kmask = mask_array(spec, 1, T, kv_len=kv_len)[0, 0]

    def one(qb):  # (B,b,K,G,hd)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qb, k).astype(jnp.float32) * scale
        if kmask is not None:
            s = jnp.where(kmask[None, None, None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)
        return o

    out = jax.lax.map(lambda i: one(qg[:, i]), jnp.arange(nq))
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, H, v.shape[-1])
    return out.astype(q.dtype)


# ------------------------------------------------------------- GQA attention

def attn_spec(cfg: ModelConfig, cross: bool = False) -> dict:
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s = {
        "wq": Spec((D, H * hd), ("embed", "heads")),
        "wk": Spec((D, K * hd), ("embed", "kv")),
        "wv": Spec((D, K * hd), ("embed", "kv")),
        "wo": Spec((H * hd, D), ("heads", "embed")),
    }
    if cfg.qkv_bias and not cross:
        s["bq"] = Spec((H * hd,), ("heads",), "zeros")
        s["bk"] = Spec((K * hd,), ("kv",), "zeros")
        s["bv"] = Spec((K * hd,), ("kv",), "zeros")
    return s


def apply_attention(p, cfg: ModelConfig, x, positions, rules: Rules,
                    mode: str = "train", cache: Optional[dict] = None,
                    cache_index=None, kv_source: Optional[jnp.ndarray] = None,
                    causal: bool = True, mesh=None, segment_ids=None):
    """GQA self-attention (or cross-attention when ``kv_source`` is given).

    mode: train | prefill | decode. Returns (y, new_cache). ``mesh``
    (threaded from the trainer/serving factories, feature-detected like
    the loss's) lets the fused attention kernels shard_map over the
    activation batch/head axes. ``segment_ids`` (B, S) int32 masks
    attention to within-document positions for packed batches (self-
    attention only: cross-attention keys are not packed, and decode
    serves one document per row).
    """
    B, S, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kv_in = kv_source if kv_source is not None else x

    q = x @ p["wq"]
    k = kv_in @ p["wk"]
    v = kv_in @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = shard(q, ("act_batch", "act_seq", "act_heads"), rules)
    k = shard(k, ("act_batch", "act_seq", "act_kv"), rules)
    v = shard(v, ("act_batch", "act_seq", "act_kv"), rules)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, kv_in.shape[1], K, hd)
    v = v.reshape(B, kv_in.shape[1], K, hd)

    if kv_source is None and cfg.pos_embed == "rope":
        cos, sin = rope_tables(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    scale = (1.0 / math.sqrt(hd) if cfg.attention_multiplier is None
             else cfg.attention_multiplier)
    new_cache = cache
    if mode == "decode" and kv_source is None:
        # insert this step's k/v at cache_index, attend over the cache.
        # The cache is sequence-sharded (cache_seq -> model axis): attention
        # reduces over the sharded T with small lse/partial all-reduces
        # instead of gathering the cache.
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), cache_index, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), cache_index, 1)
        ck = shard(ck, ("cache_batch", "cache_seq", None, "cache_kv"), rules)
        cv = shard(cv, ("cache_batch", "cache_seq", None, "cache_kv"), rules)
        new_cache = {"k": ck, "v": cv}
        out = decode_attention(q, ck, cv, cfg.attn_q_block, scale,
                               kv_len=cache_index + S, rules=rules,
                               mesh=mesh)
    elif kv_source is not None and S == 1:
        out = decode_attention(q, k, v, cfg.attn_q_block, scale, rules=rules,
                               mesh=mesh,
                               kv_axes=("act_batch", None, "act_heads", None))
    else:
        if mode == "prefill":
            new_cache = {"k": k, "v": v}
        q = shard(q, ("act_batch", "act_seq", "act_heads", None), rules)
        k = shard(k, ("act_batch", None, "act_heads", None), rules)
        v = shard(v, ("act_batch", None, "act_heads", None), rules)
        # GQA expansion (kernel route: never; ref route: repeat so the
        # head axis TP-shards cleanly) lives inside the wrappers
        fn = (causal_blockwise_attention if kv_source is None
              else cross_blockwise_attention)
        seg = None
        if segment_ids is not None and kv_source is None:
            seg = (segment_ids, segment_ids)
        out = fn(q, k, v, cfg.attn_kv_block, scale, rules=rules, mesh=mesh,
                 segments=seg)

    out = out.reshape(B, S, H * hd)
    y = out @ p["wo"]
    return shard(y, ("act_batch", "act_seq", "act_embed"), rules), new_cache


# ------------------------------------------------------------- MLA attention

def mla_spec(cfg: ModelConfig) -> dict:
    D, H = cfg.d_model, cfg.n_heads
    qn, qr, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    qlr, kvlr = cfg.q_lora_rank, cfg.kv_lora_rank
    return {
        "wq_a": Spec((D, qlr), ("embed", "lora")),
        "q_norm": Spec((qlr,), ("norm",), "ones"),
        "wq_b": Spec((qlr, H * (qn + qr)), ("lora", "heads")),
        "wkv_a": Spec((D, kvlr + qr), ("embed", "lora")),
        "kv_norm": Spec((kvlr,), ("norm",), "ones"),
        "wkv_b": Spec((kvlr, H * (qn + vd)), ("lora", "heads")),
        "wo": Spec((H * vd, D), ("heads", "embed")),
    }


def apply_mla_attention(p, cfg: ModelConfig, x, positions, rules: Rules,
                        mode: str = "train", cache=None, cache_index=None,
                        mesh=None, segment_ids=None):
    """Multi-head Latent Attention (DeepSeek-V2/V3).

    Caches only the compressed kv latent (kv_lora_rank) + shared rope key —
    the architecture's memory win.
    """
    B, S, D = x.shape
    H = cfg.n_heads
    qn, qr, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvlr = cfg.kv_lora_rank

    cq = rmsnorm(x @ p["wq_a"], p["q_norm"], cfg.rms_eps)
    q = (cq @ p["wq_b"]).reshape(B, S, H, qn + qr)
    q_nope, q_rope = q[..., :qn], q[..., qn:]

    kv_a = x @ p["wkv_a"]
    c_kv, k_rope = kv_a[..., :kvlr], kv_a[..., kvlr:]
    c_kv = rmsnorm(c_kv, p["kv_norm"], cfg.rms_eps)

    cos, sin = rope_tables(positions, qr, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope[..., None, :], cos, sin)  # single shared head

    scale = 1.0 / math.sqrt(qn + qr)
    new_cache = cache
    if mode == "decode":
        # Absorbed decode (DeepSeek's production trick): fold wkv_b into the
        # query/output so attention runs directly against the cached latent —
        # no T-sized key/value expansion per step. The latent cache is
        # sequence-sharded; softmax reduces over the sharded T with small
        # all-reduces.
        ckv = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], c_kv.astype(cache["ckv"].dtype), cache_index, 1)
        krope = jax.lax.dynamic_update_slice_in_dim(
            cache["krope"], k_rope.astype(cache["krope"].dtype), cache_index, 1)
        ckv = shard(ckv, ("cache_batch", "cache_seq", "cache_kv"), rules)
        new_cache = {"ckv": ckv, "krope": krope}
        T = ckv.shape[1]
        wkv = p["wkv_b"].reshape(kvlr, H, qn + vd)
        wk, wv = wkv[..., :qn], wkv[..., qn:]
        q_lat = jnp.einsum("bqhn,khn->bqhk", q_nope, wk)       # (B,S,H,kvlr)
        s_lat = jnp.einsum("bqhk,btk->bhqt", q_lat, ckv)
        s_rope = jnp.einsum("bqhr,btr->bhqt", q_rope, krope[:, :, 0])
        s = (s_lat + s_rope).astype(jnp.float32) * scale
        mask = jnp.arange(T) < (cache_index + S)
        s = jnp.where(mask[None, None, None, :], s, -jnp.inf)
        pattn = jax.nn.softmax(s, axis=-1)
        out_lat = jnp.einsum("bhqt,btk->bqhk", pattn.astype(x.dtype), ckv)
        out = jnp.einsum("bqhk,khv->bqhv", out_lat, wv)
    else:
        if mode == "prefill":
            new_cache = {"ckv": c_kv, "krope": k_rope}
        # expand latents to per-head keys/values (train/prefill)
        kv = (c_kv @ p["wkv_b"]).reshape(B, c_kv.shape[1], H, qn + vd)
        k_nope, vv = kv[..., :qn], kv[..., qn:]
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, c_kv.shape[1], H, qr))], -1)
        q_full = jnp.concatenate([q_nope, q_rope], -1)
        q_full = shard(q_full, ("act_batch", "act_seq", "act_heads", None), rules)
        k_full = shard(k_full, ("act_batch", None, "act_heads", None), rules)
        vv = shard(vv, ("act_batch", None, "act_heads", None), rules)
        # full-head (H == K) causal attention; the kernel route also
        # covers MLA's asymmetric head dims (qk qn+qr vs value vd)
        seg = None if segment_ids is None else (segment_ids, segment_ids)
        out = causal_blockwise_attention(q_full, k_full, vv,
                                         cfg.attn_kv_block, scale,
                                         rules=rules, mesh=mesh,
                                         segments=seg)
    y = out.reshape(B, S, H * vd) @ p["wo"]
    return shard(y, ("act_batch", "act_seq", "act_embed"), rules), new_cache


# --------------------------------------------------------------------- MLPs

def mlp_spec(cfg: ModelConfig) -> dict:
    D, F = cfg.d_model, cfg.d_ff
    if cfg.mlp_kind == "gelu":  # gpt2-style 2-matrix MLP
        return {
            "w_up": Spec((D, F), ("embed", "mlp")),
            "w_down": Spec((F, D), ("mlp", "embed")),
        }
    return {
        "w_gate": Spec((D, F), ("embed", "mlp")),
        "w_up": Spec((D, F), ("embed", "mlp")),
        "w_down": Spec((F, D), ("mlp", "embed")),
    }


def apply_mlp(p, cfg: ModelConfig, x, rules: Rules):
    if cfg.mlp_kind == "gelu":
        h = jax.nn.gelu(x @ p["w_up"])
    else:
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    h = shard(h, ("act_batch", "act_seq", "act_mlp"), rules)
    y = h @ p["w_down"]
    return shard(y, ("act_batch", "act_seq", "act_embed"), rules)


def moe_spec(cfg: ModelConfig) -> dict:
    D, F, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    s = {
        "router": Spec((D, E), ("embed", None), "small"),
        "w_gate": Spec((E, D, F), ("experts", "embed", "expert_mlp")),
        "w_up": Spec((E, D, F), ("experts", "embed", "expert_mlp")),
        "w_down": Spec((E, F, D), ("experts", "expert_mlp", "embed")),
    }
    if cfg.n_shared_experts:
        Fs = F * cfg.n_shared_experts
        s["shared_gate"] = Spec((D, Fs), ("embed", "mlp"))
        s["shared_up"] = Spec((D, Fs), ("embed", "mlp"))
        s["shared_down"] = Spec((Fs, D), ("mlp", "embed"))
    return s


def apply_moe(p, cfg: ModelConfig, x, rules: Rules):
    """Capacity-based token-dropping MoE, group-local dispatch (GShard-style).

    Tokens are partitioned into ``G`` groups that shard over the ``data``
    axis; each group scatters into its own (E, C_g, D) buffer. Because
    activations are already replicated over ``model`` and experts over
    ``data``, the dispatch scatter is device-local — the only collectives
    are the expert-dim ones XLA inserts for the combine (activation-sized,
    not dispatch-buffer-sized). Expert FLOPs ~ active-FLOPs * capacity.
    """
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    G = max(1, T // cfg.moe_group_size)
    while T % G:
        G -= 1
    Tg = T // G
    C = max(1, int(cfg.capacity_factor * Tg * k / E))
    C = min(C, Tg)
    xg = x.reshape(G, Tg, D)
    xg = shard(xg, ("act_moe_group", None, "act_embed"), rules)

    logits = jnp.einsum("gtd,de->gte", xg, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    top_w, top_i = jax.lax.top_k(probs, k)                    # (G, Tg, k)
    top_w = top_w / jnp.sum(top_w, -1, keepdims=True)

    # load-balancing aux loss (Switch): E * sum_e f_e * P_e
    me = jnp.mean(jax.nn.one_hot(top_i[..., 0], E, dtype=jnp.float32), (0, 1))
    pe = jnp.mean(probs, (0, 1))
    aux = E * jnp.sum(me * pe)

    # slot of each (token, choice) inside its expert's capacity, per group
    oh = jax.nn.one_hot(top_i, E, dtype=jnp.int32).reshape(G, Tg * k, E)
    pos = jnp.cumsum(oh, 1) - 1                               # (G, Tg*k, E)
    pos = jnp.sum(pos * oh, -1).reshape(G, Tg, k)
    keep = pos < C

    # GShard-style one-hot dispatch/combine einsums: everything downstream of
    # the mask is E-sharded (EP over 'model'), so the only collectives are
    # (a) small (G,Tg,D) partial-sum all-reduces for combine/dispatch-grad
    # and (b) FSDP weight gathers — no scatter/gather buffer movement.
    # The k axis is contracted INSIDE the einsum (a flattened (G,Tg*k,E,C)
    # intermediate would be ~5 GB for deepseek-v3).
    keep_f = keep.astype(x.dtype)
    oh_c = jax.nn.one_hot(jnp.where(keep, pos, C), C, dtype=x.dtype)  # (G,t,k,C)
    oh_e = jax.nn.one_hot(top_i, E, dtype=x.dtype)                    # (G,t,k,E)
    oh_e = shard(oh_e, ("act_moe_group", None, None, "act_experts"), rules)
    mask_c = jnp.einsum("gtke,gtkc->gtec",
                        oh_e * (top_w.astype(x.dtype) * keep_f)[..., None],
                        oh_c)                                  # weighted combine
    mask_d = jnp.einsum("gtke,gtkc->gtec", oh_e * keep_f[..., None], oh_c)
    mask_c = shard(mask_c, ("act_moe_group", None, "act_experts", None), rules)
    mask_d = shard(mask_d, ("act_moe_group", None, "act_experts", None), rules)

    xe = jnp.einsum("gtec,gtd->gecd", mask_d, xg)             # (G,E,C,D)
    xe = shard(xe, ("act_moe_group", "act_experts", None, "act_embed"), rules)

    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, p["w_gate"])) * \
        jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    ye = shard(ye, ("act_moe_group", "act_experts", None, "act_embed"), rules)

    y = jnp.einsum("gtec,gecd->gtd", mask_c, ye)
    y = shard(y, ("act_moe_group", None, "act_embed"), rules)
    y = y.reshape(T, D)

    if cfg.n_shared_experts:
        xf = x.reshape(T, D)
        hs = jax.nn.silu(xf @ p["shared_gate"]) * (xf @ p["shared_up"])
        y = y + hs @ p["shared_down"]
    y = y.reshape(B, S, D)
    return shard(y, ("act_batch", "act_seq", "act_embed"), rules), aux


# ------------------------------------------------------------------- Mamba2

def mamba_spec(cfg: ModelConfig) -> dict:
    D = cfg.d_model
    din, nh = cfg.d_inner, cfg.ssm_nheads
    gn = cfg.ssm_ngroups * cfg.ssm_state
    d_in_proj = 2 * din + 2 * gn + nh
    return {
        "in_proj": Spec((D, d_in_proj), ("embed", "ssm_inner")),
        "conv_w": Spec((cfg.ssm_dconv, cfg.conv_dim), ("conv", "ssm_inner")),
        "conv_b": Spec((cfg.conv_dim,), ("ssm_inner",), "zeros"),
        "A_log": Spec((nh,), (None,), "alog"),
        "D": Spec((nh,), (None,), "ones"),
        "dt_bias": Spec((nh,), (None,), "dtbias"),
        "gate_norm": Spec((din,), ("ssm_inner",), "ones"),
        "out_proj": Spec((din, D), ("ssm_inner", "embed")),
    }


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv1d, window ``dconv``. x (B,S,C), w (dconv,C).

    ``state`` (B, dconv-1, C) prepends history (decode); returns (y, new_state).
    """
    dconv = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], dconv - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], 1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(dconv)) + b
    new_state = xp[:, -(dconv - 1):] if dconv > 1 else pad
    return jax.nn.silu(y), new_state


def _ssd_chunked(xh, dt, A, Bm, Cm, chunk: int, rules: Optional[Rules] = None):
    """Chunked SSD (Mamba2 'state-space duality' algorithm, matmul form).

    xh (B,S,nh,hd); dt (B,S,nh) (post-softplus); A (nh,) negative;
    Bm, Cm (B,S,G,N). Returns y (B,S,nh,hd).
    """
    B_, S, nh, hd = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    chunk = _pick_block(S, S, chunk)
    nc = S // chunk
    rep = nh // G

    xc = xh.reshape(B_, nc, chunk, nh, hd)
    dtc = dt.reshape(B_, nc, chunk, nh)
    Bc = jnp.repeat(Bm.reshape(B_, nc, chunk, G, N), rep, axis=3)   # (B,nc,c,nh,N)
    Cc = jnp.repeat(Cm.reshape(B_, nc, chunk, G, N), rep, axis=3)
    if rules is not None:
        # head-shard the intra-chunk tensors: the (B,nc,c,c,nh) decay/score
        # blocks are O(17 GB) per jamba layer if the head dim replicates
        hax = ("act_batch", None, None, "act_ssm_heads", None)
        xc = shard(xc, hax, rules)
        Bc = shard(Bc, hax, rules)
        Cc = shard(Cc, hax, rules)
        dtc = shard(dtc, ("act_batch", None, None, "act_ssm_heads"), rules)

    dA = dtc * A  # (B,nc,c,nh), negative
    cum = jnp.cumsum(dA, axis=2)

    # intra-chunk: y_i += sum_{j<=i} C_i.B_j exp(cum_i - cum_j) dt_j x_j
    li = cum[:, :, :, None, :]   # i index at axis 2
    lj = cum[:, :, None, :, :]
    decay = jnp.exp(li - lj)     # (B,nc,i,j,nh)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.where(mask[None, None, :, :, None], decay, 0.0)
    cb = jnp.einsum("bcihn,bcjhn->bcijh", Cc, Bc).astype(jnp.float32)
    w = cb * decay * dtc[:, :, None, :, :]
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", w.astype(xh.dtype), xc)

    # chunk states: S_c = sum_j exp(cum_end - cum_j) dt_j B_j x_j^T  (B,nc,nh,N,hd)
    dec_end = jnp.exp(cum[:, :, -1:, :] - cum)  # (B,nc,c,nh)
    sb = (dec_end * dtc)[..., None] * Bc        # (B,nc,c,nh,N)
    states = jnp.einsum("bcjhn,bcjhp->bchnp", sb.astype(xh.dtype), xc)

    # inter-chunk recurrence over nc (small): h_c = h_{c-1} * exp(sum dA_c) + S_c
    chunk_decay = jnp.exp(jnp.sum(dA, axis=2))  # (B,nc,nh)

    def scan_fn(h, inp):
        s_c, d_c = inp
        h_new = h * d_c[..., None, None].astype(h.dtype) + s_c
        return h_new, h  # emit state *before* this chunk

    h0 = jnp.zeros((B_, nh, N, hd), jnp.float32)
    _, h_prev = jax.lax.scan(
        scan_fn, h0,
        (jnp.moveaxis(states.astype(jnp.float32), 1, 0),
         jnp.moveaxis(chunk_decay, 1, 0)))
    h_prev = jnp.moveaxis(h_prev, 0, 1)  # (B,nc,nh,N,hd)

    dec_in = jnp.exp(cum)  # (B,nc,c,nh)
    y_inter = jnp.einsum("bcihn,bchnp->bcihp",
                         (Cc * dec_in[..., None]).astype(xh.dtype),
                         h_prev.astype(xh.dtype))
    y = (y_intra + y_inter).reshape(B_, S, nh, hd)
    return y


def apply_mamba(p, cfg: ModelConfig, x, rules: Rules, mode: str = "train",
                cache: Optional[dict] = None):
    """Mamba2 block. cache = {"conv": (B,dconv-1,conv_dim), "ssm": (B,nh,N,hd)}."""
    B, S, D = x.shape
    din, nh, hd = cfg.d_inner, cfg.ssm_nheads, cfg.ssm_headdim
    G, N = cfg.ssm_ngroups, cfg.ssm_state

    zxbcdt = x @ p["in_proj"]
    zxbcdt = shard(zxbcdt, ("act_batch", "act_seq", "act_ssm_inner"), rules)
    z = zxbcdt[..., :din]
    xbc = zxbcdt[..., din:din + cfg.conv_dim]
    dt_raw = zxbcdt[..., din + cfg.conv_dim:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    new_cache = cache
    if mode == "decode":
        xbc, conv_state = _causal_conv(xbc, p["conv_w"], p["conv_b"], cache["conv"])
    else:
        xbc, conv_state = _causal_conv(xbc, p["conv_w"], p["conv_b"])
        # conv_state holds the last (dconv-1) pre-activation inputs — exactly
        # what decode needs if this pass is a prefill.

    xs = xbc[..., :din].reshape(B, S, nh, hd)
    Bm = xbc[..., din:din + G * N].reshape(B, S, G, N)
    Cm = xbc[..., din + G * N:].reshape(B, S, G, N)

    if mode == "decode":
        # single-step recurrence: h = h*exp(dt*A) + dt * x B^T ; y = C.h + D x
        h = cache["ssm"].astype(jnp.float32)           # (B,nh,N,hd)
        dt1 = dt[:, 0]                                  # (B,nh)
        dA = jnp.exp(dt1 * A)                           # (B,nh)
        Bm1 = jnp.repeat(Bm[:, 0], nh // G, axis=1)     # (B,nh,N)
        Cm1 = jnp.repeat(Cm[:, 0], nh // G, axis=1)
        x1 = xs[:, 0].astype(jnp.float32)               # (B,nh,hd)
        h = h * dA[..., None, None] + (dt1[..., None, None]
                                       * Bm1[..., :, None] * x1[..., None, :])
        y = jnp.einsum("bhn,bhnp->bhp", Cm1, h)
        y = y + p["D"].astype(jnp.float32)[None, :, None] * x1
        y = y[:, None].astype(x.dtype)                  # (B,1,nh,hd)
        new_cache = {"conv": conv_state.astype(cache["conv"].dtype),
                     "ssm": h.astype(cache["ssm"].dtype)}
    else:
        y = _ssd_chunked(xs, dt, A, Bm, Cm, cfg.ssm_chunk, rules)
        y = y + p["D"].astype(x.dtype)[None, None, :, None] * xs
        if mode == "prefill":
            new_cache = {"conv": conv_state,
                         "ssm": _final_ssm_state(xs, dt, A, Bm, Cm)}

    y = y.reshape(B, S, din)
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                p["gate_norm"], cfg.rms_eps)
    out = y @ p["out_proj"]
    return shard(out, ("act_batch", "act_seq", "act_embed"), rules), new_cache


def _final_ssm_state(xh, dt, A, Bm, Cm):
    """Final SSM state h_S = sum_j exp(cum_S - cum_j) dt_j B_j x_j^T."""
    B_, S, nh, hd = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = nh // G
    Bf = jnp.repeat(Bm, rep, axis=2)
    dA = dt * A
    cum = jnp.cumsum(dA, axis=1)
    dec = jnp.exp(cum[:, -1:, :] - cum)  # (B,S,nh)
    sb = (dec * dt)[..., None] * Bf      # (B,S,nh,N)
    return jnp.einsum("bjhn,bjhp->bhnp", sb.astype(jnp.float32),
                      xh.astype(jnp.float32))
