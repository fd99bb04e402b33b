"""Unified attention mask specification.

Before this module, the attention stack threaded three ad-hoc masking
signals separately through ``models.layers``, the fused kernels and the
dispatch layer: a ``causal`` flag, the static rectangular offset ``T - S``
it implies, and a traced ``kv_len`` fill bound. Packed multi-document
batches add a fourth — per-position segment ids — and rather than a fourth
parallel plumbing run, every entry point now consumes one
:class:`MaskSpec`.

The split is **static vs traced**: ``MaskSpec`` holds only hashable Python
values (it keys ``lru_cache``d dispatch closures and rides through
``custom_vjp`` nondiff slots), while the traced operands it *describes* —
the ``kv_len`` scalar and the ``(B, S)``/``(B, T)`` segment-id arrays —
travel separately alongside q/k/v. ``has_kv_len``/``has_segments`` record
which traced operands are live so kernels can specialize their tile
machinery statically.

A position pair (query ``i``, key ``j``) is valid iff ALL live clauses
hold:

  * ``causal``:   ``offset + i >= j`` (rectangular causal; ``offset`` is
    ``T - S`` so ``T == S`` is ordinary causal and ``T > S`` a
    cached-prefill continuation);
  * ``kv_len``:   ``j < kv_len`` (decode over a partially filled cache);
  * ``segments``: ``q_seg[b, i] == kv_seg[b, j]`` (no cross-document
    attention in packed batches; pad positions carry segment id 0 and so
    form their own island — real tokens never attend pad and vice versa).

``segments`` and ``kv_len`` are mutually exclusive by construction
(packing is a train-time format, the fill bound a decode-time one);
:func:`mask_spec` enforces it.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax.numpy as jnp

_I32 = jnp.iinfo(jnp.int32)


class MaskSpec(NamedTuple):
    """Static (hashable) description of an attention mask.

    ``offset`` is only meaningful when ``causal``; it is pinned to 0
    otherwise so specs compare/hash canonically.
    """
    causal: bool = True
    offset: int = 0
    has_kv_len: bool = False
    has_segments: bool = False


def mask_spec(S: int, T: int, *, causal: bool = True, kv_len=None,
              segments=None) -> MaskSpec:
    """Canonical :class:`MaskSpec` for a (S query, T key) problem.

    ``kv_len`` / ``segments`` are the *traced* operands (or None); only
    their presence is recorded. Rejects the two combinations with no
    coherent semantics: causal with T < S (queries past the key range) and
    segments together with kv_len (packed train batches have no partial
    cache fill).
    """
    if causal and T < S:
        raise ValueError(f"causal attention needs T >= S, got S={S} T={T}")
    if segments is not None and kv_len is not None:
        raise ValueError("segments and kv_len are mutually exclusive "
                         "(packed batches have no cache-fill bound)")
    return MaskSpec(causal=bool(causal), offset=(T - S) if causal else 0,
                    has_kv_len=kv_len is not None,
                    has_segments=segments is not None)


def mask_array(spec: MaskSpec, S: int, T: int, *, kv_len=None,
               segments: Optional[Tuple] = None) -> jnp.ndarray:
    """Dense boolean validity mask for reference/oracle paths.

    Returns ``(1, S, T)`` when the spec has no segment clause (the mask is
    batch-invariant) and ``(B, S, T)`` with one. Traced operands must be
    passed iff the spec declares them.
    """
    if spec.has_kv_len != (kv_len is not None):
        raise ValueError("kv_len operand does not match spec.has_kv_len")
    if spec.has_segments != (segments is not None):
        raise ValueError("segments operand does not match spec.has_segments")
    valid = jnp.ones((1, S, T), bool)
    if spec.causal:
        qpos = spec.offset + jnp.arange(S)
        valid &= (qpos[:, None] >= jnp.arange(T)[None, :])[None]
    if spec.has_kv_len:
        valid &= (jnp.arange(T) < kv_len)[None, None, :]
    if spec.has_segments:
        q_seg, kv_seg = segments
        valid = valid & (q_seg[:, :, None] == kv_seg[:, None, :])
    return valid


class FetchPlan(NamedTuple):
    """Live tile ranges of one attention call, (B, n_tiles) int32 each.

    ``kv_lo[b, i]``/``kv_hi[b, i]`` are the first and last kv tile that q
    tile ``i`` of row ``b`` shares a live pair with; ``q_lo[b, j]``/
    ``q_hi[b, j]`` the first and last q tile of kv tile ``j``. A tile with
    no live partner gets (0, 0).
    """
    kv_lo: jnp.ndarray
    kv_hi: jnp.ndarray
    q_lo: jnp.ndarray
    q_hi: jnp.ndarray


def _tile_ranges(seg, n: int, b: int, length: int):
    """Per-tile (min, max) of (B, length) ids over the in-bounds lanes."""
    s = jnp.pad(jnp.asarray(seg, jnp.int32), ((0, 0), (0, n * b - length)))
    s = s.reshape(s.shape[0], n, b)
    inb = (jnp.arange(n * b) < length).reshape(n, b)
    return (jnp.min(jnp.where(inb, s, _I32.max), -1),
            jnp.max(jnp.where(inb, s, _I32.min), -1))


def _first_last(live, axis: int):
    n = live.shape[axis]
    some = live.any(axis)
    first = jnp.argmax(live, axis)
    last = n - 1 - jnp.argmax(jnp.flip(live, axis), axis)
    return (jnp.where(some, first, 0).astype(jnp.int32),
            jnp.where(some, last, 0).astype(jnp.int32))


def fetch_plan(spec: MaskSpec, S: int, T: int, bq: int, bk: int, *,
               batch: int, kv_len=None, segments=None) -> FetchPlan:
    """The kernels' tile-skip test over every (q tile, kv tile) pair.

    The same test as the kernels' per-step predicate: the causal diagonal
    with its offset, the ``kv_len`` fill bound, and — with segments — the
    overlap of the two tiles' id ranges, lanes past S/T left out. Packed
    rows hold sorted ids, so the live kv tiles of a q tile (and the live q
    tiles of a kv tile) form one run; a pad tail (id 0) only widens a
    range. Works on traced and on numpy operands.
    """
    nq, nk = -(-S // bq), -(-T // bk)
    i = jnp.arange(nq)[:, None]
    j = jnp.arange(nk)[None, :]
    live = j * bk < (T if kv_len is None else kv_len)
    if spec.causal:
        live &= spec.offset + (i + 1) * bq - 1 >= j * bk
    live = live[None]
    if spec.has_segments:
        q_lo, q_hi = _tile_ranges(segments[0], nq, bq, S)
        k_lo, k_hi = _tile_ranges(segments[1], nk, bk, T)
        live &= ((q_lo[:, :, None] <= k_hi[:, None, :])
                 & (k_lo[:, None, :] <= q_hi[:, :, None]))
    live = jnp.broadcast_to(live, (batch, nq, nk))
    return FetchPlan(*_first_last(live, 2), *_first_last(live, 1))
