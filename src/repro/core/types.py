"""Optimizer framework primitives (self-contained optax-style transforms).

A :class:`GradientTransformation` is an ``(init, update)`` pair:

    state  = tx.init(params)
    updates, state = tx.update(grads, state, params)
    params = apply_updates(params, updates)

``updates`` are *deltas* (already negated / scaled by the learning rate where
applicable), so ``apply_updates`` is a plain tree add.

Optimizers whose hot path benefits from writing parameters in place (one
theta read + one theta write per step instead of materializing a full-size
update tree) may additionally provide ``update_params``:

    params, state = tx.update_params(grads, state, params)

The field defaults to ``None``; callers (e.g. the trainer) then use the
classic ``update`` + ``apply_updates`` sequence.

Every ``update_params`` (all are built by :mod:`repro.core.pipeline`)
accepts two optional keyword arguments, and the trainer passes them
without asking: ``shardings`` under a mesh, ``grad_scale`` when it clips.

  * ``shardings`` — pytree of per-parameter ``jax.sharding.NamedSharding``
    (same structure as params). Optimizers whose hot path runs custom
    kernels need it to stay correct under pjit meshes: a kernel sees only
    its local shard, so cross-shard reductions (e.g. per-column norms over
    a row-sharded matrix) must be performed explicitly.
  * ``grad_scale`` — scalar folded into the gradient at read time,
    equivalent to ``update_params(tree_map(lambda g: g * grad_scale,
    grads), ...)`` but without materializing the scaled tree. The trainer
    uses it to fuse global-norm clipping into the parameter write.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

PyTree = Any
Schedule = Callable[[jnp.ndarray], jnp.ndarray]  # step -> lr


class GradientTransformation(NamedTuple):
    init: Callable[[PyTree], PyTree]
    update: Callable[[PyTree, PyTree, Optional[PyTree]], tuple[PyTree, PyTree]]
    # optional fused path: (grads, state, params) -> (new_params, new_state)
    update_params: Optional[
        Callable[[PyTree, PyTree, PyTree], tuple[PyTree, PyTree]]] = None
    # static introspection: the per-label Stages plans a pipeline optimizer
    # was built from (None for non-pipeline transforms). Consumed by
    # repro.analysis's registry-drift pass to verify which compositions
    # actually lower to the fused kernels; never touched at trace time.
    plans: Optional[Any] = None


class EmptyState(NamedTuple):
    pass


def identity() -> GradientTransformation:
    def init(params):
        del params
        return EmptyState()

    def update(grads, state, params=None):
        del params
        return grads, state

    return GradientTransformation(init, update)


def chain(*txs: GradientTransformation) -> GradientTransformation:
    """Compose transforms left-to-right (like optax.chain)."""

    def init(params):
        return tuple(tx.init(params) for tx in txs)

    def update(grads, state, params=None):
        new_state = []
        for tx, s in zip(txs, state):
            grads, s = tx.update(grads, s, params)
            new_state.append(s)
        return grads, tuple(new_state)

    return GradientTransformation(init, update)


def apply_updates(params: PyTree, updates: PyTree) -> PyTree:
    return jax.tree_util.tree_map(
        lambda p, u: (p + u.astype(p.dtype)), params, updates
    )


def global_norm(tree: PyTree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))


def tree_zeros_like(tree: PyTree, dtype=None) -> PyTree:
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, dtype or x.dtype), tree
    )


@dataclasses.dataclass(frozen=True)
class OptimizerInfo:
    """Static metadata attached to a built optimizer (for memory accounting)."""

    name: str
    # bytes of optimizer state per parameter-group, filled by core.memory
    extra: dict = dataclasses.field(default_factory=dict)
