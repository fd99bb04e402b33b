"""Training-step factory: loss + grad (with microbatch accumulation and
optional global-norm clipping) + optimizer update, all inside one jitted
function suitable for pjit sharding. An optional in-jit anomaly guard
(:mod:`repro.training.resilience`) vets every update before it is applied.
"""
from __future__ import annotations

import inspect
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core.types import GradientTransformation, apply_updates, global_norm
from repro.models import loss_fn
from repro.models.sharding import Rules
from repro.obs.stats import StatsPolicy, make_stats_fn
from repro.training.resilience import (GuardPolicy, guard_step, guard_verdict,
                                       guarded_select, init_guard_state,
                                       inject_grad_faults)


class TrainState(NamedTuple):
    step: jnp.ndarray
    params: Any
    opt_state: Any
    # None unless the train step was built with a GuardPolicy (a None
    # subtree has no leaves, so guard-less states checkpoint identically
    # to the historical 3-field layout)
    guard: Any = None


def init_state(params, tx: GradientTransformation,
               guard: bool = False) -> TrainState:
    return TrainState(jnp.zeros((), jnp.int32), params, tx.init(params),
                      init_guard_state() if guard else None)


def make_train_step(cfg, tx: GradientTransformation, grad_accum: int = 1,
                    clip_norm: float = 0.0, aux_coef: float = 0.01,
                    rules: Optional[Rules] = None,
                    accum_dtype: str = "float32",
                    norm_metrics: bool = True,
                    fused_apply: Optional[bool] = None,
                    mesh: Optional[Mesh] = None,
                    donate: bool = False,
                    guard: Optional[GuardPolicy] = None,
                    faults=None,
                    stats: Optional[StatsPolicy] = None):
    """Build ``train_step(state, batch) -> (state, metrics)``.

    Batches are passed to the loss whole: packed-document batches
    (``data.pipeline`` with ``pack_documents``) simply carry their extra
    per-token leaves — ``segment_ids``, ``positions``, ``loss_weights`` —
    through the same dict; the microbatch reshape below tree_maps over
    every leaf, so packing and grad accumulation compose.

    ``grad_accum > 1`` splits the batch into microbatches along axis 0 and
    accumulates gradients via ``lax.scan`` (bounded activation memory, the
    standard large-scale recipe); per-microbatch auxiliary metrics (MoE
    aux-loss, token weight) are averaged alongside the loss. ``accum_dtype``
    controls the accumulator precision — f32 by default; bf16 halves the
    accumulator HBM footprint for the largest models (dry-run default for
    >300B params).

    ``fused_apply`` selects the optimizer's fused parameter write
    (``tx.update_params``: theta is read and written once, no materialized
    update tree). ``None`` (default) uses it whenever the optimizer provides
    one; ``True`` requires it; ``False`` forces the classic ``update`` +
    ``apply_updates`` sequence. Under the fused path the ``update_norm``
    metric is recovered from the old/new parameter diff (in f32 — bf16
    params would lose small updates to rounding), which re-reads both param
    trees — set ``norm_metrics=False`` to hold the fused path to its
    minimal HBM-pass count.

    ``mesh``: the pjit mesh the step will run under. Required for
    correctness whenever params are sharded and the optimizer runs custom
    kernels: the per-parameter ``NamedSharding`` tree (from ``rules`` +
    the model's logical axes) is passed to ``tx.update_params`` so the
    fused kernels shard_map over the mesh and psum their norm reductions.
    Optimizers without a ``shardings`` kwarg simply don't receive it.
    The mesh is also handed to the loss (``loss_fn(..., mesh=...)``,
    feature-detected the same way) so the fused LM-head cross-entropy can
    shard_map its kernels over the head's vocab/batch axes.

    When the optimizer's ``update_params`` accepts ``grad_scale``, global-
    norm clipping is folded into the parameter write (the clip factor
    scales the gradient inside the kernels) instead of rescaling the grad
    tree — one full grad read+write less per step, numerically identical
    to clip-then-update.

    ``donate=True`` returns the step already jitted with
    ``donate_argnums=(0,)``: the TrainState buffers are donated, which —
    combined with the apply kernels' ``input_output_aliases`` — makes the
    fused theta/momentum writes truly in-place (no fresh allocation).

    ``guard``: a :class:`repro.training.resilience.GuardPolicy`. The step
    then requires a guard-carrying state (``init_state(..., guard=True)``)
    and vets every update in-jit — non-finite loss/grad-norm or a loss
    spike skips the update (params and optimizer state pass through
    bitwise, via element-select), and the metrics gain ``skipped`` /
    ``bad_step`` / ``rollback`` (the latter trips after
    ``guard.max_bad_steps`` consecutive bad steps, signalling the host to
    restore a checkpoint and cut the LR — see ``launch/train.py``).

    ``faults``: a static :class:`repro.training.faults.FaultPlan` (resolved
    from ``REPRO_FAULTS`` outside jit). Only its gradient faults apply
    here: grads are corrupted with NaN/Inf at the spec'd steps via a
    traced select that is bitwise-inert on every other step.

    ``stats``: a :class:`repro.obs.stats.StatsPolicy`. The step then
    computes per-layer-group gradient/update/momentum statistics (the
    paper's Fig. 4/10 quantities — see :mod:`repro.obs.stats`) under a
    traced ``step % every_k == 0`` ``lax.cond`` and merges them into the
    metrics dict (``stats/<group>/<name>``, zeros plus ``stats/valid`` 0
    off the cadence step). The collector only reads — params and optimizer
    state are bitwise those of a stats-less step.
    """
    rules = rules or Rules(cfg.rule_overrides)
    acc_dt = jnp.float32 if accum_dtype == "float32" else jnp.bfloat16
    if fused_apply is None:
        fused_apply = tx.update_params is not None
    elif fused_apply and tx.update_params is None:
        raise ValueError("fused_apply=True but the optimizer has no "
                         "update_params (fused parameter write)")

    up_kwargs = {}
    if fused_apply:
        accepted = inspect.signature(tx.update_params).parameters
        if mesh is not None and "shardings" in accepted:
            from repro.models import param_logical_axes, param_shapes
            from repro.models.sharding import tree_shardings
            up_kwargs["shardings"] = tree_shardings(
                param_logical_axes(cfg), mesh, rules, param_shapes(cfg))
        fuse_clip = clip_norm > 0 and "grad_scale" in accepted
    else:
        fuse_clip = False

    # the fused-loss analog of the update_params feature-detection: only
    # pass the mesh to losses that know what to do with it
    loss_kwargs = {}
    if mesh is not None and "mesh" in inspect.signature(loss_fn).parameters:
        loss_kwargs["mesh"] = mesh

    stats_fn = make_stats_fn(stats) if stats is not None else None

    def loss_of(params, mb):
        # named scope -> the profiler groups the whole fwd (and, via jad's
        # transpose naming, the bwd) under one label in trace viewers
        with jax.named_scope("fwd"):
            return loss_fn(params, cfg, mb, aux_coef=aux_coef, rules=rules,
                           **loss_kwargs)

    grad_fn = jax.value_and_grad(loss_of, has_aux=True)

    def compute_grads(params, batch):
        if grad_accum == 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return loss, metrics, grads

        def reshape(x):
            if x.shape[0] % grad_accum:
                raise ValueError(
                    f"grad_accum={grad_accum} must divide the batch axis: "
                    f"got batch size {x.shape[0]} (remainder "
                    f"{x.shape[0] % grad_accum}); pick a batch size that is "
                    f"a multiple of grad_accum or lower grad_accum")
            return x.reshape((grad_accum, x.shape[0] // grad_accum) + x.shape[1:])

        micro = jax.tree_util.tree_map(reshape, batch)

        def body(carry, mb):
            acc, loss_acc = carry
            (loss, metrics), grads = grad_fn(params, mb)
            acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(acc_dt), acc, grads)
            # metrics (aux-loss, token weight, ...) are scalars: stack them
            # as scan outputs and average after — dropping them here loses
            # the MoE aux-loss signal whenever grad_accum > 1
            return (acc, loss_acc + loss), metrics

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, acc_dt), params)
        (gsum, loss_sum), metrics_stack = jax.lax.scan(
            body, (zeros, jnp.zeros((), jnp.float32)), micro)
        grads = jax.tree_util.tree_map(lambda g: g / grad_accum, gsum)
        loss = loss_sum / grad_accum
        metrics = jax.tree_util.tree_map(
            lambda x: jnp.mean(x, axis=0), metrics_stack)
        return loss, metrics, grads

    def train_step(state: TrainState, batch: dict):
        if mesh is None:
            return _train_step(state, batch)
        # the activation constraints (models.sharding.shard) read the mesh
        # the step is traced under
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return _train_step(state, batch)

    def _train_step(state: TrainState, batch: dict):
        if guard is not None and state.guard is None:
            raise ValueError(
                "make_train_step(guard=...) needs a guard-carrying state: "
                "build it with init_state(params, tx, guard=True)")
        loss, metrics, grads = compute_grads(state.params, batch)
        grads = inject_grad_faults(faults, state.step, grads)
        raw_grads = grads   # pre-clip: what the Fig. 4/10 stats measure
        out_metrics = {"loss": loss}
        step_kwargs = dict(up_kwargs)
        with jax.named_scope("clip"):
            if clip_norm > 0 or norm_metrics or guard is not None:
                gnorm = global_norm(grads)
                out_metrics["grad_norm"] = gnorm
            if clip_norm > 0:
                scale = jnp.minimum(1.0, clip_norm / (gnorm + 1e-9))
                if fuse_clip:
                    # folded into the optimizer's gradient read (in-kernel
                    # for fused leaves): no materialized g*scale tree
                    step_kwargs["grad_scale"] = scale
                else:
                    grads = jax.tree_util.tree_map(lambda g: g * scale,
                                                   grads)
        with jax.named_scope("optimizer_update"):
            if fused_apply:
                params, opt_state = tx.update_params(
                    grads, state.opt_state, state.params, **step_kwargs)
                updates = None
            else:
                updates, opt_state = tx.update(grads, state.opt_state,
                                               state.params)
                params = apply_updates(state.params, updates)
        gstate = state.guard
        ok = None
        if guard is not None:
            # the candidate update is computed unconditionally (NaNs and
            # all) and element-selected against the old buffers: a select
            # never propagates values from the discarded branch, so a
            # skipped step passes params and optimizer state through
            # bitwise — the exact state a clean run minus this step has
            with jax.named_scope("guard"):
                ok = guard_verdict(guard, state.guard, loss, gnorm)
                gstate, rollback = guard_step(guard, state.guard, ok, loss)
                params = guarded_select(ok, params, state.params)
                opt_state = guarded_select(ok, opt_state, state.opt_state)
            out_metrics["skipped"] = gstate.skipped
            out_metrics["bad_step"] = (~ok).astype(jnp.int32)
            out_metrics["rollback"] = rollback
        if norm_metrics:
            with jax.named_scope("step_metrics"):
                if fused_apply:
                    # diff in f32: bf16 params round small per-element
                    # updates away when differenced in the param dtype
                    # (post-guard, so a skipped step truthfully reports 0)
                    out_metrics["update_norm"] = global_norm(
                        jax.tree_util.tree_map(
                            lambda a, b: (a.astype(jnp.float32)
                                          - b.astype(jnp.float32)),
                            params, state.params))
                else:
                    unorm = global_norm(updates)
                    out_metrics["update_norm"] = (
                        jnp.where(ok, unorm, 0.0) if guard is not None
                        else unorm)
        if stats_fn is not None:
            # post-guard tensors: a skipped step truthfully reports a zero
            # update; the collector is read-only, so params/opt_state are
            # bitwise those of a stats-less build
            # cadence keys off the *completed-step* index (state.step + 1),
            # the same 1-based numbering the console lines, checkpoint
            # steps and the driver's --metrics-every cadence use — so a
            # --stats-every multiple of --metrics-every lands stats on
            # emitted records
            with jax.named_scope("obs_stats"):
                out_metrics.update(stats_fn(state.step + 1, raw_grads,
                                            state.params, params, opt_state))
        out_metrics.update({k: v for k, v in metrics.items() if k != "loss"})
        return TrainState(state.step + 1, params, opt_state,
                          gstate), out_metrics

    if donate:
        # TrainState donation + the apply kernels' input_output_aliases =
        # in-place theta/momentum writes (no fresh param-sized buffers)
        return jax.jit(train_step, donate_argnums=(0,))
    return train_step


def make_eval_step(cfg, rules: Optional[Rules] = None,
                   mesh: Optional[Mesh] = None):
    rules = rules or Rules(cfg.rule_overrides)
    loss_kwargs = {}
    if mesh is not None and "mesh" in inspect.signature(loss_fn).parameters:
        loss_kwargs["mesh"] = mesh

    def eval_step(params, batch):
        loss, metrics = loss_fn(params, cfg, batch, rules=rules,
                                **loss_kwargs)
        return {"loss": metrics["loss"], "perplexity": jnp.exp(metrics["loss"])}

    return eval_step
