"""Staged leaf-update pipeline shared by the whole optimizer zoo.

Every optimizer in :mod:`repro.core` is a *composition of stages* routed per
parameter label (``core.labels``: first / last / matrix / vector) instead of
a hand-rolled ``init``/``update``/``leaf`` triple. A :class:`Stages` value
describes what happens to one label group, in fixed order:

    grad-scale fold -> [project] -> [momentum EMA (+nesterov)] ->
    [standardize] -> [normalize] -> [adam] -> lr scale -> apply

and :func:`build_pipeline` turns ``{label: Stages}`` plans into a
:class:`~repro.core.types.GradientTransformation` with BOTH entry points:

  * ``update``        — classic delta mode (updates materialized, applied by
    ``apply_updates``);
  * ``update_params`` — write mode (theta written directly, ``shardings`` +
    ``grad_scale`` aware), for *every* pipeline optimizer. On the jnp path
    write mode replays delta mode's exact cast chain (round the update to
    the grad dtype, then to the param dtype on apply), so the two entry
    points are bitwise-equal and the trainer may auto-switch freely.

Kernel lowering
---------------
Stage compositions that match the fused primitives in
:mod:`repro.kernels.dispatch` lower to Pallas kernels under ``impl="fused"``
(compiled on TPU, interpret oracle on CPU/GPU, ``REPRO_FUSED`` override —
the same machinery as PRs 1-5):

  ======================================  ==================================
  composition                             kernel entry points
  ======================================  ==================================
  ``norm`` in {col,row,larger}, no        ``normalize`` (delta) /
  momentum/adam/standardize               ``norm_update`` (write)
  momentum EMA + ``norm`` in              ``momentum_norm`` (delta) /
  {col,row,larger}, no nesterov/adam      ``momentum_norm_update`` (write)
  ======================================  ==================================

Everything else (adam, sign/ns/svd norms, projections, nesterov blends,
standardize) stays on the jnp path per leaf; ``dispatch.supported`` gates
shape coverage exactly as before. ``grad_scale`` is threaded INTO the
kernels (multiplied at gradient read time) and applied as ``g * grad_scale``
on jnp branches — bitwise what the trainer's clip tree-map used to do.

State
-----
All pipeline optimizers share one state treedef, :class:`PipeState`
``(count, mu, nu, extra)``:

  * ``mu`` — first-moment buffer (momentum EMA or adam-m); stored in
    ``momentum_dtype`` for non-vector leaves (cast-on-read/write: the EMA
    and all math run in f32, only the *stored* buffer is rounded), f32 for
    vector adam moments (negligible; paper Appendix C).
  * ``nu`` — adam second moment, always f32.
  * ``extra`` — optimizer-specific tree: ``None`` for most, ``{"proj": ...}``
    for the GaLore family's projectors, Stable-SPAM's clip/norm EMAs.

Buffers a composition does not need are zero-length placeholders, so the
treedef is uniform at ~zero cost and ``update`` is an exact ``eval_shape``
fixed point of ``init`` (lax.scan / donated-buffer loops rely on this).

Tree-level hooks
----------------
``pre``/``pre_init`` run once per step on the whole gradient tree before
the leaf stages (Stable-SPAM's AdaClip + AdaGN live here), and
``reset_interval`` zeroes (mu, nu) every k steps (Stable-SPAM momentum
reset). When a ``pre`` hook is present the ``grad_scale`` fold is applied
up-front as a tree-map (the hook must see the clipped gradients; such
optimizers have no kernel stages, so XLA fuses the multiply for free).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .labels import LabelRules, label_tree, transposed_tree
from .normalization import flip_kind, normalize, ns_orthogonalize, resolve_larger
from .types import GradientTransformation, PyTree, Schedule

_f32 = jnp.float32

_LABELS = ("first", "last", "matrix", "vector")


def _empty(p):
    return jnp.zeros((0,), _f32)


def _zeros(p):
    return jnp.zeros(p.shape, _f32)


def _lr_at(lr, count):
    return lr(count) if callable(lr) else jnp.asarray(lr, _f32)


def muon_lr_scale(shape) -> float:
    """Muon's matched-lr scaling (Liu et al., 2025): 0.2 * sqrt(max dims)."""
    return 0.2 * float(max(shape[-2], shape[-1])) ** 0.5


def _adam_leaf(g, m, v, count, b1, b2, eps):
    gf = g.astype(_f32)
    m = b1 * m + (1.0 - b1) * gf
    v = b2 * v + (1.0 - b2) * gf * gf
    mhat = m / (1.0 - b1 ** (count + 1))
    vhat = v / (1.0 - b2 ** (count + 1))
    upd = mhat / (jnp.sqrt(vhat) + eps)
    return upd, m, v


# --------------------------------------------------------------------------
# Low-rank projection helpers (GaLore / Fira / APOLLO family).
# --------------------------------------------------------------------------

def _proj_left(shape) -> bool:
    """Project the smaller dimension (GaLore's rule): left iff d_in <= d_out."""
    return shape[-2] <= shape[-1]


def _rank_for(shape, rank: int) -> int:
    return min(rank, shape[-2], shape[-1])


def _svd_projector(g: jnp.ndarray, r: int) -> jnp.ndarray:
    """Top-r left (or right) singular vectors of g, shape (..., min_dim, r).

    Stacked (scan-over-layers / per-expert) leaves project per slice.
    """
    gf = g.astype(_f32)
    if _proj_left(g.shape):
        u, _, _ = jnp.linalg.svd(gf, full_matrices=False)
        return u[..., :, :r]  # (..., m, r)
    _, _, vt = jnp.linalg.svd(gf, full_matrices=False)
    return jnp.swapaxes(vt[..., :r, :], -1, -2)  # (..., n, r)


def _random_projector(key, shape, r: int) -> jnp.ndarray:
    d = shape[-2] if _proj_left(shape) else shape[-1]
    return jax.random.normal(key, tuple(shape[:-2]) + (d, r), _f32) / jnp.sqrt(r)


def _project(g, p):
    # left: R = P^T G  (..., r, n); right: R = G P  (..., m, r)
    if _proj_left(g.shape):
        return jnp.einsum("...dr,...dn->...rn", p, g)
    return jnp.einsum("...mn,...nr->...mr", g, p)


def _project_back(r_upd, p, shape):
    if _proj_left(shape):
        return jnp.einsum("...dr,...rn->...dn", p, r_upd)
    return jnp.einsum("...mr,...nr->...mn", r_upd, p)


@dataclasses.dataclass(frozen=True)
class Project:
    """Low-rank projection stage config (GaLore family).

    ``mode``: "galore" (SVD projector, adam in the subspace, project back),
    "fira" (+ full-rank residual scaled by the low-rank adam norm ratio),
    "apollo" (random projector, channel-wise gradient scaling) or
    "apollo_mini" (rank-1 tensor-wise variant with the sqrt(128) boost).
    """
    mode: str
    rank: int = 256
    update_proj_gap: int = 200
    scale_factor: float = 1.0
    seed: int = 0

    @property
    def eff_rank(self) -> int:
        return 1 if self.mode == "apollo_mini" else self.rank

    @property
    def random(self) -> bool:
        return self.mode in ("apollo", "apollo_mini")


@dataclasses.dataclass(frozen=True)
class Stages:
    """Stage composition for one label group (see module docstring).

    ``momentum``  — EMA coefficient for the first-moment stage (0 = off);
                    ``nesterov`` blends ``beta*m' + (1-beta)*g`` as the
                    direction instead of ``m'``.
    ``standardize`` — SWAN GradNorm: zero-mean/unit-variance per row.
    ``norm``      — normalization kind (col/row/larger/sign/ns/svd) applied
                    to the direction, or None. ``ns_steps`` parameterizes
                    the Newton-Schulz kinds. ``flip_transposed`` flips
                    col<->row for transposed-storage (tied-head) leaves —
                    opt-in, because the fixed-kind sgd_*norm ablations
                    normalize along the storage axis as defined.
    ``adam``      — full Adam on this group (``weight_decay`` decoupled);
                    mutually exclusive with momentum/norm stages.
    ``adams``     — AdamS (Huang et al., 2025): Adam's second moment is
                    replaced by the instantaneous mix
                    ``sqrt(b2*m_hat^2 + (1-b2)*g^2)``, so the group keeps
                    SGDM-sized state (first moment only) with Adam-like
                    per-element step sizes. Mutually exclusive with
                    ``adam`` and the momentum/norm stages.
    ``project``   — low-rank :class:`Project` stage (self-contained: runs
                    its own adam on the projected gradient).
    ``use_adam_lr`` / ``lr_scaling`` — lr source and Muon's per-matrix
                    spectral lr scale.
    """
    momentum: float = 0.0
    nesterov: bool = False
    standardize: bool = False
    norm: Optional[str] = None
    ns_steps: int = 5
    flip_transposed: bool = False
    adam: bool = False
    adams: bool = False
    weight_decay: float = 0.0
    project: Optional[Project] = None
    use_adam_lr: bool = False
    lr_scaling: bool = False


ADAM_STAGE = Stages(adam=True)
ADAM_LR_STAGE = Stages(adam=True, use_adam_lr=True)


class PipeState(NamedTuple):
    count: jnp.ndarray
    mu: PyTree    # first moment (momentum EMA / adam-m); empty when unused
    nu: PyTree    # adam second moment; empty when unused
    extra: PyTree = None  # projectors / clip EMAs / optimizer-specific


def _run_norm(d, kind, ns_steps, shape):
    if kind == "ns":
        return ns_orthogonalize(d, ns_steps)
    return normalize(d, resolve_larger(kind, shape))


def build_pipeline(
    plans: dict,
    lr: Schedule | float,
    adam_lr: Schedule | float | None = None,
    *,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    rules: Optional[LabelRules] = None,
    require_last: bool = False,
    impl: str = "jnp",
    momentum_dtype: str = "float32",
    pre: Optional[Callable] = None,
    pre_init: Optional[Callable] = None,
    reset_interval: int = 0,
) -> GradientTransformation:
    """Build a :class:`GradientTransformation` from per-label stage plans.

    ``plans`` maps every label in ``("first", "last", "matrix", "vector")``
    to a :class:`Stages`. ``impl="fused"`` lowers matching compositions to
    the Pallas kernels (see module docstring); ``momentum_dtype`` sets the
    storage dtype of non-vector first-moment buffers (cast-on-read/write).
    ``pre(grads, extra, count) -> (grads, extra)`` and ``pre_init(params)
    -> extra-dict`` install a tree-level hook; ``reset_interval`` zeroes
    (mu, nu) every k steps (``count % k == 0 and count > 0``).
    """
    rules = rules or LabelRules()
    adam_lr = adam_lr if adam_lr is not None else lr
    missing = [l for l in _LABELS if l not in plans]
    if missing:
        raise ValueError(f"plans missing labels {missing}")
    try:
        mdt = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[momentum_dtype]
    except KeyError:
        raise ValueError(f"momentum_dtype must be float32|bfloat16, "
                         f"got {momentum_dtype!r}") from None

    fused = impl == "fused"
    if fused:
        from repro.kernels import dispatch as _kd
    elif impl != "jnp":
        raise ValueError(f"unknown impl {impl!r}")

    projects = [st.project for st in plans.values() if st.project is not None]
    if len({id(p) for p in projects}) > 1 and len(set(projects)) > 1:
        raise ValueError("at most one Project spec per pipeline")
    proj_spec = projects[0] if projects else None

    def _mu_dtype(lab):
        return _f32 if lab == "vector" else mdt

    def _use_kernel(st, shape, kind, mode) -> bool:
        return (fused and kind is not None and not st.adam
                and not st.adams and st.project is None
                and not st.standardize and not st.nesterov
                and mode != "off" and _kd.supported(shape, kind))

    def _flat_with_labels(tree):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        labels = label_tree(tree, rules, require_last=require_last)
        return leaves, treedef, treedef.flatten_up_to(labels)

    def init(params):
        leaves, treedef, lab_l = _flat_with_labels(params)

        def mk_mu(lab, p):
            st = plans[lab]
            if st.project is not None:
                r = _rank_for(p.shape, st.project.eff_rank)
                rshape = ((r, p.shape[-1]) if _proj_left(p.shape)
                          else (p.shape[-2], r))
                return jnp.zeros(tuple(p.shape[:-2]) + rshape, _f32)
            if st.adam or st.adams or st.momentum:
                return jnp.zeros(p.shape, _mu_dtype(lab))
            return _empty(p)

        def mk_nu(lab, p):
            st = plans[lab]
            if st.project is not None:
                return mk_mu(lab, p)  # low-rank, f32 (vector is never projected)
            if st.adam:
                return _zeros(p)
            return _empty(p)

        mu = treedef.unflatten([mk_mu(l, p) for l, p in zip(lab_l, leaves)])
        nu = treedef.unflatten([mk_nu(l, p) for l, p in zip(lab_l, leaves)])
        extra = None
        if pre_init is not None:
            extra = pre_init(params)
        if proj_spec is not None:
            base_key = jax.random.PRNGKey(proj_spec.seed)

            def mk_proj(i, lab, p):
                st = plans[lab]
                if st.project is None:
                    return _empty(p)
                r = _rank_for(p.shape, st.project.eff_rank)
                if st.project.random:
                    return _random_projector(
                        jax.random.fold_in(base_key, i), p.shape, r)
                d = p.shape[-2] if _proj_left(p.shape) else p.shape[-1]
                return jnp.zeros(tuple(p.shape[:-2]) + (d, r), _f32)

            extra = {"proj": treedef.unflatten(
                [mk_proj(i, l, p)
                 for i, (l, p) in enumerate(zip(lab_l, leaves))])}
        return PipeState(count=jnp.zeros((), jnp.int32), mu=mu, nu=nu,
                         extra=extra)

    def _step(grads, state, params, write, shardings=None, grad_scale=None):
        """Shared per-leaf routing for both entry points.

        ``write=False`` -> delta mode (classic ``update`` contract);
        ``write=True``  -> new params returned directly (``update_params``).
        One copy of the label/stage/kernel branching guarantees the two
        modes cannot drift; the jnp write-mode branches replay delta mode's
        exact cast chain (round to g.dtype, then to p.dtype on apply), so
        both modes are bitwise-equal for any grad/param dtype combination.
        The fused kernel write applies in full f32 (slightly more precise,
        within the parity-test tolerance).
        """
        count = state.count
        lr_t = _lr_at(lr, count)
        alr_t = _lr_at(adam_lr, count)
        # REPRO_FUSED is re-read on every (re)trace and keys the dispatch
        # caches; an outer jit around the whole step still pins the mode at
        # its own trace time (see the dispatch module docstring)
        mode = _kd.resolve_mode() if fused else None
        extra = state.extra

        if pre is not None:
            if grad_scale is not None:
                # the hook must see clipped grads; bitwise = trainer tree-map
                grads = jax.tree_util.tree_map(
                    lambda g: g * grad_scale, grads)
                grad_scale = None
            grads, extra = pre(grads, extra, count)

        mu_in, nu_in = state.mu, state.nu
        if reset_interval:
            do_reset = ((count % reset_interval) == 0) & (count > 0)
            rz = lambda x: jnp.where(do_reset, jnp.zeros_like(x), x)
            mu_in = jax.tree_util.tree_map(rz, mu_in)
            nu_in = jax.tree_util.tree_map(rz, nu_in)

        if proj_spec is not None:
            refresh = (count % proj_spec.update_proj_gap) == 0
            base_key = jax.random.fold_in(
                jax.random.PRNGKey(proj_spec.seed),
                count // proj_spec.update_proj_gap)

        def emit(u, g, p):
            # delta mode returns the rounded update; write mode applies it
            u = u.astype(g.dtype)
            return u if not write else p + u.astype(p.dtype)

        def leaf(i, lab, tr, g, m, v, p, sh, pj):
            st = plans[lab]
            # jnp-branch view of the gradient: scaled up front, exactly the
            # op the trainer's clip tree-map used (XLA fuses it — free).
            # Kernel branches instead thread grad_scale INTO the kernels,
            # where it multiplies g at read time: scaling first would
            # materialize a full g*scale copy (pallas_call is opaque to
            # XLA fusion) — the HBM pass the fold exists to remove.
            gsc = g if grad_scale is None else g * grad_scale

            if st.project is not None:
                pr = st.project
                gf = gsc.astype(_f32)
                r = _rank_for(g.shape, pr.eff_rank)
                if pr.random:
                    new_p = _random_projector(
                        jax.random.fold_in(base_key, i), g.shape, r)
                else:
                    new_p = _svd_projector(gf, r)
                pj = jax.lax.cond(refresh, lambda: new_p, lambda: pj)
                R = _project(gf, pj)
                r_upd, m, v = _adam_leaf(R, m, v, count, b1, b2, eps)
                if pr.mode == "galore":
                    full = _project_back(r_upd, pj, g.shape) * pr.scale_factor
                elif pr.mode == "fira":
                    back = _project_back(r_upd, pj, g.shape)
                    resid = gf - _project_back(R, pj, g.shape)
                    phi = (jnp.linalg.norm(r_upd)
                           / (jnp.linalg.norm(R) + 1e-12))
                    full = (back + phi * resid) * pr.scale_factor
                else:  # apollo / apollo_mini: channel-wise gradient scaling
                    if pr.mode == "apollo_mini":
                        s = (jnp.linalg.norm(r_upd)
                             / (jnp.linalg.norm(R) + 1e-12))
                        # tensor-wise + heuristic sqrt(rank_ref) boost
                        full = gf * s * jnp.sqrt(jnp.asarray(128.0, _f32))
                    else:
                        # channel = output column when left-projected
                        axis = -2 if _proj_left(g.shape) else -1
                        num = jnp.linalg.norm(r_upd, axis=axis, keepdims=True)
                        den = (jnp.linalg.norm(R, axis=axis, keepdims=True)
                               + 1e-12)
                        full = gf * (num / den)
                    full = full * pr.scale_factor
                return emit(-lr_t * full, gsc, p), m, v, pj

            if st.adam:
                m_f = m.astype(_f32)
                upd, m_f, v = _adam_leaf(gsc, m_f, v, count, b1, b2, eps)
                if st.weight_decay:
                    if p is None:
                        raise ValueError(
                            "weight_decay requires params to be passed to "
                            "update()")
                    upd = upd + st.weight_decay * p.astype(_f32)
                lr_eff = alr_t if st.use_adam_lr else lr_t
                return (emit(-lr_eff * upd, gsc, p), m_f.astype(m.dtype), v,
                        pj)

            if st.adams:
                # AdamS: v is synthesized from the momentum and the raw
                # gradient at read time — no second-moment buffer, hence
                # SGDM-sized state with Adam-like per-element step sizes
                gf = gsc.astype(_f32)
                m_f = b1 * m.astype(_f32) + (1.0 - b1) * gf
                m_hat = m_f / (1.0 - b1 ** (count + 1))
                denom = jnp.sqrt(b2 * m_hat * m_hat
                                 + (1.0 - b2) * gf * gf) + eps
                upd = m_hat / denom
                if st.weight_decay:
                    if p is None:
                        raise ValueError(
                            "weight_decay requires params to be passed to "
                            "update()")
                    upd = upd + st.weight_decay * p.astype(_f32)
                lr_eff = alr_t if st.use_adam_lr else lr_t
                return (emit(-lr_eff * upd, gsc, p), m_f.astype(m.dtype), v,
                        pj)

            s = muon_lr_scale(g.shape) if st.lr_scaling else 1.0
            kind = st.norm
            if tr and st.flip_transposed:
                # tied head stored (V, D): the paper's normalization along
                # the output dimension is a row norm of the storage layout
                kind = flip_kind(kind)
            lr_eff = (alr_t if st.use_adam_lr else lr_t) * s

            if st.momentum:
                if _use_kernel(st, g.shape, kind, mode):
                    gf = g.astype(_f32)
                    if not write:
                        m, d = _kd.momentum_norm(
                            m, gf, st.momentum, kind, gscale=grad_scale,
                            sharding=sh, mode=mode)
                        return emit(-lr_eff * d, gsc, p), m, v, pj
                    p_new, m = _kd.momentum_norm_update(
                        p, m, gf, st.momentum, lr_eff, kind,
                        gscale=grad_scale, sharding=sh, mode=mode)
                    return p_new, m, v, pj
                gf = gsc.astype(_f32)
                # cast-on-read/write: EMA and norm in f32, storage in mdt
                m_f = st.momentum * m.astype(_f32) + (1.0 - st.momentum) * gf
                d = (st.momentum * m_f + (1.0 - st.momentum) * gf
                     if st.nesterov else m_f)
                m_out = m_f.astype(m.dtype)
            else:
                if _use_kernel(st, g.shape, kind, mode):
                    gf = g.astype(_f32)
                    if not write:
                        return emit(-lr_eff * _kd.normalize(
                            gf, kind, gscale=grad_scale, sharding=sh,
                            mode=mode), gsc, p), m, v, pj
                    return _kd.norm_update(
                        p, gf, lr_eff, kind, gscale=grad_scale, sharding=sh,
                        mode=mode), m, v, pj
                d = gsc.astype(_f32)
                m_out = m

            if st.standardize:
                mean = jnp.mean(d, axis=-1, keepdims=True)
                std = jnp.std(d, axis=-1, keepdims=True)
                d = (d - mean) / (std + 1e-8)
            if kind is not None:
                d = _run_norm(d, kind, st.ns_steps, g.shape)
            return emit(-lr_eff * d, gsc, p), m_out, v, pj

        g_leaves, treedef, lab_l = _flat_with_labels(grads)
        n = len(g_leaves)
        flat = treedef.flatten_up_to
        mu_l, nu_l = flat(mu_in), flat(nu_in)
        tr_l = flat(transposed_tree(grads, rules)) if rules.tied_last \
            else [False] * n
        p_l = flat(params) if params is not None else [None] * n
        sh_l = flat(shardings) if shardings is not None else [None] * n
        pj_l = flat(extra["proj"]) if proj_spec is not None else [None] * n
        out = [leaf(*args) for args in zip(range(n), lab_l, tr_l, g_leaves,
                                           mu_l, nu_l, p_l, sh_l, pj_l)]
        result = treedef.unflatten([o[0] for o in out])
        mu = treedef.unflatten([o[1] for o in out])
        nu = treedef.unflatten([o[2] for o in out])
        if proj_spec is not None:
            extra = {**extra, "proj": treedef.unflatten([o[3] for o in out])}
        return result, PipeState(count + 1, mu, nu, extra)

    def update(grads, state, params=None):
        return _step(grads, state, params, write=False)

    def update_params(grads, state, params, shardings=None, grad_scale=None):
        """Fused step: write theta directly (no materialized update tree).

        ``shardings``: optional pytree of per-param NamedSharding — makes
        the fused kernels mesh-correct under pjit (see module docstring).
        ``grad_scale``: optional scalar folded into the gradient read
        (the trainer's global-norm clip factor).
        """
        return _step(grads, state, params, write=True,
                     shardings=shardings, grad_scale=grad_scale)

    return GradientTransformation(init, update, update_params,
                                  plans=dict(plans))
