"""Backend- and mesh-aware dispatch for the fused optimizer-update kernels.

This is the single place that decides, per (op, shape, norm kind, sharding),
whether a SCALE update runs through the Pallas kernels and in which mode:

  * on TPU the kernels run **compiled** (the real fused, 3-HBM-pass path);
  * on CPU/GPU they run in **interpret** mode, which executes the same
    kernel bodies through the Pallas interpreter — a slow but exact oracle
    that keeps parity tests meaningful on any machine. The interpreter is
    a correctness tool, not a performance path: for actual off-TPU
    *training* with ``impl="fused"``, set ``REPRO_FUSED=off`` to take the
    compiled-XLA jnp path (the benchmarks do this automatically);
  * shapes/kinds outside the coverage matrix fall back to the jnp reference.

Coverage matrix (``supported`` / ``xent_supported``):

  ==================  =====================================================
  op family           covered
  ==================  =====================================================
  optimizer updates   ndim in {2, 3} x kind in {col, row, larger} x any
                      dtype (math is f32 internally) x arbitrary shapes
                      (remainder tiles are masked inside the kernels).
                      ``larger`` resolves to col/row per shape at trace
                      time. sign/ns/svd norms and >3-D params are not
                      fused.
  xent (LM head)      h (N, D) or (B, S, D) x w (D, V) x any dtype x
                      arbitrary shapes (padded vocab and remainder tiles
                      masked via the tile iota) x masked (-1) labels. One
                      head at a time — the audio multi-codebook head
                      dispatches per codebook (its 4-D (B, C, S, D) case
                      never reaches dispatch directly).
  xent, weighted      optional per-token ``weights`` (labels.shape, f32):
                      zero-weight tokens demote to label -1 before the
                      kernel (no gradient work), fractional weights scale
                      loss and grads linearly. Composes *outside* the
                      custom_vjp, so fused and reference routes stay
                      weight-oblivious and weighting never changes the
                      route. Used by packed-document batches, where the
                      per-token weight doubles as the loss mask.
  xent, transposed w  ``transposed=True``: w is a **tied embedding** in
                      (V, D) storage — blocks index ``w[vocab_tile, d]``,
                      dW is emitted in (V, D) so the gradient lands on the
                      embedding, and the shard plan reads the vocab axes
                      off w's dim 0 (dim 1 — FSDP embed — is gathered).
                      Same shape/dtype/masking coverage as the (D, V) row.
  flash attention     q (B, S, H, hd) x k (B, T, K, hd) x v (B, T, K, hdv)
                      with H % K == 0 — native GQA: kv blocks are indexed
                      by ``q_head // group``, the H/K repeat is never
                      materialized (dK/dV reduce the group in VMEM and
                      land in the (B, T, K, *) storage layout). Causal is
                      rectangular (T >= S, query i sees keys <= T-S+i) or
                      off (cross-attention); a traced ``kv_len`` bounds
                      the key positions (decode over a partially filled
                      cache — tiles past the fill, like tiles above the
                      causal diagonal, skip their compute; kv_len is
                      non-causal only, the combination raises). Any dtype
                      (softmax statistics in f32), arbitrary/ragged S and
                      T (remainder tiles masked via the tile iota).
                      Uncovered: v whose (B, T, K) disagrees with k, and
                      causal T < S.
  attn, segment mask  packed-document masking: a ((B, S), (B, T)) int32
                      ``segments`` pair (one :class:`MaskSpec` clause —
                      see :mod:`repro.kernels.attention.mask`) restricts
                      every query to keys of its own document; pad id 0
                      is its own island. Tile pairs whose segment-id
                      ranges cannot overlap skip their compute like
                      above-diagonal causal tiles. The shard plan carries
                      the id arrays batch-sharded alongside q/kv; ids get
                      float0 cotangents (index data, like kv_len and
                      xent's labels). Mutually exclusive with ``kv_len``
                      (packing is a train-time format).
  ==================  =====================================================

Per-optimizer lowering (registry names, via ``core/pipeline.build_pipeline``
with ``impl="fused"``): a pipeline stage composition lowers to these kernels
iff it is a bare {col,row,larger}-norm, optionally with a plain momentum EMA
(no nesterov, no projection, no standardize, no Adam on that leaf). All
registry optimizers still provide ``update_params`` via the pipeline's jnp
write path (bitwise-equal to update+apply) even when never fused.

.. lowering-table-begin
(generated from core.api.OPTIMIZER_REGISTRY — edit the specs'
``lowering`` text and run ``python -m repro.analysis --fix``)

  ==================  =====  ==================================================
  registry optimizer  fused  lowering
  ==================  =====  ==================================================
  scale               yes    stateless matrices -> normalize / norm_update;
                             momentum groups (LM head) -> momentum_norm /
                             momentum_norm_update; Adam vectors stay jnp
  scale_fused         yes    as scale, built with impl="fused" by default
  sgd                 no     never fused: plain SGD has no norm stage; jnp
                             write path only
  sgd_momentum        no     never fused: a bare momentum EMA without a col/row
                             norm has no kernel composition
  adam                no     never fused: Adam moments have no kernel
                             composition; jnp write path only
  adamw               no     as adam (decoupled weight decay folds into the
                             Adam stage)
  adams               no     never fused: the synthesized AdamS denominator
                             (sqrt(b2*m^2 + (1-b2)*g^2)) has no kernel
                             composition; jnp write path only
  adapm               yes    as scale with momentum on the embedding and the LM
                             head (partial momentum); hidden matrices stay
                             stateless normalize / norm_update
  stable_spam         no     never fused: AdaClip/AdaGN run as the tree-level
                             pre hook; the Adam stage stays jnp
  muon                no     never fused: nesterov EMA + Newton-Schulz
                             orthogonalization sit outside kernel coverage
  swan                no     never fused: standardize (GradNorm) precedes the
                             norm stage
  galore              no     never fused: the low-rank projection stage has no
                             kernel composition
  fira                no     as galore (adds the full-rank residual)
  apollo              no     as galore (random projector, channel-wise scaling)
  apollo_mini         no     as apollo (rank-1 projector, tensor-wise scaling)
  sgd_colnorm         yes    all matrix groups -> normalize / norm_update when
                             built with impl="fused"; vectors stay jnp
  sgd_rownorm         yes    as sgd_colnorm with the row kind
  sgd_signnorm        no     never fused: sign norm is outside kernel coverage
  sgd_nsnorm          no     never fused: Newton-Schulz norm is outside kernel
                             coverage
  sgd_svdnorm         no     never fused: SVD norm is outside kernel coverage
  ==================  =====  ==================================================
.. lowering-table-end

Sharded dispatch (pjit meshes)
------------------------------
A bare ``pallas_call`` has no SPMD partitioning rule: under a mesh a kernel
sees only its local shard, and the norm kernels would sum squares over a
*fraction* of the rows — silently normalizing by the wrong norm. The entry
points therefore take their operands' ``NamedSharding`` (derived by the
trainer from ``models/sharding.Rules``) and, when a role is sharded, run
the kernels under ``shard_map`` on each device's local shard. Each family
names one role per operand dim; one planner (``_plan``) maps the roles to
mesh axes as a :class:`Plan`, and one helper turns a plan and roles into
PartitionSpecs:

  =========  ===============================  =================================
  family     roles, one per dim               collectives under a plan
  =========  ===============================  =================================
  updates    canon3 (L, m, n):                the per-slice sums of squares
             (stack, rows, cols)              psum over the reduce dim's axes —
                                              ``rows`` for col norms (FSDP
                                              ``data``), ``cols`` for row norms
                                              (``model``); one vector, not the
                                              matrix, crosses the ICI
  xent       h (tok, -[, -]); labels, lse     (lse, ll) pmax/psum over ``voc``
             (tok[, -]); w (gathered, voc),   with a global column offset per
             tied (V, D) w (voc, gathered)    shard; dH psums over ``voc``, dW
                                              (in f32) over ``tok``
  attention  q, k, v (batch, -, heads, -);    none: every (batch, head) pair is
             lse (batch, heads, -);           device-local (the softmax reduces
             segment ids (batch, -)           over the unsharded T; the GQA
                                              group ratio holds per shard)
  =========  ===============================  =================================

``-`` is a dim that must not be sharded. ``gathered`` (xent's embed dim)
may be sharded and is all-gathered at shard_map entry — the same gather
GSPMD inserts for the unfused head matmul. The planner's rules, in order:

  1. no operand has a sharding: the single-device path;
  2. a sharding that is not a ``NamedSharding``, or two meshes: reference;
  3. a ``-`` dim sharded (a sequence- or head_dim-sharded activation, the
     ``cache_seq`` decode cache): reference;
  4. a role sharded differently by two operands (q against kv): reference;
  5. one mesh axis serving two roles (tokens and vocab — the lse/ll psum
     would mix token shards): reference;
  6. an axis not in the mesh, or a dim its axes do not divide (shard_map
     needs exact divisibility): reference;
  7. no role sharded (a replicated layout): the single-device path.

The reference is the jnp path, which GSPMD partitions correctly on its
own. Callers that own a memory-safe jnp path ask ``xent_route`` /
``attn_route`` first and keep that path on "ref": ``models.model.lm_loss``
its chunked scan (``xent_loss``'s own reference is the full-logit
test-scale oracle), ``models.layers`` its blockwise scans
(``flash_attention``'s reference delegates to those same layer functions).

The ``REPRO_FUSED`` environment variable overrides the mode: ``auto``
(default), ``interpret``, ``compiled``, or ``off`` (always use the jnp
reference — an escape hatch if a backend miscompiles). It is re-read on
every entry-point call and threaded through as a **static argument**, so it
participates in the jit cache key: flipping it mid-process takes effect on
the next call instead of serving stale compilations. (Inside an outer
``jax.jit`` — e.g. a jitted train step — the read still happens at the
outer trace time; the outer cache is not keyed on it.)

Every kernel route additionally runs under a **failure guard**: a
``REPRO_FAULTS`` ``dispatch_fail`` injection, or — off the TPU, where the
kernels are interpreted — a kernel path that raises, degrades to the jnp
reference instead of killing the run, warned once per (op, exception
type) and counted per op in :func:`fallback_counts` so the training
driver can surface degradations in its step logs. On a TPU a real kernel
failure re-raises: the chip must never silently run the reference.
Kernel failures surface at trace/lower time (host-side), which is exactly
where the guard sits.

Entry points (scalar lr/beta/gscale may be traced schedule outputs). The
update ops accept ``gscale`` — a scalar multiplied into the gradient at
read time inside the kernels, used by the trainer to fold the global-norm
clip factor into the fused step without a separate full grad read+write.
HBM passes count every full-matrix read/write, jnp-path counts in
parentheses; the per-slice norm vector is negligible (see the accounting
note in :mod:`repro.kernels.colnorm.colnorm`):

  ========================  =======================================  ======
  op                        computes                                 passes
  ========================  =======================================  ======
  ``normalize``             g / (||slice|| + eps)                    3  (4)
  ``norm_update``           theta - lr * normalize(g)                4  (6)
  ``momentum_norm``         m' = EMA(m, g); (m', normalize(m'))      5  (6)
  ``momentum_norm_update``  m' = EMA(m, g); theta - lr*normalize(m') 6  (9)
  ========================  =======================================  ======

Under a mesh the update counts hold *per shard* (each device streams only
its 1/N of every matrix). The theta writes in ``norm_update`` and
``momentum_norm_update`` alias theta to the output, so with buffer donation
(``donate_argnums`` on the train step) the apply stage allocates no fresh
theta. ``xent_loss`` and ``flash_attention`` are ``custom_vjp`` functions
over the blockwise kernels in :mod:`repro.kernels.xent` and
:mod:`repro.kernels.attention`: logit and score tiles never leave VMEM, and
the backward recomputes them (attention from the saved ``lse``).
"""
from __future__ import annotations

import functools
import os
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.experimental.xla_metadata import set_xla_metadata
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .attention import attention as _ak
from .attention.mask import MaskSpec, mask_spec
from .colnorm import colnorm as _ck
from .colnorm import ref as _cref
from .colnorm.colnorm import _canon3 as _c3
from .scale_head import ref as _href
from .scale_head import scale_head as _hk
from .xent import ref as _xref
from .xent import xent as _xk

FUSED_KINDS = ("col", "row", "larger")
FUSED_NDIMS = (2, 3)

_MODES = ("auto", "interpret", "compiled", "off")


def resolve_mode() -> str:
    """Read REPRO_FUSED now (never cached — see the module docstring)."""
    m = os.environ.get("REPRO_FUSED", "auto")
    if m not in _MODES:
        raise ValueError(f"REPRO_FUSED must be auto|interpret|compiled|off, "
                         f"got {m!r}")
    return m


def backend() -> str:
    return jax.devices()[0].platform


def use_interpret(mode: str | None = None) -> bool:
    """Compiled on TPU, interpret oracle elsewhere (unless overridden)."""
    mode = resolve_mode() if mode is None else mode
    if mode == "interpret":
        return True
    if mode == "compiled":
        return False
    return backend() != "tpu"


def resolve_kind(kind: str, shape) -> str:
    """Resolve ``larger`` to col/row by shape (Table 13 row 4; static).

    Delegates to :func:`repro.core.normalization.resolve_larger` so the
    jnp impl and the kernel dispatch share one tie-break for square shapes.
    Always resolved on the **global** shape, before any shard_map: a shard
    of a tall matrix can be wide, and the two impls must agree.
    """
    from repro.core.normalization import resolve_larger
    return resolve_larger(kind, shape)


def _ref_norm(g: jnp.ndarray, kind: str, eps: float) -> jnp.ndarray:
    """jnp fallback for any norm kind (col/row honor eps; others delegate
    to repro.core.normalization, whose kinds have no eps knob)."""
    kind = resolve_kind(kind, g.shape)
    if kind in ("col", "row"):
        return _cref.normalize(g, kind, eps)
    from repro.core.normalization import normalize as _core_normalize
    return _core_normalize(g, kind)


def supported(shape, kind: str) -> bool:
    """True when (shape, kind) is covered by the fused update kernels."""
    if len(shape) not in FUSED_NDIMS or kind not in FUSED_KINDS:
        return False
    return all(d >= 1 for d in shape)


def xent_supported(h_shape, w_shape, transposed: bool = False) -> bool:
    """True when (h, w) shapes are covered by the fused xent kernels.

    ``transposed``: w is a tied embedding stored (V, D) — the contraction
    dim is then w's dim 1 instead of dim 0.
    """
    if len(h_shape) not in (2, 3) or len(w_shape) != 2:
        return False
    if h_shape[-1] != w_shape[1 if transposed else 0]:
        return False
    return all(d >= 1 for d in tuple(h_shape) + tuple(w_shape))


def attn_supported(q_shape, kv_shape, causal: bool = True,
                   v_shape=None) -> bool:
    """True when (q, kv) shapes are covered by the fused attention kernels.

    ``kv_shape`` is k's (B, T, K, hd); v (``v_shape``, when given) may
    differ from k only in its last dim. Causal needs T >= S (the
    rectangular offset T - S would otherwise put queries past the last
    key).
    """
    if len(q_shape) != 4 or len(kv_shape) != 4:
        return False
    if v_shape is not None and tuple(v_shape[:3]) != tuple(kv_shape[:3]):
        return False
    B, S, H, hd = q_shape
    if kv_shape[0] != B or kv_shape[3] != hd:
        return False
    K = kv_shape[2]
    if K < 1 or H % K:
        return False
    if causal and kv_shape[1] < S:
        return False
    return all(d >= 1 for d in tuple(q_shape) + tuple(kv_shape))


# --------------------------------------------------------------------------
# Shard plans: roles per operand dim (see the module docstring's table)
# --------------------------------------------------------------------------

GATHERED = "gathered"
CANON3 = ("stack", "rows", "cols")        # update operands, (L, m, n)
QKV = ("batch", None, "heads", None)      # (B, S|T, H|K, hd)
LSE = ("batch", "heads", None)            # attention lse, (B, H, S)
SEG = ("batch", None)                     # segment ids, (B, S) / (B, T)


def _tok_roles(ndim: int) -> tuple:
    """xent roles of h, labels and lse: tokens lead, the rest unsharded."""
    return ("tok",) + (None,) * (ndim - 1)


def _w_roles(transposed: bool) -> tuple:
    return ("voc", GATHERED) if transposed else (GATHERED, "voc")


# (sharding, shape, roles) per operand whose sharding the family is given
def _update_operands(sharding, shape):
    return ((sharding, shape, CANON3[-len(shape):]),)


def _xent_operands(h_sharding, w_sharding, h_shape, w_shape, transposed):
    return ((h_sharding, h_shape, _tok_roles(len(h_shape))),
            (w_sharding, w_shape, _w_roles(transposed)))


def _attn_operands(q_sharding, kv_sharding, q_shape, kv_shape):
    return ((q_sharding, q_shape, QKV), (kv_sharding, kv_shape, QKV))


def spec_mesh_axes(spec: P, ndim: int) -> tuple[tuple[str, ...], ...]:
    """Canonicalize a PartitionSpec to per-dim tuples of mesh axis names.

    Pads short specs with replicated dims, normalizes ``None`` -> ``()`` and
    single names -> 1-tuples.
    """
    entries = tuple(spec) + (None,) * (ndim - len(tuple(spec)))
    out = []
    for e in entries[:ndim]:
        if e is None:
            out.append(())
        elif isinstance(e, (tuple, list)):
            out.append(tuple(e))
        else:
            out.append((e,))
    return tuple(out)


class Plan(NamedTuple):
    """Static (hashable) shard_map recipe: the mesh, and the mesh axes of
    each sharded role as (role, axes) pairs. A role left out is unsharded.
    """
    mesh: Mesh
    axes: tuple

    def of(self, role) -> tuple:
        """Mesh axes sharding ``role``; () when it is not sharded."""
        return dict(self.axes).get(role, ())


def _plan(operands):
    """-> None (single-device path) | "ref" (GSPMD jnp fallback) | Plan.

    ``operands``: one (sharding, shape, roles) triple per operand whose
    sharding the caller knows (``None`` for an unknown one, read as
    replicated), with one role per dim. The rules are the module
    docstring's, in its order.
    """
    if all(sh is None for sh, _, _ in operands):
        return None
    mesh = None
    for sh, _, _ in operands:
        if sh is None:
            continue
        if not isinstance(sh, NamedSharding) or (
                mesh is not None and sh.mesh != mesh):
            return "ref"
        mesh = sh.mesh
    role_axes = {}
    for sh, shape, roles in operands:
        per = (spec_mesh_axes(sh.spec, len(shape)) if sh is not None
               else ((),) * len(shape))
        for dim, role, axs in zip(shape, roles, per):
            if role == GATHERED:
                continue
            if role is None:
                if axs:
                    return "ref"
                continue
            if role_axes.setdefault(role, axs) != axs:
                return "ref"
            k = 1
            for a in axs:
                if a not in mesh.shape:
                    return "ref"
                k *= mesh.shape[a]
            if dim % k:
                return "ref"
    used = [a for axs in role_axes.values() for a in axs]
    if len(used) != len(set(used)):
        return "ref"
    if not used:
        return None
    return Plan(mesh, tuple((r, axs) for r, axs in role_axes.items() if axs))


def _shard(body, plan, in_roles, out_roles):
    """``body`` under shard_map per ``plan``; ``body`` itself when None.

    ``in_roles`` holds one roles tuple per argument (``()`` for a
    replicated scalar); ``out_roles`` is one roles tuple, or a list of
    them for a body that returns several arrays.
    """
    if plan is None:
        return body

    def spec(roles):
        return P(*(plan.of(r) or None for r in roles))

    out_specs = (tuple(map(spec, out_roles)) if isinstance(out_roles, list)
                 else spec(out_roles))
    return shard_map(body, mesh=plan.mesh,
                     in_specs=tuple(map(spec, in_roles)),
                     out_specs=out_specs, check_vma=False)


def _route(mode, covered: bool, operands):
    """-> ("ref", None) | ("kernel", None | Plan).

    The one routing rule: ``REPRO_FUSED=off``, a shape outside the
    coverage matrix, or a sharding the planner cannot express takes the
    reference.
    """
    mode = resolve_mode() if mode is None else mode
    if mode == "off" or not covered:
        return "ref", None
    plan = _plan(operands)
    if plan == "ref":
        return "ref", None
    return "kernel", plan


def _red_axes(plan: Plan, axis: str):
    """Mesh axes the per-slice sums-of-squares must psum over."""
    return plan.of("rows" if axis == "col" else "cols")


def _psum_ss(ss, plan, axis):
    axes = _red_axes(plan, axis)
    if not axes:
        return ss
    # the one collective SCALE adds; the benchmark's norm_psum_share reads
    # its device time by this name. XLA's all-reduce combiner would merge
    # it with any independent all-reduce (the loss sum), and a merged op
    # keeps the first one's name; the attribute keeps the column sums of
    # one update in an all-reduce of their own.
    with jax.named_scope("norm_psum"), set_xla_metadata(norm_psum="1"):
        return jax.lax.psum(ss, axes)


# --------------------------------------------------------------------------
# Graceful degradation: kernel-route failure capture. A kernel path that
# fails on some backend surfaces its error at trace/lower time — host-side
# Python, exactly where these wrappers sit — so a failing kernel route
# degrades to the jnp reference instead of killing the run. Each (op,
# exception type) is warned once per process; per-op counts are exposed so
# the training driver can log degradations at its metrics cadence.
# --------------------------------------------------------------------------

_FALLBACK_COUNTS: dict = {}       # op -> kernel->reference degradations
_FALLBACK_LOGGED: set = set()     # (op, exc type): warn once per process


def fallback_counts() -> dict:
    """Per-op count of kernel-route failures degraded to the reference."""
    return dict(_FALLBACK_COUNTS)


def reset_fallbacks() -> None:
    """Forget recorded degradations (tests isolate cases with this)."""
    _FALLBACK_COUNTS.clear()
    _FALLBACK_LOGGED.clear()


def fallback_snapshot() -> dict:
    """Immutable copy of the cumulative per-op fallback counters.

    Pair with :func:`fallback_delta` for per-interval metric emission:
    the training driver snapshots at each metrics record and logs the
    delta since the previous one — cumulative counters stay untouched, so
    the chaos tests' whole-run assertions (which read
    :func:`fallback_counts`) never race a metrics-cadence reset.
    """
    return dict(_FALLBACK_COUNTS)


def fallback_delta(prev: dict, cur: dict | None = None) -> dict:
    """Per-op fallback increments since ``prev`` (a prior snapshot).

    ``cur`` defaults to a fresh snapshot. Ops with no new degradations are
    omitted, so an all-healthy interval is ``{}`` (nothing to log).
    """
    if cur is None:
        cur = fallback_snapshot()
    return {op: n - prev.get(op, 0) for op, n in cur.items()
            if n - prev.get(op, 0)}


def _dispatch_fault_gate(op: str) -> None:
    # chaos hook (REPRO_FAULTS dispatch_fail@op): no-op unless set; the
    # env check keeps the training package off dispatch's import path
    if not os.environ.get("REPRO_FAULTS"):
        return
    from repro.training import faults
    faults.dispatch_gate(op)


def _degrade(op: str, e: Exception, ref_thunk):
    _FALLBACK_COUNTS[op] = _FALLBACK_COUNTS.get(op, 0) + 1
    key = (op, type(e).__name__)
    if key not in _FALLBACK_LOGGED:
        _FALLBACK_LOGGED.add(key)
        warnings.warn(
            f"dispatch: kernel route for {op!r} failed "
            f"({type(e).__name__}: {e}); degrading to the jnp "
            "reference path")
    return ref_thunk()


def _guarded(op: str, kernel_thunk, ref_thunk):
    """Run the kernel route; degrade to the reference on a failure.

    An injected ``REPRO_FAULTS`` ``dispatch_fail`` always degrades. A real
    kernel failure degrades only off the TPU, where the kernels run in
    the interpreter: on a TPU it re-raises, so a broken kernel stops the
    run instead of hiding behind the jnp path. Catches Exception only: a
    KeyboardInterrupt or a SimulatedKill (BaseException) must never be
    absorbed into a silent fallback. The degradation is baked into
    whatever jit trace is being built, so a compiled train step that hit
    a failing kernel route keeps running the reference until retraced.
    """
    try:
        _dispatch_fault_gate(op)
    except Exception as e:
        return _degrade(op, e, ref_thunk)
    try:
        return kernel_thunk()
    except Exception as e:
        if backend() == "tpu":
            raise
        return _degrade(op, e, ref_thunk)


def _run(op: str, mode, covered: bool, operands, kernel, ref):
    """Every entry point's preamble: resolve REPRO_FUSED, route, then run
    ``kernel(plan, mode)`` under the failure guard, or ``ref(mode)``."""
    mode = resolve_mode() if mode is None else mode
    route, plan = _route(mode, covered, operands)
    if route == "ref":
        return ref(mode)
    return _guarded(op, lambda: kernel(plan, mode), lambda: ref(mode))


# --------------------------------------------------------------------------
# Optimizer updates. The jitted impls take the resolved mode and the plan
# (or "ref") as static args, so both key their caches.
# --------------------------------------------------------------------------

def _gs_arg(gscale):
    return (gscale is not None,
            jnp.asarray(1.0 if gscale is None else gscale, jnp.float32))


def _scaled_ref(g, gs, has_gs):
    # mirrors the trainer's clip tree-map (g * scale in g's promoted dtype)
    return g * gs if has_gs else g


def _update(op: str, impl, shape, kind, sharding, mode, *args, **kw):
    """Route one update op: ``impl(*args)`` with its kernel plan or "ref"."""
    def call(plan, mode):
        return impl(*args, plan=plan, mode=mode, kind=kind, **kw)

    return _run(op, mode, supported(shape, kind),
                _update_operands(sharding, shape), call,
                lambda mode: call("ref", mode))


@functools.partial(jax.jit, static_argnames=("kind", "eps", "mode", "plan",
                                             "has_gs"))
def _normalize_impl(g, gs, *, kind, eps, mode, plan, has_gs):
    if plan == "ref":
        return _ref_norm(_scaled_ref(g, gs, has_gs), kind, eps)
    axis = resolve_kind(kind, g.shape)
    interp = use_interpret(mode)

    def body(g3, gs):
        ss = _ck.norm_sumsq(g3, axis, interpret=interp, gscale=gs)
        if plan is not None:
            ss = _psum_ss(ss, plan, axis)
        return _ck.norm_apply(g3, ss, axis, eps=eps, interpret=interp,
                              gscale=gs)

    return _shard(body, plan, (CANON3, ()), CANON3)(
        _c3(g), gs).reshape(g.shape)


def normalize(g: jnp.ndarray, kind: str = "col", eps: float = 1e-8, *,
              gscale=None, sharding=None, mode: str | None = None):
    """Fused gscale*g / (||slice||+eps); jnp oracle off-matrix."""
    has_gs, gs = _gs_arg(gscale)
    return _update("normalize", _normalize_impl, g.shape, kind, sharding,
                   mode, g, gs, eps=eps, has_gs=has_gs)


@functools.partial(jax.jit, static_argnames=("kind", "eps", "mode", "plan",
                                             "has_gs"))
def _norm_update_impl(theta, g, lr, gs, *, kind, eps, mode, plan, has_gs):
    if plan == "ref":
        g = _scaled_ref(g, gs, has_gs)
        return (theta.astype(jnp.float32)
                - jnp.asarray(lr, jnp.float32)
                * _ref_norm(g, kind, eps).astype(jnp.float32)
                ).astype(theta.dtype)
    axis = resolve_kind(kind, theta.shape)
    interp = use_interpret(mode)

    def body(t3, g3, gs, lr):
        ss = _ck.norm_sumsq(g3, axis, interpret=interp, gscale=gs)
        if plan is not None:
            ss = _psum_ss(ss, plan, axis)
        return _ck.update_apply(t3, g3, ss, lr, axis, eps=eps,
                                interpret=interp, gscale=gs)

    lr = jnp.asarray(lr, jnp.float32)
    return _shard(body, plan, (CANON3, CANON3, (), ()), CANON3)(
        _c3(theta), _c3(g), gs, lr).reshape(theta.shape)


def norm_update(theta: jnp.ndarray, g: jnp.ndarray, lr, kind: str = "col",
                eps: float = 1e-8, *, gscale=None, sharding=None,
                mode: str | None = None):
    """Fused theta - lr*normalize(gscale*g); 3-pass per-shard apply stage."""
    has_gs, gs = _gs_arg(gscale)
    return _update("norm_update", _norm_update_impl, theta.shape, kind,
                   sharding, mode, theta, g, lr, gs, eps=eps, has_gs=has_gs)


@functools.partial(jax.jit, static_argnames=("kind", "eps", "mode", "plan",
                                             "has_gs"))
def _momentum_norm_impl(m, g, beta, gs, *, kind, eps, mode, plan, has_gs):
    if plan == "ref":
        g = _scaled_ref(g, gs, has_gs)
        m_new = (jnp.asarray(beta, jnp.float32) * m.astype(jnp.float32)
                 + (1.0 - jnp.asarray(beta, jnp.float32))
                 * g.astype(jnp.float32))
        # momentum storage dtype is m's dtype (cast-on-write; the norm is
        # computed from the pre-cast f32 EMA, matching the kernel)
        return m_new.astype(m.dtype), _ref_norm(m_new, kind, eps)
    axis = resolve_kind(kind, m.shape)
    interp = use_interpret(mode)

    def body(m3, g3, gs, beta):
        m_new, ss = _hk.momentum_sumsq(m3, g3, beta, axis, interpret=interp,
                                       gscale=gs)
        if plan is not None:
            ss = _psum_ss(ss, plan, axis)
        # d is emitted f32 even when the stored momentum is bf16 (the
        # update tree must not inherit the storage quantization). Its
        # numerator is the *stored* m' — re-emitting a f32 copy for the
        # apply would double the momentum traffic — so under bf16 storage
        # the direction differs from the jnp route's pre-cast-EMA norm by
        # bf16 rounding (see the momentum_dtype note in core/scale.py).
        d = _ck.norm_apply(m_new, ss, axis, eps=eps, interpret=interp,
                           out_dtype=jnp.float32)
        return m_new, d

    beta = jnp.asarray(beta, jnp.float32)
    m_new, d = _shard(body, plan, (CANON3, CANON3, (), ()),
                      [CANON3, CANON3])(_c3(m), _c3(g), gs, beta)
    return m_new.reshape(m.shape), d.reshape(m.shape)


def momentum_norm(m: jnp.ndarray, g: jnp.ndarray, beta, kind: str = "col",
                  eps: float = 1e-8, *, gscale=None, sharding=None,
                  mode: str | None = None):
    """(m', normalize(m')) with the EMA and sumsq fused into one kernel."""
    has_gs, gs = _gs_arg(gscale)
    return _update("momentum_norm", _momentum_norm_impl, m.shape, kind,
                   sharding, mode, m, g, beta, gs, eps=eps, has_gs=has_gs)


@functools.partial(jax.jit, static_argnames=("kind", "eps", "mode", "plan",
                                             "has_gs"))
def _momentum_norm_update_impl(theta, m, g, beta, lr, gs, *, kind, eps, mode,
                               plan, has_gs):
    if plan == "ref":
        m_new, d = _momentum_norm_impl(m, g, beta, gs, kind=kind, eps=eps,
                                       mode=mode, plan="ref", has_gs=has_gs)
        theta_new = (theta.astype(jnp.float32)
                     - jnp.asarray(lr, jnp.float32) * d.astype(jnp.float32)
                     ).astype(theta.dtype)
        return theta_new, m_new
    axis = resolve_kind(kind, theta.shape)
    interp = use_interpret(mode)

    def body(t3, m3, g3, gs, beta, lr):
        m_new, ss = _hk.momentum_sumsq(m3, g3, beta, axis, interpret=interp,
                                       gscale=gs)
        if plan is not None:
            ss = _psum_ss(ss, plan, axis)
        theta_new = _hk.head_update_apply(t3, m_new, ss, lr, axis, eps=eps,
                                          interpret=interp)
        return theta_new, m_new

    beta = jnp.asarray(beta, jnp.float32)
    lr = jnp.asarray(lr, jnp.float32)
    t_new, m_new = _shard(body, plan, (CANON3, CANON3, CANON3, (), (), ()),
                          [CANON3, CANON3])(
        _c3(theta), _c3(m), _c3(g), gs, beta, lr)
    return t_new.reshape(theta.shape), m_new.reshape(m.shape)


def momentum_norm_update(theta: jnp.ndarray, m: jnp.ndarray, g: jnp.ndarray,
                         beta, lr, kind: str = "col", eps: float = 1e-8, *,
                         gscale=None, sharding=None, mode: str | None = None):
    """Fully fused stateful step: (theta', m') in two kernel launches."""
    has_gs, gs = _gs_arg(gscale)
    return _update("momentum_norm_update", _momentum_norm_update_impl,
                   theta.shape, kind, sharding, mode, theta, m, g, beta, lr,
                   gs, eps=eps, has_gs=has_gs)


# --------------------------------------------------------------------------
# Fused LM-head cross-entropy
# --------------------------------------------------------------------------

def xent_route(h_shape, w_shape, mode: str | None = None, h_sharding=None,
               w_sharding=None, transposed: bool = False):
    """-> ("ref", None) | ("kernel", None | Plan).

    Callers that must never materialize full logits (the model's loss)
    take their own chunked path on "ref"; ``xent_loss``'s built-in ref is
    the full-logit test-scale oracle. ``transposed``: w is the tied (V, D)
    embedding (see the coverage matrix).
    """
    return _route(mode, xent_supported(h_shape, w_shape, transposed),
                  _xent_operands(h_sharding, w_sharding, h_shape, w_shape,
                                 transposed))


@functools.lru_cache(maxsize=None)
def _xent_fused(vocab_size: int, interp: bool, plan, block,
                transposed: bool = False):
    """Build the custom_vjp'd fused xent for one static configuration.

    Cached so repeated traces reuse one custom_vjp object (and its jit
    caches). ``plan`` is a Plan or None; ``block`` a (bn, bv) tuple or
    None; ``transposed`` selects the tied (V, D) w layout — dW then comes
    back in (V, D), landing directly on the embedding cotangent.
    """
    tok_axes = plan.of("tok") if plan is not None else ()
    voc_axes = plan.of("voc") if plan is not None else ()
    w_roles = _w_roles(transposed)
    _v_local = (lambda wb: wb.shape[0]) if transposed \
        else (lambda wb: wb.shape[1])

    def _voffset(v_local: int):
        """Global column id of this shard's first w column (0 off-mesh)."""
        if not voc_axes:
            return 0
        idx = jnp.int32(0)
        for a in voc_axes:  # major-to-minor, matching GSPMD's dim split
            idx = idx * plan.mesh.shape[a] + jax.lax.axis_index(a)
        return idx * v_local

    def _fwd_parts(h, w, labels):
        def body(hb, wb, lab):
            lse, ll = _xk.xent_fwd(
                hb.reshape(-1, hb.shape[-1]), wb, lab.reshape(-1),
                vocab_size=vocab_size, col_offset=_voffset(_v_local(wb)),
                block=block, interpret=interp, transposed=transposed)
            if voc_axes:
                m = jax.lax.pmax(lse, voc_axes)
                lse = m + jnp.log(jax.lax.psum(jnp.exp(lse - m), voc_axes))
                ll = jax.lax.psum(ll, voc_axes)
            return lse.reshape(lab.shape), ll.reshape(lab.shape)

        hr, lab = _tok_roles(h.ndim), _tok_roles(labels.ndim)
        return _shard(body, plan, (hr, w_roles, lab), [lab, lab])(
            h, w, labels)

    def _bwd_parts(h, w, labels, lse, gl):
        def body(hb, wb, lab, lse_, gl_):
            h2 = hb.reshape(-1, hb.shape[-1])
            args = (h2, wb, lab.reshape(-1), lse_.reshape(-1),
                    gl_.reshape(-1))
            kw = dict(vocab_size=vocab_size, block=block, interpret=interp,
                      col_offset=_voffset(_v_local(wb)),
                      transposed=transposed)
            # partial sums psum in f32, then round to the cotangent dtype
            dh, dw = _xk.xent_bwd(
                *args, **kw,
                dw_dtype=jnp.float32 if tok_axes else wb.dtype)
            if voc_axes:
                dh = jax.lax.psum(dh, voc_axes)
            dh = dh.astype(hb.dtype)
            if tok_axes:
                dw = jax.lax.psum(dw, tok_axes).astype(wb.dtype)
                # the layers' backward needs dH at once: tied to it, the
                # psum runs before that backward, and the kernel's f32 dW
                # does not stay live through it (the step's memory peak)
                dh, dw = jax.lax.optimization_barrier((dh, dw))
            return dh.reshape(hb.shape), dw

        hr, lab = _tok_roles(h.ndim), _tok_roles(labels.ndim)
        return _shard(body, plan, (hr, w_roles, lab, lab, lab),
                      [hr, w_roles])(h, w, labels, lse, gl)

    @jax.custom_vjp
    def fused(h, w, labels):
        lse, ll = _fwd_parts(h, w, labels)
        return jnp.where(labels >= 0, lse - ll, 0.0)

    def fwd(h, w, labels):
        lse, ll = _fwd_parts(h, w, labels)
        return jnp.where(labels >= 0, lse - ll, 0.0), (h, w, labels, lse)

    def bwd(res, g):
        h, w, labels, lse = res
        gl = g.astype(jnp.float32) * (labels >= 0)
        dh, dw = _bwd_parts(h, w, labels, lse, gl)
        return dh, dw, np.zeros(labels.shape, jax.dtypes.float0)

    fused.defvjp(fwd, bwd)
    return fused


def _xent_ref(h, w, labels, *, vocab_size: int, transposed: bool = False):
    """Full-logit jnp oracle (test scale; see ``xent_route``).

    The transpose of a tied w is lazy (fused into the dot); grads flow
    back through it, so dW arrives in the (V, D) storage layout here too.
    """
    if transposed:
        w = jnp.swapaxes(w, -1, -2)
    return _xref.losses(h, w, labels, vocab_size)


def xent_loss(h: jnp.ndarray, w: jnp.ndarray, labels: jnp.ndarray, *,
              vocab_size: int, weights=None, mode: str | None = None,
              h_sharding=None, w_sharding=None, block=None,
              transposed: bool = False):
    """Fused per-token LM-head cross-entropy (custom_vjp, see module doc).

    h (..., D), w (D, V) — or the tied (V, D) embedding with
    ``transposed=True`` — labels h.shape[:-1] int32 (-1 = masked).
    Returns f32 losses of labels.shape; masked tokens are 0 in both the
    value and the (h, w) gradients. ``weights`` (optional, labels.shape,
    f32) scales each token's loss *and* gradient: zero-weight tokens are
    additionally masked outright (their labels are demoted to -1 before
    the kernel, so they cost no gradient work), fractional weights scale
    linearly. The weighting wraps both routes identically — it composes
    outside the custom_vjp, so the fused kernels stay weight-oblivious.
    Padded vocab columns (>= vocab_size) never enter the logsumexp. dW
    always matches w's own layout.
    """
    if weights is not None:
        labels = jnp.where(weights > 0, labels, -1)
    losses = _run(
        "xent_loss", mode, xent_supported(h.shape, w.shape, transposed),
        _xent_operands(h_sharding, w_sharding, h.shape, w.shape, transposed),
        lambda plan, mode: _xent_fused(
            vocab_size, use_interpret(mode), plan,
            tuple(block) if block is not None else None, transposed)(
                h, w, labels),
        lambda mode: _xent_ref(h, w, labels, vocab_size=vocab_size,
                               transposed=transposed))
    if weights is not None:
        losses = losses * weights.astype(losses.dtype)
    return losses


# --------------------------------------------------------------------------
# Fused flash attention
# --------------------------------------------------------------------------

def attn_route(q_shape, kv_shape, causal: bool = True,
               mode: str | None = None, q_sharding=None, kv_sharding=None,
               v_shape=None):
    """-> ("ref", None) | ("kernel", None | Plan).

    Callers with their own memory-safe jnp path (``models.layers``) take
    it on "ref"; ``flash_attention``'s built-in ref delegates back to the
    layer-level scan/chunked implementations.
    """
    return _route(mode, attn_supported(q_shape, kv_shape, causal, v_shape),
                  _attn_operands(q_sharding, kv_sharding, q_shape, kv_shape))


def _check_kv_len(causal: bool, kv_len):
    if causal and kv_len is not None:
        raise ValueError(
            "flash_attention: kv_len requires causal=False — the decode "
            "window is non-causal within the filled cache (neither the "
            "kernels nor the reference implement a causal-over-fill mask, "
            "and silently picking one would differ between routes)")


def _attn_ref(q, k, v, *, scale, causal: bool = True, kv_len=None,
              segments=None):
    """jnp fallback: the layer-level reference implementations.

    The blockwise ``lax.scan`` (bitwise pre-kernel path) for plain
    causal/cross attention — segment-masked through the same scan's
    ``MaskSpec`` when packed segment ids are live — and
    ``chunked_q_attention`` when a ``kv_len`` cache bound is involved.
    GQA kv is repeated here — exactly what the kernels avoid.
    """
    from repro.models import layers as L  # lazy: avoids an import cycle
    _check_kv_len(causal, kv_len)
    if kv_len is not None:
        return L.chunked_q_attention(
            q, k, v, L.largest_divisor(q.shape[1], 128), scale,
            kv_len=kv_len)
    H, K = q.shape[2], k.shape[2]
    if K != H:
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
    if segments is not None:
        spec = mask_spec(q.shape[1], k.shape[1], causal=causal,
                         segments=segments)
        block = L.largest_divisor(q.shape[1], 128)
        return L.masked_flash_attention(q, k, v, segments[0], segments[1],
                                        block, scale, spec)
    return L.flash_attention(q, k, v, 128, scale, causal)


@functools.lru_cache(maxsize=None)
def _attn_fused(scale: float, spec: MaskSpec, interp: bool, plan, block):
    """Build the custom_vjp'd fused attention for one static configuration.

    Cached so repeated traces reuse one custom_vjp object. ``spec`` is the
    (hashable) :class:`MaskSpec`; ``plan`` a Plan or None; ``block`` a
    (bq, bk) tuple or None. The traced mask operands — the ``kv_len``
    scalar and the (B, S)/(B, T) segment ids — ride along as custom_vjp
    arguments with float0 cotangents (index data, like xent's labels);
    when the spec declares no segments the pair is a zero-size dummy the
    kernels never read.
    """
    def _segs(qs, ks):
        return (qs, ks) if spec.has_segments else None

    def _fwd_parts(q, k, v, kl, qs, ks):
        def body(qb, kb, vb, kl_, qsb, ksb):
            return _ak.mha_fwd(qb, kb, vb, kl_, scale=scale, spec=spec,
                               segments=_segs(qsb, ksb), block=block,
                               interpret=interp)

        return _shard(body, plan, (QKV, QKV, QKV, (), SEG, SEG),
                      [QKV, LSE])(q, k, v, kl, qs, ks)

    def _bwd_parts(q, k, v, kl, qs, ks, out, lse, do):
        def body(qb, kb, vb, kl_, qsb, ksb, ob, lseb, dob):
            delta = jnp.swapaxes(
                jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32),
                        -1), 1, 2)
            segs = _segs(qsb, ksb)
            dq = _ak.mha_bwd_dq(qb, kb, vb, dob, lseb, delta, kl_,
                                scale=scale, spec=spec, segments=segs,
                                block=block, interpret=interp)
            dk, dv = _ak.mha_bwd_dkv(qb, kb, vb, dob, lseb, delta, kl_,
                                     scale=scale, spec=spec, segments=segs,
                                     block=block, interpret=interp)
            return dq, dk, dv

        return _shard(body, plan,
                      (QKV, QKV, QKV, (), SEG, SEG, QKV, LSE, QKV),
                      [QKV, QKV, QKV])(q, k, v, kl, qs, ks, out, lse, do)

    @jax.custom_vjp
    def fused(q, k, v, kl, qs, ks):
        return _fwd_parts(q, k, v, kl, qs, ks)[0]

    def fwd(q, k, v, kl, qs, ks):
        out, lse = _fwd_parts(q, k, v, kl, qs, ks)
        return out, (q, k, v, kl, qs, ks, out, lse)

    def bwd(res, do):
        q, k, v, kl, qs, ks, out, lse = res
        dq, dk, dv = _bwd_parts(q, k, v, kl, qs, ks, out, lse, do)
        f0 = lambda x: np.zeros(x.shape, jax.dtypes.float0)
        return dq, dk, dv, f0(kl), f0(qs), f0(ks)

    fused.defvjp(fwd, bwd)
    return fused


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    scale: float, causal: bool = True, kv_len=None,
                    segments=None, block=None, q_sharding=None,
                    kv_sharding=None, mode: str | None = None):
    """Fused blockwise attention (custom_vjp, see module doc).

    q (B, S, H, hd); k (B, T, K, hd), v (B, T, K, hdv) with H % K == 0 —
    the GQA repeat is never materialized (dK/dV come back in kv's own
    (B, T, K, *) layout). ``causal`` masks rectangularly (query i sees
    keys <= T-S+i); ``kv_len`` (traced scalar) bounds the key positions
    for decode over a partially filled cache; ``segments`` — a
    ((B, S), (B, T)) int32 pair — forbids attention across packed-document
    boundaries (ids must match; pad id 0 is its own island). Returns
    (B, S, H, hdv) in q's dtype. ``kv_len`` is only meaningful without
    causal masking (causal + kv_len raises — no route implements that
    combination) and mutually exclusive with ``segments`` (packed batches
    have no cache-fill bound).
    """
    _check_kv_len(causal, kv_len)
    spec = mask_spec(q.shape[1], k.shape[1], causal=causal, kv_len=kv_len,
                     segments=segments)

    def kernel(plan, mode):
        kl = jnp.asarray(k.shape[1] if kv_len is None else kv_len, jnp.int32)
        if segments is not None:
            qs = segments[0].astype(jnp.int32)
            ks = segments[1].astype(jnp.int32)
        else:  # fixed custom_vjp arity: zero-size stand-ins, never read
            qs = ks = jnp.zeros((q.shape[0], 0), jnp.int32)
        return _attn_fused(float(scale), spec, use_interpret(mode), plan,
                           tuple(block) if block is not None else None)(
                               q, k, v, kl, qs, ks)

    return _run(
        "flash_attention", mode,
        attn_supported(q.shape, k.shape, causal, v.shape),
        _attn_operands(q_sharding, kv_sharding, q.shape, k.shape), kernel,
        lambda mode: _attn_ref(q, k, v, scale=scale, causal=causal,
                               kv_len=kv_len, segments=segments))


# Introspection: op name -> (fused entry point, jnp reference). Tests iterate
# this to keep the parity matrix and the dispatch table in sync.
REGISTRY = {
    "normalize": (normalize, _cref.normalize),
    "norm_update": (norm_update, _cref.norm_update),
    "momentum_norm": (momentum_norm, _href.momentum_norm),
    "momentum_norm_update": (momentum_norm_update, _href.momentum_norm_update),
    "xent_loss": (xent_loss, _xent_ref),
    "flash_attention": (flash_attention, _attn_ref),
}
