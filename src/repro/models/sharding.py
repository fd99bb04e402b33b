"""Logical-axis sharding (MaxText-style rules table).

Every parameter and annotated activation carries a tuple of logical axis
names; ``RULES`` maps each name to zero or more mesh axes. Mapping is
mesh-aware: rules referencing axes absent from the current mesh are dropped,
and a dim is only sharded if its size is divisible by the product of the
mapped mesh axis sizes (otherwise it is left replicated) — this is what makes
the same model lower on (data, model), (pod, data, model) and single-device
CPU meshes without per-mesh configs.

Tied LM heads: a ``tie_embeddings`` model stores the head as the embedding,
logical axes ("vocab", "embed"), where the untied head is ("embed", "vocab").
Under the default rules both map to the same physical pair — vocab -> TP
("model"), embed -> FSDP ("data") — just with the dims swapped, so the fused
xent/optimizer shard plans swap their psum/gather axes accordingly (the
vocab-axis psum of the loss reduces dim 0 of the tied matrix, and its FSDP
embed gather is dim 1; see ``repro.kernels.dispatch``).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Default logical -> mesh-axis rules. Weights: 2-D sharded (FSDP over "data"
# x TP over "model"). Activations: batch over (pod, data), model-parallel
# features over "model".
DEFAULT_RULES: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    # --- weight axes ---
    ("embed", ("data",)),          # contracting/model dim of weights -> FSDP
    ("mlp", ("model",)),           # ffn hidden -> TP
    ("heads", ("model",)),         # flattened q heads*head_dim -> TP
    ("kv", ("model",)),            # flattened kv heads*head_dim -> TP
    ("vocab", ("model",)),         # vocab dim of embed/head -> TP
    ("experts", ("model",)),       # expert dim -> EP over model axis
    ("expert_mlp", ()),            # per-expert ffn dim (already EP-sharded)
    ("lora", ()),                  # MLA low-rank dims: replicated
    ("conv", ()),
    ("ssm_inner", ("model",)),     # mamba d_inner -> TP
    ("ssm_state", ()),
    ("norm", ()),
    # --- activation axes ---
    ("act_batch", ("pod", "data")),
    ("act_seq", ()),
    ("act_embed", ()),
    # scan-carry residual between layers; mapping this to ("model",) stores
    # the per-layer saved activations TP-sharded (sequence-parallel style)
    ("act_residual", ()),
    ("act_mlp", ("model",)),
    ("act_heads", ("model",)),
    ("act_kv", ("model",)),
    ("act_vocab", ("model",)),
    ("act_experts", ("model",)),
    # expert capacity rows shard over data: EP = experts x model, tokens x
    # data — without this every data-replica computes every expert's rows
    ("act_expert_cap", ("data",)),
    ("act_moe_group", ("pod", "data")),
    ("act_ssm_inner", ("model",)),
    ("act_ssm_heads", ("model",)),
    ("act_ssm_state", ()),
    # --- cache axes ---
    # KV caches shard over (batch x sequence): attention over the sharded T
    # becomes local partial-softmax + small lse all-reduces (no cache gather),
    # and head_dim stays whole so no score-sized partial-sum all-reduces.
    ("cache_batch", ("data",)),
    ("cache_seq", ("model",)),
    ("cache_kv", ()),
)


class Rules:
    def __init__(self, overrides: Sequence[Tuple[str, Tuple[str, ...]]] = ()):
        self._map = dict(DEFAULT_RULES)
        for k, v in overrides:
            self._map[k] = tuple(v) if v is not None else ()

    def spec(self, logical_axes: Sequence[Optional[str]], mesh: Mesh,
             shape: Optional[Sequence[int]] = None) -> P:
        """Build a PartitionSpec for ``logical_axes`` on ``mesh``.

        Divisibility-guarded: a dim whose size does not divide by the mapped
        mesh-axis product is replicated instead (prevents lowering failures
        for e.g. 8 kv heads on a 16-way model axis).
        """
        parts = []
        used = set()
        for i, ax in enumerate(logical_axes):
            if ax is None:
                parts.append(None)
                continue
            mesh_axes = tuple(a for a in self._map.get(ax, ())
                              if a in mesh.axis_names and a not in used)
            if not mesh_axes:
                parts.append(None)
                continue
            if shape is not None:
                k = int(np.prod([mesh.shape[a] for a in mesh_axes]))
                if shape[i] % k != 0:
                    # try a prefix of the mesh axes that divides
                    while mesh_axes:
                        k = int(np.prod([mesh.shape[a] for a in mesh_axes]))
                        if shape[i] % k == 0:
                            break
                        mesh_axes = mesh_axes[:-1]
                    if not mesh_axes:
                        parts.append(None)
                        continue
                    if shape[i] % int(np.prod([mesh.shape[a] for a in mesh_axes])) != 0:
                        parts.append(None)
                        continue
            used.update(mesh_axes)
            parts.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
        return P(*parts)

    def sharding(self, logical_axes, mesh, shape=None) -> NamedSharding:
        return NamedSharding(mesh, self.spec(logical_axes, mesh, shape))


def spec_mesh_axes(spec: P, ndim: int) -> Tuple[Tuple[str, ...], ...]:
    """Canonicalize a PartitionSpec to per-dim tuples of mesh axis names.

    Pads short specs with replicated dims, normalizes ``None`` -> ``()`` and
    single names -> 1-tuples. This is the form the fused-update dispatch
    consumes to decide which mesh axes a col/row norm must ``psum`` over
    (the axes sharding the reduce dim of the matrix).
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


def shard(x, logical_axes, rules: Rules, mesh: Optional[Mesh] = None):
    """Annotate an activation with its logical sharding (no-op off-mesh).

    Without an explicit ``mesh`` the mesh the caller traces under is used:
    ``jax.set_mesh`` outside jit, or ``jax.sharding.use_abstract_mesh``
    inside it (the trainer's step does the latter with its own mesh).
    """
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return x
    spec = rules.spec(logical_axes, mesh, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def tree_shardings(logical_tree, mesh: Mesh, rules: Rules, shape_tree=None):
    """Map a pytree of logical-axis tuples to NamedShardings."""
    is_axes = lambda x: isinstance(x, tuple) and all(
        (a is None or isinstance(a, str)) for a in x)
    if shape_tree is None:
        return jax.tree_util.tree_map(
            lambda ax: rules.sharding(ax, mesh), logical_tree, is_leaf=is_axes)
    return jax.tree_util.tree_map(
        lambda ax, s: rules.sharding(ax, mesh, tuple(s.shape) if hasattr(s, "shape") else tuple(s)),
        logical_tree, shape_tree, is_leaf=is_axes)
