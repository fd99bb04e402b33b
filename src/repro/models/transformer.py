"""Super-block assembly: every architecture is a sequence of scanned
segments of homogeneous super-blocks (see config.ModelConfig.segments).

Super-block kinds:
  dense : [self-attn + SwiGLU]
  moe   : [self-attn + MoE]
  ssm   : [mamba2]                      (attention-free; no FFN, as mamba2)
  vlm   : [cross-attn + MLP] + (N-1) x [self-attn + MLP]
  hybrid: [attn + MLP] + 7 x [mamba + (MoE | MLP alternating)]   (jamba 1:7)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import layers as L
from .config import ModelConfig
from .sharding import Rules, shard


# --------------------------------------------------------------- sub-layers

def _sub_spec(cfg: ModelConfig, sub: str) -> dict:
    if sub == "attn":
        spec = (L.mla_spec(cfg) if cfg.attention_kind == "mla"
                else L.attn_spec(cfg))
        return {"norm": L.Spec((cfg.d_model,), ("norm",), "ones"), **spec}
    if sub == "cross":
        return {"norm": L.Spec((cfg.d_model,), ("norm",), "ones"),
                **L.attn_spec(cfg, cross=True)}
    if sub == "mlp":
        return {"norm": L.Spec((cfg.d_model,), ("norm",), "ones"),
                **L.mlp_spec(cfg)}
    if sub == "moe":
        return {"norm": L.Spec((cfg.d_model,), ("norm",), "ones"),
                **L.moe_spec(cfg)}
    if sub == "mamba":
        return {"norm": L.Spec((cfg.d_model,), ("norm",), "ones"),
                **L.mamba_spec(cfg)}
    raise ValueError(sub)


def _apply_sub(sub: str, p: dict, cfg: ModelConfig, x, positions, rules: Rules,
               mode: str, cache, cache_index, image_embeds, mesh=None,
               segment_ids=None):
    """Pre-norm residual sub-layer. Returns (x, new_cache, aux).

    ``mesh`` rides along to the attention layers so the fused flash
    kernels can shard_map over the activation batch/head axes (the same
    feature-detected plumbing the fused LM-head loss uses).
    ``segment_ids`` (B, S) int32 — packed-document ids, consumed by the
    *self*-attention subs only (cross-attention keys are not packed and
    mamba's sequence mixing has no segment mask — packed batches are an
    attention-family format).

    The whole sub-layer (pre-norm, layer, residual add) runs under one
    named scope, the sub-layer kind (``attn``, ``cross``, ``mlp``, ``moe``,
    ``mamba``), so every architecture's trace is named the same way.
    """
    with jax.named_scope(sub):
        h = L.rmsnorm(x, p["norm"], cfg.rms_eps)
        aux = jnp.zeros((), jnp.float32)
        if sub == "attn":
            if cfg.attention_kind == "mla":
                y, cache = L.apply_mla_attention(p, cfg, h, positions, rules,
                                                 mode, cache, cache_index,
                                                 mesh=mesh,
                                                 segment_ids=segment_ids)
            else:
                y, cache = L.apply_attention(p, cfg, h, positions, rules,
                                             mode, cache, cache_index,
                                             mesh=mesh,
                                             segment_ids=segment_ids)
        elif sub == "cross":
            y, _ = L.apply_attention(p, cfg, h, positions, rules, mode="train",
                                     kv_source=image_embeds, causal=False,
                                     mesh=mesh)
        elif sub == "mlp":
            y = L.apply_mlp(p, cfg, h, rules)
        elif sub == "moe":
            y, aux = L.apply_moe(p, cfg, h, rules)
        elif sub == "mamba":
            y, cache = L.apply_mamba(p, cfg, h, rules, mode, cache)
        else:
            raise ValueError(sub)
        return x + y.astype(x.dtype), cache, aux


# ------------------------------------------------------------- super-blocks

def superblock_layout(cfg: ModelConfig, kind: str) -> tuple:
    """Ordered (name, sub_kind) pairs of one super-block."""
    if kind == "dense":
        return (("attn", "attn"), ("ffn", "mlp"))
    if kind == "moe":
        out = [("attn", "attn"), ("ffn", "moe")]
        return tuple(out)
    if kind == "ssm":
        return (("mamba", "mamba"),)
    if kind == "vlm":
        out = [("cross", "cross"), ("cross_ffn", "mlp")]
        for i in range(1, cfg.cross_attn_every):
            out += [(f"attn{i}", "attn"), (f"ffn{i}", "mlp")]
        return tuple(out)
    if kind == "hybrid":
        out = [("attn", "attn"), ("ffn0", "mlp")]
        for i in range(1, cfg.hybrid_period):
            out.append((f"mamba{i}", "mamba"))
            out.append((f"ffn{i}", "moe" if i % 2 == 1 else "mlp"))
        return tuple(out)
    raise ValueError(kind)


def superblock_spec(cfg: ModelConfig, kind: str) -> dict:
    return {name: _sub_spec(cfg, sub) for name, sub in superblock_layout(cfg, kind)}


def _needs_cache(sub: str) -> bool:
    return sub in ("attn", "mamba")


def superblock_cache(cfg: ModelConfig, kind: str, batch: int, max_seq: int,
                     dtype) -> dict:
    """Zero cache for one super-block (decode/prefill)."""
    out = {}
    for name, sub in superblock_layout(cfg, kind):
        if sub == "attn":
            if cfg.attention_kind == "mla":
                out[name] = {
                    "ckv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
                    "krope": jnp.zeros((batch, max_seq, 1, cfg.qk_rope_dim), dtype),
                }
            else:
                kshape = (batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
                out[name] = {"k": jnp.zeros(kshape, dtype),
                             "v": jnp.zeros(kshape, dtype)}
        elif sub == "mamba":
            out[name] = {
                "conv": jnp.zeros((batch, cfg.ssm_dconv - 1, cfg.conv_dim), dtype),
                "ssm": jnp.zeros((batch, cfg.ssm_nheads, cfg.ssm_state,
                                  cfg.ssm_headdim), jnp.float32),
            }
    return out


def cache_axes(cfg: ModelConfig, kind: str) -> dict:
    out = {}
    for name, sub in superblock_layout(cfg, kind):
        if sub == "attn":
            if cfg.attention_kind == "mla":
                out[name] = {"ckv": (None, "cache_batch", "cache_seq", "cache_kv"),
                             "krope": (None, "cache_batch", "cache_seq", None, None)}
            else:
                ax = (None, "cache_batch", "cache_seq", None, "cache_kv")
                out[name] = {"k": ax, "v": ax}
        elif sub == "mamba":
            out[name] = {"conv": (None, "cache_batch", None, "ssm_inner"),
                         "ssm": (None, "cache_batch", None, None, None)}
    return out


def apply_superblock(kind: str, cfg: ModelConfig, params: dict, x, positions,
                     rules: Rules, mode: str, cache: Optional[dict],
                     cache_index, image_embeds, mesh=None, segment_ids=None):
    new_cache = dict(cache) if cache is not None else None
    aux_total = jnp.zeros((), jnp.float32)
    for name, sub in superblock_layout(cfg, kind):
        sub_cache = cache.get(name) if (cache is not None and _needs_cache(sub)) else None
        x, sub_cache, aux = _apply_sub(sub, params[name], cfg, x, positions,
                                       rules, mode, sub_cache, cache_index,
                                       image_embeds, mesh=mesh,
                                       segment_ids=segment_ids)
        if new_cache is not None and _needs_cache(sub) and sub_cache is not None:
            new_cache[name] = sub_cache
        aux_total = aux_total + aux
    # scan-carry sharding: lets the dry-run store saved residuals TP-sharded
    x = shard(x, ("act_batch", "act_seq", "act_residual"), rules)
    return x, new_cache, aux_total


# ------------------------------------------------------------ scanned stack

def _remat_policy(cfg: ModelConfig):
    if cfg.remat == "none":
        return None
    if cfg.remat == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable


def apply_segment(kind: str, n_blocks: int, cfg: ModelConfig, stacked: dict,
                  x, positions, rules: Rules, mode: str, cache, cache_index,
                  image_embeds, mesh=None, segment_ids=None):
    """Scan ``n_blocks`` super-blocks with stacked params (+ stacked cache).

    ``segment_ids`` (packed-document masking) is closed over, not scanned:
    it is the same (B, S) operand for every block. (Not to be confused
    with the layer-group "segments" this function scans over.)
    """

    def block(x, inputs):
        p, c = inputs
        x, c, aux = apply_superblock(kind, cfg, p, x, positions, rules, mode,
                                     c, cache_index, image_embeds, mesh=mesh,
                                     segment_ids=segment_ids)
        return x, (c, aux)

    policy = _remat_policy(cfg)
    if policy is not None:
        block = jax.checkpoint(block, policy=policy, prevent_cse=False)

    if cache is None:
        xs = (stacked, None)
        # scan needs a pytree of equal-length leading axes; replace None cache
        # with per-block empty dicts
        xs = (stacked, jnp.zeros((n_blocks, 0)))

        def block_nc(x, inputs):
            p, _ = inputs
            x, _, aux = apply_superblock(kind, cfg, p, x, positions, rules,
                                         mode, None, cache_index,
                                         image_embeds, mesh=mesh,
                                         segment_ids=segment_ids)
            return x, aux

        body = jax.checkpoint(block_nc, policy=policy, prevent_cse=False) \
            if policy is not None else block_nc
        x, auxs = jax.lax.scan(body, x, xs)
        return x, None, jnp.sum(auxs)

    x, (new_cache, auxs) = jax.lax.scan(block, x, (stacked, cache))
    return x, new_cache, jnp.sum(auxs)
