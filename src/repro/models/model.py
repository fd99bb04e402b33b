"""Top-level model API: init / forward / loss / caches / logical axes.

Parameter tree layout (labels drive the SCALE optimizer branches):

    {"tok_embed": {"w"},                  # 'first' group
     "segments": {"seg<i>_<kind>": {...stacked super-block params...}},
     "final_norm": {"s"},
     "lm_head": {"w"}}                    # 'last' group (momentum)

With ``cfg.tie_embeddings`` the ``lm_head`` entry does not exist: the head
is ``tok_embed.w`` read transposed ((V, D) storage, (D, V) use; audio:
(C, V, D) vs (C, D, V)). :func:`head_weight` is the single accessor — the
serving/loss paths either fold the transpose into their contraction or
dispatch the transposed-w fused kernels, and the optimizer must label the
tied matrix ``last`` (``LabelRules.tied()``) so it keeps head momentum.
"""
from __future__ import annotations

import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from . import layers as L
from . import transformer as T
from .config import ModelConfig
from .sharding import Rules, shard

_is_spec = lambda x: isinstance(x, L.Spec)


# ----------------------------------------------------------------- spec tree

def _embed_spec(cfg: ModelConfig) -> dict:
    V, D = cfg.padded_vocab, cfg.d_model
    if cfg.family == "audio":
        return {"w": L.Spec((cfg.n_codebooks, V, D), (None, "vocab", "embed"))}
    return {"w": L.Spec((V, D), ("vocab", "embed"))}


def _head_spec(cfg: ModelConfig) -> dict:
    V, D = cfg.padded_vocab, cfg.d_model
    if cfg.family == "audio":
        return {"w": L.Spec((cfg.n_codebooks, D, V), (None, "embed", "vocab"))}
    return {"w": L.Spec((D, V), ("embed", "vocab"))}


def _stacked(spec_tree: dict, n: int) -> dict:
    return jax.tree_util.tree_map(
        lambda s: L.Spec((n,) + tuple(s.shape), (None,) + tuple(s.axes), s.init),
        spec_tree, is_leaf=_is_spec)


def model_spec(cfg: ModelConfig) -> dict:
    segs = {}
    for i, (kind, n) in enumerate(cfg.segments):
        segs[f"seg{i}_{kind}"] = _stacked(T.superblock_spec(cfg, kind), n)
    out = {
        "tok_embed": _embed_spec(cfg),
        "segments": segs,
        "final_norm": {"s": L.Spec((cfg.d_model,), ("norm",), "ones")},
    }
    if not cfg.tie_embeddings:
        # tied models have no separate head: tok_embed.w is read transposed
        out["lm_head"] = _head_spec(cfg)
    if cfg.pos_embed == "learned":
        out["pos_embed"] = {"w": L.Spec((cfg.max_position, cfg.d_model),
                                        (None, "embed"))}
    return out


def param_shapes(cfg: ModelConfig) -> dict:
    return L.shapes_from_spec(model_spec(cfg))


def param_logical_axes(cfg: ModelConfig) -> dict:
    return L.axes_from_spec(model_spec(cfg))


def count_params(shapes, cfg: Optional[ModelConfig] = None,
                 active_only: bool = False) -> int:
    """Total (or MoE-active) parameter count from a shapes tree."""
    import numpy as np
    if not active_only or cfg is None or not cfg.n_experts:
        return int(sum(np.prod(s) for s in jax.tree_util.tree_leaves(
            shapes, is_leaf=lambda x: isinstance(x, tuple))))
    spec = model_spec(cfg)
    total = 0
    for s in jax.tree_util.tree_leaves(spec, is_leaf=_is_spec):
        n = int(np.prod(s.shape))
        if "experts" in s.axes:
            n = n * cfg.top_k // cfg.n_experts
        total += n
    return int(total)


def init_params(key, cfg: ModelConfig) -> dict:
    spec = model_spec(cfg)
    dtype = cfg.jdtype
    flat = {}
    keys = jax.random.split(key, 3 + len(cfg.segments))
    flat["tok_embed"] = L.init_from_spec(keys[0], spec["tok_embed"], dtype)
    flat["final_norm"] = L.init_from_spec(keys[1], spec["final_norm"], dtype)
    if "lm_head" in spec:  # untied only; keys[2] stays reserved so the
        # tied/untied trees share every other leaf's init stream
        flat["lm_head"] = L.init_from_spec(keys[2], spec["lm_head"], dtype)
    if "pos_embed" in spec:
        flat["pos_embed"] = L.init_from_spec(
            jax.random.fold_in(key, 99), spec["pos_embed"], dtype)
    segs = {}
    for i, (kind, n) in enumerate(cfg.segments):
        sb_spec = T.superblock_spec(cfg, kind)
        ks = jax.random.split(keys[3 + i], n)
        segs[f"seg{i}_{kind}"] = jax.vmap(
            lambda k: L.init_from_spec(k, sb_spec, dtype))(ks)
    flat["segments"] = segs
    return flat


# -------------------------------------------------------------------- cache

def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None) -> dict:
    dtype = dtype or cfg.jdtype
    out = {}
    for i, (kind, n) in enumerate(cfg.segments):
        one = T.superblock_cache(cfg, kind, batch, max_seq, dtype)
        out[f"seg{i}_{kind}"] = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (n,) + x.shape).copy(), one)
    return out


def cache_logical_axes(cfg: ModelConfig) -> dict:
    return {f"seg{i}_{kind}": T.cache_axes(cfg, kind)
            for i, (kind, n) in enumerate(cfg.segments)}


# ------------------------------------------------------------------ forward

def forward(params, cfg: ModelConfig, tokens, *, image_embeds=None,
            mode: str = "train", cache=None, cache_index=None,
            rules: Optional[Rules] = None, mesh=None, positions=None,
            segment_ids=None):
    """Run the backbone. Returns (hidden, new_cache, aux_loss).

    ``mesh`` (optional, threaded from the trainer/serving factories the
    same way ``loss_fn`` receives it) reaches the attention layers so the
    fused flash kernels can shard_map over the batch/head mesh axes.
    ``positions``/``segment_ids`` (both (B, S) int32, optional) are the
    packed-document operands: within-document positions (RoPE/learned
    positions restart at every document boundary) and the per-token
    document ids the attention mask keeps separated (pad id 0). When
    ``positions`` is None the usual 0..S-1 (or cache-offset) ramp is used.
    """
    rules = rules or Rules(cfg.rule_overrides)
    S = tokens.shape[-1]
    if positions is None:
        if mode == "decode":
            positions = cache_index + jnp.arange(S)
        else:
            positions = jnp.arange(S)
    # one named scope per layer (embed / layers / final_norm here, one per
    # sub-layer kind in transformer._apply_sub, head in loss_fn): the
    # backward pass and the rematerialised forward inherit the names, so a
    # device trace charges each op to its layer
    with jax.named_scope("embed"):
        ew = params["tok_embed"]["w"]
        if cfg.family == "audio":
            # tokens (B, n_codebooks, S): sum codebook embeddings
            x = sum(jnp.take(ew[c], tokens[:, c], axis=0)
                    for c in range(cfg.n_codebooks))
        else:
            x = jnp.take(ew, tokens, axis=0)
        x = shard(x, ("act_batch", "act_seq", "act_embed"), rules)
        if cfg.pos_embed == "learned":
            x = x + jnp.take(params["pos_embed"]["w"], positions, axis=0)

    new_cache = {} if cache is not None else None
    aux = jnp.zeros((), jnp.float32)
    for i, (kind, n) in enumerate(cfg.segments):
        name = f"seg{i}_{kind}"
        seg_cache = cache[name] if cache is not None else None
        with jax.named_scope("layers"):
            x, seg_cache, seg_aux = T.apply_segment(
                kind, n, cfg, params["segments"][name], x, positions, rules,
                mode, seg_cache, cache_index, image_embeds, mesh=mesh,
                segment_ids=segment_ids)
        if new_cache is not None:
            new_cache[name] = seg_cache
        aux = aux + seg_aux
    with jax.named_scope("final_norm"):
        x = L.rmsnorm(x, params["final_norm"]["s"], cfg.rms_eps)
    return x, new_cache, aux


def head_weight(params, cfg: ModelConfig):
    """(w, transposed): the logit-producing matrix and its storage layout.

    Untied: ``params["lm_head"]["w"]`` in (D, V) use layout ((C, D, V)
    audio), ``transposed=False``. Tied: ``params["tok_embed"]["w"]`` in
    (V, D) storage ((C, V, D) audio), ``transposed=True`` — consumers fold
    the transpose into their contraction (reference paths) or dispatch the
    transposed-w kernels; the gradient then lands directly on the embedding
    in its storage layout.
    """
    if cfg.tie_embeddings:
        return params["tok_embed"]["w"], True
    return params["lm_head"]["w"], False


def logits_from_hidden(params, cfg: ModelConfig, hidden,
                       rules: Optional[Rules] = None):
    """Full-vocab logits (serving). hidden (B,S,D) -> (B,S,V[,per codebook])."""
    rules = rules or Rules(cfg.rule_overrides)
    w, tied = head_weight(params, cfg)
    if cfg.family == "audio":
        out = jnp.einsum("bsd,cvd->bcsv" if tied else "bsd,cdv->bcsv",
                         hidden, w)
    elif tied:
        # XLA folds the transpose into the dot (no materialized w.T)
        out = jnp.einsum("bsd,vd->bsv", hidden, w)
    else:
        out = hidden @ w
    out = _mask_pad_vocab(out, cfg)
    return shard(out, ("act_batch", "act_seq", "act_vocab"), rules)


def _mask_pad_vocab(logits, cfg: ModelConfig):
    if cfg.padded_vocab == cfg.vocab_size:
        return logits
    neg = jnp.asarray(-1e9, logits.dtype)
    idx = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return jnp.where(idx < cfg.vocab_size, logits, neg)


def _pick_chunk(S: int, target: int) -> int:
    """Largest divisor of S that is <= min(target, S).

    Delegates the divisor search to ``layers.largest_divisor`` (shared
    with the attention tile fallback ``layers._pick_block``) — and stays
    *audible*: a prime or awkward S used to silently degrade to chunk=1,
    turning the loss scan into a per-token loop with an (S,)-step trace.
    """
    target = min(target, S)
    best = L.largest_divisor(S, target)
    if best * 2 < target:
        warnings.warn(
            f"lm_loss: seq_len={S} has no divisor in ({target // 2}, "
            f"{target}]; loss chunk shrinks to {best} ({S // best} scan "
            f"steps). Pick a seq_len with a divisor near loss_chunk="
            f"{target} to keep the loss scan short.", stacklevel=3)
    return best


def _xent_chunk(h_chunk, w, labels_chunk, cfg: ModelConfig, rules: Rules,
                weights_chunk=None):
    """h (B,c,D), w (D,V), labels (B,c) -> (sum_loss, sum_weight).

    ``weights_chunk`` (optional, (B,c) f32) scales each token's loss; the
    effective weight is 0 wherever the label is masked (-1) *or* the
    weight is 0 — the returned sum_weight counts exactly the tokens that
    contributed, so the caller's mean divides by the right denominator.
    """
    logits = (h_chunk @ w).astype(jnp.float32)
    logits = _mask_pad_vocab(logits, cfg)
    logits = shard(logits, ("act_batch", "act_seq", "act_vocab"), rules)
    lse = jax.nn.logsumexp(logits, axis=-1)
    lab = jnp.clip(labels_chunk, 0)
    ll = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
    weight = (labels_chunk >= 0).astype(jnp.float32)
    if weights_chunk is not None:
        weight = weight * weights_chunk.astype(jnp.float32)
    return jnp.sum((lse - ll) * weight), jnp.sum(weight)


def lm_loss(params, cfg: ModelConfig, hidden, labels,
            rules: Optional[Rules] = None, mesh=None, weights=None):
    """Cross-entropy over the LM head without full-sequence logits.

    Two implementations, selected by ``repro.kernels.dispatch.xent_route``:

    * **fused** (default where covered): the Pallas blockwise kernels
      behind ``dispatch.xent_loss`` — logits live only as a
      (token-tile, vocab-tile) VMEM block, the backward emits dH/dW from
      the same tiles (custom_vjp). ``mesh`` (passed by the trainer, which
      feature-detects this kwarg) lets the dispatch shard_map the kernels
      using the head's ("embed", "vocab") and the activations'
      ("act_batch", ...) logical axes.
    * **chunked jnp scan** (``REPRO_FUSED=off`` or uncovered
      shape/sharding): the original reference path — (chunk, V) f32
      logit blocks per scan step, bitwise-stable across PRs.

    labels: (B,S) int32, -1 = masked; audio: (B, n_codebooks, S).
    ``weights`` (optional, (B,S) f32 — packed-document loss weights)
    scales each token's loss; the mean divides by the summed *effective*
    weight, counting only tokens with label >= 0 AND weight > 0 (an
    all-masked batch returns loss 0, not a division by a clamped fake
    denominator — see the weight handling below). Audio heads do not take
    weights (packing is a text-family format).
    Returns (mean_loss, total_weight).

    Tied heads (``cfg.tie_embeddings``): ``w`` is the (V, D) embedding; the
    fused route dispatches the transposed-w kernel variants (dW lands in
    (V, D), directly on the embedding) and the scan fallback contracts
    ``tok_embed.w.T`` chunk by chunk. The head's sharding is derived from
    the storage layout's ("vocab", "embed") logical axes — the same
    physical axes as the untied head's ("embed", "vocab"), swapped.
    """
    rules = rules or Rules(cfg.rule_overrides)
    if weights is not None and cfg.family == "audio":
        raise ValueError("lm_loss: per-token weights are not supported for "
                         "the audio multi-codebook head")
    w, tied = head_weight(params, cfg)
    B, S = hidden.shape[0], hidden.shape[1]

    from repro.kernels import dispatch as _kd  # lazy: optional kernel layer
    head_shape = tuple(w.shape[-2:])
    h_sh = w_sh = None
    if mesh is not None:
        h_sh = rules.sharding(("act_batch", "act_seq", "act_embed"), mesh,
                              hidden.shape)
        w_sh = rules.sharding(("vocab", "embed") if tied
                              else ("embed", "vocab"), mesh, head_shape)
    # resolve REPRO_FUSED once and thread it through: the branch taken
    # here and the route inside xent_loss must come from the same read
    mode = _kd.resolve_mode()
    route, _ = _kd.xent_route(hidden.shape, head_shape, mode,
                              h_sharding=h_sh, w_sharding=w_sh,
                              transposed=tied)
    # mean = sum / effective weight; a zero effective weight (all tokens
    # masked) yields loss 0 via a neutral denominator — NOT max(ws, 1),
    # which silently deflated fractional-weight sums in (0, 1)
    _mean = lambda ls, ws: ls / jnp.where(ws > 0, ws, 1.0)

    if route == "kernel":
        def head_loss_sums(wh, labs):
            losses = _kd.xent_loss(hidden, wh, labs,
                                   vocab_size=cfg.vocab_size, mode=mode,
                                   weights=weights,
                                   h_sharding=h_sh, w_sharding=w_sh,
                                   transposed=tied)
            if weights is not None:
                ws = jnp.sum(jnp.where(labs >= 0, weights, 0.0))
            else:
                ws = jnp.sum((labs >= 0).astype(jnp.float32))
            return jnp.sum(losses), ws

        if cfg.family == "audio":
            tot_l = tot_w = 0.0
            for c in range(cfg.n_codebooks):
                ls, ws = head_loss_sums(w[c], labels[:, c])
                tot_l, tot_w = tot_l + ls, tot_w + ws
            return _mean(tot_l, tot_w), tot_w
        ls, ws = head_loss_sums(w, labels)
        return _mean(ls, ws), ws

    chunk = _pick_chunk(S, cfg.loss_chunk)
    nch = S // chunk

    def per_head(wh, labs):
        if tied:
            # chunked scan over tok_embed.w.T: the transpose is lazy and
            # fuses into each chunk's dot; grads land on the (V, D) storage
            wh = jnp.swapaxes(wh, -1, -2)

        def body(carry, i):
            s0 = i * chunk
            h_c = jax.lax.dynamic_slice_in_dim(hidden, s0, chunk, 1)
            l_c = jax.lax.dynamic_slice_in_dim(labs, s0, chunk, 1)
            w_c = None if weights is None else \
                jax.lax.dynamic_slice_in_dim(weights, s0, chunk, 1)
            ls, ws = _xent_chunk(h_c, wh, l_c, cfg, rules, weights_chunk=w_c)
            return (carry[0] + ls, carry[1] + ws), None

        (ls, ws), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            jnp.arange(nch))
        return ls, ws

    if cfg.family == "audio":
        tot_l = tot_w = 0.0
        for c in range(cfg.n_codebooks):
            ls, ws = per_head(w[c], labels[:, c])
            tot_l, tot_w = tot_l + ls, tot_w + ws
        return _mean(tot_l, tot_w), tot_w
    ls, ws = per_head(w, labels)
    return _mean(ls, ws), ws


def loss_fn(params, cfg: ModelConfig, batch: dict, aux_coef: float = 0.01,
            rules: Optional[Rules] = None, mesh=None):
    """Full training loss. batch: tokens, labels, [image_embeds],
    [positions, segment_ids, loss_weights] (packed-document batches).

    ``mesh`` is forwarded to :func:`lm_loss` for the mesh-aware fused
    cross-entropy AND to :func:`forward` for the mesh-aware fused
    attention; callers (the trainer) feature-detect this kwarg. Packed
    batches (``data.pipeline`` with ``pack_documents``) carry
    within-document positions, the segment ids the attention mask keeps
    separated, and per-token loss weights — all picked up here by key.
    """
    hidden, _, aux = forward(params, cfg, batch["tokens"],
                             image_embeds=batch.get("image_embeds"),
                             mode="train", rules=rules, mesh=mesh,
                             positions=batch.get("positions"),
                             segment_ids=batch.get("segment_ids"))
    with jax.named_scope("head"):
        loss, weight = lm_loss(params, cfg, hidden, batch["labels"],
                               rules=rules, mesh=mesh,
                               weights=batch.get("loss_weights"))
    total = loss + aux_coef * aux
    return total, {"loss": loss, "aux": aux, "weight": weight}
