"""Serving-step factories: batched prefill + single-token decode with a
persistent sharded KV/SSM cache.
"""
from __future__ import annotations

import time
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models import (forward, init_cache, logits_from_hidden)
from repro.models.sharding import Rules


class ServeState(NamedTuple):
    cache: Any
    index: jnp.ndarray  # current cache fill (next write position)


def make_prefill_step(cfg, max_seq: int, rules: Optional[Rules] = None,
                      mesh=None):
    """prefill(params, tokens[, image_embeds]) -> (ServeState, last_logits).

    The returned cache is sized ``max_seq`` so decode can continue in
    place. ``mesh`` reaches the attention layers for the mesh-aware fused
    flash kernels (feature-detected plumbing, like the trainer's loss).
    """
    rules = rules or Rules(cfg.rule_overrides)

    def prefill_step(params, tokens, image_embeds=None):
        B = tokens.shape[0]
        S = tokens.shape[-1]
        cache = init_cache(cfg, B, max_seq)
        hidden, pre_cache, _ = forward(params, cfg, tokens,
                                       image_embeds=image_embeds,
                                       mode="prefill", cache=cache,
                                       rules=rules, mesh=mesh)

        def merge(full, pre):
            if full.shape == pre.shape:
                return pre.astype(full.dtype)
            return jax.lax.dynamic_update_slice(
                full, pre.astype(full.dtype), (0,) * full.ndim)

        cache = jax.tree_util.tree_map(merge, cache, pre_cache)
        logits = logits_from_hidden(params, cfg, hidden[:, -1:], rules=rules)
        return ServeState(cache, jnp.asarray(S, jnp.int32)), logits

    return prefill_step


def make_decode_step(cfg, rules: Optional[Rules] = None, mesh=None):
    """decode(params, state, tokens) -> (state, logits). tokens (B, 1).

    Single-device decode routes attention over the cache through the
    fused flash kernels (the ``kv_len`` bound skips unfilled cache
    tiles); under a mesh the sequence-sharded cache falls back to the
    GSPMD-partitioned chunked path (see ``layers.decode_attention``).
    """
    rules = rules or Rules(cfg.rule_overrides)

    def decode_step(params, state: ServeState, tokens, image_embeds=None):
        hidden, cache, _ = forward(params, cfg, tokens,
                                   image_embeds=image_embeds, mode="decode",
                                   cache=state.cache, cache_index=state.index,
                                   rules=rules, mesh=mesh)
        logits = logits_from_hidden(params, cfg, hidden, rules=rules)
        return ServeState(cache, state.index + tokens.shape[-1]), logits

    return decode_step


def _quantile(sorted_vals: list, q: float) -> float:
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, max(0, round(q * (len(sorted_vals) - 1))))
    return sorted_vals[i]


def greedy_generate(cfg, params, prompt, n_steps: int, max_seq: int,
                    rules: Optional[Rules] = None, mesh=None, logger=None):
    """Greedy generation loop (prefill + jitted decode steps).

    ``logger``: an optional :class:`repro.obs.MetricsLogger`. Prefill and
    decode latencies then flow through the same metrics plane as training:
    one ``kind="serve"`` record per phase — prefill wall time + prompt
    tokens/s, and the decode latency distribution (mean/p50/p99 per token,
    tokens/s) over the generated steps. Timings block on device results
    (``block_until_ready``), so they measure real step latency, not
    dispatch time; the first decode step includes compile and is also
    reported separately (``compile_ms``).
    """
    prefill = jax.jit(make_prefill_step(cfg, max_seq, rules, mesh=mesh))
    decode = jax.jit(make_decode_step(cfg, rules, mesh=mesh))
    t0 = time.perf_counter()
    state, logits = prefill(params, prompt)
    if logger is not None:
        jax.block_until_ready(logits)
        dt = time.perf_counter() - t0
        n_prompt = int(prompt.shape[0]) * int(prompt.shape[-1])
        logger.log("serve", 0, phase="prefill", batch=int(prompt.shape[0]),
                   prompt_tokens=n_prompt, latency_ms=1e3 * dt,
                   tokens_per_s=n_prompt / max(dt, 1e-9))
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    out = [tok]
    lat: list = []
    for _ in range(n_steps - 1):
        t0 = time.perf_counter()
        state, logits = decode(params, state, tok)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        if logger is not None:
            jax.block_until_ready(tok)
            lat.append(time.perf_counter() - t0)
        out.append(tok)
    if logger is not None and lat:
        # first decode step pays compile; report it apart from the steady
        # distribution so p50/p99 describe serving, not tracing
        steady = sorted(lat[1:]) if len(lat) > 1 else sorted(lat)
        logger.log("serve", 0, phase="decode", batch=int(prompt.shape[0]),
                   decode_steps=len(lat), compile_ms=1e3 * lat[0],
                   mean_ms=1e3 * sum(steady) / len(steady),
                   p50_ms=1e3 * _quantile(steady, 0.50),
                   p99_ms=1e3 * _quantile(steady, 0.99),
                   tokens_per_s=int(prompt.shape[0]) * len(steady)
                   / max(sum(steady), 1e-9))
    return jnp.concatenate(out, axis=1)
