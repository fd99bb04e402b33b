"""Fused vs chunked-jnp LM-head cross-entropy: HBM-byte accounting, peak
logit-activation bytes, kernel parity, and backend-appropriate timing.

Accounting model (one full loss+grad; be = element size of h/w, f32
intermediates 4 bytes). The jnp chunked path
(``models.model.lm_loss`` scan under full remat) per chunk of ``B*chunk``
tokens: the forward reads the h chunk and w and materializes + reads the
(B*chunk, V) f32 logit block (logsumexp assumed fused into one
write+read — best case for XLA); the backward recomputes the logits,
materializes dlogits (write+read), writes the dH chunk, and reads+writes
the f32 (D, V) dW accumulator the scan carries across every chunk. The
fused path (:mod:`repro.kernels.xent`): forward reads h once and w once
per token tile; the backward reads w once, h once per vocab tile, writes
the f32 dH partial sum per vocab tile (reading it back from the second),
writes dW once, and dH is then rounded to the element size — logits and
dlogits never leave VMEM.

The memory figure of merit is the peak logit activation: the jnp path
holds a (B*chunk, V) f32 block in HBM — O(S*V) as chunk approaches S —
while the fused path's is one (bn, bv) f32 VMEM tile, the same few MiB at
every head size (independent of V and S; see
``xent/peak_logit_bytes_*``).

Timing follows the convention of :mod:`benchmarks.fused_update`: off-TPU
the compiled-kernel path would time the Pallas *interpreter*, so the
wall-clock section compares the two jnp code paths (chunked scan vs full
logits) under compiled XLA, and the fused kernels are timed only on TPU
(``--tiny`` also times the interpret oracle at toy shapes so the harness
itself cannot rot). Parity runs the real kernels on every backend.
"""
from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp
import numpy as np

# paper-scale head shapes (bf16 h/w): LLaMA-60M-ish and a 1B model with a
# 128k tokenizer — the V sweep is the point (head dominance, cf. APOLLO)
HEADS = {
    "60M": dict(B=4, S=4096, D=512, V=32768),
    "1B": dict(B=4, S=4096, D=2048, V=131072),
}
CHUNK = 2048  # cfg.loss_chunk default


def _tiles(N, D, V, be):
    from repro.kernels.xent.xent import _pick_blocks
    fwd = _pick_blocks(N, D, V, el_bytes=be)
    bwd = _pick_blocks(N, D, V, el_bytes=be, out_bytes=be, kernel="bwd")
    return fwd, bwd


def jnp_chunk_bytes(B, S, D, V, chunk, be=2):
    """(total_bytes, peak_logit_bytes) for the chunked-scan jnp path."""
    chunk = min(chunk, S)
    nch = math.ceil(S / chunk)
    c = B * chunk
    logit = c * V * 4
    fwd = nch * (c * D * be + D * V * be + 2 * logit)
    bwd = nch * (c * D * be + D * V * be + 2 * logit   # remat logits
                 + 2 * logit                           # dlogits
                 + c * D * be                          # dH chunk
                 + 2 * D * V * 4)                      # f32 dW accum r+w
    return fwd + bwd, logit


def fused_bytes(B, S, D, V, be=2):
    """(total_bytes, peak_logit_bytes) for the fused kernel path."""
    N = B * S
    (bn_f, bv_f), (bn_b, bv_b) = _tiles(N, D, V, be)
    fwd = N * D * be + math.ceil(N / bn_f) * D * V * be
    nv = math.ceil(V / bv_b)
    bwd = (2 * D * V * be + nv * N * D * be + (2 * nv - 1) * N * D * 4
           + N * D * (4 + be))
    # loss/lse/labels vectors are noise (N * 4 each)
    return fwd + bwd, max(bn_f * bv_f, bn_b * bv_b) * 4


def _accounting_rows(heads, chunk):
    rows = []
    peaks = {}
    for name, s in heads.items():
        jb, jpeak = jnp_chunk_bytes(**s, chunk=chunk)
        fb, fpeak = fused_bytes(**s)
        peaks[name] = fpeak
        rows += [
            (f"xent/{name}/jnp_chunk_hbm_bytes", None,
             f"{jb / 1e9:.2f} GB (peak logit block {jpeak / 1e6:.0f} MB "
             f"in HBM)"),
            (f"xent/{name}/fused_hbm_bytes", None,
             f"{fb / 1e9:.2f} GB (peak logit tile {fpeak / 1e6:.2f} MB "
             f"in VMEM)"),
            (f"xent/{name}/hbm_ratio", None,
             f"{jb / fb:.2f}x fewer bytes fused"),
        ]
        assert fb < jb, (name, fb, jb)  # the PR's acceptance bar
    if len(peaks) > 1:
        vals = sorted(set(peaks.values()))
        rows.append(("xent/peak_logit_bytes_fused", None,
                     f"{' vs '.join(f'{v / 1e6:.2f} MB' for v in vals)} "
                     f"across {', '.join(peaks)} — O(bn*bv) VMEM tile, "
                     f"set by the D-dependent tile budget and independent "
                     f"of V and S (jnp peak is O(chunk*V) in HBM)"))
    return rows


def _parity_rows(B=2, S=64, D=64, V=512, VS=500, tied: bool = False):
    """Real kernels (interpret oracle off-TPU) vs the full-logit jnp ref.

    ``tied``: exercise the transposed-w variants — w lives in the (V, D)
    embedding layout, dW must come back in that layout, and the oracle
    contracts ``w.T``.
    """
    from repro.kernels import dispatch
    from repro.kernels.xent import ref as xref

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(ks[0], (B, S, D), jnp.float32)
    w = jax.random.normal(ks[1], (V, D) if tied else (D, V), jnp.float32)
    lab = jax.random.randint(ks[2], (B, S), -1, VS)
    # explicit mode: a user-exported REPRO_FUSED=off must not silently
    # turn this into a reference-vs-reference comparison
    mode = "compiled" if jax.devices()[0].platform == "tpu" else "interpret"
    assert dispatch.xent_route(h.shape, w.shape, mode,
                               transposed=tied)[0] == "kernel"

    def f_fused(h, w):
        return jnp.sum(dispatch.xent_loss(h, w, lab, vocab_size=VS,
                                          mode=mode, transposed=tied))

    def f_ref(h, w):
        return jnp.sum(xref.losses(h, w.T if tied else w, lab, VS))

    (v1, (dh1, dw1)) = jax.value_and_grad(f_fused, argnums=(0, 1))(h, w)
    (v2, (dh2, dw2)) = jax.value_and_grad(f_ref, argnums=(0, 1))(h, w)
    assert dw1.shape == w.shape
    errs = {
        "loss": abs(float(v1) - float(v2)) / max(abs(float(v2)), 1e-9),
        "dH": float(jnp.max(jnp.abs(dh1 - dh2))),
        "dW": float(jnp.max(jnp.abs(dw1 - dw2))),
    }
    assert errs["loss"] < 1e-5 and errs["dH"] < 1e-4 and errs["dW"] < 1e-4, \
        errs
    tag = "tied_parity" if tied else "parity"
    return [(f"xent/{tag}_{k}_err", None, f"{e:.2e}")
            for k, e in errs.items()]


def _timing_rows(tiny: bool):
    """Wall time of loss+grad; see the module docstring for what is
    compared on which backend."""
    from repro.kernels import dispatch
    from repro.kernels.xent import ref as xref
    from repro.models import ModelConfig, lm_loss

    from .common import time_call

    on_tpu = jax.devices()[0].platform == "tpu"
    B, S, D, V = (2, 64, 32, 512) if tiny else (4, 512, 256, 4096)
    cfg = ModelConfig(d_model=D, vocab_size=V, loss_chunk=max(S // 4, 1),
                      dtype="float32")
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    h = jax.random.normal(ks[0], (B, S, D))
    w = jax.random.normal(ks[1], (D, cfg.padded_vocab))
    lab = jax.random.randint(ks[2], (B, S), -1, V)

    def scan_loss(h, w):
        return lm_loss({"lm_head": {"w": w}}, cfg, h, lab)[0]

    def full_loss(h, w):
        return jnp.mean(xref.losses(h, w, lab, V))

    def fused_loss(h, w):
        losses = dispatch.xent_loss(h, w, lab, vocab_size=V)
        return jnp.sum(losses) / jnp.maximum(
            jnp.sum((lab >= 0).astype(jnp.float32)), 1.0)

    rows = [("xent/timing_backend", None,
             f"{jax.devices()[0].platform} "
             f"REPRO_FUSED={os.environ.get('REPRO_FUSED', 'auto')}")]
    from .common import repro_fused
    with repro_fused("off"):  # scan path, compiled XLA
        g_scan = jax.jit(jax.grad(scan_loss, argnums=(0, 1)))
        us_scan = time_call(g_scan, h, w)
    g_full = jax.jit(jax.grad(full_loss, argnums=(0, 1)))
    us_full = time_call(g_full, h, w)
    rows += [
        ("xent/step_jnp_chunk_scan", round(us_scan, 1),
         f"grad of chunked scan, B={B} S={S} D={D} V={V}"),
        ("xent/step_jnp_full_logits", round(us_full, 1),
         "grad of full-logit reference (unbounded activation memory)"),
    ]
    if on_tpu or tiny:
        g_fused = jax.jit(jax.grad(fused_loss, argnums=(0, 1)))
        us_fused = time_call(g_fused, h, w)
        label = "compiled kernels" if on_tpu else \
            "interpret oracle (correctness harness, not a perf number)"
        rows.append(("xent/step_fused", round(us_fused, 1), label))
    else:
        rows.append(("xent/step_fused", None,
                     "skipped off-TPU (interpret oracle would time the "
                     "Pallas interpreter; run --tiny for the harness "
                     "smoke, or on TPU for real numbers)"))
    return rows


def _tied_rows():
    """Tied-head (transposed-w) kernel smoke: parity + end-to-end lm_loss.

    Keeps the transposed kernels exercised by ``bench-smoke`` (CI passes
    ``--tied``): the tied lm_loss route must stay on the kernels and match
    the chunked scan over ``tok_embed.w.T``.
    """
    from repro.models import ModelConfig, init_params, lm_loss

    from .common import repro_fused

    rows = _parity_rows(tied=True)
    cfg = ModelConfig(d_model=32, vocab_size=500, loss_chunk=16,
                      dtype="float32", tie_embeddings=True)
    params = init_params(jax.random.PRNGKey(2), cfg)
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 64, cfg.d_model))
    lab = jax.random.randint(jax.random.PRNGKey(4), (2, 64), -1,
                             cfg.vocab_size)
    # pin the mode and assert the route like _parity_rows: an exported
    # REPRO_FUSED=off (or a cfg tweak off the coverage matrix) must fail
    # loudly, not silently compare the scan reference with itself
    from repro.kernels import dispatch
    mode = "compiled" if jax.devices()[0].platform == "tpu" else "interpret"
    assert dispatch.xent_route(
        tuple(h.shape), (cfg.padded_vocab, cfg.d_model), mode,
        transposed=True)[0] == "kernel"
    with repro_fused(mode):
        l_f = float(lm_loss(params, cfg, h, lab)[0])
    with repro_fused("off"):
        l_r = float(lm_loss(params, cfg, h, lab)[0])
    err = abs(l_f - l_r) / max(abs(l_r), 1e-9)
    assert err < 1e-5, (l_f, l_r)
    rows.append(("xent/tied_lm_loss_vs_scan_err", None, f"{err:.2e}"))
    return rows


def run(quick: bool = False, tied: bool = False):
    """``quick`` (the CLI's ``--tiny``) swaps the paper-scale shape sweep
    for toy shapes and times the interpret oracle — the CI smoke mode.
    ``tied`` adds the transposed-w (tied-embedding head) kernel smoke."""
    tiny = quick
    heads = ({"tiny": dict(B=2, S=64, D=32, V=512)} if tiny else HEADS)
    rows = [("xent/mode", None,
             f"backend={jax.devices()[0].platform} tiny={tiny} "
             f"chunk={CHUNK} be=2 (bf16 h/w)")]
    rows += _accounting_rows(heads, CHUNK)
    rows += _parity_rows()
    if tied:
        rows += _tied_rows()
    rows += _timing_rows(tiny)
    return rows


if __name__ == "__main__":
    import sys

    from .common import emit, json_arg
    emit(run(quick="--tiny" in sys.argv, tied="--tied" in sys.argv),
         json_path=json_arg(sys.argv))
