"""Fused LM-head cross-entropy: pinned jnp reference + kernel parity.

Layered like the optimizer-kernel tests: first pin the jnp reference
(`_mask_pad_vocab`, chunked-scan vs full-logit equality over padded vocab /
masked labels / audio codebooks), then hold the fused dispatch path
(`kernels.dispatch.xent_loss`, Pallas kernels — interpret oracle on CPU)
to that reference for loss, dH and dW across dtypes and ragged shapes, and
finally the shard_map'd variant on a forced-8-device (4, 2) host mesh.
"""
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import repro_fused, tiny_cfg
from repro.kernels import dispatch
from repro.kernels.xent import ref as xref
from repro.models import init_params, loss_fn
from repro.models.model import _mask_pad_vocab, _pick_chunk, lm_loss

DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return 3e-2 if dtype == jnp.bfloat16 else 1e-4


def _mk(B, S, D, V, VS, dtype=jnp.float32, seed=0, mask_frac=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = jax.random.normal(ks[0], (B, S, D), jnp.float32).astype(dtype)
    w = jax.random.normal(ks[1], (D, V), jnp.float32).astype(dtype)
    lo = -1 if mask_frac else 0
    labels = jax.random.randint(ks[2], (B, S), lo, VS)
    return h, w, labels


# ---- reference pinning ----------------------------------------------------

def test_mask_pad_vocab():
    cfg = tiny_cfg(vocab_size=250)  # padded_vocab 256
    logits = jnp.zeros((2, 4, cfg.padded_vocab))
    out = _mask_pad_vocab(logits, cfg)
    assert float(jnp.max(out[..., cfg.vocab_size:])) <= -1e8
    np.testing.assert_array_equal(np.asarray(out[..., :cfg.vocab_size]), 0.0)
    # exact-multiple vocab: identity
    cfg2 = tiny_cfg(vocab_size=256)
    np.testing.assert_array_equal(
        np.asarray(_mask_pad_vocab(logits, cfg2)), np.asarray(logits))
    # audio logits are (B, C, S, V): mask applies to the last axis
    cfg3 = tiny_cfg(family="audio", n_codebooks=2, vocab_size=250)
    la = jnp.zeros((2, 2, 4, cfg3.padded_vocab))
    out3 = _mask_pad_vocab(la, cfg3)
    assert float(jnp.max(out3[..., cfg3.vocab_size:])) <= -1e8
    assert float(jnp.min(out3[..., :cfg3.vocab_size])) == 0.0


def test_pick_chunk_largest_divisor():
    assert _pick_chunk(32, 2048) == 32
    assert _pick_chunk(32, 16) == 16
    assert _pick_chunk(30, 16) == 15
    assert _pick_chunk(36, 16) == 12
    assert _pick_chunk(1, 16) == 1


def test_pick_chunk_warns_on_degenerate_fallback():
    with pytest.warns(UserWarning, match="loss chunk shrinks to 1"):
        assert _pick_chunk(17, 16) == 1  # prime S: per-token scan
    with pytest.warns(UserWarning, match="loss chunk shrinks"):
        assert _pick_chunk(2 * 97, 64) == 2
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # >= half the target: silent
        assert _pick_chunk(32, 16) == 16
        assert _pick_chunk(24, 16) == 12


def _scan_lm_loss(params, cfg, hidden, labels):
    """The chunked jnp reference path, forced regardless of REPRO_FUSED."""
    with repro_fused("off"):
        return lm_loss(params, cfg, hidden, labels)


@pytest.mark.parametrize("vocab_size,loss_chunk", [(250, 16), (256, 7)],
                         ids=["padded_vocab", "ragged_chunk"])
def test_chunked_scan_equals_full_logits(vocab_size, loss_chunk):
    """The scan path == naive full-logit cross-entropy (the contract the
    fused kernels are later held to)."""
    cfg = tiny_cfg(vocab_size=vocab_size, loss_chunk=loss_chunk)
    B, S, D = 2, 32, cfg.d_model
    h, w, labels = _mk(B, S, D, cfg.padded_vocab, vocab_size, seed=1)
    params = {"lm_head": {"w": w}}
    loss, weight = _scan_lm_loss(params, cfg, h, labels)
    ref = xref.losses(h, w, labels, vocab_size)
    ref_w = float(jnp.sum((labels >= 0).astype(jnp.float32)))
    np.testing.assert_allclose(float(loss),
                               float(jnp.sum(ref)) / max(ref_w, 1.0),
                               rtol=1e-6)
    assert float(weight) == ref_w


def test_chunked_scan_all_masked_rows():
    cfg = tiny_cfg(vocab_size=250)
    h, w, _ = _mk(2, 32, cfg.d_model, cfg.padded_vocab, 250, seed=2)
    labels = jnp.full((2, 32), -1, jnp.int32)
    loss, weight = _scan_lm_loss({"lm_head": {"w": w}}, cfg, h, labels)
    assert float(weight) == 0.0 and float(loss) == 0.0


def test_chunked_scan_audio_codebooks():
    cfg = tiny_cfg(family="audio", n_codebooks=2, vocab_size=200)
    B, S, D = 2, 16, cfg.d_model
    params = init_params(jax.random.PRNGKey(3), cfg)
    h = jax.random.normal(jax.random.PRNGKey(4), (B, S, D),
                          jnp.float32).astype(cfg.jdtype)
    labels = jax.random.randint(jax.random.PRNGKey(5), (B, 2, S), -1, 200)
    loss, weight = _scan_lm_loss(params, cfg, h, labels)
    w = params["lm_head"]["w"]
    tot = sum(float(jnp.sum(xref.losses(h, w[c], labels[:, c], 200)))
              for c in range(2))
    ref_w = float(jnp.sum((labels >= 0).astype(jnp.float32)))
    np.testing.assert_allclose(float(loss), tot / max(ref_w, 1.0), rtol=2e-3)
    assert float(weight) == ref_w


# ---- fused dispatch parity ------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", [(2, 32, 64, 512, 500),
                                   (1, 70, 33, 257, 200),
                                   (2, 16, 128, 384, 384)],
                         ids=["padded", "ragged", "exact"])
def test_fused_xent_loss_and_grads_match_reference(shape, dtype):
    B, S, D, V, VS = shape
    h, w, labels = _mk(B, S, D, V, VS, dtype, seed=6)
    tol = _tol(dtype)

    def f_fused(h, w):
        return jnp.sum(dispatch.xent_loss(h, w, labels, vocab_size=VS))

    def f_ref(h, w):
        return jnp.sum(xref.losses(h, w, labels, VS))

    v1, (dh1, dw1) = jax.value_and_grad(f_fused, argnums=(0, 1))(h, w)
    v2, (dh2, dw2) = jax.value_and_grad(f_ref, argnums=(0, 1))(h, w)
    np.testing.assert_allclose(float(v1), float(v2),
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5)
    assert dh1.dtype == h.dtype and dw1.dtype == w.dtype
    np.testing.assert_allclose(np.asarray(dh1, np.float32),
                               np.asarray(dh2, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(dw1, np.float32),
                               np.asarray(dw2, np.float32), atol=tol)


def test_fused_xent_masked_tokens_contribute_nothing():
    h, w, _ = _mk(2, 16, 32, 256, 256, seed=7)
    labels = jnp.full((2, 16), -1, jnp.int32)
    losses = dispatch.xent_loss(h, w, labels, vocab_size=256)
    np.testing.assert_array_equal(np.asarray(losses), 0.0)
    dh, dw = jax.grad(
        lambda h, w: jnp.sum(dispatch.xent_loss(h, w, labels,
                                                vocab_size=256)),
        argnums=(0, 1))(h, w)
    np.testing.assert_array_equal(np.asarray(dh), 0.0)
    np.testing.assert_array_equal(np.asarray(dw), 0.0)


def test_xent_routing_and_fallbacks(monkeypatch):
    assert dispatch.xent_supported((4, 8, 16), (16, 128))
    assert dispatch.xent_supported((32, 16), (16, 128))
    assert not dispatch.xent_supported((4, 8, 16), (17, 128))  # D mismatch
    assert not dispatch.xent_supported((16,), (16, 128))       # no token dim
    assert dispatch.xent_route((4, 8, 16), (16, 128))[0] == "kernel"
    monkeypatch.setenv("REPRO_FUSED", "off")
    assert dispatch.xent_route((4, 8, 16), (16, 128))[0] == "ref"
    # the off-route still yields correct (reference) values
    h, w, labels = _mk(2, 8, 16, 128, 100, seed=8)
    np.testing.assert_allclose(
        np.asarray(dispatch.xent_loss(h, w, labels, vocab_size=100)),
        np.asarray(xref.losses(h, w, labels, 100)), atol=1e-6)


def test_lm_loss_fused_equals_scan_reference():
    """End-to-end: the default (fused) lm_loss == the REPRO_FUSED=off scan
    path, values and gradients, dense + audio."""
    for cfg in (tiny_cfg(vocab_size=250),
                tiny_cfg(family="audio", n_codebooks=2, vocab_size=200)):
        params = init_params(jax.random.PRNGKey(9), cfg)
        B, S = 2, 32
        h = jax.random.normal(jax.random.PRNGKey(10), (B, S, cfg.d_model),
                              jnp.float32).astype(cfg.jdtype)
        lab_shape = (B, cfg.n_codebooks, S) if cfg.family == "audio" \
            else (B, S)
        labels = jax.random.randint(jax.random.PRNGKey(11), lab_shape, -1,
                                    cfg.vocab_size)

        def head_loss(p, force_off):
            if force_off:
                return _scan_lm_loss(p, cfg, h, labels)[0]
            return lm_loss(p, cfg, h, labels)[0]

        head = {"lm_head": params["lm_head"]}
        l_f, g_f = jax.value_and_grad(head_loss)(head, False)
        l_r, g_r = jax.value_and_grad(head_loss)(head, True)
        np.testing.assert_allclose(float(l_f), float(l_r), rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(g_f),
                        jax.tree_util.tree_leaves(g_r)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=2e-4)


def test_train_step_runs_fused_loss_by_default():
    """The trainer needs no new plumbing off-mesh: loss_fn routes to the
    fused xent wherever covered and the step stays finite/deterministic."""
    from repro.core import make_optimizer
    from repro.data import make_dataset
    from repro.training import init_state, make_train_step
    cfg = tiny_cfg(vocab_size=250)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ds = make_dataset(cfg, seq_len=32, global_batch=4)
    batch = ds.host_batch_at(0)
    tx = make_optimizer("scale", 1e-3)
    step = jax.jit(make_train_step(cfg, tx))
    state, metrics = step(init_state(params, tx), batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    # and the value agrees with the scan-path loss
    with repro_fused("off"):
        step_off = jax.jit(make_train_step(cfg, tx))
        _, m_off = step_off(init_state(params, tx), batch)
    np.testing.assert_allclose(float(metrics["loss"]), float(m_off["loss"]),
                               rtol=1e-5)


def test_loss_fn_accepts_mesh_kwarg():
    """The trainer feature-detects loss_fn(mesh=...); a 1-device mesh must
    behave exactly like no mesh (replicated plan -> single-device path)."""
    import inspect
    assert "mesh" in inspect.signature(loss_fn).parameters
    from repro.data import make_dataset
    cfg = tiny_cfg(vocab_size=250)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ds = make_dataset(cfg, seq_len=32, global_batch=2)
    batch = ds.host_batch_at(0)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    (l1, _) = loss_fn(params, cfg, batch)
    (l2, _) = loss_fn(params, cfg, batch, mesh=mesh)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


def test_vocab_shard_remainder_tiles_masked():
    """Non-last vocab shards: remainder-tile lanes past the local w width
    are undefined memory whose *global* column ids are still < vocab_size
    — they must not enter the logsumexp, the label one-hot, or either
    gradient contraction (regression: the mask only checked the global
    bound, NaN-ing every non-last shard with local_V % bv != 0)."""
    from repro.kernels.xent import xent as xk
    n, d, V, VS = 16, 16, 384, 384
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    h = jax.random.normal(ks[0], (n, d))
    w = jax.random.normal(ks[1], (d, V))
    lab = jax.random.randint(ks[2], (n,), -1, VS)
    gl = jnp.abs(jax.random.normal(ks[0], (n,))) * (lab >= 0)

    # two hand-combined shards of local width 192; bv=128 leaves a
    # 64-lane undefined remainder region on each shard's second tile
    halves = [(0, 192), (192, 384)]
    parts = [xk.xent_fwd(h, w[:, a:b], lab, vocab_size=VS, col_offset=a,
                         block=(32, 128)) for a, b in halves]
    for lse, _ in parts:
        assert bool(jnp.all(jnp.isfinite(lse)))
    m = jnp.maximum(parts[0][0], parts[1][0])
    lse_g = m + jnp.log(sum(jnp.exp(p[0] - m) for p in parts))
    ll_g = parts[0][1] + parts[1][1]
    rlse, rll = xref.lse_ll(h, w, lab, VS)
    np.testing.assert_allclose(np.asarray(lse_g), np.asarray(rlse),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(ll_g), np.asarray(rll), atol=1e-4)

    rdh, rdw = jax.grad(
        lambda h, w: jnp.sum(xref.losses(h, w, lab, VS) * gl),
        argnums=(0, 1))(h, w)
    grads = [xk.xent_bwd(h, w[:, a:b], lab, lse_g, gl, vocab_size=VS,
                         col_offset=a, block=(32, 128)) for a, b in halves]
    dh = sum(g[0] for g in grads)
    dw = jnp.concatenate([g[1] for g in grads], axis=1)
    np.testing.assert_allclose(np.asarray(dh), np.asarray(rdh), atol=1e-4)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(rdw), atol=1e-4)


@pytest.mark.parametrize("dw_dtype", DTYPES, ids=["dw_f32", "dw_bf16"])
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("transposed", [False, True],
                         ids=["untied", "transposed"])
def test_xent_bwd_matches_reference_grads(transposed, dtype, dw_dtype):
    """The one backward kernel's (dH, dW) against the reference's
    gradients: padded vocabulary (300 of 320), remainder tiles on both
    axes (70 tokens over 32-row tiles, 320 columns over 128-lane tiles),
    masked labels and tokens whose upstream weight is 0 (their dH rows
    must come back exactly 0). dH is f32 whatever the operands; dW comes
    back in ``dw_dtype`` and in w's layout."""
    from repro.kernels.xent import xent as xk
    n, d, V, VS = 70, 64, 320, 300
    ks = jax.random.split(jax.random.PRNGKey(16), 4)
    h = jax.random.normal(ks[0], (n, d)).astype(dtype)
    w = jax.random.normal(ks[1], (d, V)).astype(dtype)
    lab = jax.random.randint(ks[2], (n,), -1, VS)
    keep = jnp.arange(n) % 3 != 0                    # zero upstream weights
    gl = jnp.abs(jax.random.normal(ks[3], (n,))) * (lab >= 0) * keep
    lse, _ = xref.lse_ll(h, w, lab, VS)
    rdh, rdw = jax.grad(
        lambda h, w: jnp.sum(xref.losses(h, w, lab, VS) * gl),
        argnums=(0, 1))(h.astype(jnp.float32), w.astype(jnp.float32))
    dh, dw = xk.xent_bwd(h, w.T if transposed else w, lab, lse, gl,
                         vocab_size=VS, block=(32, 128), dw_dtype=dw_dtype,
                         transposed=transposed)
    assert dh.shape == (n, d) and dh.dtype == jnp.float32
    assert dw.dtype == dw_dtype
    assert dw.shape == ((V, d) if transposed else (d, V))
    dw = dw.T if transposed else dw
    np.testing.assert_array_equal(np.asarray(dh)[np.asarray(gl) == 0], 0.0)
    # relative to the gradient's scale: a bf16 dW is rounded by up to
    # 2^-9 of it; a wrong mask or tile is O(1)
    for got, ref, rel in ((dh, rdh, 2e-5),
                          (dw, rdw, 4e-3 if dw_dtype == jnp.bfloat16
                           else 2e-5)):
        ref = np.asarray(ref)
        np.testing.assert_allclose(np.asarray(got, np.float32), ref,
                                   atol=rel * np.abs(ref).max())


@pytest.mark.parametrize("n,d,v,dw_dtype,transposed",
                         [(16384, 3584, 152064, jnp.bfloat16, False),
                          (8192, 4096, 49280, jnp.float32, True)],
                         ids=["qwen2-7b", "granite-3-8b"])
def test_xent_bwd_tiles_fit_wide_vmem(n, d, v, dw_dtype, transposed):
    """At the benchmark cells' heads the backward's tile keeps bv >= 512
    (compute-bound streaming of h and the f32 dH) and its VMEM count
    fits the wide budget, which sits under the wide limit, under v5e's
    128 MiB."""
    from repro.kernels import vmem
    from repro.kernels.xent import xent as xk
    out = jnp.dtype(dw_dtype).itemsize
    bn, bv = xk._pick_blocks(n, d, v, el_bytes=2, out_bytes=out,
                             kernel="bwd")
    assert bv >= 512 and bn >= 128
    assert xk._vmem_cost(bn, bv, d, 2, out, "bwd") \
        <= vmem.WIDE_VMEM_BUDGET_BYTES
    assert vmem.WIDE_VMEM_BUDGET_BYTES < vmem.WIDE_VMEM_LIMIT_BYTES \
        < 128 << 20


# ---- sharded matrix on a forced 8-device host mesh ------------------------

def test_sharded_xent_parity_under_forced_8_devices():
    """(4, 2) mesh: batch over "data", head FSDP+TP over ("data","model").
    loss/dH/dW must match the unsharded reference for f32 and bf16."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.kernels import dispatch
from repro.kernels.xent import ref as xref

assert len(jax.devices()) == 8
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(data=4, model=2)  # Auto axes, as the trainer's
B, S, D, V, VS = 8, 16, 32, 256, 200
ks = jax.random.split(jax.random.PRNGKey(0), 3)
for dtype in (jnp.float32, jnp.bfloat16):
    h = jax.random.normal(ks[0], (B, S, D), jnp.float32).astype(dtype)
    w = jax.random.normal(ks[1], (D, V), jnp.float32).astype(dtype)
    lab = jax.random.randint(ks[2], (B, S), -1, VS)
    h_sh = NamedSharding(mesh, P("data", None, None))
    w_sh = NamedSharding(mesh, P("data", "model"))  # FSDP embed + TP vocab
    route, plan = dispatch.xent_route(h.shape, w.shape, None, h_sh, w_sh)
    assert route == "kernel" and plan.of("tok") == ("data",) \
        and plan.of("voc") == ("model",), (route, plan)
    h_s, w_s = jax.device_put(h, h_sh), jax.device_put(w, w_sh)

    def f_fused(h, w):
        return jnp.sum(dispatch.xent_loss(
            h, w, lab, vocab_size=VS, h_sharding=h_sh, w_sharding=w_sh))

    def f_ref(h, w):
        return jnp.sum(xref.losses(h, w, lab, VS))

    v1, (dh1, dw1) = jax.value_and_grad(f_fused, argnums=(0, 1))(h_s, w_s)
    v2, (dh2, dw2) = jax.value_and_grad(f_ref, argnums=(0, 1))(h, w)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(
        float(v1), float(v2), rtol=1e-2 if dtype == jnp.bfloat16 else 1e-6)
    np.testing.assert_allclose(np.asarray(dh1, np.float32),
                               np.asarray(dh2, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(dw1, np.float32),
                               np.asarray(dw2, np.float32), atol=tol)

# ragged local vocab (V=320 over 2-way model axis -> local 160, bv=128
# leaves an undefined remainder region on every shard): remainder lanes
# must stay masked (regression for the local-bound term in _col_masks)
V2, VS2 = 320, 300
w2 = jax.random.normal(ks[1], (D, V2))
lab2 = jax.random.randint(ks[2], (B, S), -1, VS2)
h32 = jax.random.normal(ks[0], (B, S, D))
w_sh2 = NamedSharding(mesh, P(None, "model"))
h_sh2 = NamedSharding(mesh, P("data", None, None))
assert dispatch.xent_route(h32.shape, w2.shape, None, h_sh2,
                           w_sh2)[0] == "kernel"

def f2(h, w):
    return jnp.sum(dispatch.xent_loss(h, w, lab2, vocab_size=VS2,
                                      h_sharding=h_sh2, w_sharding=w_sh2,
                                      block=(32, 128)))
v1, (dh1, dw1) = jax.value_and_grad(f2, argnums=(0, 1))(
    jax.device_put(h32, h_sh2), jax.device_put(w2, w_sh2))
v2, (dh2, dw2) = jax.value_and_grad(
    lambda h, w: jnp.sum(xref.losses(h, w, lab2, VS2)),
    argnums=(0, 1))(h32, w2)
np.testing.assert_allclose(float(v1), float(v2), rtol=1e-6)
np.testing.assert_allclose(np.asarray(dh1), np.asarray(dh2), atol=1e-4)
np.testing.assert_allclose(np.asarray(dw1), np.asarray(dw2), atol=1e-4)

# non-divisible vocab on the mesh: must fall back to ref, not mis-shard
bad_w_sh = NamedSharding(mesh, P(None, "model"))
assert dispatch.xent_route((8, 16, 32), (32, 129), None, None,
                           bad_w_sh)[0] == "ref"
# one axis sharding BOTH tokens and vocab: the lse/ll psum would mix
# statistics across token shards — must fall back to ref
assert dispatch.xent_route(
    (8, 16, 32), (32, 256), None,
    NamedSharding(mesh, P("data", None, None)),
    NamedSharding(mesh, P(None, "data")))[0] == "ref"
print("OK")
"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_FUSED", None)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout


# ---- weighted loss (packed-document denominators) -------------------------

def _weighted_ref(h, w, labels, weights, vocab):
    """Naive weighted mean: sum(w_i * loss_i) / sum of effective weights."""
    per = np.asarray(xref.losses(h, w, labels, vocab), np.float64)
    eff = np.asarray(weights, np.float64) * (np.asarray(labels) >= 0)
    denom = eff.sum()
    return (per * eff).sum() / (denom if denom > 0 else 1.0), eff.sum()


@pytest.mark.parametrize("fused", ["interpret", "off"],
                         ids=["fused", "chunked"])
def test_lm_loss_fractional_weight_denominator(fused):
    """Regression: the mean must divide by the summed effective weight.

    With every weight fractional and the total below 1.0 the old
    ``max(ws, 1.0)`` clamp silently deflated the loss (divided a 0.3-token
    batch by 1.0); the fix divides by ws whenever ws > 0.
    """
    cfg = tiny_cfg(vocab_size=250, loss_chunk=8)
    B, S, D = 1, 16, cfg.d_model
    h, w, labels = _mk(B, S, D, cfg.padded_vocab, 250, seed=11,
                       mask_frac=False)
    weights = jnp.zeros((B, S)).at[0, 3].set(0.3)   # total weight 0.3 < 1
    with repro_fused(fused):
        loss, wt = lm_loss({"lm_head": {"w": w}}, cfg, h, labels,
                           weights=weights)
    ref, ref_w = _weighted_ref(h, w, labels, weights, 250)
    np.testing.assert_allclose(float(loss), ref, rtol=1e-5)
    np.testing.assert_allclose(float(wt), ref_w, rtol=1e-6)


@pytest.mark.parametrize("fused", ["interpret", "off"],
                         ids=["fused", "chunked"])
def test_lm_loss_partial_mask_weights(fused):
    """Mixed masking — label -1, weight 0, and fractional weights — in one
    batch: only label>=0 AND weight>0 tokens count, each at its weight."""
    cfg = tiny_cfg(vocab_size=250, loss_chunk=8)
    B, S, D = 2, 16, cfg.d_model
    h, w, labels = _mk(B, S, D, cfg.padded_vocab, 250, seed=12,
                       mask_frac=False)
    labels = labels.at[0, :4].set(-1)               # label-masked
    weights = jnp.ones((B, S))
    weights = weights.at[1, 8:].set(0.0)            # weight-masked
    weights = weights.at[0, 10].set(0.25)           # fractional
    with repro_fused(fused):
        loss, wt = lm_loss({"lm_head": {"w": w}}, cfg, h, labels,
                           weights=weights)
    ref, ref_w = _weighted_ref(h, w, labels, weights, 250)
    np.testing.assert_allclose(float(loss), ref, rtol=1e-5)
    np.testing.assert_allclose(float(wt), ref_w, rtol=1e-6)


@pytest.mark.parametrize("fused", ["interpret", "off"],
                         ids=["fused", "chunked"])
def test_lm_loss_all_masked_weights_zero_loss_finite_grads(fused):
    """An all-weight-zero batch yields loss 0 / weight 0 — no NaN from a
    0/0 mean — and the gradient through it is finite (exactly zero)."""
    cfg = tiny_cfg(vocab_size=250, loss_chunk=8)
    B, S, D = 2, 16, cfg.d_model
    h, w, labels = _mk(B, S, D, cfg.padded_vocab, 250, seed=13,
                       mask_frac=False)
    weights = jnp.zeros((B, S))
    with repro_fused(fused):
        loss, wt = lm_loss({"lm_head": {"w": w}}, cfg, h, labels,
                           weights=weights)
        assert float(loss) == 0.0 and float(wt) == 0.0
        g = jax.grad(lambda hh: lm_loss({"lm_head": {"w": w}}, cfg, hh,
                                        labels, weights=weights)[0])(h)
    assert bool(jnp.all(jnp.isfinite(g)))
    np.testing.assert_array_equal(np.asarray(g), 0.0)


def test_lm_loss_weighted_fused_matches_chunked():
    """The two routes agree on a weighted batch (same denominator law)."""
    cfg = tiny_cfg(vocab_size=256, loss_chunk=8)
    B, S, D = 2, 32, cfg.d_model
    h, w, labels = _mk(B, S, D, cfg.padded_vocab, 256, seed=14)
    weights = jax.random.uniform(jax.random.PRNGKey(15), (B, S))
    weights = jnp.where(weights > 0.2, weights, 0.0)
    params = {"lm_head": {"w": w}}
    with repro_fused("interpret"):
        lf, wf = lm_loss(params, cfg, h, labels, weights=weights)
    with repro_fused("off"):
        lc, wc = lm_loss(params, cfg, h, labels, weights=weights)
    np.testing.assert_allclose(float(lf), float(lc), rtol=1e-5)
    np.testing.assert_allclose(float(wf), float(wc), rtol=1e-6)
