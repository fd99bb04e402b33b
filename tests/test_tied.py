"""Tied-embedding LM heads (``tie_embeddings=True``), end to end.

Layers mirror the untied suites:

  * model layer — the param tree has no ``lm_head``; forward/serving/loss
    read ``tok_embed.w`` transposed and the fused xent dispatches the
    transposed-w kernels (no reference fallback on covered shapes);
  * kernel layer — fused loss/dH/dW parity vs the full-logit oracle over
    ``w.T`` across dtypes / padded vocab / ragged shapes, dW emitted in
    the (V, D) storage layout, plus the forced-8-device (4, 2) mesh matrix
    (run in the ``tier1-multidevice`` CI job);
  * optimizer layer — the tied matrix routes to the ``last`` momentum
    group under ``LabelRules.tied()`` (hard error under the untied default
    rules), its col norm flips to a row norm of the (V, D) storage, the
    state is an eval_shape fixed point, and memory accounting counts tied
    params once.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import repro_fused, tiny_cfg
from repro.core import LabelRules, make_optimizer
from repro.core.labels import label_tree, transposed_tree
from repro.core.memory import memory_report
from repro.core.normalization import rownorm
from repro.kernels import dispatch
from repro.kernels.xent import ref as xref
from repro.models import (head_weight, init_params, lm_loss,
                          logits_from_hidden, model_spec,
                          param_logical_axes, param_shapes)
from repro.models.model import _mask_pad_vocab, loss_fn

DTYPES = [jnp.float32, jnp.bfloat16]


def tied_cfg(**kw):
    kw.setdefault("vocab_size", 250)  # padded_vocab 256: padding exercised
    return tiny_cfg(tie_embeddings=True, **kw)


# ---- model layer ----------------------------------------------------------

def test_tied_tree_has_no_lm_head():
    cfg = tied_cfg()
    assert "lm_head" not in model_spec(cfg)
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert "lm_head" not in params
    w, tied = head_weight(params, cfg)
    assert tied and w.shape == (cfg.padded_vocab, cfg.d_model)
    assert param_logical_axes(cfg)["tok_embed"]["w"] == ("vocab", "embed")
    # tied params are counted once: exactly one (V, D) head/embedding
    untied = tiny_cfg(vocab_size=250)
    n_tied = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))
    n_untied = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        param_shapes(untied), is_leaf=lambda x: isinstance(x, tuple)))
    assert n_untied - n_tied == cfg.padded_vocab * cfg.d_model


def test_tied_serving_logits_match_transposed_matmul():
    cfg = tied_cfg()
    params = init_params(jax.random.PRNGKey(1), cfg)
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 8, cfg.d_model))
    got = logits_from_hidden(params, cfg, h)
    want = _mask_pad_vocab(h @ params["tok_embed"]["w"].T, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_tied_audio_heads_match_reference():
    cfg = tied_cfg(family="audio", n_codebooks=2, vocab_size=200)
    params = init_params(jax.random.PRNGKey(3), cfg)
    B, S = 2, 16
    h = jax.random.normal(jax.random.PRNGKey(4), (B, S, cfg.d_model),
                          jnp.float32).astype(cfg.jdtype)
    labels = jax.random.randint(jax.random.PRNGKey(5), (B, 2, S), -1, 200)
    loss, weight = lm_loss(params, cfg, h, labels)
    ew = params["tok_embed"]["w"]  # (C, V, D)
    tot = sum(float(jnp.sum(xref.losses(h, ew[c].T, labels[:, c], 200)))
              for c in range(2))
    ref_w = float(jnp.sum((labels >= 0).astype(jnp.float32)))
    np.testing.assert_allclose(float(loss), tot / max(ref_w, 1.0), rtol=2e-3)
    assert float(weight) == ref_w
    # serving logits: per-codebook h @ w[c].T
    got = logits_from_hidden(params, cfg, h)
    want = _mask_pad_vocab(jnp.einsum("bsd,cvd->bcsv", h, ew), cfg)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=1e-2)


# ---- kernel / dispatch layer ----------------------------------------------

def test_transposed_route_covered_not_fallback():
    """Covered tied shapes must take the kernel route (acceptance bar: no
    reference fallback), and the D-mismatch check follows the layout."""
    assert dispatch.xent_supported((4, 8, 16), (128, 16), transposed=True)
    assert not dispatch.xent_supported((4, 8, 16), (16, 128), transposed=True)
    assert dispatch.xent_route((4, 8, 16), (128, 16),
                               transposed=True)[0] == "kernel"
    cfg = tied_cfg()
    h_shape = (2, 32, cfg.d_model)
    w, _ = head_weight(init_params(jax.random.PRNGKey(0), cfg), cfg)
    assert dispatch.xent_route(h_shape, tuple(w.shape),
                               transposed=True)[0] == "kernel"


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", [(2, 32, 64, 512, 500),
                                   (1, 70, 33, 257, 200),
                                   (2, 16, 128, 384, 384)],
                         ids=["padded", "ragged", "exact"])
def test_transposed_xent_loss_and_grads_match_reference(shape, dtype):
    """Same parity matrix as the untied kernels, with w in (V, D); dW must
    come back in (V, D) so it lands directly on the embedding."""
    B, S, D, V, VS = shape
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    h = jax.random.normal(ks[0], (B, S, D), jnp.float32).astype(dtype)
    wt = jax.random.normal(ks[1], (V, D), jnp.float32).astype(dtype)
    labels = jax.random.randint(ks[2], (B, S), -1, VS)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4

    def f_fused(h, wt):
        return jnp.sum(dispatch.xent_loss(h, wt, labels, vocab_size=VS,
                                          transposed=True))

    def f_ref(h, wt):
        return jnp.sum(xref.losses(h, wt.swapaxes(-1, -2), labels, VS))

    v1, (dh1, dw1) = jax.value_and_grad(f_fused, argnums=(0, 1))(h, wt)
    v2, (dh2, dw2) = jax.value_and_grad(f_ref, argnums=(0, 1))(h, wt)
    np.testing.assert_allclose(float(v1), float(v2),
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5)
    assert dw1.shape == wt.shape and dw1.dtype == wt.dtype
    np.testing.assert_allclose(np.asarray(dh1, np.float32),
                               np.asarray(dh2, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(dw1, np.float32),
                               np.asarray(dw2, np.float32), atol=tol)


def test_tied_lm_loss_fused_equals_scan_reference():
    """End-to-end tied lm_loss: fused (default) == REPRO_FUSED=off chunked
    scan over tok_embed.w.T, values and gradients — the same tolerances as
    the untied parity test."""
    for cfg in (tied_cfg(),
                tied_cfg(family="audio", n_codebooks=2, vocab_size=200)):
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
                with repro_fused("off"):
                    return lm_loss(p, cfg, h, labels)[0]
            return lm_loss(p, cfg, h, labels)[0]

        head = {"tok_embed": params["tok_embed"]}
        l_f, g_f = jax.value_and_grad(head_loss)(head, False)
        l_r, g_r = jax.value_and_grad(head_loss)(head, True)
        np.testing.assert_allclose(float(l_f), float(l_r), rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(g_f),
                        jax.tree_util.tree_leaves(g_r)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=2e-4)


# ---- optimizer layer ------------------------------------------------------

def test_tied_rules_route_embedding_to_last_with_momentum():
    """The routing satellite: under LabelRules.tied() the tied embedding
    carries momentum state; under the untied default it does not."""
    cfg = tied_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rules = LabelRules.tied()
    labels = label_tree(params, rules)
    assert labels["tok_embed"]["w"] == "last"
    assert transposed_tree(params, rules)["tok_embed"]["w"] is True
    tx = make_optimizer("scale", 1e-3, rules=rules)
    state = tx.init(params)
    assert state.mu["tok_embed"]["w"].shape == params["tok_embed"]["w"].shape
    # untied model, untied rules: the embedding is 'first', no momentum
    ucfg = tiny_cfg(vocab_size=250)
    uparams = init_params(jax.random.PRNGKey(0), ucfg)
    ustate = make_optimizer("scale", 1e-3).init(uparams)
    assert ustate.mu["tok_embed"]["w"].size == 0


def test_tied_tree_under_untied_rules_is_hard_error():
    """An unmatched logit-producing matrix must not silently land outside
    the 'last' group: scale on a tied tree with the default rules raises."""
    cfg = tied_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    tx = make_optimizer("scale", 1e-3)
    with pytest.raises(ValueError, match="LabelRules.tied"):
        tx.init(params)
    # and the same guard holds in the update path (state built elsewhere)
    rules_state = make_optimizer("scale", 1e-3,
                                 rules=LabelRules.tied()).init(params)
    with pytest.raises(ValueError, match="LabelRules.tied"):
        tx.update(params, rules_state, params)


def test_tied_head_update_is_row_normalized_momentum():
    """Output-dim normalization is preserved: the (V, D) tied head's update
    is -lr * rownorm(EMA) — the row norm of the storage layout IS the col
    norm of the (D, V) use layout."""
    lr, beta = 1e-2, 0.9
    cfg = tied_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    grads = jax.tree_util.tree_map(
        lambda p: (0.1 * jnp.ones_like(p) + 0.01 * p).astype(jnp.float32),
        params)
    tx = make_optimizer("scale", lr, beta=beta, rules=LabelRules.tied())
    state = tx.init(params)
    upd, state = tx.update(grads, state, params)
    m1 = (1 - beta) * grads["tok_embed"]["w"]
    np.testing.assert_allclose(np.asarray(upd["tok_embed"]["w"]),
                               np.asarray(-lr * rownorm(m1)), atol=1e-6)
    upd2, _ = tx.update(grads, state, params)
    m2 = beta * m1 + (1 - beta) * grads["tok_embed"]["w"]
    np.testing.assert_allclose(np.asarray(upd2["tok_embed"]["w"]),
                               np.asarray(-lr * rownorm(m2)), atol=1e-6)


@pytest.mark.parametrize("impl", ["jnp", "fused"])
def test_tied_state_is_eval_shape_fixed_point(impl):
    """The eval_shape fixed point holds for the tied tree through both
    entry points (lax.scan loops / donated buffers depend on it)."""
    cfg = tied_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    grads = jax.tree_util.tree_map(lambda p: jnp.zeros_like(p), params)
    tx = make_optimizer("scale", 1e-3, impl=impl, rules=LabelRules.tied())
    s0 = jax.eval_shape(tx.init, params)
    for step in (lambda g, s, p: tx.update(g, s, p)[1],
                 lambda g, s, p: tx.update_params(g, s, p)[1]):
        s1 = jax.eval_shape(step, grads, s0, params)
        assert (jax.tree_util.tree_structure(s0)
                == jax.tree_util.tree_structure(s1))
        for a, b in zip(jax.tree_util.tree_leaves(s0),
                        jax.tree_util.tree_leaves(s1)):
            assert a.shape == b.shape and a.dtype == b.dtype, (impl, a, b)


def test_tied_fused_scale_matches_jnp_reference():
    cfg = tied_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    grads = jax.tree_util.tree_map(
        lambda p: (0.1 * jnp.ones_like(p) + 0.02 * p).astype(p.dtype), params)
    txs = [make_optimizer("scale", 1e-2, impl=i, rules=LabelRules.tied())
           for i in ("jnp", "fused")]
    states = [tx.init(params) for tx in txs]
    ps = [params, params]
    for _ in range(3):
        for i, tx in enumerate(txs):
            ps[i], states[i] = tx.update_params(grads, states[i], ps[i])
    for a, b in zip(jax.tree_util.tree_leaves((ps[0], states[0])),
                    jax.tree_util.tree_leaves((ps[1], states[1]))):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-5)


def test_tied_memory_accounted_once():
    """Tied shapes + tied rules: weights shrink by one head matrix and the
    SCALE momentum follows the tie onto the embedding."""
    cfg, ucfg = tied_cfg(), tiny_cfg(vocab_size=250)
    tied_r = memory_report(param_shapes(cfg), "scale",
                           rules=LabelRules.tied())
    untied_r = memory_report(param_shapes(ucfg), "scale")
    head_bytes = cfg.padded_vocab * cfg.d_model * 2
    assert untied_r.weight_bytes - tied_r.weight_bytes == head_bytes
    # momentum moved onto the tied matrix, not dropped
    assert tied_r.state_bytes == untied_r.state_bytes
    # without tied rules the head momentum silently disappears — the
    # accounting mirrors the optimizer's (hard-error-guarded) behavior
    assert memory_report(param_shapes(cfg), "scale").state_bytes \
        < tied_r.state_bytes


# ---- trainer end-to-end ---------------------------------------------------

def test_tied_train_step_fused_paths_active():
    """Acceptance: tie_embeddings=True trains through make_train_step with
    the fused xent + fused SCALE paths on covered shapes, produces no
    lm_head, and matches the REPRO_FUSED=off reference loss."""
    from repro.data import make_dataset
    from repro.training import init_state, make_train_step
    cfg = tied_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert "lm_head" not in params
    # the shapes this step will dispatch are kernel-covered (no fallback)
    w, tied = head_weight(params, cfg)
    assert tied
    assert dispatch.xent_route((4, 32, cfg.d_model), tuple(w.shape),
                               transposed=True)[0] == "kernel"
    assert dispatch.supported(tuple(w.shape), "row")
    ds = make_dataset(cfg, seq_len=32, global_batch=4)
    batch = ds.host_batch_at(0)
    tx = make_optimizer("scale", 3e-3, impl="fused", rules=LabelRules.tied())
    step = jax.jit(make_train_step(cfg, tx, clip_norm=1.0))
    state = init_state(params, tx)
    losses = []
    for i in range(8):
        state, metrics = step(state, ds.host_batch_at(i))
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert state.opt_state.mu["tok_embed"]["w"].size > 0
    with repro_fused("off"):
        step_off = jax.jit(make_train_step(cfg, tx, clip_norm=1.0))
        _, m_off = step_off(init_state(params, tx), batch)
    _, m_on = step(init_state(params, tx), batch)
    np.testing.assert_allclose(float(m_on["loss"]), float(m_off["loss"]),
                               rtol=1e-5)


def test_tied_loss_fn_mesh_kwarg_single_device():
    """1-device mesh must equal no mesh for the tied loss (replicated plan
    -> single-device kernel path), mirroring the untied test."""
    from repro.data import make_dataset
    cfg = tied_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ds = make_dataset(cfg, seq_len=32, global_batch=2)
    batch = ds.host_batch_at(0)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    (l1, _) = loss_fn(params, cfg, batch)
    (l2, _) = loss_fn(params, cfg, batch, mesh=mesh)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


# ---- sharded matrix on a forced 8-device host mesh ------------------------

def test_sharded_tied_xent_parity_under_forced_8_devices():
    """(4, 2) mesh: batch over "data"; tied w (V, D) with vocab TP over
    "model" (dim 0) and FSDP embed over "data" (dim 1, gathered at entry).
    loss/dH/dW must match the unsharded reference for f32 and bf16, dW in
    the (V, D) storage layout."""
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
    wt = jax.random.normal(ks[1], (V, D), jnp.float32).astype(dtype)
    lab = jax.random.randint(ks[2], (B, S), -1, VS)
    h_sh = NamedSharding(mesh, P("data", None, None))
    # (V, D) storage: vocab TP on dim 0, FSDP embed on dim 1 (gathered)
    w_sh = NamedSharding(mesh, P("model", "data"))
    route, plan = dispatch.xent_route(h.shape, wt.shape, None, h_sh, w_sh,
                                      transposed=True)
    assert route == "kernel" and plan.tok_axes == ("data",) \
        and plan.voc_axes == ("model",), (route, plan)
    h_s, w_s = jax.device_put(h, h_sh), jax.device_put(wt, w_sh)

    def f_fused(h, wt):
        return jnp.sum(dispatch.xent_loss(
            h, wt, lab, vocab_size=VS, h_sharding=h_sh, w_sharding=w_sh,
            transposed=True))

    def f_ref(h, wt):
        return jnp.sum(xref.losses(h, wt.swapaxes(-1, -2), lab, VS))

    v1, (dh1, dw1) = jax.value_and_grad(f_fused, argnums=(0, 1))(h_s, w_s)
    v2, (dh2, dw2) = jax.value_and_grad(f_ref, argnums=(0, 1))(h, wt)
    assert dw1.shape == wt.shape
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(
        float(v1), float(v2), rtol=1e-2 if dtype == jnp.bfloat16 else 1e-6)
    np.testing.assert_allclose(np.asarray(dh1, np.float32),
                               np.asarray(dh2, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(dw1, np.float32),
                               np.asarray(dw2, np.float32), atol=tol)

# ragged local vocab (V=320 over 2-way model axis -> local 160, bv=128
# leaves an undefined remainder region on every shard): remainder ROWS of
# the transposed w must stay masked
V2, VS2 = 320, 300
wt2 = jax.random.normal(ks[1], (V2, D))
lab2 = jax.random.randint(ks[2], (B, S), -1, VS2)
h32 = jax.random.normal(ks[0], (B, S, D))
w_sh2 = NamedSharding(mesh, P("model", None))
h_sh2 = NamedSharding(mesh, P("data", None, None))
assert dispatch.xent_route(h32.shape, wt2.shape, None, h_sh2, w_sh2,
                           transposed=True)[0] == "kernel"

def f2(h, wt):
    return jnp.sum(dispatch.xent_loss(h, wt, lab2, vocab_size=VS2,
                                      h_sharding=h_sh2, w_sharding=w_sh2,
                                      block=(32, 128), transposed=True))
v1, (dh1, dw1) = jax.value_and_grad(f2, argnums=(0, 1))(
    jax.device_put(h32, h_sh2), jax.device_put(wt2, w_sh2))
v2, (dh2, dw2) = jax.value_and_grad(
    lambda h, wt: jnp.sum(xref.losses(h, wt.T, lab2, VS2)),
    argnums=(0, 1))(h32, wt2)
np.testing.assert_allclose(float(v1), float(v2), rtol=1e-6)
np.testing.assert_allclose(np.asarray(dh1), np.asarray(dh2), atol=1e-4)
np.testing.assert_allclose(np.asarray(dw1), np.asarray(dw2), atol=1e-4)

# non-divisible vocab (dim 0 now) on the mesh: fall back, don't mis-shard
assert dispatch.xent_route(
    (8, 16, 32), (129, 32), None, None,
    NamedSharding(mesh, P("model", None)), transposed=True)[0] == "ref"
# one axis sharding BOTH tokens and vocab: must fall back
assert dispatch.xent_route(
    (8, 16, 32), (256, 32), None,
    NamedSharding(mesh, P("data", None, None)),
    NamedSharding(mesh, P("data", None)), transposed=True)[0] == "ref"

# end-to-end: tied model + sharded fused train step stays finite and
# matches the unsharded run
from conftest import tiny_cfg
from repro.core import LabelRules, make_optimizer
from repro.data import make_dataset
from repro.models import init_params
from repro.training import init_state, make_train_step

cfg = tiny_cfg(vocab_size=250, tie_embeddings=True)
params = init_params(jax.random.PRNGKey(0), cfg)
tx = make_optimizer("scale", 3e-3, impl="fused", rules=LabelRules.tied())
ds = make_dataset(cfg, seq_len=32, global_batch=8)
batch = ds.host_batch_at(0)
s1, m1 = jax.jit(make_train_step(cfg, tx))(init_state(params, tx), batch)
step_m = make_train_step(cfg, tx, mesh=mesh)
with jax.set_mesh(mesh):
    s2, m2 = jax.jit(step_m)(init_state(params, tx), batch)
np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                jax.tree_util.tree_leaves(s2.params)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=1e-5)
print("OK")
"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_FUSED", None)
    here = os.path.dirname(__file__)
    root = os.path.join(here, "..")
    # src (repro), tests (conftest), repo root (benchmarks, via conftest)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), here, root,
         env.get("PYTHONPATH", "")])
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout
