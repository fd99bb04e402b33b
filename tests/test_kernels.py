"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(on CPU the dispatch layer runs the kernel bodies through the Pallas
interpreter, so these exercise the real kernels)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch
from repro.kernels.colnorm import ref as cref
from repro.kernels.scale_head import ref as href

SHAPES = [(8, 128), (256, 256), (256, 512), (512, 256), (1024, 512),
          (64, 384), (768, 128)]
# non-tile-divisible 2-D (vocab-like / odd MLP dims) and stacked 3-D
# (scan-over-layers / per-expert) shapes that must NOT fall back to jnp
RAGGED_SHAPES = [(7, 33), (50, 257), (300, 300), (513, 128), (8, 130)]
STACKED_SHAPES = [(2, 8, 128), (4, 100, 64), (3, 50, 129)]
DTYPES = [jnp.float32, jnp.bfloat16]
KINDS = ["col", "row", "larger"]


def _mk(shape, dtype, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    g = jax.random.normal(ks[0], shape, jnp.float32).astype(dtype)
    th = jax.random.normal(ks[1], shape, jnp.float32).astype(dtype)
    m = jax.random.normal(ks[2], shape, jnp.float32)
    return th, g, m


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 1e-5


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_colnorm_kernel(shape, dtype):
    _, g, _ = _mk(shape, dtype, 0)
    out = dispatch.normalize(g, "col")
    ref = cref.colnorm(g)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=_tol(dtype))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_colnorm_update_kernel(shape, dtype):
    th, g, _ = _mk(shape, dtype, 1)
    out = dispatch.norm_update(th, g, 0.01, "col")
    ref = cref.colnorm_update(th, g, 0.01)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=_tol(dtype))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("beta", [0.9, 0.5])
def test_head_update_kernel(shape, dtype, beta):
    th, g, m = _mk(shape, dtype, 2)
    t_new, m_new = dispatch.momentum_norm_update(th, m, g, beta, 0.01, "col")
    rt, rm = href.head_update(th, m, g, beta, 0.01)
    np.testing.assert_allclose(np.asarray(t_new, np.float32),
                               np.asarray(rt, np.float32), atol=_tol(dtype))
    np.testing.assert_allclose(np.asarray(m_new), np.asarray(rm), atol=1e-5)


def test_momentum_colnorm_direction_unit_columns():
    _, g, m = _mk((256, 256), jnp.float32, 3)
    m_new, d = dispatch.momentum_norm(m, g, 0.9, "col")
    norms = np.linalg.norm(np.asarray(d), axis=0)
    np.testing.assert_allclose(norms, 1.0, atol=1e-4)


def test_ragged_shape_stays_fused():
    g = jax.random.normal(jax.random.PRNGKey(4), (7, 33))
    assert dispatch.supported(g.shape, "col")
    out = dispatch.normalize(g, "col")
    np.testing.assert_allclose(np.asarray(out), np.asarray(cref.colnorm(g)),
                               atol=1e-6)


# ---- dispatch coverage matrix: ndim x norm-kind x dtype x raggedness ------

@pytest.mark.parametrize("shape", RAGGED_SHAPES + STACKED_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kind", KINDS)
def test_dispatch_parity_matrix(shape, dtype, kind):
    """Fused vs jnp reference over the full coverage matrix (rtol<=1e-5)."""
    assert dispatch.supported(shape, kind), (shape, kind)
    axis = dispatch.resolve_kind(kind, shape)
    th, g, m = _mk(shape, dtype, 11)
    tol = _tol(dtype)
    np.testing.assert_allclose(
        np.asarray(dispatch.normalize(g, kind), np.float32),
        np.asarray(cref.normalize(g, axis), np.float32), atol=tol)
    np.testing.assert_allclose(
        np.asarray(dispatch.norm_update(th, g, 0.01, kind), np.float32),
        np.asarray(cref.norm_update(th, g, 0.01, axis), np.float32), atol=tol)
    gf = g.astype(jnp.float32)
    m_new, d = dispatch.momentum_norm(m, gf, 0.9, kind)
    rm, rd = href.momentum_norm(m, gf, 0.9, axis)
    np.testing.assert_allclose(np.asarray(m_new), np.asarray(rm), atol=1e-5)
    np.testing.assert_allclose(np.asarray(d), np.asarray(rd), atol=1e-5)
    t_new, m_new2 = dispatch.momentum_norm_update(th, m, gf, 0.9, 0.01, kind)
    rt, rm2 = href.momentum_norm_update(th, m, gf, 0.9, 0.01, axis)
    np.testing.assert_allclose(np.asarray(t_new, np.float32),
                               np.asarray(rt, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(m_new2), np.asarray(rm2), atol=1e-5)


def test_registry_covers_every_op():
    """Every dispatch entry point is registered and parity-checked here.

    Keeps the REGISTRY introspection table honest: a new op added to
    dispatch.py without a REGISTRY entry (or vice versa) fails this test.
    """
    public_ops = {"normalize", "norm_update", "momentum_norm",
                  "momentum_norm_update", "xent_loss", "flash_attention"}
    assert set(dispatch.REGISTRY) == public_ops
    th, g, m = _mk((50, 257), jnp.float32, 21)
    h = jax.random.normal(jax.random.PRNGKey(22), (40, 50))
    lab = jax.random.randint(jax.random.PRNGKey(23), (40,), -1, 250)
    aq = jax.random.normal(jax.random.PRNGKey(24), (2, 16, 4, 8))
    akv = jax.random.normal(jax.random.PRNGKey(25), (2, 16, 2, 8))
    args = {
        "normalize": ((g,), {}),
        "norm_update": ((th, g, 0.01), {}),
        "momentum_norm": ((m, g, 0.9), {}),
        "momentum_norm_update": ((th, m, g, 0.9, 0.01), {}),
        "xent_loss": ((h, th, lab), {"vocab_size": 250}),
        "flash_attention": ((aq, akv, akv), {"scale": 0.35, "causal": True}),
    }
    for op, (fused_fn, ref_fn) in dispatch.REGISTRY.items():
        a, kw = args[op]
        out = fused_fn(*a, **kw)
        ref = ref_fn(*a, **kw)
        out = out if isinstance(out, tuple) else (out,)
        ref = ref if isinstance(ref, tuple) else (ref,)
        for a, b in zip(out, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, err_msg=op)


def test_dispatch_fallback_kinds_do_not_crash():
    """Off-matrix kinds/shapes fall back to jnp instead of erroring."""
    g = jax.random.normal(jax.random.PRNGKey(9), (16, 24))
    from repro.core.normalization import normalize as core_norm
    for kind in ("sign", "ns"):
        np.testing.assert_allclose(
            np.asarray(dispatch.normalize(g, kind)),
            np.asarray(core_norm(g, kind)), atol=1e-6)
    g4 = jax.random.normal(jax.random.PRNGKey(10), (2, 2, 8, 8))
    np.testing.assert_allclose(
        np.asarray(dispatch.normalize(g4, "col")),
        np.asarray(core_norm(g4, "col")), atol=1e-6)
    with pytest.raises(ValueError):
        dispatch.resolve_kind("larger", (16,))


def test_dispatch_backend_mode():
    """Compiled on TPU, interpret oracle elsewhere; 'larger' resolves by shape."""
    assert dispatch.use_interpret() == (dispatch.backend() != "tpu")
    assert dispatch.resolve_kind("larger", (256, 128)) == "col"
    assert dispatch.resolve_kind("larger", (128, 256)) == "row"
    assert dispatch.resolve_kind("larger", (4, 128, 256)) == "row"
    assert not dispatch.supported((128,), "col")       # vectors: Adam branch
    assert not dispatch.supported((2, 2, 8, 8), "col")  # >3-D: jnp fallback
    assert not dispatch.supported((8, 8), "ns")         # NS: jnp fallback


def test_dispatch_off_env(monkeypatch):
    monkeypatch.setenv("REPRO_FUSED", "off")
    # coverage is a property of the shape; the mode decides the route
    assert dispatch.supported((256, 256), "col")
    assert dispatch._route(None, True, ())[0] == "ref"
    monkeypatch.setenv("REPRO_FUSED", "bogus")
    with pytest.raises(ValueError):
        dispatch.normalize(jnp.ones((256, 256)), "col")


# ---- fused optimizer end-to-end ------------------------------------------

def _scale_params():
    # wsq is square on purpose: the 'larger' kind's tie-break must resolve
    # to the same axis in both impls (shared via core.normalization)
    return {
        "tok_embed": {"w": jax.random.normal(jax.random.PRNGKey(5), (50, 32))},
        "layers": {"wq": jax.random.normal(jax.random.PRNGKey(6), (2, 33, 32)),
                   "w2": jax.random.normal(jax.random.PRNGKey(7), (37, 129)),
                   "wsq": jax.random.normal(jax.random.PRNGKey(9), (24, 24))},
        "norm": {"s": jnp.ones((32,))},
        "lm_head": {"w": jax.random.normal(jax.random.PRNGKey(8), (32, 77))},
    }


@pytest.mark.parametrize("kw", [
    {}, {"norm_rest": "row"}, {"norm_last": "larger", "norm_rest": "larger"},
    {"lr_scaling": True}, {"momentum_on": ("last", "matrix")},
], ids=["col", "row", "larger", "lr_scaling", "mmt_matrix"])
def test_fused_scale_optimizer_equals_reference(kw):
    """Fused == jnp over ragged 2-D + stacked 3-D params, all branches."""
    from repro.core import apply_updates, make_optimizer
    params = _scale_params()
    grads = jax.tree_util.tree_map(
        lambda p: 0.1 * jnp.ones_like(p) + 0.01 * p, params)
    a = make_optimizer("scale", 1e-2, **kw)
    b = make_optimizer("scale", 1e-2, impl="fused", **kw)
    sa, sb, sc = a.init(params), b.init(params), b.init(params)
    pa = pb = pc = params
    for _ in range(3):
        ua, sa = a.update(grads, sa, pa)
        pa = apply_updates(pa, ua)
        ub, sb = b.update(grads, sb, pb)
        pb = apply_updates(pb, ub)
        pc, sc = b.update_params(grads, sc, pc)  # fused in-place write
    for x, y, z in zip(jax.tree_util.tree_leaves(pa),
                       jax.tree_util.tree_leaves(pb),
                       jax.tree_util.tree_leaves(pc)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-6)
        np.testing.assert_allclose(np.asarray(x), np.asarray(z), atol=2e-6)
    for x, y, z in zip(jax.tree_util.tree_leaves(sa),
                       jax.tree_util.tree_leaves(sb),
                       jax.tree_util.tree_leaves(sc)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-6)
        np.testing.assert_allclose(np.asarray(x), np.asarray(z), atol=2e-6)


def test_fused_state_treedef_identical_to_jnp():
    """impl='fused' and impl='jnp' states are interchangeable (checkpoints)."""
    from repro.core import make_optimizer
    params = _scale_params()
    sa = make_optimizer("scale", 1e-2).init(params)
    sb = make_optimizer("scale_fused", 1e-2).init(params)
    assert (jax.tree_util.tree_structure(sa)
            == jax.tree_util.tree_structure(sb))
    for x, y in zip(jax.tree_util.tree_leaves(sa),
                    jax.tree_util.tree_leaves(sb)):
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
