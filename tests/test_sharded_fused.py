"""Sharded fused-update correctness (PR 2).

The fused SCALE step must match the single-device jnp reference when
params/grads are sharded over a ("data", "model") mesh: the kernels run on
local shards and the per-slice sums-of-squares are psum-ed over the mesh
axes sharding each matrix's reduce dim. On a stock single-CPU run these
tests still execute the full shard_map code path (1x1 mesh, size-1
collectives); CI additionally runs this module under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` where the mesh is
genuinely 4x2, and the subprocess test below forces 8 devices regardless
of the parent process.

Also covers the PR's satellite regressions: REPRO_FUSED participating in
the dispatch cache key, clip-factor folding being exactly clip-then-update,
f32 update_norm under bf16 params, make_host_mesh divisibility validation,
and the grad-accum batch-divisibility error.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from conftest import tiny_cfg
from repro.core import make_optimizer
from repro.kernels import dispatch
from repro.kernels.colnorm import ref as cref
from repro.kernels.scale_head import ref as href

SHAPES_2D = [(64, 128), (128, 64)]
SHAPES_3D = [(2, 64, 128)]
DTYPES = [jnp.float32, jnp.bfloat16]
KINDS = ["col", "row", "larger"]


def _mesh():
    """(data, model) mesh over every available device (4x2 when forced to
    8 host devices, 1x1 on a stock CPU run)."""
    n = len(jax.devices())
    data = max(d for d in range(1, n + 1) if n % d == 0 and d <= max(n // 2, 1))
    return jax.make_mesh((data, n // data), ("data", "model"))


def _sharding(mesh, ndim):
    spec = P("data", "model") if ndim == 2 else P(None, "data", "model")
    return NamedSharding(mesh, spec)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 1e-5


def _mk(shape, dtype, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    g = jax.random.normal(ks[0], shape, jnp.float32).astype(dtype)
    th = jax.random.normal(ks[1], shape, jnp.float32).astype(dtype)
    m = jax.random.normal(ks[2], shape, jnp.float32)
    return th, g, m


@pytest.mark.parametrize("shape", SHAPES_2D + SHAPES_3D)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kind", KINDS)
def test_sharded_dispatch_parity(shape, dtype, kind):
    """All four entry points: sharded kernels == unsharded jnp oracle."""
    mesh = _mesh()
    sh = _sharding(mesh, len(shape))
    axis = dispatch.resolve_kind(kind, shape)
    th, g, m = _mk(shape, dtype, 3)
    th_s, g_s, m_s = (jax.device_put(x, sh) for x in (th, g, m))
    tol = _tol(dtype)

    np.testing.assert_allclose(
        np.asarray(dispatch.normalize(g_s, kind, sharding=sh), np.float32),
        np.asarray(cref.normalize(g, axis), np.float32), atol=tol)
    np.testing.assert_allclose(
        np.asarray(dispatch.norm_update(th_s, g_s, 0.01, kind, sharding=sh),
                   np.float32),
        np.asarray(cref.norm_update(th, g, 0.01, axis), np.float32), atol=tol)
    gf, gf_s = g.astype(jnp.float32), g_s.astype(jnp.float32)
    m_new, d = dispatch.momentum_norm(m_s, gf_s, 0.9, kind, sharding=sh)
    rm, rd = href.momentum_norm(m, gf, 0.9, axis)
    np.testing.assert_allclose(np.asarray(m_new), np.asarray(rm), atol=1e-5)
    np.testing.assert_allclose(np.asarray(d), np.asarray(rd), atol=1e-5)
    t_new, m_new2 = dispatch.momentum_norm_update(th_s, m_s, gf_s, 0.9, 0.01,
                                                  kind, sharding=sh)
    rt, rm2 = href.momentum_norm_update(th, m, gf, 0.9, 0.01, axis)
    np.testing.assert_allclose(np.asarray(t_new, np.float32),
                               np.asarray(rt, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(m_new2), np.asarray(rm2), atol=1e-5)


def _census_params(dtype=jnp.float32):
    # head (momentum) + 2-D/3-D matrices + vector: every dispatch branch
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    return {
        "tok_embed": {"w": jax.random.normal(ks[0], (64, 32)).astype(dtype)},
        "layers": {"wq": jax.random.normal(ks[1], (2, 32, 64)).astype(dtype),
                   "w2": jax.random.normal(ks[2], (32, 128)).astype(dtype)},
        "norm": {"s": jnp.ones((32,), dtype)},
        "lm_head": {"w": jax.random.normal(ks[3], (32, 64)).astype(dtype)},
    }


def _census_shardings(params, mesh):
    def leaf(p):
        if p.ndim == 2:
            return NamedSharding(mesh, P("data", "model"))
        if p.ndim == 3:
            return NamedSharding(mesh, P(None, "data", "model"))
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map(leaf, params)


@pytest.mark.parametrize("dtype", DTYPES)
def test_sharded_fused_step_matches_jnp_reference(dtype):
    """update_params with shardings + folded clip == clip-then-update jnp."""
    mesh = _mesh()
    params = _census_params(dtype)
    grads = jax.tree_util.tree_map(
        lambda p: (0.1 * jnp.ones_like(p, jnp.float32)
                   + 0.03 * p.astype(jnp.float32)).astype(p.dtype), params)
    shardings = _census_shardings(params, mesh)
    params_s = jax.device_put(params, shardings)
    grads_s = jax.device_put(grads, shardings)
    clip = jnp.asarray(0.7, jnp.float32)

    ref = make_optimizer("scale", 1e-2)
    fused = make_optimizer("scale", 1e-2, impl="fused")
    p_ref, s_ref = ref.update_params(
        jax.tree_util.tree_map(lambda g: g * clip, grads),
        ref.init(params), params)
    p_sh, s_sh = fused.update_params(grads_s, fused.init(params_s), params_s,
                                     shardings=shardings, grad_scale=clip)
    tol = _tol(dtype)
    for a, b in zip(jax.tree_util.tree_leaves(p_sh),
                    jax.tree_util.tree_leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=tol)
    for a, b in zip(jax.tree_util.tree_leaves(s_sh),
                    jax.tree_util.tree_leaves(s_ref)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)


def test_grad_scale_fold_bitwise_on_jnp_path(monkeypatch):
    """With kernels off, folding the clip factor is clip-then-update
    *bitwise* (the scale multiplies g exactly like the trainer tree-map)."""
    monkeypatch.setenv("REPRO_FUSED", "off")
    params = _census_params(jnp.float32)
    grads = jax.tree_util.tree_map(
        lambda p: 0.1 * jnp.ones_like(p) + 0.03 * p, params)
    clip = jnp.asarray(0.37, jnp.float32)
    tx = make_optimizer("scale", 1e-2, impl="fused")
    a, sa = tx.update_params(grads, tx.init(params), params, grad_scale=clip)
    b, sb = tx.update_params(
        jax.tree_util.tree_map(lambda g: g * clip, grads),
        tx.init(params), params)
    for x, y in zip(jax.tree_util.tree_leaves((a, sa)),
                    jax.tree_util.tree_leaves((b, sb))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_repro_fused_mode_keys_dispatch_cache(monkeypatch):
    """Flipping REPRO_FUSED mid-process must not serve stale compilations:
    the resolved mode is a static arg of the jitted impls (cache-keyed)."""
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 128))
    dispatch._normalize_impl.clear_cache()
    monkeypatch.setenv("REPRO_FUSED", "off")
    a = dispatch.normalize(g)
    assert dispatch._normalize_impl._cache_size() == 1
    monkeypatch.setenv("REPRO_FUSED", "interpret")
    b = dispatch.normalize(g)
    # same shape, new mode -> new cache entry, not a stale 'off' replay
    assert dispatch._normalize_impl._cache_size() == 2
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def _one_device_mesh(names=("data", "model")):
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), names)


# (family, shapes, specs, transposed, expected plan axes | None | "ref"),
# one case per planner rule (module docstring of kernels/dispatch.py) plus
# the plans the cells run. A spec of None is an operand with no sharding;
# "single" a sharding that is not a NamedSharding; "other" a NamedSharding
# on a second mesh; "wide" a (data=2) abstract mesh, for divisibility.
PLAN_CASES = {
    "r1-updates-no-sharding": ("updates", [(8, 16)], [None], False, None),
    "r2-updates-not-named": ("updates", [(8, 16)], ["single"], False, "ref"),
    "r2-xent-two-meshes": ("xent", [(4, 8, 16), (16, 32)],
                           [P("data"), "other"], False, "ref"),
    "r3-xent-seq-sharded": ("xent", [(4, 8, 16), (16, 32)],
                            [P(None, "data"), None], False, "ref"),
    "r3-attn-seq-sharded": ("attn", [(2, 8, 4, 16), (2, 8, 2, 16)],
                            [P(None, "data"), None], False, "ref"),
    "r4-attn-q-vs-kv": ("attn", [(2, 8, 4, 16), (2, 8, 2, 16)],
                        [P("data"), P()], False, "ref"),
    "r5-xent-tokens-and-vocab": ("xent", [(4, 8, 16), (16, 32)],
                                 [P("data"), P(None, "data")], False, "ref"),
    "r6-updates-indivisible": ("updates", [(3, 16)], [("wide", P("data"))],
                               False, "ref"),
    "r7-xent-only-embed-gathered": ("xent", [(4, 8, 16), (16, 32)],
                                    [P(), P("data")], False, None),
    "r7-attn-replicated": ("attn", [(2, 8, 4, 16), (2, 8, 2, 16)],
                           [P(), P()], False, None),
    "updates-rows-cols": ("updates", [(2, 8, 16)], [P(None, "data", "model")],
                          False, (("rows", ("data",)), ("cols", ("model",)))),
    "xent-tied-fsdp": ("xent", [(4, 8, 16), (32, 16)],
                       [P("data"), P(None, "data")], True,
                       (("tok", ("data",)),)),
    "attn-batch-heads": ("attn", [(2, 8, 4, 16), (2, 8, 2, 16)],
                         [P("data", None, "model")] * 2, False,
                         (("batch", ("data",)), ("heads", ("model",)))),
}


@pytest.mark.parametrize("case", sorted(PLAN_CASES))
def test_plan_rules(case):
    """One planner for the three kernel families, rule by rule."""
    from jax.sharding import AbstractMesh, SingleDeviceSharding
    family, shapes, specs, transposed, want = PLAN_CASES[case]
    mesh = _one_device_mesh()

    def sharding(spec):
        if spec is None:
            return None
        if spec == "single":
            return SingleDeviceSharding(jax.devices()[0])
        if spec == "other":
            return NamedSharding(_one_device_mesh(("x", "y")), P())
        if isinstance(spec, tuple):
            return NamedSharding(AbstractMesh((2, 1), ("data", "model")),
                                 spec[1])
        return NamedSharding(mesh, spec)

    shs = [sharding(sp) for sp in specs]
    if family == "updates":
        ops = dispatch._update_operands(shs[0], shapes[0])
    elif family == "xent":
        ops = dispatch._xent_operands(*shs, *shapes, transposed)
    else:
        ops = dispatch._attn_operands(*shs, *shapes)
    got = dispatch._plan(ops)
    if isinstance(want, tuple):
        assert got == dispatch.Plan(mesh, want), got
        assert all(got.of(role) == axes for role, axes in want)
    else:
        assert got == want, got


def test_make_host_mesh_rejects_non_divisor():
    from repro.launch.mesh import make_host_mesh
    n = len(jax.devices())
    bad = n + 1 if n > 1 else 3
    with pytest.raises(ValueError, match=f"{n} device"):
        make_host_mesh(data=bad)
    with pytest.raises(ValueError):
        make_host_mesh(data=0)
    assert make_host_mesh(data=n).shape["data"] == n


def test_grad_accum_remainder_raises():
    cfg = tiny_cfg()
    tx = make_optimizer("scale", 3e-3)
    from repro.data import make_dataset
    from repro.models import init_params
    from repro.training import init_state, make_train_step
    params = init_params(jax.random.PRNGKey(0), cfg)
    ds = make_dataset(cfg, seq_len=32, global_batch=8, seed=0)
    step_fn = jax.jit(make_train_step(cfg, tx, grad_accum=3))
    with pytest.raises(ValueError, match=r"batch size 8 \(remainder 2\)"):
        step_fn(init_state(params, tx), ds.host_batch_at(0))


def test_update_norm_bf16_fused_matches_unfused():
    """Fused-path update_norm (param diff) must be computed in f32: bf16
    params would otherwise round small updates away."""
    cfg = tiny_cfg(dtype="bfloat16")
    from repro.data import make_dataset
    from repro.models import init_params
    from repro.training import init_state, make_train_step
    params = init_params(jax.random.PRNGKey(0), cfg)
    ds = make_dataset(cfg, seq_len=32, global_batch=8, seed=0)
    batch = ds.host_batch_at(0)
    norms = {}
    for fused in (True, False):
        tx = make_optimizer("scale", 1e-3)
        step_fn = jax.jit(make_train_step(cfg, tx, clip_norm=1.0,
                                          fused_apply=fused))
        _, metrics = step_fn(init_state(params, tx), batch)
        norms[fused] = float(metrics["update_norm"])
    assert norms[True] > 0
    # diff-of-params (fused) vs update-tree norm (classic): identical up to
    # the param-dtype rounding of the applied update
    np.testing.assert_allclose(norms[True], norms[False], rtol=0.05)


def test_sharded_parity_under_forced_8_devices():
    """End-to-end 8-way host mesh in a subprocess (works from any parent)."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import make_optimizer
from repro.kernels import dispatch
from repro.kernels.colnorm import ref as cref

assert len(jax.devices()) == 8
mesh = jax.make_mesh((4, 2), ("data", "model"))
for dtype in (jnp.float32, jnp.bfloat16):
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    for shape, spec in [((64, 128), P("data", "model")),
                        ((2, 64, 128), P(None, "data", "model"))]:
        sh = NamedSharding(mesh, spec)
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        g = jax.random.normal(ks[0], shape, jnp.float32).astype(dtype)
        th = jax.random.normal(ks[1], shape, jnp.float32).astype(dtype)
        g_s, th_s = jax.device_put(g, sh), jax.device_put(th, sh)
        for kind in ("col", "row", "larger"):
            axis = dispatch.resolve_kind(kind, shape)
            out = dispatch.norm_update(th_s, g_s, 0.01, kind, sharding=sh)
            assert out.sharding.is_equivalent_to(sh, len(shape))
            np.testing.assert_allclose(
                np.asarray(out, np.float32),
                np.asarray(cref.norm_update(th, g, 0.01, axis), np.float32),
                atol=tol)
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


FUSED_ZOO = [("scale_fused", "scale", {}),
             ("sgd_colnorm", "sgd_colnorm", {"impl": "fused"}),
             ("sgd_rownorm", "sgd_rownorm", {"impl": "fused"})]


@pytest.mark.parametrize("name,ref_name,kw", FUSED_ZOO,
                         ids=[n for n, _, _ in FUSED_ZOO])
def test_sharded_fused_registry_zoo_matches_reference(name, ref_name, kw):
    """Every fused-capable registry optimizer: sharded update_params with a
    folded clip factor == clip-then-update on the unsharded jnp reference.
    Generalizes the scale-only parity test to the whole fused zoo now that
    the staged pipeline owns the kernel lowering."""
    mesh = _mesh()
    params = _census_params(jnp.float32)
    grads = jax.tree_util.tree_map(
        lambda p: 0.1 * jnp.ones_like(p) + 0.03 * p, params)
    shardings = _census_shardings(params, mesh)
    params_s = jax.device_put(params, shardings)
    grads_s = jax.device_put(grads, shardings)
    clip = jnp.asarray(0.7, jnp.float32)

    ref = make_optimizer(ref_name, 1e-2)
    fused = make_optimizer(name, 1e-2, **kw)
    p_ref, s_ref = ref.update_params(
        jax.tree_util.tree_map(lambda g: g * clip, grads),
        ref.init(params), params)
    p_sh, s_sh = fused.update_params(grads_s, fused.init(params_s), params_s,
                                     shardings=shardings, grad_scale=clip)
    for a, b in zip(jax.tree_util.tree_leaves(p_sh),
                    jax.tree_util.tree_leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(s_sh),
                    jax.tree_util.tree_leaves(s_ref)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)


def test_fused_zoo_parity_under_forced_8_devices():
    """Fused-capable registry optimizers end-to-end on a real 4x2 mesh:
    sharded update_params == unsharded jnp reference, in a subprocess so
    the 8 forced host devices don't depend on the parent's XLA_FLAGS."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import make_optimizer

assert len(jax.devices()) == 8
mesh = jax.make_mesh((4, 2), ("data", "model"))
ks = jax.random.split(jax.random.PRNGKey(5), 3)
params = {"tok_embed": {"w": jax.random.normal(ks[0], (64, 32))},
          "layers": {"wq": jax.random.normal(ks[1], (2, 32, 64))},
          "lm_head": {"w": jax.random.normal(ks[2], (32, 64))},
          "norm": {"s": jnp.ones((32,))}}
grads = jax.tree_util.tree_map(lambda p: 0.1 * jnp.ones_like(p) + 0.03 * p,
                               params)
def sh(p):
    if p.ndim == 2:
        return NamedSharding(mesh, P("data", "model"))
    if p.ndim == 3:
        return NamedSharding(mesh, P(None, "data", "model"))
    return NamedSharding(mesh, P())
shardings = jax.tree_util.tree_map(sh, params)
params_s = jax.device_put(params, shardings)
grads_s = jax.device_put(grads, shardings)
for name, ref_name, kw in [("scale_fused", "scale", {}),
                           ("sgd_colnorm", "sgd_colnorm", {"impl": "fused"})]:
    ref = make_optimizer(ref_name, 1e-2)
    fused = make_optimizer(name, 1e-2, **kw)
    p_ref, _ = ref.update_params(grads, ref.init(params), params)
    p_sh, _ = fused.update_params(grads_s, fused.init(params_s), params_s,
                                  shardings=shardings)
    for a, b in zip(jax.tree_util.tree_leaves(p_sh),
                    jax.tree_util.tree_leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
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
