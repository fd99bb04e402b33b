"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret-mode parity cannot see a scoped-VMEM overrun or a misaligned
tile: only the chip's compiler can. It is installed here and compiles for
a described (not attached) ``v5e:2x2`` topology, so these tests hand each
kernel ``ShapeDtypeStruct`` operands placed on one described chip and
compile it. Shapes are those of the paper's LLaMA-1B/7B training steps:
the xent head at N=2048 tokens and V=32000 (and the backward at the
benchmark cells' Qwen2-7B and Granite-3.0-8B heads), attention at head_dim 64
(T=256) and 128 (T=4096) and packed 16k rows at Qwen2-7B's heads, the
SCALE update on the stacked MLP weight of
LLaMA-1B and the LM-head momentum.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file. The persistent compilation cache is off while these tests run (an
entry written for a described chip cannot be read back without one).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.attention import attention as ak
from repro.kernels.colnorm import colnorm as ck
from repro.kernels.scale_head import scale_head as hk
from repro.kernels.xent import xent as xk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; return the compiled text."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
N, V = 2048, 32000


@pytest.mark.parametrize("transposed", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("d", [512, 2048, 4096])
@pytest.mark.parametrize("kernel", ["fwd", "bwd_bf16", "bwd_f32"])
def test_xent_compiles(one_chip, kernel, d, transposed):
    """The forward, and the backward with a bf16 dW (one chip) or an f32
    dW (the partial sum a mesh axis psums)."""
    w = ((V, d) if transposed else (d, V), BF16)
    common = [((N, d), BF16), w, ((N,), I32)]
    kw = dict(vocab_size=V, interpret=False, transposed=transposed)
    if kernel == "fwd":
        _compile(lambda h, w, lab: xk.xent_fwd(h, w, lab, **kw), one_chip,
                 *common)
        return
    out = BF16 if kernel == "bwd_bf16" else F32
    _compile(lambda h, w, lab, lse, gl: xk.xent_bwd(h, w, lab, lse, gl,
                                                     dw_dtype=out, **kw),
             one_chip, *common, ((N,), F32), ((N,), F32))


@pytest.mark.parametrize("n,d,v,dw_dtype,transposed",
                         [(16384, 3584, 152064, BF16, False),
                          (8192, 4096, 49280, F32, True)],
                         ids=["qwen2-7b", "granite-3-8b"])
def test_xent_bwd_compiles_at_cell_heads(one_chip, n, d, v, dw_dtype,
                                         transposed):
    """The backward at the benchmark cells' heads, where its tiles fill
    most of the wide scoped-VMEM limit: Qwen2-7B untied with a bf16 dW on
    one chip, Granite-3.0-8B tied with the f32 dW its token psum takes."""
    w = ((v, d) if transposed else (d, v), BF16)
    kw = dict(vocab_size=v, interpret=False, transposed=transposed,
              dw_dtype=dw_dtype)
    _compile(lambda h, w, lab, lse, gl: xk.xent_bwd(h, w, lab, lse, gl,
                                                     **kw),
             one_chip, ((n, d), BF16), w, ((n,), I32), ((n,), F32),
             ((n,), F32))


@pytest.mark.parametrize("B,S,H,K,hd", [(16, 256, 32, 32, 64),
                                        (1, 4096, 32, 8, 128)],
                         ids=["hd64_T256", "hd128_T4096"])
@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
def test_attention_compiles(one_chip, kernel, B, S, H, K, hd):
    q, kv = ((B, S, H, hd), BF16), ((B, S, K, hd), BF16)
    kw = dict(scale=hd ** -0.5, causal=True, interpret=False)
    if kernel == "fwd":
        _compile(lambda q, k, v: ak.mha_fwd(q, k, v, **kw), one_chip,
                 q, kv, kv)
        return
    bwd = ak.mha_bwd_dq if kernel == "dq" else ak.mha_bwd_dkv
    row = ((B, H, S), F32)
    _compile(lambda q, k, v, do, lse, dl: bwd(q, k, v, do, lse, dl, **kw),
             one_chip, q, kv, kv, q, row, row)


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
def test_packed_attention_compiles(one_chip, kernel):
    """Packed rows at the benchmark's 16k shape (Qwen2-7B's heads): the
    segment operands, the scalar-prefetched fetch plan and the clamped
    index maps compile for the chip."""
    B, S, H, K, hd = 1, 16384, 28, 4, 128
    q, kv = ((B, S, H, hd), BF16), ((B, S, K, hd), BF16)
    seg = ((B, S), I32)
    kw = dict(scale=hd ** -0.5, causal=True, interpret=False)
    if kernel == "fwd":
        _compile(lambda q, k, v, s: ak.mha_fwd(q, k, v, segments=(s, s),
                                                **kw),
                 one_chip, q, kv, kv, seg)
        return
    bwd = ak.mha_bwd_dq if kernel == "dq" else ak.mha_bwd_dkv
    row = ((B, H, S), F32)
    _compile(lambda q, k, v, do, lse, dl, s: bwd(q, k, v, do, lse, dl,
                                                 segments=(s, s), **kw),
             one_chip, q, kv, kv, q, row, row, seg)


MLP = (24, 2048, 5461)   # LLaMA-1B's stacked (layers, d_model, d_ff) weight
HEAD = (1, 2048, 32000)  # LLaMA-1B's LM head, canonicalised to 3-D


@pytest.mark.parametrize("axis", ["col", "row"])
def test_colnorm_sumsq_compiles(one_chip, axis):
    _compile(lambda g: ck.norm_sumsq(g, axis, interpret=False), one_chip,
             (MLP, BF16))


@pytest.mark.parametrize("axis", ["col", "row"])
def test_colnorm_apply_compiles(one_chip, axis):
    ss = (1, MLP[2]) if axis == "col" else (MLP[1], 1)
    _compile(lambda g, ss: ck.norm_apply(g, ss, axis, interpret=False),
             one_chip, (MLP, BF16), ((MLP[0],) + ss, F32))


def test_scale_head_momentum_compiles(one_chip):
    _compile(lambda m, g: hk.momentum_sumsq(m, g, 0.9, "col",
                                            interpret=False),
             one_chip, (HEAD, F32), (HEAD, BF16))


def test_scale_head_update_compiles(one_chip):
    _compile(lambda t, m, ss: hk.head_update_apply(t, m, ss, 1e-3, "col",
                                                   interpret=False),
             one_chip, (HEAD, BF16), (HEAD, F32), ((1, 1, HEAD[2]), F32))
