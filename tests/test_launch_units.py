"""Launch-layer units: sharding rules, compiled-step introspection, the
compile cache, batch placement."""
import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.launch import hlo_analysis as HA
from repro.models.sharding import Rules


class FakeMesh:
    """Stands in for a (data=16, model=16) mesh in rule resolution."""
    axis_names = ("data", "model")
    shape = {"data": 16, "model": 16}


def test_rules_divisibility_guard():
    r = Rules()
    mesh = FakeMesh()
    # 8 kv heads cannot shard over 16-way model axis -> replicated
    spec = r.spec(("act_heads",), mesh, (8,))
    assert spec == P(None)
    spec = r.spec(("act_heads",), mesh, (64,))
    assert spec == P("model")
    # multi-axis batch rule with missing 'pod' axis silently drops it
    spec = r.spec(("act_batch",), mesh, (256,))
    assert spec == P("data")


def test_rules_overrides():
    r = Rules(overrides=(("act_seq", ("data",)),))
    spec = r.spec(("act_seq",), FakeMesh(), (4096,))
    assert spec == P("data")


def test_pallas_kernels_counts_named_tpu_custom_calls():
    hlo = "\n".join([
        '  %xent_fwd.1 = (f32[8,1]) custom-call(%h), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(step)/fwd/xent_fwd/'
        'pallas_call" stack_frame_id=5}, backend_config={}',
        '  %c.2 = f32[8] custom-call(%g), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp('
        'attn))/attn_bwd_dq/pallas_call"}',
        '  %c.3 = f32[8] custom-call(%g), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(step)/attn_bwd_dq/'
        'pallas_call"}',
        '  %sort.4 = f32[8] custom-call(%g), custom_call_target="TopK"',
    ])
    assert HA.pallas_kernels(hlo) == {"xent_fwd": 1, "attn_bwd_dq": 2}
    assert HA.pallas_kernels("") == {}


def test_compile_cache_left_to_jax_when_env_set(monkeypatch):
    from repro.launch import train
    monkeypatch.setattr(jax.config, "update", lambda *a: (_ for _ in ()).throw(
        AssertionError(f"set {a}")))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    train.use_compile_cache()      # env set: JAX keeps it, nothing set
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    train.use_compile_cache()      # the CPU keeps no cache
    assert train.JAX_CACHE_DIR.name == ".jax_cache"
    assert (train.JAX_CACHE_DIR.parent / "chip_smoke.py").exists()


def test_place_batch_splits_along_data():
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import place_batch
    mesh = make_host_mesh(data=1)
    batch = {"tokens": np.zeros((4, 8), np.int32),
             "labels": np.zeros((4, 8), np.int32)}
    placed = place_batch(batch, mesh, Rules())
    for v in placed.values():
        assert isinstance(v, jax.Array) and v.shape == (4, 8)
        assert v.sharding.mesh == mesh
