"""Launch-layer units that don't need 512 devices: sharding rules, HLO cost
parser, roofline math, input specs."""
import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.launch import hlo_analysis as HA
from repro.launch import hlo_cost as HC
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


SAMPLE_HLO = """
HloModule test

%inner (p0: f32[128,64]) -> f32[128,64] {
  %p0 = f32[128,64]{1,0} parameter(0)
  %c = f32[64,64]{1,0} constant({...})
  %dot.1 = f32[128,64]{1,0} dot(%p0, %c), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %ar = f32[128,64]{1,0} all-reduce(%dot.1), replica_groups={{0,1,2,3}}, to_apply=%add
}

%body (p: (s32[], f32[128,64])) -> (s32[], f32[128,64]) {
  %p = (s32[], f32[128,64]{1,0}) parameter(0)
  %g = f32[128,64]{1,0} get-tuple-element(%p), index=1
  %call.1 = f32[128,64]{1,0} call(%g), to_apply=%inner
  ROOT %t = (s32[], f32[128,64]{1,0}) tuple(%g, %call.1)
}

ENTRY %main (a: f32[128,64]) -> f32[128,64] {
  %a = f32[128,64]{1,0} parameter(0)
  %w = (s32[], f32[128,64]{1,0}) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  ROOT %out = f32[128,64]{1,0} get-tuple-element(%w), index=1
}
"""


def test_hlo_cost_while_multiplier():
    cost = HC.analyze(SAMPLE_HLO)
    # dot: 2*128*64*64 flops, x10 trips
    assert cost.flops == 10 * 2 * 128 * 64 * 64
    # all-reduce: 2*bytes*(g-1)/g with g=4, x10
    out_bytes = 128 * 64 * 4
    assert abs(cost.coll_bytes["all-reduce"] - 10 * 2 * out_bytes * 3 / 4) < 1
    assert cost.coll_counts["all-reduce"] == 10


def test_roofline_terms_and_bottleneck():
    cost = {"flops": 197e12, "bytes_accessed": 819e9 / 2,
            "transcendentals": 0}
    coll = HA.CollectiveStats({"all-reduce": 50e9 * 2}, {"all-reduce": 1})
    roof = HA.roofline(cost, coll, model_flops=197e12 * 256 * 0.5,
                       n_chips=256)
    assert abs(roof["compute_s"] - 1.0) < 1e-9
    assert abs(roof["memory_s"] - 0.5) < 1e-9
    assert abs(roof["collective_s"] - 2.0) < 1e-9
    assert roof["bottleneck"] == "collective_s"
    assert abs(roof["useful_flop_ratio"] - 0.5) < 1e-9


def test_model_flops_kinds():
    from repro.configs import get_arch
    cfg = get_arch("qwen2-7b")
    t = HA.model_flops_for(cfg, "train", 4096, 256)
    p = HA.model_flops_for(cfg, "prefill", 4096, 256)
    d = HA.model_flops_for(cfg, "decode", 4096, 256)
    assert abs(t / p - 3.0) < 1e-6      # 6ND vs 2ND
    assert d < p / 1000                 # one token per sequence


def test_collective_stats_regex_group_formats():
    txt = ('%ag = bf16[1024]{0} all-gather(%x), replica_groups=[8,2]<=[16]\n'
           '%ar = f32[256,4]{1,0} all-reduce(%y), replica_groups={{0,1}}\n')
    st = HA.collective_stats(txt)
    assert st.bytes_by_kind["all-gather"] == 1024 * 2
    assert st.bytes_by_kind["all-reduce"] == 2 * 256 * 4 * 4 // 2


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
