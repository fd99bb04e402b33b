"""The trace reduction on a hand-built trace (no TPU, no profiler)."""
import pytest

from bench import trace as tr


def op(s, e, name, shape="f32[8]", opcode="fusion"):
    return (s, e, f"%{name} = {shape} {opcode}(f32[8] %p)")


@pytest.fixture
def two_steps():
    # device 0: step 1 in [0, 100), step 2 in [130, 230); a while loop
    # holds two body ops; an all-gather wait in step 2
    ops0 = [op(0, 10, "fusion.1"), op(10, 40, "attn_fwd.3"),
            op(40, 90, "while.2", opcode="while"),
            op(40, 60, "xent_fwd.1"), op(65, 90, "colnorm_update.4"),
            op(95, 100, "scale_head_momentum.1"),
            op(130, 150, "all-gather-done.7", opcode="all-gather-done"),
            op(150, 230, "fusion.9"),
            op(160, 160, "get-tuple-element.1")]
    ops1 = [op(0, 50, "fusion.1"), op(130, 200, "fusion.9")]
    modules = {0: [(0, 100, "jit_train_step(1)"),
                   (130, 230, "jit_train_step(1)")],
               1: [(0, 100, "jit_train_step(1)"),
                   (130, 230, "jit_train_step(1)")]}
    host = [(95, 125, "data_wait"), (125, 128, "train_step"),
            (100, 101, "sync")]
    return tr.Trace({0: ops0, 1: ops1}, modules, host)


def test_union_and_busy(two_steps):
    assert tr.union([(5, 10), (0, 3), (2, 4), (10, 12)]) == [(0, 4), (5, 12)]
    assert tr.length([(0, 10), (5, 15), (20, 25)]) == 20.0
    # device 0 busy 0..100 except 90..95, and 130..230 -> 195; device 1: 120
    assert tr.busy_ns(two_steps) == pytest.approx((195 + 120) / 2)


def test_idle_share_reader(two_steps):
    from bench.harness import Run
    import importlib.util
    from pathlib import Path
    path = Path(tr.__file__).parent / "metrics" / "device_idle_share.py"
    spec = importlib.util.spec_from_file_location("idle", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    run = Run(model={}, chips=2, peak={}, setup_s=0.0, window_s=300e-9,
              batches=[], step_hbm_bytes=0, trace=two_steps)
    assert mod.read(run) == pytest.approx(100 * (1 - 157.5 / 300))
    run.trace = None
    assert mod.read(run) is None


def test_leaves_drop_containers_and_instants(two_steps):
    names = [tr.op_name(n) for *_, n in tr.leaves(two_steps.ops[0])]
    assert "while.2" not in names
    assert "get-tuple-element.1" not in names
    assert {"xent_fwd.1", "colnorm_update.4", "fusion.1"} <= set(names)


def test_kernel_time_by_prefix(two_steps):
    assert tr.kernel_ns(two_steps, ("xent_",)) == pytest.approx(20 / 2)
    assert tr.kernel_ns(two_steps, ("colnorm_", "scale_head_")) == \
        pytest.approx((25 + 5) / 2)
    assert tr.kernel_ns(two_steps, ("missing_",)) == 0.0
    assert tr.op_kind("%attn_fwd.16 = bf16[4] custom-call()") == "attn_fwd"


def test_step_gaps(two_steps):
    assert tr.step_gaps_ns(two_steps, "jit_train_step") == [30]
    assert tr.program_runs(two_steps, "jit_train_step") == 2
    assert tr.step_gaps_ns(two_steps, "jit_other") == []


def test_exposed_collectives(two_steps):
    # the all-gather wait on device 0 overlaps no other op: 20 exposed
    assert tr.exposed_collective_ns(two_steps) == 20
    assert tr.is_collective("%all-reduce.3 = f32[2] all-reduce(f32[2] %x)")
    assert not tr.is_collective("%fusion.3 = f32[2] fusion(f32[2] %x)")
    assert tr.subtract([(0, 10)], [(2, 4), (6, 7)]) == [(0, 2), (4, 6),
                                                          (7, 10)]


def test_scopes_and_breakdown(two_steps):
    hlo = ('  %fusion.9 = f32[8] fusion(f32[8] %p), metadata={op_name='
           '"jit(train_step)/optimizer_update/mul" stack_frame_id=1}\n'
           '  %fusion.1 = f32[8] fusion(f32[8] %p), metadata={op_name='
           '"jit(train_step)/jvp(fwd)/dot_general"}\n')
    scopes = tr.hlo_scopes(hlo)
    assert scopes["fusion.9"].endswith("optimizer_update/mul")
    assert tr.scope_ns(two_steps, scopes, "optimizer_update") == \
        pytest.approx((80 + 70) / 2)
    top = dict(tr.top_ops(two_steps, scopes, ("xent_", "attn_")))
    assert top["fusion (optimizer_update)"] == pytest.approx(150e-9)
    assert top["xent_fwd"] == pytest.approx(20e-9)
    idle = dict(tr.idle_by_host(two_steps, ("data_wait", "train_step",
                                            "sync")))
    # device 0 idles 90..95 (no span) and 100..130 (data_wait overlaps most)
    assert idle["data_wait"] == pytest.approx(30e-9)
    assert idle["other"] == pytest.approx(5e-9)
