"""The per-layer share readers (``mlp_share``, ``remat_share``,
``embed_share``) on a hand-built trace and its op_name map."""
import pytest

from bench import trace as tr
from bench.harness import Run, read_metric

FWD = "jit(train_step)/jvp(fwd)"
BWD = "jit(train_step)/transpose(jvp(fwd))"
BODY = "layers/while/body/closed_call"


def op(s, e, name, opcode="fusion"):
    return (s, e, f"%{name} = f32[8] {opcode}(f32[8] %p)")


# (start, end, instruction, op_name): the forward, backward and recomputed
# copies of the MLP and attention, the embedding's gather and scatter-add,
# the scan's dynamic-update-slice (charged to ``layers``, not ``mlp``), a
# kernel under ``head`` and a recomputed op outside the layer stack
OPS = [
    (0, 60, "while.1", f"{FWD}/{BODY}/while"),
    (0, 10, "fusion.1", f"{FWD}/{BODY}/mlp/dot_general"),
    (10, 30, "fusion.2", f"{BWD}/{BODY}/checkpoint/mlp/dot_general"),
    (30, 45, "fusion.3",
     f"{BWD}/{BODY}/checkpoint/rematted_computation/mlp/dot_general"),
    (45, 50, "fusion.4",
     f"{BWD}/{BODY}/checkpoint/rematted_computation/attn/dot_general"),
    (50, 52, "gather.5", f"{FWD}/embed/jit(_take)/gather"),
    (52, 60, "scatter.6", f"{BWD}/embed/jit(_take)/scatter-add"),
    (60, 80, "bitcast_dynamic-update-slice_fusion.7",
     f"{BWD}/layers/while/body/dynamic_update_slice"),
    (80, 100, "xent_fwd.8", f"{FWD}/head/xent_fwd"),
    (100, 110, "fusion.9",
     f"{BWD}/head/checkpoint/rematted_computation/mul"),
]


def run_of(scopes, trace=True):
    ops = {0: [op(s, e, n) for s, e, n, _ in OPS],
           1: [op(s, e, n) for s, e, n, _ in OPS]}
    t = tr.Trace(ops, {}, []) if trace else None
    return Run(model={}, chips=2, peak={}, setup_s=0.0, window_s=110e-9,
               batches=[], step_hbm_bytes=0, trace=t, scopes=scopes)


SCOPES = {n: meta for _, _, n, meta in OPS}


@pytest.mark.parametrize("name, ns", [
    ("mlp_share", 10 + 20 + 15),     # forward, backward, recomputed
    ("remat_share", 15 + 5),         # recomputed MLP and attention only
    ("embed_share", 2 + 8),          # gather and scatter-add
])
def test_share_of_busy_time(name, ns):
    assert read_metric(name, run_of(SCOPES)) == pytest.approx(100 * ns / 110)


@pytest.mark.parametrize("name", ["mlp_share", "remat_share",
                                  "embed_share"])
def test_share_is_none_without_a_trace(name):
    assert read_metric(name, run_of(SCOPES, trace=False)) is None


@pytest.mark.parametrize("name", ["mlp_share", "remat_share",
                                  "embed_share"])
def test_share_is_none_where_the_program_names_no_layer(name):
    # a program without the layer scopes: only the trainer's ``fwd`` and
    # jax.checkpoint's own names reach the op_names
    bare = {n: meta.replace("/layers", "").replace("/mlp", "")
            .replace("/attn", "").replace("/embed", "").replace("/head", "")
            for n, meta in SCOPES.items()}
    assert read_metric(name, run_of(bare)) is None
