"""``attn_fetch_skip_share`` on the tiny configuration and a hand-built
trace: one traced step that ran the forward kernel twice (forward and
recomputation) and each backward kernel once."""
import numpy as np
import pytest

from bench import trace as tr
from bench.harness import Run, read_metric
from bench.tests import tiny


def op(s, e, name):
    return (s, e, f"%{name} = bf16[8] custom-call(bf16[8] %p)")


def run_of(segment_ids, trace=True):
    ops = [op(0, 10, "attn_fwd.1"), op(10, 20, "attn_fwd.2"),
           op(20, 30, "attn_bwd_dq.3"), op(30, 40, "attn_bwd_dkv.4"),
           op(40, 50, "fusion.5")]
    t = tr.Trace({0: ops}, {0: [(0, 50, "jit_train_step(1)")]}, []) \
        if trace else None
    # the second batch lies outside the traced step and is not counted
    batches = [{"segment_ids": segment_ids},
               {"segment_ids": np.zeros_like(segment_ids)}]
    return Run(model=tiny.config()["model"], chips=1, peak={}, setup_s=0.0,
               window_s=50e-9, batches=batches, step_hbm_bytes=0, trace=t)


def test_hand_counted_share():
    # one document over a 1024-token row: 512-token tiles, 4 query heads
    # on 2 kv heads. Forward and dQ walk kv tiles 0, 0 | 0, 1 for each
    # head: 8 of 16 steps repeat their block. dK/dV walk q tiles 0, 1
    # under kv tile 0 and 1, 1 under kv tile 1 for each group head: 4 of
    # 16. Weighted 2 : 1 : 1 -> (2*8 + 8 + 4) / (4*16).
    seg = np.ones((1, 1024), np.int32)
    assert read_metric("attn_fetch_skip_share", run_of(seg)) == \
        pytest.approx(100 * 28 / 64)


def test_none_without_a_trace():
    seg = np.ones((1, 1024), np.int32)
    assert read_metric("attn_fetch_skip_share", run_of(seg, False)) is None
