"""The SCALE update's cross-chip sum of column sums of squares carries
the ``norm_psum`` scope in the compiled step, and ``norm_psum_share``
reads it from a trace."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench import trace as tr
from bench.harness import Run, read_metric

ROOT = Path(__file__).resolve().parents[2]

_COMPILE = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from bench import harness, packing, trace
from bench.tests import tiny_granite as tg
cfg = tg.config(chips=4)
ring = packing.make_ring(tg.TRAFFIC, cfg["tokens_per_step"],
                         cfg["model"]["vocab_size"], 1)
prog = harness.Program(cfg, tg.TRAFFIC)
prog.start(1)
scopes = trace.hlo_scopes(prog.compiled(ring[0]).as_text())
print(json.dumps({{k: v for k, v in scopes.items()
                  if trace.is_collective(k)}}))
"""


def test_column_sums_all_reduce_is_named():
    """Four CPU devices, the tiny Granite step with FSDP over ``data``:
    the all-reduces under ``norm_psum`` are those of the optimizer update,
    and nothing else is named so."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _COMPILE.format(root=str(ROOT), src=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    coll = json.loads(p.stdout.strip().splitlines()[-1])
    named = {k: v for k, v in coll.items() if "/norm_psum/" in v}
    assert named, coll
    assert all(k.startswith("all-reduce") and "/optimizer_update/" in v
               for k, v in named.items()), named


_CHIP = """
import sys
sys.path[:0] = [{root!r}, {src!r}]
import numpy as np, jax
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from bench import harness, packing
from bench.tests import tiny_granite as tg
from repro.models import init_params, param_logical_axes, param_shapes
from repro.models.sharding import tree_shardings
from repro.training import GuardPolicy, init_state, make_train_step
jax.config.update("jax_enable_compilation_cache", False)
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception:
    print("no topology"); sys.exit(0)
cfg = tg.config(chips=4)
cfg["model"]["torch_dtype"] = "bfloat16"
prog = harness.Program(cfg, tg.TRAFFIC)
mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
pabs = jax.eval_shape(lambda k: init_params(k, prog.cfg),
                      jax.random.PRNGKey(0))
params = jax.tree_util.tree_map(
    lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), pabs,
    tree_shardings(param_logical_axes(prog.cfg), mesh, prog.rules,
                   param_shapes(prog.cfg)))
rep = NamedSharding(mesh, P())
state = jax.tree_util.tree_map(
    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
    jax.eval_shape(lambda p: init_state(p, prog.tx, guard=True), pabs))
batch = packing.make_ring(tg.TRAFFIC, cfg["tokens_per_step"],
                          cfg["model"]["vocab_size"], 1)[0]
batch = {{k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=NamedSharding(
    mesh, P("data"))) for k, v in batch.items()}}
step = make_train_step(prog.cfg, prog.tx, clip_norm=1.0, rules=prog.rules,
                       mesh=mesh, donate=True, guard=GuardPolicy())
print(step.lower(state._replace(params=params), batch).compile().as_text())
"""
_ALL_REDUCE = re.compile(r"^\s*%(\S+) = .*? all-reduce\((.*?)\), channel")


def test_column_sums_all_reduce_is_named_for_the_chip():
    """The same step compiled for a described v5e:2x2, whose compiler
    combines independent all-reduces: the column sums of one update travel
    in one all-reduce of their own, named ``norm_psum``, whose operands
    are the six SCALE sums of squares (five stacked matrices and the tied
    table) and not, say, the loss sum."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_FUSED="compiled",
               TPU_LOG_DIR="disabled",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _CHIP.format(root=str(ROOT), src=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    if p.stdout.strip() == "no topology":
        pytest.skip("no v5e:2x2 topology can be described here")
    scopes = tr.hlo_scopes(p.stdout)
    named = {}
    for line in p.stdout.splitlines():
        m = _ALL_REDUCE.match(line)
        if m and "/norm_psum/" in scopes.get(m.group(1), ""):
            named[m.group(1)] = re.findall(r"%([^ ,]+)", m.group(2))
    assert len(named) == 1, named
    ops, = named.values()
    assert len(ops) == 6, ops
    assert all("/optimizer_update/" in scopes[o] for o in ops), \
        {o: scopes[o] for o in ops}


PSUM = "jit(train_step)/optimizer_update/jit(_norm_update_impl)/" \
    "shard_map/norm_psum/psum"
OTHER = "jit(train_step)/clip/reduce_sum"


def run_of(scopes, trace=True):
    ops = [(0, 40, "%fusion.1 = f32[8] fusion(f32[8] %p)"),
           (40, 45, "%all-reduce.2 = f32[8] all-reduce(f32[8] %p)"),
           (45, 50, "%all-reduce.3 = f32[8] all-reduce(f32[8] %p)"),
           (60, 100, "%fusion.4 = f32[8] fusion(f32[8] %p)")]
    t = tr.Trace({0: ops, 1: ops}, {}, []) if trace else None
    return Run(model={}, chips=2, peak={}, setup_s=0.0, window_s=100e-9,
               batches=[], step_hbm_bytes=0, trace=t, scopes=scopes)


def test_norm_psum_share_of_busy_time():
    scopes = {"all-reduce.2": PSUM, "all-reduce.3": OTHER}
    # busy 0..50 and 60..100 on each device: 90; the named psum 5
    assert read_metric("norm_psum_share", run_of(scopes)) == \
        pytest.approx(100 * 5 / 90)


@pytest.mark.parametrize("scopes, trace", [
    ({"all-reduce.2": PSUM}, False),                # untraced run
    ({"all-reduce.2": OTHER, "all-reduce.3": OTHER}, True),  # no scope
], ids=["no_trace", "no_scope"])
def test_norm_psum_share_is_none(scopes, trace):
    assert read_metric("norm_psum_share", run_of(scopes, trace)) is None
