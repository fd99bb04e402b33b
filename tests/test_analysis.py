"""Tests for the repro.analysis static-analysis subsystem.

Each analyzer pass gets fixture snippets with seeded violations asserting
the exact rule IDs fire, plus a clean negative fixture; the registry-drift
pass is exercised against mutated registry rows and a mutated docstring
table; the CLI contract (exit 0 on the committed tree, non-zero on a
seeded fixture) runs through ``python -m repro.analysis`` itself.
"""
import dataclasses
import json
import subprocess
import sys
from pathlib import Path

from repro.analysis import collective_axes, jax_hygiene, kernel_contract
from repro.analysis import registry_drift
from repro.analysis.findings import (Finding, load_baseline,
                                     split_by_baseline, write_baseline)
from repro.analysis.lowering import (extract_region, region_matches,
                                     render_lowering_table)
from repro.core.api import OPTIMIZER_REGISTRY

REPO = Path(__file__).resolve().parents[1]
DISPATCH = REPO / "src" / "repro" / "kernels" / "dispatch.py"


def rules(findings):
    return {f.rule for f in findings}


# --------------------------------------------------------------------------
# kernel-contract (KC)
# --------------------------------------------------------------------------

BAD_KERNEL = '''
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, y_ref, o_ref, acc):
    o_ref[...] = jnp.dot(x_ref[...], y_ref[...])
    acc[...] += jnp.sum(x_ref[...])


def run(x, y):
    grid = (4, 4, 2)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((8, 8), lambda i, j: (i, j)),
                  pl.BlockSpec((8, 8), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((8, 8), lambda i, j: (i, 0)),
        input_output_aliases={5: 0},
    )(x, y)
'''

BAD_SCRATCH = '''
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, o_ref, acc):
    o_ref[...] = x_ref[...]


def run(x):
    return pl.pallas_call(
        _kernel,
        grid=(2,),
        in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 8), lambda i: (i, 0)),
        scratch_shapes=[pltpu.VMEM((8, 1), jnp.bfloat16)],
    )(x)
'''

CLEAN_KERNEL = '''
import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _mask(m, bm, i):
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0) + i * bm
    return rows < m


def _kernel(x_ref, o_ref, *, m, bm):
    i = pl.program_id(0)
    xm = jnp.where(_mask(m, bm, i), x_ref[...], 0.0)
    o_ref[...] = jnp.dot(xm, xm)


def run(x, m, bm):
    return pl.pallas_call(
        functools.partial(_kernel, m=m, bm=bm),
        grid=(2,),
        in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 8), lambda i: (i, 0)),
    )(x)
'''


def test_kc_missing_mask_and_arity_and_alias():
    found = kernel_contract.analyze_source("fixture.py", BAD_KERNEL)
    assert rules(found) == {"KC001", "KC003", "KC002"}
    # index_map arity flagged for all three 2-arg specs on the 3-D grid
    assert sum(f.rule == "KC001" for f in found) == 3
    # both the dot and the scratch sum accumulation are unmasked
    assert sum(f.rule == "KC003" for f in found) == 2
    # alias key 5 is out of range of the 2 inputs
    assert any(f.rule == "KC002" and "out of range" in f.message
               for f in found)


def test_kc_low_precision_scratch():
    found = kernel_contract.analyze_source("fixture.py", BAD_SCRATCH)
    assert rules(found) == {"KC004"}
    assert "bfloat16" in found[0].message


def test_kc_clean_fixture_negative():
    assert kernel_contract.analyze_source("fixture.py", CLEAN_KERNEL) == []


def test_kc_masked_through_nested_when_and_helper():
    # the real-kernel shape: compute hidden in a nested @pl.when function,
    # mask produced by a tuple-returning helper (resolver must follow both)
    src = '''
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _masks(i, bm, m):
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0) + i * bm
    return rows, rows < m


def _kernel(x_ref, o_ref, *, m, bm):
    i = pl.program_id(0)

    @pl.when(i >= 0)
    def _compute():
        _, valid = _masks(i, bm, m)
        xm = jnp.where(valid, x_ref[...], 0.0)
        o_ref[...] = jnp.dot(xm, xm)


def run(x, m, bm):
    import functools
    return pl.pallas_call(
        functools.partial(_kernel, m=m, bm=bm),
        grid=(2,),
        in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 8), lambda i: (i, 0)),
    )(x)
'''
    assert kernel_contract.analyze_source("fixture.py", src) == []


# The segment-id mask shape PR 9's packed attention kernels use: the score
# tile's validity ANDs the iota remainder/causal bounds with a segment-id
# equality ((bq, 1) == (1, bk)) read from dedicated operand refs.
_SEG_KERNEL_TEMPLATE = '''
import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _masks(i, j, bq, bk, kl, qseg, kseg):
    rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = {valid_expr}
    valid &= qseg == kseg
    return valid


def _kernel(q_ref, k_ref, v_ref, qs_ref, ks_ref, o_ref, *, bq, bk, kl):
    i, j = pl.program_id(0), pl.program_id(1)
    s = jnp.dot(q_ref[...], k_ref[...])
    valid = _masks(i, j, bq, bk, kl, qs_ref[...], ks_ref[...])
    s = jnp.where(valid, s, -1e30)
    p = jnp.where(valid, jnp.exp(s), 0.0)
    o_ref[...] = jnp.dot(p, v_ref[...])


def run(q, k, v, qs, ks, kl):
    return pl.pallas_call(
        functools.partial(_kernel, bq=8, bk=8, kl=kl),
        grid=(2, 2),
        in_specs=[pl.BlockSpec((8, 8), lambda i, j: (i, 0)),
                  pl.BlockSpec((8, 8), lambda i, j: (0, j)),
                  pl.BlockSpec((8, 8), lambda i, j: (j, 0)),
                  pl.BlockSpec((8, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((1, 8), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((8, 8), lambda i, j: (i, 0)),
    )(q, k, v, qs, ks)
'''

SEGMENT_KERNEL_CLEAN = _SEG_KERNEL_TEMPLATE.format(
    valid_expr="(cols < kl) & (rows >= cols)")
# segment equality ALONE: remainder lanes of a ragged tile are never
# bounded by the tile iota, so undefined memory still reaches both dots
SEGMENT_KERNEL_SEG_ONLY = _SEG_KERNEL_TEMPLATE.format(
    valid_expr="jnp.full((bq, bk), True)")


def test_kc_segment_mask_with_iota_bound_is_clean():
    assert kernel_contract.analyze_source(
        "fixture.py", SEGMENT_KERNEL_CLEAN) == []


def test_kc_segment_equality_alone_is_not_a_remainder_mask():
    found = kernel_contract.analyze_source("fixture.py",
                                           SEGMENT_KERNEL_SEG_ONLY)
    assert rules(found) == {"KC003"}
    # both the score dot and the p @ v contraction are unprotected
    assert sum(f.rule == "KC003" for f in found) == 2


# --------------------------------------------------------------------------
# collective-axes (CX)
# --------------------------------------------------------------------------

BAD_AXES = '''
from jax import lax
from jax.experimental.shard_map import shard_map

AXIS = "data"


def f(x):
    return lax.psum(x, "model")


def g(x):
    return lax.pmax(x, AXIS)


def h(x, mesh, sp):
    def body(a, b):
        return a + b
    return shard_map(body, mesh=mesh, in_specs=(sp,), out_specs=sp)(x)
'''

CLEAN_AXES = '''
from jax import lax
from jax.experimental.shard_map import shard_map


def f(x, plan):
    axes = plan.of("rows")
    return lax.psum(x, axes) if axes else x


def h(x, y, mesh, sp):
    def body(a, b):
        return a + b
    return shard_map(body, mesh=mesh, in_specs=(sp, sp),
                     out_specs=sp)(x, y)
'''


def test_cx_seeded_violations():
    found = collective_axes.analyze_source("fixture.py", BAD_AXES)
    assert rules(found) == {"CX001", "CX002", "CX003"}
    by_rule = {f.rule: f for f in found}
    assert "'model'" in by_rule["CX001"].message
    assert "'data'" in by_rule["CX002"].message
    assert "1 entries" in by_rule["CX003"].message


def test_cx_clean_fixture_negative():
    assert collective_axes.analyze_source("fixture.py", CLEAN_AXES) == []


def test_cx_dynamic_dispatch_probe_clean():
    assert collective_axes.check_dispatch_contract() == []


# --------------------------------------------------------------------------
# jax-hygiene (JH)
# --------------------------------------------------------------------------

BAD_HYGIENE = '''
import os
import jax
import jax.numpy as jnp


def step(x):
    if jnp.abs(x).max() > 1.0:
        x = x / 2
    return x


def probe(fn, x):
    try:
        return fn(x, extra=1)
    except TypeError:
        return fn(x)


@jax.jit
def jitted(x):
    mode = os.environ.get("REPRO_FUSED", "auto")
    return x if mode == "off" else x * 2
'''

CLEAN_HYGIENE = '''
import inspect
import os
import jax
import jax.numpy as jnp


def resolve_mode():
    return os.environ.get("REPRO_FUSED", "auto")  # outside jit: fine


def step(x, mode):
    if jnp.issubdtype(x.dtype, jnp.floating):  # static fact: fine
        x = jnp.where(jnp.abs(x) > 1.0, x / 2, x)
    return x


def probe(fn):
    return "extra" in inspect.signature(fn).parameters
'''


def test_jh_seeded_violations():
    found = jax_hygiene.analyze_source("fixture.py", BAD_HYGIENE)
    assert rules(found) == {"JH001", "JH002", "JH003"}


def test_jh_clean_fixture_negative():
    assert jax_hygiene.analyze_source("fixture.py", CLEAN_HYGIENE) == []


# --------------------------------------------------------------------------
# registry-drift (RD)
# --------------------------------------------------------------------------

def test_rd_committed_tree_clean():
    assert registry_drift.run() == []


def test_rd_fused_flag_mutation_fails():
    mutated = dict(OPTIMIZER_REGISTRY)
    mutated["sgd_colnorm"] = dataclasses.replace(
        mutated["sgd_colnorm"], fused=False)
    found = registry_drift.run(registry=mutated)
    got = rules(found)
    # the lowering table drifts, the Stages plans contradict the flag,
    # and the col kind is fused-coverable but marked unfused
    assert {"RD001", "RD003", "RD005"} <= got
    assert any("sgd_colnorm" in f.message for f in found)


def test_rd_registry_row_rename_fails():
    mutated = {("scole" if k == "scale" else k): v
               for k, v in OPTIMIZER_REGISTRY.items()}
    found = registry_drift.run(registry=mutated, build=False)
    assert "RD001" in rules(found)


def test_rd_docstring_table_mutation_fails():
    source = DISPATCH.read_text()
    region, _, _ = extract_region(source)
    assert "sgd_rownorm" in region
    mutated = source.replace("sgd_rownorm         yes",
                             "sgd_rownorm         no ")
    assert not region_matches(mutated)
    found = registry_drift.run(dispatch_source=mutated, build=False)
    assert "RD001" in rules(found)


def test_rd_coverage_matrix_missing_op():
    rendered = render_lowering_table()
    from repro.kernels import dispatch
    ops = [op for op in dispatch.REGISTRY if op != "flash_attention"]
    doc = ('"""' + " ".join(f"``{op}``" for op in ops)
           + "\n\n.. lowering-table-begin\n" + rendered
           + "\n.. lowering-table-end\n" + '"""\n')
    found = registry_drift.run(dispatch_source=doc, build=False)
    assert rules(found) == {"RD002"}
    assert any("flash_attention" in f.message for f in found)


def test_rd_unreachable_fused_flag():
    def no_impl_factory(lr, kind="col"):
        from repro.core.optimizers import normalized_sgd
        return normalized_sgd(lr, kind=kind)

    mutated = dict(OPTIMIZER_REGISTRY)
    mutated["sgd_colnorm"] = dataclasses.replace(
        mutated["sgd_colnorm"], factory=no_impl_factory)
    found = registry_drift.run(registry=mutated, build=False)
    assert "RD004" in rules(found)


def test_lowering_table_in_sync_on_disk():
    assert region_matches(DISPATCH.read_text())


def test_pipeline_carries_plans():
    from repro.core import make_optimizer
    tx = make_optimizer("scale")
    assert tx.plans is not None and set(tx.plans) == {
        "first", "last", "matrix", "vector"}
    # the plans drive RD003: scale's matrix plan is a bare col norm
    assert tx.plans["matrix"].norm == "col"


# --------------------------------------------------------------------------
# findings / baseline mechanics
# --------------------------------------------------------------------------

def test_baseline_roundtrip(tmp_path):
    f1 = Finding("KC003", "a.py", 10, "msg one")
    f2 = Finding("CX001", "b.py", 20, "msg two")
    path = tmp_path / "baseline.json"
    write_baseline(path, [f1])
    baseline = load_baseline(path)
    # line numbers do not participate in the key
    shifted = Finding("KC003", "a.py", 99, "msg one")
    new, suppressed = split_by_baseline([shifted, f2], baseline)
    assert new == [f2] and suppressed == [shifted]


def test_committed_baseline_is_empty():
    doc = json.loads(
        (REPO / "src" / "repro" / "analysis" / "baseline.json").read_text())
    assert doc["schema"] == "repro.analysis/baseline/v1"
    assert doc["suppressions"] == []


# --------------------------------------------------------------------------
# CLI contract
# --------------------------------------------------------------------------

def _run_cli(*args):
    import os
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, cwd=str(REPO), env=env)


def test_cli_exits_zero_on_committed_tree(tmp_path):
    out = tmp_path / "report.json"
    proc = _run_cli("--json", str(out))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(out.read_text())
    assert doc["schema"] == "repro.analysis/v1"
    assert doc["counts"]["new"] == 0


def test_cli_exits_nonzero_on_seeded_fixture(tmp_path):
    for name, src, want in [("bad_kernel.py", BAD_KERNEL, "KC"),
                            ("bad_axes.py", BAD_AXES, "CX"),
                            ("bad_hygiene.py", BAD_HYGIENE, "JH")]:
        fix = tmp_path / name
        fix.write_text(src)
        proc = _run_cli("--paths", str(fix), "--json", "-")
        assert proc.returncode == 2, (name, proc.stdout, proc.stderr)
        assert want in proc.stdout, (name, proc.stdout)


def test_cli_clean_fixture_exits_zero(tmp_path):
    fix = tmp_path / "clean.py"
    fix.write_text(CLEAN_KERNEL)
    proc = _run_cli("--paths", str(fix))
    assert proc.returncode == 0, proc.stdout + proc.stderr
