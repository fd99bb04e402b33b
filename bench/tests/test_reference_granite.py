"""The Granite reference (``bench/reference_granite.py``) against the
program at the SMOKE widths: the four multipliers and the tied head, on
one CPU device and on four (FSDP over ``data``=4, so the SCALE kernels'
cross-chip psum of column sums runs), and the program with any one
multiplier left out."""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import compare, harness, packing, reference_granite
from bench.tests import tiny_granite

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 3
# float32 on both sides: only summation order differs (sound runs read
# under 1e-6 on each)
LIMITS = {"loss_gap": 1e-5, "grad_gap": 1e-5, "delta_gap": 1e-3,
          "delta_diff": 1e-3}
NEUTRAL = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
           "attention_multiplier": None, "logits_scaling": 1.0}


def readings(chips: int = 1, seed: int = SEED) -> dict:
    """The compared numbers of the program's first steps against the
    reference's, on ``chips`` devices."""
    cfg = tiny_granite.config(chips)
    ring = packing.make_ring(tiny_granite.TRAFFIC, cfg["tokens_per_step"],
                             cfg["model"]["vocab_size"], seed)
    prog = harness.Program(cfg, tiny_granite.TRAFFIC)
    prog.start(seed)
    mine = harness.first_steps(prog, ring)
    prog.close()
    ref = reference_granite.Reference(
        cfg["model"], cfg["optimizer"], jax.devices()[:chips]).run(
            seed, ring[:harness.REF_STEPS], against={"p": mine.pop("params")})
    mine.update(ref["against"]["p"])
    return {k: v for k, (v, _) in compare.readings(mine, ref).items()}


def test_weights_match_the_program_bitwise():
    cfg = tiny_granite.config()
    prog = harness.Program(cfg, tiny_granite.TRAFFIC)
    assert prog.cfg.tie_embeddings
    mine = harness.flat_paths(prog.init(jax.random.PRNGKey(SEED)))
    ref = reference_granite.Reference(cfg["model"], cfg["optimizer"],
                                      jax.devices()[:1])
    theirs = dict(reference_granite.R.leaf_paths(ref.init(SEED)))
    assert "lm_head/w" not in theirs
    assert sorted(mine) == sorted(theirs)
    for k in mine:
        np.testing.assert_array_equal(np.asarray(mine[k]),
                                      np.asarray(theirs[k]))


def test_float32_program_agrees_with_the_reference():
    got = readings()
    assert compare.judge(got, LIMITS), got


@pytest.mark.parametrize("name", sorted(NEUTRAL))
def test_program_without_one_multiplier_is_not_correct(monkeypatch, name):
    from repro.configs import granite_3_8b
    monkeypatch.setattr(granite_3_8b, "CONFIG", dataclasses.replace(
        granite_3_8b.CONFIG, **{name: NEUTRAL[name]}))
    got = readings()
    assert not compare.judge(got, LIMITS), got


_FOUR = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from bench.tests import test_reference_granite as t
print(json.dumps(t.readings(chips=4)))
"""


def test_four_devices_agree_with_the_reference():
    """FSDP over four CPU devices: the sharded tied table, its head
    momentum and the hidden matrices' cross-device column sums."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _FOUR.format(root=str(ROOT), src=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert compare.judge(got, LIMITS), got
