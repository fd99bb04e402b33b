"""The plain reference against the program at a CPU size, and its fp8
control against the reference."""
import jax
import numpy as np
import pytest

from bench import compare, harness, packing, reference
from bench.tests import tiny

SEED = 2**31 + 3


def first(dtype, seed):
    cfg = tiny.config(dtype)
    ring = packing.make_ring(tiny.TRAFFIC, cfg["tokens_per_step"],
                             cfg["model"]["vocab_size"], seed)
    return cfg, ring


def test_weights_match_the_program_bitwise():
    cfg, _ = first("bfloat16", SEED)
    prog = harness.Program(cfg, tiny.TRAFFIC)
    mine = harness.flat_paths(prog.init(jax.random.PRNGKey(SEED)))
    ref = reference.Reference(cfg["model"], cfg["optimizer"],
                              jax.devices()[:1])
    theirs = dict(reference.leaf_paths(ref.init(SEED)))
    assert sorted(mine) == sorted(theirs)
    for k in mine:
        np.testing.assert_array_equal(np.asarray(mine[k], np.float32),
                                      np.asarray(theirs[k], np.float32))


@pytest.mark.parametrize("bias", [True, False], ids=["qkv_bias", "no_bias"])
def test_float32_program_agrees_with_the_reference(bias):
    cfg, ring = first("float32", SEED)
    cfg["model"]["attention_bias"] = bias
    prog = harness.Program(cfg, tiny.TRAFFIC)
    prog.start(SEED)
    mine = harness.first_steps(prog, ring)
    prog.close()
    ref = reference.Reference(cfg["model"], cfg["optimizer"],
                              jax.devices()[:1]).run(
        SEED, ring[:harness.REF_STEPS], against={"p": mine.pop("params")})
    mine.update(ref["against"]["p"])
    got = {k: v for k, (v, _) in compare.readings(mine, ref).items()}
    # float32 on both sides: only summation order differs
    assert got["loss_gap"] < 1e-5
    assert got["grad_gap"] < 1e-5
    assert got["delta_gap"] < 1e-3
    assert got["delta_diff"] < 1e-3


@pytest.mark.parametrize("seed", [1, 2])
def test_fp8_control_is_not_correct(seed):
    """The control (the reference in fp8 in the program's place) fails a
    limit that the bfloat16 program meets at this size, on each seed."""
    limits = {"loss_gap": 1.2e-4, "grad_gap": 0.03, "delta_gap": 0.12,
              "delta_diff": 0.06}
    cfg, ring = first("bfloat16", seed)
    prog = harness.Program(cfg, tiny.TRAFFIC)
    prog.start(seed)
    mine = harness.first_steps(prog, ring)
    prog.close()
    devs = jax.devices()[:1]
    ctl = reference.Reference(cfg["model"], cfg["optimizer"], devs,
                              precision="fp8").run(
        seed, ring[:harness.REF_STEPS], keep_params=True)
    ref = reference.Reference(cfg["model"], cfg["optimizer"], devs).run(
        seed, ring[:harness.REF_STEPS],
        against={"p": mine.pop("params"), "c": ctl.pop("params")})
    mine.update(ref["against"]["p"])
    ctl.update(ref["against"]["c"])
    sound = {k: v for k, (v, _) in compare.readings(mine, ref).items()}
    control = {k: v for k, (v, _) in compare.readings(ctl, ref).items()}
    assert compare.judge(sound, limits), sound
    assert not compare.judge(control, limits), control
