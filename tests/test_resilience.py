"""Resilience chaos matrix: in-jit anomaly guard, hardened checkpoint
recovery, and the REPRO_FAULTS fault-injection harness end to end.

The invariants under test (ISSUE 8 acceptance):
  * NaN/Inf grads injected at step k -> the run completes and its params +
    optimizer state are **bitwise** equal to a clean run with step k's
    batch dropped (the guard's element-select passthrough);
  * a corrupted-latest checkpoint costs one checkpoint interval, not the
    run (restore_latest degrades to the newest verifiable committed step);
  * a simulated kill mid-commit never leaves a COMMITTED step that fails
    verification;
  * a forced kernel-dispatch failure degrades to the jnp reference path,
    logged once.
"""
import json
import os
import signal
import subprocess
import sys
import warnings
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.checkpoint as ckpt
from repro.core import make_optimizer
from repro.data import make_dataset
from repro.kernels import dispatch
from repro.models import init_params
from repro.training import (GuardPolicy, SimulatedKill, faults,
                            guard_step, guard_verdict, init_guard_state,
                            init_state, make_train_step, parse_faults,
                            resolve_plan)


@pytest.fixture(autouse=True)
def _clean_fault_state():
    """Every case starts and ends with rewound fault counters and fallback
    tallies (REPRO_FAULTS itself is scoped per-test via monkeypatch)."""
    faults.reset()
    dispatch.reset_fallbacks()
    yield
    faults.reset()
    dispatch.reset_fallbacks()


# --------------------------------------------------------------------------
# REPRO_FAULTS grammar
# --------------------------------------------------------------------------

def test_parse_faults_grammar_roundtrip():
    p = parse_faults("nan_grad@3;inf_grad@5; io_error@save:2 ;"
                     "kill@commit:1;dispatch_fail@norm_update")
    assert p.grad_fault_steps("nan") == (3,)
    assert p.grad_fault_steps("inf") == (5,)
    assert p.any_grad_faults
    assert p.io_errors == (("save", 2),)
    assert p.kills == (("commit", 1),)
    assert p.dispatch_ops == ("norm_update",)


@pytest.mark.parametrize("bad", [
    "nan_grad",                # no @arg
    "nan_grad@x",              # non-integer step
    "nan_grad@-1",             # negative step
    "nan_grad@3:4",            # grad faults take exactly one arg
    "io_error@tmp:1",          # unknown site
    "kill@save",               # missing occurrence count
    "dispatch_fail@",          # empty op
    "frobnicate@1",            # unknown kind
])
def test_parse_faults_rejects_bad_clauses(bad):
    with pytest.raises(ValueError, match="REPRO_FAULTS"):
        parse_faults(bad)


def test_resolve_plan_none_when_unset(monkeypatch):
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    assert resolve_plan() is None
    monkeypatch.setenv(faults.ENV_VAR, "  ")
    assert resolve_plan() is None
    monkeypatch.setenv(faults.ENV_VAR, "nan_grad@7")
    assert resolve_plan().grad_fault_steps("nan") == (7,)


# --------------------------------------------------------------------------
# Guard unit behavior (pure scalar arithmetic, no training loop)
# --------------------------------------------------------------------------

def test_guard_verdict_finite_checks():
    policy = GuardPolicy()
    gs = init_guard_state()
    ok = guard_verdict(policy, gs, jnp.float32(1.0), jnp.float32(2.0))
    assert bool(ok)
    for loss, gnorm in [(jnp.nan, 1.0), (1.0, jnp.nan),
                        (jnp.inf, 1.0), (1.0, jnp.inf)]:
        assert not bool(guard_verdict(policy, gs, jnp.float32(loss),
                                      jnp.float32(gnorm)))


def test_guard_spike_detection_arms_after_warmup():
    policy = GuardPolicy(spike_factor=2.0, spike_warmup=2, ema_beta=0.5)
    gs = init_guard_state()
    # before any accepted step the spike check is unarmed: a huge finite
    # loss passes (a fresh run's first losses are legitimately huge)
    assert bool(guard_verdict(policy, gs, jnp.float32(100.0),
                              jnp.float32(1.0)))
    for _ in range(3):
        ok = guard_verdict(policy, gs, jnp.float32(1.0), jnp.float32(1.0))
        gs, rb = guard_step(policy, gs, ok, jnp.float32(1.0))
        assert not bool(rb)
    # debiased EMA of three accepted 1.0 losses is 1.0
    np.testing.assert_allclose(float(gs.loss_ema) / (1 - 0.5 ** 3), 1.0)
    assert not bool(guard_verdict(policy, gs, jnp.float32(5.0),
                                  jnp.float32(1.0)))  # 5 > 2*1: spike
    assert bool(guard_verdict(policy, gs, jnp.float32(1.5),
                              jnp.float32(1.0)))      # 1.5 <= 2*1: calm


def test_guard_streak_and_rollback_flag():
    policy = GuardPolicy(max_bad_steps=2)
    gs = init_guard_state()
    bad, good = jnp.zeros((), bool), jnp.ones((), bool)
    gs, rb = guard_step(policy, gs, bad, jnp.float32(jnp.nan))
    assert (int(gs.consecutive_bad), int(gs.skipped), bool(rb)) == (1, 1, False)
    gs, rb = guard_step(policy, gs, bad, jnp.float32(jnp.nan))
    assert (int(gs.consecutive_bad), int(gs.skipped), bool(rb)) == (2, 2, True)
    gs, rb = guard_step(policy, gs, good, jnp.float32(1.0))
    assert (int(gs.consecutive_bad), int(gs.skipped), bool(rb)) == (0, 2, False)
    # the bad loss never poisons the EMA (only the accepted 1.0 entered)
    np.testing.assert_allclose(float(gs.loss_ema), 0.01, rtol=1e-5)


def test_guard_requires_guard_carrying_state(tiny):
    tx = make_optimizer("scale", 1e-3)
    params = init_params(jax.random.PRNGKey(0), tiny)
    state = init_state(params, tx)  # guard=False: no GuardState leaves
    step_fn = make_train_step(tiny, tx, guard=GuardPolicy())
    ds = make_dataset(tiny, seq_len=32, global_batch=8, seed=0)
    with pytest.raises(ValueError, match="guard-carrying"):
        step_fn(state, ds.host_batch_at(0))


# --------------------------------------------------------------------------
# The acceptance invariant: injected grad fault at step k == clean run
# minus that step, bitwise
# --------------------------------------------------------------------------

def _guarded_run(cfg, batch_ids, plan=None):
    tx = make_optimizer("scale", 3e-3)
    params = init_params(jax.random.PRNGKey(0), cfg)
    state = init_state(params, tx, guard=True)
    step_fn = jax.jit(make_train_step(cfg, tx, clip_norm=1.0,
                                      guard=GuardPolicy(), faults=plan))
    ds = make_dataset(cfg, seq_len=32, global_batch=8, seed=0)
    metrics = {}
    for i in batch_ids:
        state, metrics = step_fn(state, ds.host_batch_at(i))
    return state, metrics


@pytest.mark.parametrize("kind", ["nan_grad", "inf_grad"])
def test_injected_grad_fault_skips_step_bitwise(tiny, kind):
    """Faulted 8-step run == clean run that never saw step 3's batch,
    bitwise on params AND optimizer state (the element-select passthrough
    leaves the old buffers untouched; the candidate NaN update and the
    discarded loss never leak into anything)."""
    faulted, fm = _guarded_run(tiny, range(8),
                               plan=parse_faults(f"{kind}@3"))
    clean, _ = _guarded_run(tiny, [0, 1, 2, 4, 5, 6, 7])
    assert int(fm["skipped"]) == 1
    assert not bool(fm["rollback"])
    for name, tree_f, tree_c in [("params", faulted.params, clean.params),
                                 ("opt_state", faulted.opt_state,
                                  clean.opt_state)]:
        for a, b in zip(jax.tree_util.tree_leaves(tree_f),
                        jax.tree_util.tree_leaves(tree_c)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
    # the faulted run still advanced its step counter through the skip
    assert int(faulted.step) == 8 and int(clean.step) == 7


def test_faulted_build_is_bitwise_inert_off_the_fault_step(tiny):
    """A train step built WITH a fault plan matches the clean build bitwise
    on every non-fault step (the traced select is inert when step != k)."""
    faulted, _ = _guarded_run(tiny, range(3), plan=parse_faults("nan_grad@9"))
    clean, _ = _guarded_run(tiny, range(3))
    for a, b in zip(jax.tree_util.tree_leaves(faulted.params),
                    jax.tree_util.tree_leaves(clean.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_guarded_state_checkpoint_roundtrip(tiny, tmp_path):
    """TrainState.guard leaves survive save/restore_latest exactly."""
    state, _ = _guarded_run(tiny, range(2))
    ckpt.save(str(tmp_path), 2, state)
    restored, step = ckpt.restore_latest(str(tmp_path), state)
    assert step == 2
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# Hardened checkpoint recovery
# --------------------------------------------------------------------------

def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": {"w": jax.random.normal(k, (8, 16)),
                  "b": jnp.arange(5, dtype=jnp.int32)},
            "step": jnp.asarray(7, jnp.int32)}


def _corrupt_shard(step_dir):
    (shard,) = (os.path.join(step_dir, n) for n in os.listdir(step_dir)
                if n.startswith("shard_00000"))
    with open(shard, "r+b") as f:
        f.seek(10)
        f.write(b"\x00\x00\x00\x00")


def _assert_committed_steps_verifiable(directory, like):
    """The atomicity invariant: every step dir carrying a COMMITTED marker
    must pass full verification — a kill at any injected point may lose a
    checkpoint but never corrupt a committed one."""
    for name in os.listdir(directory):
        full = os.path.join(directory, name)
        if name.startswith("step_") and not name.endswith(".tmp") \
                and os.path.exists(os.path.join(full, "COMMITTED")):
            ckpt.restore(directory, int(name[5:]), like)


def test_restore_latest_degrades_past_corrupt_shard(tmp_path):
    tree = _tree()
    ckpt.save(str(tmp_path), 1, tree)
    path2 = ckpt.save(str(tmp_path), 2, tree)
    _corrupt_shard(path2)
    with pytest.warns(UserWarning, match="falling back"):
        got, step = ckpt.restore_latest(str(tmp_path), tree)
    assert step == 1
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_latest_none_when_all_corrupt(tmp_path):
    tree = _tree()
    _corrupt_shard(ckpt.save(str(tmp_path), 1, tree))
    with pytest.warns(UserWarning, match="falling back"):
        assert ckpt.restore_latest(str(tmp_path), tree) is None


def test_leaf_checksum_mismatch_names_the_leaf(tmp_path):
    """Per-leaf crc32s catch (and name) a corruption the shard-level crc
    cannot localize; here the manifest entry is tampered so the shard crc
    still passes and only the leaf check can object."""
    tree = _tree()
    path = ckpt.save(str(tmp_path), 4, tree)
    man_path = os.path.join(path, "manifest.00000.json")
    with open(man_path) as f:
        man = json.load(f)
    assert man["leaf_checksums"]  # the new field is present
    man["leaf_checksums"]["a/w"] += 1
    with open(man_path, "w") as f:
        json.dump(man, f)
    with pytest.raises(ckpt.CorruptCheckpointError, match="a/w"):
        ckpt.restore(str(tmp_path), 4, tree)
    # and restore_latest degrades across it like any other corruption
    with pytest.warns(UserWarning, match="falling back"):
        assert ckpt.restore_latest(str(tmp_path), tree) is None


def test_leaf_checksums_match_shard_contents(tmp_path):
    tree = _tree()
    path = ckpt.save(str(tmp_path), 1, tree)
    with open(os.path.join(path, "manifest.00000.json")) as f:
        man = json.load(f)
    assert man["leaf_checksums"]["a/b"] == zlib.crc32(
        np.asarray(tree["a"]["b"]).tobytes())


def test_io_errors_absorbed_by_retry(tmp_path, monkeypatch):
    tree = _tree()
    monkeypatch.setenv(faults.ENV_VAR, "io_error@save:2")
    with pytest.warns(UserWarning, match="retry"):
        ckpt.save(str(tmp_path), 1, tree, io_retries=3, io_backoff=0.01)
    monkeypatch.delenv(faults.ENV_VAR)
    got = ckpt.restore(str(tmp_path), 1, tree)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_io_errors_beyond_retry_budget_raise(tmp_path, monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "io_error@save:9")
    with pytest.warns(UserWarning, match="retry"):
        with pytest.raises(OSError, match="injected IO error"):
            ckpt.save(str(tmp_path), 1, _tree(), io_retries=2,
                      io_backoff=0.01)
    assert ckpt.latest_step(str(tmp_path)) is None


def test_commit_io_error_absorbed_by_retry(tmp_path, monkeypatch):
    tree = _tree()
    monkeypatch.setenv(faults.ENV_VAR, "io_error@commit:1")
    with pytest.warns(UserWarning, match="retry"):
        ckpt.save(str(tmp_path), 1, tree, io_retries=2, io_backoff=0.01)
    monkeypatch.delenv(faults.ENV_VAR)
    assert ckpt.latest_step(str(tmp_path)) == 1
    _assert_committed_steps_verifiable(str(tmp_path), tree)


def test_mid_commit_kill_never_yields_committed_step(tmp_path, monkeypatch):
    """Kill after the merged manifest but before the COMMITTED marker: the
    step is lost (never committed), the tree never half-committed, and the
    next save of the same step recovers fully."""
    tree = _tree()
    monkeypatch.setenv(faults.ENV_VAR, "kill@commit:1")
    with pytest.raises(SimulatedKill):
        ckpt.save(str(tmp_path), 5, tree)
    assert ckpt.latest_step(str(tmp_path)) is None
    _assert_committed_steps_verifiable(str(tmp_path), tree)
    # retries must not have been able to absorb the kill
    monkeypatch.delenv(faults.ENV_VAR)
    faults.reset()
    ckpt.save(str(tmp_path), 5, tree)
    assert ckpt.latest_step(str(tmp_path)) == 5
    _assert_committed_steps_verifiable(str(tmp_path), tree)


def test_mid_save_kill_leaves_unvouched_shard_only(tmp_path, monkeypatch):
    """Kill between the shard write and this host's manifest: the tmp dir
    holds a shard no manifest vouches for; nothing is committed and a
    clean re-save overwrites the debris."""
    tree = _tree()
    monkeypatch.setenv(faults.ENV_VAR, "kill@save:1")
    with pytest.raises(SimulatedKill):
        ckpt.save(str(tmp_path), 3, tree)
    tmp_dir = str(tmp_path / "step_0000000003.tmp")
    assert os.path.isdir(tmp_dir)
    assert any(n.startswith("shard_") for n in os.listdir(tmp_dir))
    assert not any(n.startswith("manifest.") for n in os.listdir(tmp_dir))
    assert ckpt.latest_step(str(tmp_path)) is None
    monkeypatch.delenv(faults.ENV_VAR)
    faults.reset()
    ckpt.save(str(tmp_path), 3, tree)
    assert ckpt.latest_step(str(tmp_path)) == 3
    _assert_committed_steps_verifiable(str(tmp_path), tree)


def test_async_save_raises_from_wait_and_done(tmp_path, monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "io_error@save:9")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # worker retry warnings
        handle = ckpt.save_async(str(tmp_path), 1, _tree(), io_retries=0,
                                 io_backoff=0.0)
        with pytest.raises(OSError, match="injected IO error"):
            handle.wait()
    # the error keeps surfacing: done must raise too, never report a clean
    # True for a save that failed
    with pytest.raises(OSError, match="injected IO error"):
        handle.done


# --------------------------------------------------------------------------
# Forced kernel-dispatch failure -> reference-path degradation
# --------------------------------------------------------------------------

def test_dispatch_fault_degrades_to_reference(monkeypatch):
    g = jnp.asarray(np.random.RandomState(0).randn(32, 16), jnp.float32)
    th = jnp.asarray(np.random.RandomState(1).randn(32, 16), jnp.float32)
    monkeypatch.setenv("REPRO_FUSED", "off")
    ref = dispatch.norm_update(th, g, 0.01, "col")
    monkeypatch.setenv("REPRO_FUSED", "interpret")  # force the kernel route
    monkeypatch.setenv(faults.ENV_VAR, "dispatch_fail@norm_update")
    with pytest.warns(UserWarning, match="degrading to the jnp reference"):
        out = dispatch.norm_update(th, g, 0.01, "col")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert dispatch.fallback_counts() == {"norm_update": 1}
    # the warning fires once per (op, failure class); the count keeps going
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out2 = dispatch.norm_update(th, g, 0.01, "col")
    assert not any("degrading" in str(x.message) for x in w)
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(ref))
    assert dispatch.fallback_counts() == {"norm_update": 2}


def test_dispatch_fault_wildcard_hits_every_op(monkeypatch):
    g = jnp.asarray(np.random.RandomState(0).randn(32, 16), jnp.float32)
    monkeypatch.setenv("REPRO_FUSED", "off")
    ref = dispatch.normalize(g, "col")
    monkeypatch.setenv("REPRO_FUSED", "interpret")
    monkeypatch.setenv(faults.ENV_VAR, "dispatch_fail@*")
    with pytest.warns(UserWarning, match="degrading"):
        out = dispatch.normalize(g, "col")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert dispatch.fallback_counts().get("normalize") == 1


def test_real_kernel_failure_raises_on_tpu(monkeypatch):
    """Off the TPU a failing kernel route degrades; on a TPU only an
    injected dispatch_fail does — a real failure stops the run."""
    def boom():
        raise RuntimeError("kernel does not lower")

    ref = lambda: "ref"
    with pytest.warns(UserWarning, match="degrading"):
        assert dispatch._guarded("normalize", boom, ref) == "ref"
    monkeypatch.setattr(dispatch, "backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="does not lower"):
        dispatch._guarded("normalize", boom, ref)
    monkeypatch.setenv(faults.ENV_VAR, "dispatch_fail@normalize")
    with pytest.warns(UserWarning, match="degrading"):
        assert dispatch._guarded("normalize", lambda: "kernel", ref) == "ref"
    assert dispatch.fallback_counts() == {"normalize": 2}


def test_guarded_train_step_survives_dispatch_fault(tiny, monkeypatch):
    """The full stack degrades gracefully: a train step whose optimizer
    kernels are forced to fail still trains (reference path), finite."""
    monkeypatch.setenv("REPRO_FUSED", "interpret")
    monkeypatch.setenv(faults.ENV_VAR, "dispatch_fail@*")
    with pytest.warns(UserWarning, match="degrading"):
        state, metrics = _guarded_run(tiny, range(2))
    assert np.isfinite(float(metrics["loss"]))
    assert int(metrics["skipped"]) == 0
    assert sum(dispatch.fallback_counts().values()) >= 1


# --------------------------------------------------------------------------
# Driver-level recovery (launch/train.py)
# --------------------------------------------------------------------------

def test_cli_skips_injected_nan_and_completes(tmp_path, monkeypatch, capsys):
    from repro.launch.train import main
    monkeypatch.setenv(faults.ENV_VAR, "nan_grad@2")
    loss = main(["--arch", "qwen2-7b", "--smoke", "--steps", "6",
                 "--batch", "4", "--seq", "32", "--log-every", "1",
                 "--ckpt-dir", str(tmp_path), "--ckpt-every", "6"])
    out = capsys.readouterr().out
    assert np.isfinite(loss)
    assert "skipped 1" in out
    assert ckpt.latest_step(str(tmp_path)) == 6


def test_cli_rollback_without_checkpoint_cuts_lr_and_continues(
        monkeypatch, capsys):
    from repro.launch.train import main
    monkeypatch.setenv(faults.ENV_VAR, "nan_grad@2;nan_grad@3")
    loss = main(["--arch", "qwen2-7b", "--smoke", "--steps", "6",
                 "--batch", "4", "--seq", "32", "--log-every", "1",
                 "--max-bad-steps", "2"])
    out = capsys.readouterr().out
    assert np.isfinite(loss)
    assert "rollback #1" in out and "peak lr x0.5" in out


def test_cli_bounded_rollbacks_abort(tmp_path, monkeypatch):
    """Deterministic faults replay identically after a rollback restore —
    the driver must abort after --max-rollbacks instead of looping."""
    from repro.launch.train import main
    # checkpoints land at steps 2 and 4 (before the first fault), so every
    # rollback restores to step 4 and replays straight into the same two
    # injected faults: rollback #2 must abort, not loop
    monkeypatch.setenv(faults.ENV_VAR, "nan_grad@4;nan_grad@5")
    with pytest.raises(RuntimeError, match="giving up after 1 rollbacks"):
        main(["--arch", "qwen2-7b", "--smoke", "--steps", "8",
              "--batch", "4", "--seq", "32", "--log-every", "1",
              "--ckpt-dir", str(tmp_path), "--ckpt-every", "2",
              "--max-bad-steps", "2", "--max-rollbacks", "1"])


def _cli_env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_FUSED", None)
    env.pop("REPRO_FAULTS", None)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    return env


def test_sigterm_writes_final_checkpoint_and_exits_cleanly(tmp_path):
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen2-7b",
         "--smoke", "--steps", "100000", "--batch", "2", "--seq", "32",
         "--log-every", "1", "--ckpt-dir", str(tmp_path), "--ckpt-every",
         "100000", "--resume", "auto"],
        env=_cli_env(), stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    lines = []
    try:
        for line in proc.stdout:           # wait for the first real step
            lines.append(line)
            if line.startswith("step "):
                break
        else:
            pytest.fail("driver exited before its first step:\n"
                        + "".join(lines))
        proc.send_signal(signal.SIGTERM)
        lines.extend(proc.stdout)
        assert proc.wait(timeout=300) == 0, "".join(lines)
    finally:
        proc.kill()
    out = "".join(lines)
    assert "exiting cleanly" in out, out
    assert ckpt.latest_step(str(tmp_path)) is not None


def test_guard_skips_nan_step_under_forced_8_devices():
    """The guard's select passthrough under a real 8-way sharded mesh (the
    tier1-multidevice configuration): the skipped step leaves the sharded
    params finite and training continues."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["REPRO_FAULTS"] = "nan_grad@1"
import jax, jax.numpy as jnp, numpy as np
from repro.core import make_optimizer
from repro.data import make_dataset
from repro.launch.mesh import make_host_mesh
from repro.models import ModelConfig, init_params, param_logical_axes
from repro.models.sharding import Rules, tree_shardings
from repro.training import (GuardPolicy, init_state, make_train_step,
                            resolve_plan)

assert len(jax.devices()) == 8
cfg = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                  dtype="float32", attn_kv_block=16, attn_q_block=16,
                  loss_chunk=16)
mesh = make_host_mesh(data=8)
rules = Rules(cfg.rule_overrides)
tx = make_optimizer("scale", 1e-3)
params = init_params(jax.random.PRNGKey(0), cfg)
params = jax.device_put(params, tree_shardings(param_logical_axes(cfg),
                                               mesh, rules, params))
state = init_state(params, tx, guard=True)
step_fn = make_train_step(cfg, tx, clip_norm=1.0, rules=rules, mesh=mesh,
                          donate=True, guard=GuardPolicy(),
                          faults=resolve_plan())
ds = make_dataset(cfg, seq_len=32, global_batch=8, seed=0)
m = {}
for i in range(3):
    state, m = step_fn(state, ds.host_batch_at(i))
assert int(m["skipped"]) == 1, m
assert np.isfinite(float(m["loss"])), m
for leaf in jax.tree_util.tree_leaves(state.params):
    assert np.isfinite(np.asarray(leaf)).all()
print("OK")
"""
    res = subprocess.run([sys.executable, "-c", script], env=_cli_env(),
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout
