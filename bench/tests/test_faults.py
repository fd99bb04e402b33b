"""A whole run of the harness on the CPU, past its look for a chip, with
the timed path broken underneath: ``correct`` has to come out false for
each fault a training cell can have, and true without one."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import harness
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
# float32 on both sides at this size: sound runs read under 1e-5
LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-3, "delta_gap": 1e-2,
          "delta_diff": 1e-2}
METRICS = [{"name": "tokens_per_s", "unit": "tokens/s"},
           {"name": "step_hbm_gib", "unit": "GiB"},
           {"name": "setup_s", "unit": "s"}]


def run(chips: int = 1) -> dict:
    return harness.run_cell({"name": "tiny", "chips": chips},
                            tiny.config("float32", chips), tiny.TRAFFIC,
                            LIMITS, METRICS, 2**31 + 5, 0.3, False,
                            t_start=time.time(), need_tpu=False,
                            log=lambda s: None)


def broken(monkeypatch, fault: str) -> None:
    """Wrap the program's train step so that it makes ``fault``."""
    import jax
    import repro.training as training
    real = training.make_train_step

    def make(cfg, tx, **kw):
        kw["donate"] = False
        step = real(cfg, tx, **kw)

        def frozen(state, batch):
            return state, step(state, batch)[1]

        def half(state, batch):
            w = batch["loss_weights"]
            return step(state, dict(batch, loss_weights=w.at[
                w.shape[0] // 2:].set(0.0)))
        return jax.jit({"frozen": frozen, "half_batch": half}[fault])

    monkeypatch.setattr(training, "make_train_step", make)


def test_sound_run_is_correct():
    out = run()
    assert out["correct"], out["compared"]
    assert list(out)[-1] == "compared"
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "step_hbm_gib", "setup_s"}


@pytest.mark.parametrize("fault", ["frozen", "half_batch"])
def test_broken_step_is_not_correct(monkeypatch, fault):
    broken(monkeypatch, fault)
    out = run()
    assert not out["correct"], out["compared"]


def test_sign_flipped_update_is_not_correct():
    """The hidden matrices stepped up their gradient instead of down: each
    column's change keeps its norm, so only ``delta_diff`` sees it."""
    from bench.calibrate import planted
    with planted("sign_flip"):
        out = run()
    got = {k: v["value"] for k, v in out["compared"].items()}
    assert not out["correct"], got
    assert got["delta_diff"] > 1.5, got
    assert got["delta_gap"] < LIMITS["delta_gap"], got


_EXCHANGE = """
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}]
from bench.tests import test_faults
from repro.kernels import dispatch
if {broken}:
    dispatch._psum_ss = lambda ss, plan, axis: ss
print(json.dumps(test_faults.run(chips=2)["correct"]))
"""


@pytest.mark.parametrize("broken_exchange", [False, True])
def test_exchange_between_chips(broken_exchange):
    """Two CPU devices: the SCALE update's cross-chip psum of column sums
    of squares left out makes the run not correct."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    code = _EXCHANGE.format(root=str(ROOT), src=str(ROOT / "src"),
                            broken=broken_exchange)
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) is (
        not broken_exchange)
