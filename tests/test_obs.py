"""Telemetry plane (repro.obs): sinks + logger, the in-jit stats
collector's bitwise-inertness and paper-shaped output, timing/profiling
units, dispatch fallback deltas, and the driver's multi-host log hygiene
(SIGTERM flush, single-writer JSONL under forced 8 devices)."""
import json
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_optimizer
from repro.core.labels import LAYER_GROUPS, layer_group
from repro.data import make_dataset
from repro.kernels import dispatch
from repro.obs import (SCHEMA, JSONLSink, MemorySink, MetricsLogger,
                       ProfileWindow, StatsPolicy, StepTimer, jsonable,
                       split_stats, stats_keys, validate_jsonl,
                       validate_record)
from repro.training import GuardPolicy, init_state, make_train_step
from tests.conftest import tiny_cfg

from repro.models import init_params


# --------------------------------------------------------------- labels

def test_layer_group_shared_helper():
    assert layer_group("lm_head/w") == "lm_head"
    assert layer_group("tok_embed/w") == "embedding"
    assert layer_group("segments/seg0/attn/wq") == "hidden"
    # tied models have no lm_head: the embedding IS the head
    assert layer_group("tok_embed/w", tied=True) == "lm_head"
    assert layer_group("segments/seg0/mlp/w1", tied=True) == "hidden"
    assert LAYER_GROUPS == ("embedding", "hidden", "lm_head")


def test_variance_analysis_uses_shared_helper():
    import benchmarks.variance_analysis as va
    assert not hasattr(va, "_group_of")
    assert va.layer_group is layer_group


# ------------------------------------------------------- record grammar

def test_validate_record_accepts_well_formed():
    validate_record({"schema": SCHEMA, "kind": "train_step", "host": 0,
                     "step": 3, "t": 1.5, "loss": 2.0, "tag": "x",
                     "fallbacks": {"attention": 2}, "dims": [1, 2]})


@pytest.mark.parametrize("bad", [
    {"schema": SCHEMA, "kind": "x", "host": 0, "step": 1},          # no t
    {"schema": "other/v9", "kind": "x", "host": 0, "step": 1, "t": 0.0},
    {"schema": SCHEMA, "kind": "", "host": 0, "step": 1, "t": 0.0},
    {"schema": SCHEMA, "kind": "x", "host": "0", "step": 1, "t": 0.0},
    {"schema": SCHEMA, "kind": "x", "host": 0, "step": 1, "t": 0.0,
     "loss": float("nan")},                                         # raw NaN
    {"schema": SCHEMA, "kind": "x", "host": 0, "step": 1, "t": 0.0,
     "deep": {"a": {"b": {"c": 1}}}},                               # too deep
])
def test_validate_record_rejects(bad):
    with pytest.raises(ValueError):
        validate_record(bad)


def test_jsonable_coerces_device_and_nonfinite():
    assert jsonable(jnp.float32(1.5)) == 1.5
    assert jsonable(np.int64(7)) == 7 and isinstance(jsonable(np.int64(7)),
                                                     int)
    assert jsonable(float("nan")) is None
    assert jsonable(float("inf")) is None
    assert jsonable(jnp.array([1.0, 2.0])) == [1.0, 2.0]
    assert jsonable({"a": np.float32("nan")}) == {"a": None}


# ------------------------------------------------------- sinks + logger

def test_jsonl_roundtrip_and_validation(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with MetricsLogger([JSONLSink(path)], host=0, flush_every=2) as lg:
        lg.log("train_step", 1, loss=2.5,
               fields={"stats/lm_head/grad_norm": jnp.float32(3.0)})
        lg.log("event", 2, event="rollback", bad=float("nan"))
    assert validate_jsonl(path) == 2
    recs = [json.loads(x) for x in open(path)]
    assert recs[0]["loss"] == 2.5
    assert recs[0]["stats/lm_head/grad_norm"] == 3.0
    assert recs[1]["bad"] is None       # NaN -> null, line stays strict JSON
    assert all(r["schema"] == SCHEMA and r["host"] == 0 for r in recs)


def test_logger_rejects_shadowed_required_key():
    with MetricsLogger([MemorySink()]) as lg:
        with pytest.raises(ValueError, match="shadow"):
            lg.log("x", 0, fields={"step": 7})


def test_memory_sink_background_flush_cadence():
    sink = MemorySink()
    lg = MetricsLogger([sink], flush_every=3)
    for i in range(7):
        lg.log("x", i)
    assert lg.flush()                    # synchronous barrier
    assert [r["step"] for r in sink.records] == list(range(7))
    assert sink.flushes >= 2             # two cadence flushes + barrier
    lg.close()
    lg.log("late", 99)                   # post-close logs are dropped
    assert len(sink.records) == 7


def test_console_host_gating(capsys):
    with MetricsLogger([], host=1) as lg:
        lg.console("hello", step=3)
    assert capsys.readouterr().out == ""
    with MetricsLogger([], host=0) as lg:
        lg.console("hello", step=3)
        lg.console("step    10 loss 1.0", raw=True)
    out = capsys.readouterr().out.splitlines()
    assert out[0] == "[h0 s3] hello"
    # raw lines keep their greppable start and still carry the host tag
    assert out[1].startswith("step ") and out[1].endswith("host 0")


# ------------------------------------------------------ fallback deltas

def test_fallback_snapshot_delta_no_reset():
    dispatch.reset_fallbacks()
    before = dispatch.fallback_snapshot()
    dispatch._FALLBACK_COUNTS["attention"] = 3
    mid = dispatch.fallback_snapshot()
    assert dispatch.fallback_delta(before, mid) == {"attention": 3}
    dispatch._FALLBACK_COUNTS["attention"] = 5
    dispatch._FALLBACK_COUNTS["xent"] = 1
    assert dispatch.fallback_delta(mid) == {"attention": 2, "xent": 1}
    # delta never mutates the cumulative counters chaos tests assert on
    assert dispatch.fallback_counts()["attention"] == 5
    dispatch.reset_fallbacks()


# ------------------------------------------------------- timing/profile

def test_step_timer_snapshot_resets():
    t = StepTimer()
    with t.section("data"):
        time.sleep(0.01)
    with t.section("data"):
        pass
    snap = t.snapshot()
    assert snap["time/data_n"] == 2 and snap["time/data_s"] >= 0.01
    assert snap["time/wall_s"] >= snap["time/data_s"]
    snap2 = t.snapshot()
    assert "time/data_s" not in snap2    # deltas: accumulators reset


@pytest.mark.parametrize("spec,want", [
    ("", None), ("5", (5, 5)), ("2:9", (2, 9))])
def test_profile_window_parse(spec, want, tmp_path):
    win = ProfileWindow.parse(spec, str(tmp_path))
    if want is None:
        assert win is None
    else:
        assert (win.start, win.stop) == want


@pytest.mark.parametrize("spec", ["a:b", "1:2:3", "9:2", "-1"])
def test_profile_window_parse_rejects(spec, tmp_path):
    with pytest.raises(ValueError):
        ProfileWindow.parse(spec, str(tmp_path))


# ------------------------------------------------- the stats collector

def _run(steps, stats, guard=None, seed=0, pack=False, tied=False, **cfg_kw):
    if tied:
        from repro.core.labels import LabelRules
        cfg = tiny_cfg(tie_embeddings=True, **cfg_kw)
        tx = make_optimizer("scale", 1e-2, rules=LabelRules.tied())
    else:
        cfg = tiny_cfg(**cfg_kw)
        tx = make_optimizer("scale", 1e-2)
    state = init_state(init_params(jax.random.PRNGKey(seed), cfg),
                       tx, guard=guard is not None)
    fn = jax.jit(make_train_step(cfg, tx, clip_norm=1.0, guard=guard,
                                 stats=stats))
    ds = make_dataset(cfg, seq_len=32, global_batch=4, seed=seed,
                      pack_documents=pack)
    metrics = {}
    for i in range(steps):
        state, metrics = fn(state, ds.host_batch_at(i))
    return state, metrics


def test_stats_bitwise_inert_with_guard():
    """The acceptance invariant: a run with the collector woven in ends in
    *bitwise* the params/opt_state of a run without it."""
    base, _ = _run(4, stats=None, guard=GuardPolicy())
    obs, metrics = _run(4, stats=StatsPolicy(every_k=2), guard=GuardPolicy())
    for a, b in zip(jax.tree_util.tree_leaves((base.params, base.opt_state)),
                    jax.tree_util.tree_leaves((obs.params, obs.opt_state))):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(metrics["stats/valid"]) == 1.0   # step 4 is on cadence


def test_stats_cadence_and_split():
    policy = StatsPolicy(every_k=3)
    _, m_on = _run(3, stats=policy)        # completed step 3: on cadence
    _, m_off = _run(4, stats=policy)       # completed step 4: off cadence
    assert float(m_on["stats/valid"]) == 1.0
    assert float(m_off["stats/valid"]) == 0.0
    for k in m_off:
        if k.startswith("stats/"):
            assert float(m_off[k]) == 0.0, k   # dead branch: zeros exactly
    plain, stat_vals = split_stats(m_on, policy)
    assert stat_vals and not any(k.startswith("stats/") for k in plain)
    assert "loss" in plain
    plain_off, stats_off = split_stats(m_off, policy)
    assert stats_off == {}                 # off-cadence records stay small
    assert split_stats(m_on, None) == (dict(m_on), {})


def test_stats_keys_cover_groups():
    keys = stats_keys(StatsPolicy())
    for grp in LAYER_GROUPS:
        for name in ("grad_norm", "colnorm_disp", "update_ratio",
                     "momentum_norm"):
            assert f"stats/{grp}/{name}" in keys
    lean = stats_keys(StatsPolicy(momentum=False, colnorms=False,
                                  ratios=False))
    assert lean == sorted(["stats/valid"] + [f"stats/{g}/grad_norm"
                                             for g in LAYER_GROUPS])


def test_stats_paper_ordering_and_momentum_placement():
    """Fig. 4/10 live: lm-head gradient column-norm dispersion dominates
    the hidden stack, and (SCALE) only the head carries first-moment
    state. Needs a non-toy vocab: token-frequency imbalance is what the
    head's column norms trace, and a 256-token vocab has too little of
    it."""
    _, m = _run(4, stats=StatsPolicy(every_k=4), vocab_size=1024)
    disp = {g: float(m[f"stats/{g}/colnorm_disp"]) for g in LAYER_GROUPS}
    assert disp["lm_head"] > disp["hidden"] > 0
    assert float(m["stats/lm_head/grad_norm"]) > 0
    # SCALE: the head carries momentum; the embedding is stateless (its mu
    # leaf is a zero-size placeholder the collector skips). Hidden is not
    # asserted zero — the norm gains there carry the non-matrix Adam state.
    assert float(m["stats/lm_head/momentum_norm"]) > 0
    assert float(m["stats/embedding/momentum_norm"]) == 0.0


def test_stats_under_packed_training():
    """Packed multi-document batches thread extra leaves through the step;
    the collector must coexist with them (and with the guard)."""
    _, m = _run(2, stats=StatsPolicy(every_k=2), guard=GuardPolicy(),
                pack=True)
    assert float(m["stats/valid"]) == 1.0
    assert np.isfinite(float(m["stats/lm_head/grad_norm"]))
    assert float(m["stats/lm_head/update_ratio"]) >= 0


def test_stats_tied_head_reports_under_lm_head():
    _, m = _run(2, stats=StatsPolicy(every_k=2, tied=True), tied=True)
    assert float(m["stats/valid"]) == 1.0
    # the tied (V, D) embedding is the head: stats land in lm_head and the
    # embedding group is empty
    assert float(m["stats/lm_head/grad_norm"]) > 0
    assert float(m["stats/embedding/grad_norm"]) == 0.0


def test_stats_every_k_validation():
    from repro.obs import make_stats_fn
    with pytest.raises(ValueError, match="every_k"):
        make_stats_fn(StatsPolicy(every_k=0))


# ------------------------------------------------ driver integration

def _cli_env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_FUSED", None)
    env.pop("REPRO_FAULTS", None)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    return env


def test_cli_writes_schema_valid_jsonl_with_stats(tmp_path, capsys):
    """In-process tiny run: the JSONL validates, stats records appear on
    cadence, and the head's dispersion dominates (the acceptance check)."""
    from repro.launch.train import main
    main(["--arch", "qwen2-7b", "--smoke", "--steps", "4", "--batch", "4",
          "--seq", "32", "--log-every", "2", "--log-dir", str(tmp_path),
          "--metrics-every", "2", "--stats-every", "2"])
    capsys.readouterr()
    path = tmp_path / "metrics.0.jsonl"
    assert validate_jsonl(str(path)) >= 4
    recs = [json.loads(x) for x in open(path)]
    kinds = [r["kind"] for r in recs]
    assert kinds[0] == "run_header" and kinds[-1] == "run_end"
    steps = [r for r in recs if r["kind"] == "train_step"]
    assert [r["step"] for r in steps] == sorted({r["step"] for r in steps})
    on_cadence = [r for r in steps if "stats/lm_head/colnorm_disp" in r]
    assert on_cadence, steps
    for r in on_cadence:
        assert r["stats/lm_head/colnorm_disp"] > \
            r["stats/hidden/colnorm_disp"]
    assert all("time/step_s" in r and "tokens_per_s" in r for r in steps)
    assert recs[-1]["reason"] == "done"
    # the step program: no compiled Pallas kernels off the TPU (interpret)
    prog = [r for r in recs if r["kind"] == "step_program"]
    assert len(prog) == 1 and prog[0]["kernels"] == {}, prog
    assert prog[0]["argument_size_in_bytes"] > 0


def test_cli_profile_window_names_host_spans(tmp_path, capsys):
    """--profile-steps: the trace's host plane holds the training loop's spans
    (data_wait, train_step and the guard's rollback read, sync), the names
    the benchmark's spans use, and one numbered "train" step per turn."""
    import glob
    from repro.launch.train import main
    main(["--arch", "qwen2-7b", "--smoke", "--steps", "3", "--batch", "2",
          "--seq", "32", "--log-every", "1", "--log-dir", str(tmp_path),
          "--profile-steps", "1:2"])
    capsys.readouterr()
    files = glob.glob(str(tmp_path / "profile" / "**" / "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    data = jax.profiler.ProfileData.from_file(files[0])
    spans, steps = {}, []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in ("data_wait", "train_step", "sync"):
                    spans[e.name] = spans.get(e.name, 0) + 1
                elif e.name == "train":
                    steps.append(dict(e.stats)["step_num"])
    assert spans == {"data_wait": 2, "train_step": 2, "sync": 2}, spans
    assert sorted(steps) == [1, 2]


class _Dev:
    def __init__(self, platform, stats):
        self.platform, self._stats = platform, stats

    def memory_stats(self):
        return self._stats


def test_device_memory_reports_and_raises_on_silent_tpu(monkeypatch):
    from repro.obs import device_memory
    monkeypatch.setattr(jax, "local_devices", lambda: [_Dev("cpu", None)])
    assert device_memory() == {}
    monkeypatch.setattr(jax, "local_devices", lambda: [_Dev(
        "tpu", {"bytes_in_use": 5, "peak_bytes_in_use": 7})])
    assert device_memory() == {
        "mem/dev0/bytes_in_use": 5, "mem/dev0/peak_bytes": 7,
        "mem/total_bytes_in_use": 5, "mem/total_peak_bytes": 7}
    monkeypatch.setattr(jax, "local_devices", lambda: [_Dev("tpu", None)])
    with pytest.raises(RuntimeError, match="no memory_stats"):
        device_memory()


def test_cli_sigterm_flushes_metrics_tail(tmp_path):
    """SIGTERM mid-run: the logger's flush-on-exit gets the run_end record
    (reason=sigterm) onto disk before the process dies."""
    logdir = tmp_path / "logs"
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen2-7b",
         "--smoke", "--steps", "100000", "--batch", "2", "--seq", "32",
         "--log-every", "1", "--metrics-every", "1", "--log-dir",
         str(logdir), "--ckpt-dir", str(tmp_path / "ckpt"),
         "--ckpt-every", "100000"],
        env=_cli_env(), stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    lines = []
    try:
        for line in proc.stdout:
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
    path = logdir / "metrics.0.jsonl"
    assert validate_jsonl(str(path)) >= 2
    recs = [json.loads(x) for x in open(path)]
    assert recs[-1]["kind"] == "run_end"
    assert recs[-1]["reason"] == "sigterm"
    assert any(r["kind"] == "train_step" for r in recs)


def test_single_writer_jsonl_under_forced_8_devices(tmp_path):
    """8-way sharded run, single process: exactly one metrics file
    (metrics.0.jsonl), every record host 0, schema-valid."""
    script = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import glob, json
from repro.launch.train import main
from repro.obs import validate_jsonl
logdir = sys.argv[1]
main(["--arch", "qwen2-7b", "--smoke", "--steps", "3", "--batch", "8",
      "--seq", "32", "--log-every", "1", "--log-dir", logdir,
      "--metrics-every", "1", "--stats-every", "3"])
files = sorted(glob.glob(os.path.join(logdir, "metrics.*.jsonl")))
assert files == [os.path.join(logdir, "metrics.0.jsonl")], files
n = validate_jsonl(files[0])
assert n >= 5, n
recs = [json.loads(x) for x in open(files[0])]
assert all(r["host"] == 0 for r in recs), recs
assert any("stats/lm_head/grad_norm" in r for r in recs)
print("OK")
"""
    res = subprocess.run([sys.executable, "-c", script, str(tmp_path)],
                         env=_cli_env(), capture_output=True, text=True,
                         timeout=900)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout


def test_serving_latency_records():
    from repro.training.serving import greedy_generate
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    sink = MemorySink()
    with MetricsLogger([sink]) as lg:
        prompt = jnp.zeros((2, 16), jnp.int32)
        out = greedy_generate(cfg, params, prompt, n_steps=4, max_seq=64,
                              logger=lg)
    assert out.shape == (2, 4)
    phases = {r["phase"]: r for r in sink.records if r["kind"] == "serve"}
    assert set(phases) == {"prefill", "decode"}
    assert phases["prefill"]["prompt_tokens"] == 32
    assert phases["prefill"]["latency_ms"] > 0
    d = phases["decode"]
    assert d["decode_steps"] == 3 and d["p99_ms"] >= d["p50_ms"] >= 0
    for r in sink.records:
        validate_record(r)
