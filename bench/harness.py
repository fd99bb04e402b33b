"""One run of one benchmark cell: set-up, a timed window, the comparison.

The program is driven the way ``launch/train.py`` drives it (``build``,
``make_host_mesh``, parameters born sharded from the seed, a guarded,
donated, clipped ``make_train_step``, ``place_batch`` for every batch and
a host read of the rollback flag after every step), in a loop of the
benchmark's own, since ``train.main`` takes no time window.

Set-up runs the first ``WARM_STEPS`` steps through that same loop on
distinct batches. They compile every program the window runs, and they are
the steps the reference follows: the losses, the first gradient as the
optimizer got it (from its state after step 1) and the parameters after
step ``REF_STEPS`` (copied to host memory, so that no second copy of the
model is held on the device) are read on the way. The window then runs the
same loop for ``--seconds``, opening and closing on ``block_until_ready``.
After it, device memory is read, the program's arrays are freed and the
reference (``bench/reference.py``) repeats the first steps and measures
each leaf's change against the program's, so that ``correct`` judges what
the timed path produced at the timed sizes.

Metric values come from readers in ``bench/metrics/<name>.py`` that take
the :class:`Run` record; a reader that finds nothing returns None and its
metric is left out of the line.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
WARM_STEPS = 3
REF_STEPS = 2
PROGRAM = "jit_train_step"
KERNELS = ("attn_", "xent_", "colnorm_", "scale_head_")
SPANS = ("data_wait", "train_step", "sync")

# configuration keys (published names) -> the program's ModelConfig fields
FIELDS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "vocab_pad_multiple": "vocab_pad_multiple",
          "num_hidden_layers": "n_layers", "attention_bias": "qkv_bias",
          "rope_theta": "rope_theta", "rms_norm_eps": "rms_eps",
          "tie_word_embeddings": "tie_embeddings", "torch_dtype": "dtype"}
PROGRAM_INIT_STD = 0.02


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, bad files)."""


@dataclasses.dataclass
class Run:
    """What the metric readers see of one run."""
    model: dict
    chips: int
    peak: dict
    setup_s: float
    window_s: float
    batches: list            # the window's batches, in order
    step_hbm_bytes: int
    trace: object = None     # bench.trace.Trace of the window (--trace 1)
    scopes: dict = dataclasses.field(default_factory=dict)
    program: str = PROGRAM


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def process_start() -> float:
    """Wall-clock time this process started (Linux), else now."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(l.split()[1]) for l in f if l.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration, IndexError):
        return time.time()


class CompileCounter:
    """Backend compiles and persistent-cache hits seen by this process
    (JAX monitoring events)."""

    def __init__(self):
        import jax
        self.n = self.hits = 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


class GcPauses:
    """Number and seconds of the collector's runs between start and stop."""

    def __init__(self):
        self.n, self.seconds, self._t = 0, 0.0, None
        self._on = False

    def _cb(self, phase, info):
        if not self._on:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.n += 1
            self.seconds += time.perf_counter() - self._t

    def start(self):
        self._on = True
        gc.callbacks.append(self._cb)

    def stop(self):
        self._on = False
        gc.callbacks.remove(self._cb)


def load_traffic(name: str) -> dict:
    """A traffic mix with its corpus (``bench/corpora/<corpus>.json``)."""
    traffic = load_json(BENCH / "traffic" / f"{name}.json")
    traffic["corpus"] = load_json(BENCH / "corpora"
                                  / f"{traffic['corpus']}.json")
    return traffic


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def flat_paths(tree) -> dict:
    """{"a/b/c": leaf} of a nested dict, the program's stacked layer
    segment renamed to the reference's ``layers``."""
    import jax
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        p = "/".join(str(getattr(k, "key", k)) for k in path)
        out[p.replace("segments/seg0_dense/", "layers/")] = leaf
    return out


def _leaf_norms(flat: dict) -> dict:
    import jax.numpy as jnp

    def one(p, x):
        x = x.astype(jnp.float32)
        if p.startswith("layers/"):
            return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        return jnp.sqrt(jnp.sum(x * x))
    return {p: one(p, x) for p, x in flat.items()}


class Program:
    """The system under test for one configuration, on ``chips`` chips."""

    def __init__(self, config: dict, traffic: dict):
        import jax
        from repro.launch.mesh import make_host_mesh
        from repro.launch.train import build, place_batch
        from repro.models import (init_params, param_logical_axes,
                                  param_shapes)
        from repro.models.sharding import Rules, tree_shardings
        from repro.training import (GuardPolicy, init_state,
                                    make_train_step)
        model, opt = config["model"], config["optimizer"]
        if model["initializer_range"] != PROGRAM_INIT_STD:
            raise BenchError("the program initialises with std "
                             f"{PROGRAM_INIT_STD}, the configuration states "
                             f"{model['initializer_range']}")
        cfg, self.tx = build(SimpleNamespace(
            arch=config["registry"], smoke=False, seq=traffic["row_len"],
            dtype=model["torch_dtype"],
            tie_embeddings=model["tie_word_embeddings"], lr=opt["lr"],
            steps=opt["schedule_steps"], optimizer=opt["name"]))
        for key, field in FIELDS.items():
            setattr(cfg, field, model[key])
        self.cfg, self.chips = cfg, int(config["chips"])
        self.rules = Rules(cfg.rule_overrides)
        self.mesh = make_host_mesh(data=self.chips)
        self._place = place_batch
        self._init_state = init_state
        self.init = jax.jit(
            lambda key: init_params(key, cfg),
            out_shardings=tree_shardings(param_logical_axes(cfg), self.mesh,
                                         self.rules, param_shapes(cfg)))
        self.step_fn = make_train_step(
            cfg, self.tx, clip_norm=opt["clip_norm"], rules=self.rules,
            mesh=self.mesh if self.chips > 1 else None, donate=True,
            guard=GuardPolicy())
        self.state = None
        self.opt = opt

    def start(self, seed: int) -> None:
        import jax
        self.state = self._init_state(self.init(jax.random.PRNGKey(seed)),
                                      self.tx, guard=True)

    def place(self, batch: dict) -> dict:
        return self._place(batch, self.mesh, self.rules)

    def step(self, batch: dict) -> dict:
        """One turn of ``launch/train.py``'s loop."""
        with span("data_wait"):
            placed = self.place(batch)
        with span("train_step"):
            self.state, metrics = self.step_fn(self.state, placed)
        with span("sync"):
            if bool(float(metrics["rollback"])):
                raise BenchError("the guard asked for a rollback")
        return metrics

    def grad_norms(self) -> dict:
        """First gradient as the optimizer got it, per leaf with state:
        the Adam first moment over (1 - b1), the head momentum over
        (1 - beta), read after one step."""
        import jax
        flat = {p: x for p, x in flat_paths(self.state.opt_state.mu).items()
                if x.size}
        norms = jax.device_get(jax.jit(_leaf_norms)(flat))
        return {p: v / (1 - (self.opt["beta"] if p == "lm_head/w"
                             else self.opt["adam_b1"]))
                for p, v in norms.items()}

    def params_on_host(self) -> dict:
        """{path: numpy array} of the parameters as they stand."""
        import jax
        return jax.device_get(flat_paths(self.state.params))

    def compiled(self, batch: dict):
        return self.step_fn.lower(self.state, self.place(batch)).compile()

    def skipped(self) -> int:
        return int(self.state.guard.skipped)

    def close(self) -> None:
        import jax
        self.state = None
        for a in jax.live_arrays():
            a.delete()


def first_steps(prog: Program, ring: list) -> dict:
    """Run the ``WARM_STEPS`` set-up steps on distinct batches and read
    what the reference is compared on: the losses of the first
    ``REF_STEPS`` steps, the first gradient as the optimizer got it, the
    parameters after step ``REF_STEPS`` (``params``, on the host)."""
    from bench.compare import named_norms
    out = {"loss": []}
    for i in range(WARM_STEPS):
        loss = float(prog.step(ring[i])["loss"])
        if i < REF_STEPS:
            out["loss"].append(loss)
        if i == 0:
            out["grad"] = named_norms(prog.grad_norms())
        if i + 1 == REF_STEPS:
            out["params"] = prog.params_on_host()
    return out


def step_hbm(compiled) -> int:
    """Bytes the compiled step needs on its fullest device: arguments +
    outputs - aliased + temp (``memory_analysis()``, per device)."""
    ma = compiled.memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def reference_of(config: dict):
    """The plain reference module the configuration names
    (``bench/<reference>.py``)."""
    import importlib
    return importlib.import_module(f"bench.{config['reference']}")


def read_metric(name: str, run: Run):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def check_devices(chips: int, need_tpu: bool):
    import jax
    devs = jax.devices()
    if need_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs


def run_cell(cell: dict, config: dict, traffic: dict, limits: dict,
             metrics: list, seed: int, seconds: float, trace: bool, *,
             t_start: float, need_tpu: bool = True, log=print) -> dict:
    """One run; returns the result line's dict."""
    import jax
    from bench import compare, packing
    from bench import trace as tr
    from bench.peaks import peak

    chips = int(cell["chips"])
    devices = check_devices(chips, need_tpu)[:chips]
    kind = devices[0].device_kind
    peaks = peak(kind) if need_tpu else {"bf16_flops": 1.0,
                                         "hbm_bytes_per_s": 1.0}
    counter = CompileCounter()
    model = config["model"]
    marks = [("jax", time.time())]
    ring = packing.make_ring(traffic, int(config["tokens_per_step"]),
                             int(model["vocab_size"]), seed)
    marks.append(("traffic", time.time()))
    prog = Program(config, traffic)
    prog.start(seed)
    jax.block_until_ready(prog.state)
    marks.append(("init", time.time()))
    mine = first_steps(prog, ring)
    marks.append(("first_steps", time.time()))
    compiled = prog.compiled(ring[0])
    hbm = step_hbm(compiled)
    scopes = tr.hlo_scopes(compiled.as_text()) if trace else {}
    del compiled
    marks.append(("memory_analysis", time.time()))
    log("setup: " + " ".join(f"{k} {b - a:.2f}s" for (_, a), (k, b) in
                             zip([("start", t_start)] + marks, marks))
        + f" compiles {counter.n} cache_hits {counter.hits}")
    skipped0 = prog.skipped()
    # set-up's objects are never garbage: keep the collector off them
    gc.collect()
    gc.freeze()
    pauses = GcPauses()

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        jax.block_until_ready(prog.state)
        compiles0, hits0 = counter.n, counter.hits
        if trace:
            jax.profiler.start_trace(trace_dir)
        t0 = time.perf_counter()
        setup_s = time.time() - t_start
        batches, ends, i = [], [], WARM_STEPS
        pauses.start()
        while True:
            batch = ring[i % len(ring)]
            prog.step(batch)
            batches.append(batch)
            i += 1
            ends.append(time.perf_counter() - t0)
            if ends[-1] >= seconds:
                break
        jax.block_until_ready(prog.state)
        window_s = time.perf_counter() - t0
        pauses.stop()
        if trace:
            jax.profiler.stop_trace()
        in_window = (counter.n - compiles0, counter.hits - hits0)
        traced = tr.load(trace_dir) if trace else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    log("window: step ends (s) " + " ".join(f"{e:.4f}" for e in ends)
        + f" gc {pauses.n} pauses {pauses.seconds:.4f}s")
    if any(in_window):
        raise BenchError(f"{in_window[0]} programs compiled and "
                         f"{in_window[1]} loaded inside the window")
    failed = prog.skipped() - skipped0

    stats = [d.memory_stats() or {} for d in devices]
    for d, s in zip(devices, stats):
        log(f"memory: {d} peak_bytes_in_use {s.get('peak_bytes_in_use')} "
            f"bytes_limit {s.get('bytes_limit')} step_hbm_bytes {hbm}")
    mem_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    prog.close()
    del prog

    run = Run(model=model, chips=chips, peak=peaks, setup_s=setup_s,
              window_s=window_s, batches=batches, step_hbm_bytes=hbm,
              trace=traced, scopes=scopes)
    values = {}
    for m in metrics:
        v = read_metric(m["name"], run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}

    t_ref = time.perf_counter()
    ref = reference_of(config).Reference(
        model, config["optimizer"], devices).run(
            seed, ring[:REF_STEPS], against={"program": mine.pop("params")})
    log(f"reference: {time.perf_counter() - t_ref:.1f} s")
    mine.update(ref["against"]["program"])
    got = compare.readings(mine, ref)
    for name, (v, where) in got.items():
        log(f"compared: {name} {v!r} limit {limits[name]!r} ({where})")
    vals = {k: v for k, (v, _) in got.items()}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    out = {"correct": compare.judge(vals, limits),
           "attempted": len(batches), "failed": failed, "metrics": values,
           "device": device}
    if traced is not None:
        device["busy_s"] = tr.busy_ns(traced) * 1e-9
        device["window_s"] = window_s
        out["breakdown"] = {
            "device_ops": tr.top_ops(traced, scopes, KERNELS),
            "idle_gaps": tr.idle_by_host(traced, SPANS)}
    out["compared"] = {k: {"value": vals[k], "limit": limits[k]}
                       for k in compare.NAMES}
    return out


def cell_metrics(bench: dict, name: str, trace: bool) -> list:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if name in m.get("workloads", [name])]


def main(argv, t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="bench/run.py",
                                 description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = load_json(ROOT / "BENCHMARK.json")
        cells = {c["name"]: c for c in bench["workloads"]}
        if args.workload not in cells:
            raise BenchError(f"unknown workload {args.workload!r}; "
                             f"cells: {sorted(cells)}")
        cell = cells[args.workload]
        config = load_json(BENCH / "configs" / f"{cell['config']}.json")
        traffic = load_traffic(cell["traffic"])
        limits = load_json(BENCH / "limits" / f"{cell['name']}.json")
        import jax
        from repro.launch.train import use_compile_cache
        check_devices(int(cell["chips"]), need_tpu=True)
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        out = run_cell(cell, config, traffic, limits,
                       cell_metrics(bench, cell["name"], bool(args.trace)),
                       args.seed, args.seconds, bool(args.trace),
                       t_start=t_start,
                       log=lambda s: print(s, file=sys.stderr, flush=True))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for k, v in out["compared"].items():
        print(f"compared {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
