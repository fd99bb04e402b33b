"""Smoke run of the training main path on a TPU: does it start, train and
resume on the chip, through the entry point a user calls?

    python chip_smoke.py               # one chip: LLaMA-1B
    python chip_smoke.py --four-chips  # one four-chip host: LLaMA-7B

One chip: the paper's LLaMA-1B at full width and depth (24 layers,
d_model 2048, 32 heads, d_ff 5461, vocab 32000, bf16, random weights from
``--seed 0``) trains 8 steps with the fused SCALE update through
``repro.launch.train.main``, checkpoints, then resumes with
``--resume auto`` for 2 more steps. It fails unless every loss is finite,
the first is near ln(32000) and the last below it, the resume continues
from the saved step, no kernel route fell back to jnp, the compiled step
holds the colnorm/scale_head update, xent and attention Pallas kernels,
and the chip reports a peak of device memory.

Four chips: the paper's LLaMA-7B (32 layers, d_model 4096, d_ff 11008)
trains 4 steps FSDP-sharded over ``make_host_mesh(data=4)`` with the
fused update (shard_mapped kernels that psum their norms over the mesh),
then the same 4 steps from the same seed with the jnp update, in the same
process. The per-step losses must agree within ``FOUR_CHIP_RTOL``.

The lines before the last are smoke readings, not benchmark metrics. The
last line is ``{"ok": true, "device": {...}}`` with the platform, kind and
count of the devices JAX reports. Without a TPU, with ``REPRO_FUSED`` set
to anything but ``auto``, or with ``REPRO_FAULTS`` set, the script exits
non-zero and prints no result. Everything runs in this one process, which
holds the chip; checkpoints and logs go to a temporary directory that is
removed at exit.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# per-step loss agreement of the fused and jnp SCALE updates on LLaMA-7B:
# the two differ only in the summation order of the column norms, a few
# roundings of the bf16 parameters (1.2e-4 at worst on four v5e chips),
# while a norm missing its cross-shard psum moves a step's update 2x
FOUR_CHIP_RTOL = 1e-3
KERNEL_FAMILIES = {"colnorm/scale_head update": ("colnorm_", "scale_head_"),
                   "xent": ("xent_",), "attention": ("attn_",)}


def run(log_dir: str, argv: list, counter: "CompileCounter") -> dict:
    """One ``train.main`` run logging every step to ``log_dir``; returns
    its train_step records, its step_program record and the compiles it
    made."""
    from repro.launch import train
    before = counter.snapshot()
    train.main(argv + ["--log-dir", log_dir, "--metrics-every", "1",
                       "--log-every", "1"])
    with open(os.path.join(log_dir, "metrics.0.jsonl")) as f:
        recs = [json.loads(line) for line in f]
    steps = [r for r in recs if r["kind"] == "train_step"]
    program = [r for r in recs if r["kind"] == "step_program"]
    after = counter.snapshot()
    return {"steps": steps, "program": program[-1] if program else {},
            "compile": {k: after[k] - before[k] for k in after}}


def readings(name: str, res: dict, tokens_per_step: int) -> None:
    """Print one run's smoke readings (not benchmark metrics)."""
    c = res["compile"]
    # step 1 holds the compile; steps 3.. are warm
    warm = statistics.median(r["time/wall_s"] for r in res["steps"][2:])
    print(f"smoke: {name} losses "
          f"{[round(r['loss'], 4) for r in res['steps']]}")
    print(f"smoke: {name} compile_s {c['seconds']:.1f} ({c['compiles']} "
          f"compiles, {c['cache_hits']} persistent-cache hits, "
          f"{c['cache_misses']} misses) warm_step_s {warm:.4f} "
          f"tokens_per_s {tokens_per_step / warm:.0f}")


def train_and_resume(work: str, arch: str, batch: int, seq: int,
                     steps: int, more: int, counter: "CompileCounter") -> tuple:
    """(first run, resumed run): ``steps`` steps with a checkpoint at the
    end, then ``--resume auto`` to ``steps + more``."""
    ckpt = os.path.join(work, "ckpt")
    argv = ["--arch", arch, "--optimizer", "scale_fused", "--lr", "1e-2",
            "--batch", str(batch), "--seq", str(seq), "--seed", "0",
            "--ckpt-dir", ckpt, "--ckpt-every", str(steps)]
    first = run(os.path.join(work, "log_train"),
                argv + ["--steps", str(steps)], counter)
    resumed = run(os.path.join(work, "log_resume"),
                  argv + ["--steps", str(steps + more), "--resume", "auto"],
                  counter)
    return first, resumed


def check_train_and_resume(first: dict, resumed: dict, steps: int,
                           more: int) -> list:
    """Failures of the loss, resume and fallback checks (empty: passed)."""
    from repro.kernels import dispatch
    bad = []
    losses = [r["loss"] for r in first["steps"] + resumed["steps"]]
    if not losses or not all(isinstance(x, float) and math.isfinite(x)
                             for x in losses):
        bad.append(f"non-finite or missing losses: {losses}")
        return bad
    l0, ln = first["steps"][0]["loss"], first["steps"][-1]["loss"]
    if abs(l0 - math.log(32000)) > 0.5:
        bad.append(f"first loss {l0} is not near ln(32000)")
    if not ln < l0:
        bad.append(f"loss did not fall: first {l0}, last {ln}")
    got = [r["step"] for r in resumed["steps"]]
    if got != list(range(steps + 1, steps + more + 1)):
        bad.append(f"resume did not continue from step {steps}: {got}")
    elif not resumed["steps"][0]["loss"] < l0:
        bad.append("resumed loss is back at the start: "
                   f"{resumed['steps'][0]['loss']} vs {l0}")
    if dispatch.fallback_counts():
        bad.append(f"kernel fallbacks: {dispatch.fallback_counts()}")
    return bad


def missing_kernels(program: dict) -> list:
    names = program.get("kernels") or {}
    return [fam for fam, prefixes in KERNEL_FAMILIES.items()
            if not any(n.startswith(prefixes) for n in names)]


class CompileCounter:
    """Backend compiles (count, seconds) and persistent-cache hits/misses
    seen by this process, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.n = {"compiles": 0, "cache_hits": 0, "cache_misses": 0}
        self.seconds = 0.0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n["compiles"] += 1
                self.seconds += duration

        def on_event(event, **kw):
            for key in ("cache_hits", "cache_misses"):
                if event == f"/jax/compilation_cache/{key}":
                    self.n[key] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> dict:
        return dict(self.n, seconds=self.seconds)


def one_chip(work: str, counter: CompileCounter) -> list:
    import jax
    steps, more, batch, seq = 8, 2, 16, 256
    first, resumed = train_and_resume(work, "llama-1b", batch, seq, steps,
                                      more, counter)
    bad = check_train_and_resume(first, resumed, steps, more)
    bad += [f"no {fam} kernel in the compiled step"
            for fam in missing_kernels(first["program"])]
    peak = jax.devices()[0].memory_stats().get("peak_bytes_in_use")
    if not peak:
        bad.append("memory_stats() reports no peak")
    print(f"smoke: llama-1b scale_fused batch {batch} seq {seq}")
    readings("train", first, batch * seq)
    print(f"smoke: resume losses "
          f"{[round(r['loss'], 4) for r in resumed['steps']]} compile_s "
          f"{resumed['compile']['seconds']:.1f} "
          f"({resumed['compile']['cache_hits']} persistent-cache hits)")
    prog = first["program"]
    print(f"smoke: step program kernels {prog.get('kernels')} temp_bytes "
          f"{prog.get('temp_size_in_bytes')} argument_bytes "
          f"{prog.get('argument_size_in_bytes')} peak_hbm_bytes {peak}")
    return bad


def four_chips(work: str, counter: CompileCounter) -> list:
    """LLaMA-7B, fused against jnp SCALE, same seed, same process."""
    import jax
    from repro.kernels import dispatch
    argv = ["--arch", "llama-7b", "--lr", "1e-2", "--batch", "8",
            "--seq", "256", "--steps", "4", "--seed", "0"]
    runs = {opt: run(os.path.join(work, opt), argv + ["--optimizer", opt],
                     counter)
            for opt in ("scale_fused", "scale")}
    fused = [r["loss"] for r in runs["scale_fused"]["steps"]]
    ref = [r["loss"] for r in runs["scale"]["steps"]]
    for opt, res in runs.items():
        readings(f"llama-7b {opt}", res, 8 * 256)
    print(f"smoke: llama-7b per-step |fused - jnp| / |jnp| "
          f"{[abs(a - b) / abs(b) for a, b in zip(fused, ref)]}")
    for d in jax.devices():
        print(f"smoke: {d} peak_hbm_bytes "
              f"{d.memory_stats().get('peak_bytes_in_use')}")
    bad = [f"no {fam} kernel in the fused step"
           for fam in missing_kernels(runs["scale_fused"]["program"])]
    if len(fused) != 4 or len(ref) != 4 or not all(
            math.isfinite(a) and abs(a - b) <= FOUR_CHIP_RTOL * abs(b)
            for a, b in zip(fused, ref)):
        bad.append(f"fused and jnp losses disagree beyond rtol "
                   f"{FOUR_CHIP_RTOL}")
    if dispatch.fallback_counts():
        bad.append(f"kernel fallbacks: {dispatch.fallback_counts()}")
    return bad


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the LLaMA-7B fused-vs-jnp comparison on "
                         "a four-chip host")
    args = ap.parse_args()
    if os.environ.get("REPRO_FUSED", "auto") != "auto" \
            or os.environ.get("REPRO_FAULTS"):
        print("chip_smoke: unset REPRO_FUSED (or set it to auto) and "
              "REPRO_FAULTS", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if args.four_chips and len(devices) != 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    from repro.launch import train
    train.use_compile_cache()
    counter = CompileCounter()
    print(f"smoke: jax {jax.__version__} device {devices[0].device_kind} "
          f"x{len(devices)}")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        bad = four_chips(work, counter) if args.four_chips \
            else one_chip(work, counter)
    for b in bad:
        print(f"chip_smoke: FAILED: {b}", file=sys.stderr)
    if bad:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
