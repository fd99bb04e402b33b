"""Readings that the limits of ``correct`` are set from, on the chip at a
cell's own size, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1-12 \
        --control 3 --faults half_batch,sign_flip --fault-seeds 3

For every seed the program runs its first steps through the timed path,
the float32 reference repeats them, and the compared numbers of
``bench/compare.py`` are printed (``sound``). For the first ``--control``
seeds the reference also runs in fp8 in the program's place
(``control``), and for the first ``--fault-seeds`` seeds the program runs
again with a fault planted in its timed path (``fault:<name>``):

* ``half_batch``: the loss weights of half of each batch are zeroed, so
  the mean is taken over the rest;
* ``sign_flip``: the column-normalised update of the hidden matrices is
  applied with its sign turned (``dispatch.norm_update``);
* ``no_exchange``: the SCALE kernels' cross-chip psum of the column sums
  of squares is left out (cells on more than one chip).

One float32 reference run per seed measures them all. A step that returns
its state unchanged reads exactly 1 on ``grad_gap``, ``delta_gap`` and
``delta_diff`` by their definition and is not run. Each reading is one
JSON line on standard output and in
``chiprun_out/calibrate/<cell>.jsonl``.
"""
import argparse
import contextlib
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import compare, harness, packing  # noqa: E402


def seeds(spec: str) -> list:
    out = []
    for part in spec.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b) + 1)) if b else [int(a)]
    return out


def half_batch(ring: list) -> list:
    out = []
    for b in ring:
        b = dict(b)
        w = b["loss_weights"].copy()
        if w.shape[0] > 1:
            w[w.shape[0] // 2:] = 0.0
        else:
            w[:, w.shape[1] // 2:] = 0.0
        b["loss_weights"] = w
        out.append(b)
    return out


@contextlib.contextmanager
def planted(fault: str):
    """The program's kernels dispatch with ``fault`` planted."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import dispatch
    name = {"no_exchange": "_psum_ss", "sign_flip": "norm_update"}.get(fault)
    if name is None:
        yield
        return
    real = getattr(dispatch, name)
    if fault == "no_exchange":
        setattr(dispatch, name, lambda ss, plan, axis: ss)
    else:
        def flipped(theta, *a, **kw):
            new = real(theta, *a, **kw).astype(jnp.float32)
            return (2 * theta.astype(jnp.float32) - new).astype(theta.dtype)
        setattr(dispatch, name, flipped)
    jax.clear_caches()   # retrace the kernels' wrappers
    try:
        yield
    finally:
        setattr(dispatch, name, real)
        jax.clear_caches()


def program_readings(config, traffic, ring, seed) -> dict:
    prog = harness.Program(config, traffic)
    prog.start(seed)
    got = harness.first_steps(prog, ring)
    prog.close()
    return got


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", default="half_batch,sign_flip")
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--out", default=os.path.join(_ROOT, "chiprun_out",
                                                  "calibrate"))
    args = ap.parse_args()
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = {c["name"]: c for c in bench["workloads"]}[args.workload]
    config = harness.load_json(harness.BENCH / "configs"
                               / f"{cell['config']}.json")
    traffic = harness.load_traffic(cell["traffic"])
    import jax
    from repro.launch.train import use_compile_cache
    devices = harness.check_devices(int(cell["chips"]), need_tpu=True)
    devices = devices[:int(cell["chips"])]
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    reference = harness.reference_of(config)
    os.makedirs(args.out, exist_ok=True)
    sink = open(os.path.join(args.out, f"{args.workload}.jsonl"), "a")

    def emit(kind, seed, mine, ref, secs):
        got = compare.readings(mine, ref)
        rec = {"cell": args.workload, "kind": kind, "seed": seed,
               "seconds": secs,
               **{k: v for k, (v, _) in got.items()},
               "where": {k: w for k, (_, w) in got.items()}}
        print(json.dumps(rec), flush=True)
        rec["detail"] = compare.detail(mine, ref)
        sink.write(json.dumps(rec) + "\n")
        sink.flush()

    faults = [f for f in args.faults.split(",") if f]
    for i, seed in enumerate(seeds(args.seeds)):
        ring = packing.make_ring(traffic, int(config["tokens_per_step"]),
                                 int(config["model"]["vocab_size"]), seed)
        first = ring[:harness.REF_STEPS]
        runs, secs = {}, {}
        t = time.perf_counter()
        runs["sound"] = program_readings(config, traffic, ring, seed)
        secs["sound"] = time.perf_counter() - t
        for f in faults if i < args.fault_seeds else []:
            t = time.perf_counter()
            with planted(f):
                runs[f"fault:{f}"] = program_readings(
                    config, traffic,
                    half_batch(ring) if f == "half_batch" else ring, seed)
            secs[f"fault:{f}"] = time.perf_counter() - t
        if i < args.control:
            t = time.perf_counter()
            runs["control"] = reference.Reference(
                config["model"], config["optimizer"], devices,
                precision="fp8").run(seed, first, keep_params=True)
            secs["control"] = time.perf_counter() - t
        t = time.perf_counter()
        ref = reference.Reference(config["model"], config["optimizer"],
                                  devices).run(
            seed, first,
            against={k: r.pop("params") for k, r in runs.items()})
        t_ref = time.perf_counter() - t
        for kind, mine in runs.items():
            mine.update(ref["against"][kind])
            emit(kind, seed, mine, ref,
                 {"run": secs[kind], "reference": t_ref})
    sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
