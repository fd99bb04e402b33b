import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production mesh and extract memory / cost / collective stats.

The two lines above MUST stay first: jax locks the device count at first
initialization, and the dry-run needs 512 host-platform placeholder devices
to build the (pod=2, data=16, model=16) mesh. Smoke tests and benchmarks
import repro without this module and see the real single CPU device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-67b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out results/dryrun
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_cell, iter_cells
from repro.core import make_optimizer
from repro.launch import hlo_analysis as H
from repro.launch.mesh import HW, make_production_mesh
from repro.models import (cache_logical_axes, count_params, init_cache,
                          init_params, param_logical_axes, param_shapes)
from repro.models.sharding import Rules, tree_shardings
from repro.training import (ServeState, make_decode_step, make_prefill_step,
                            make_train_step)
from repro.training.trainer import TrainState


def _abstract(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def input_specs(cfg, shape, mesh, rules):
    """ShapeDtypeStruct stand-ins for every model input of one cell."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    tok_shape = ((B, cfg.n_codebooks, S) if cfg.family == "audio" else (B, S))
    tok_axes = (("act_batch", None, "act_seq") if cfg.family == "audio"
                else ("act_batch", "act_seq"))
    if shape.kind == "decode":
        tok_shape = tok_shape[:-1] + (1,)
    specs = {"tokens": jax.ShapeDtypeStruct(tok_shape, i32)}
    sh = {"tokens": rules.sharding(tok_axes, mesh, tok_shape)}
    if shape.kind == "train":
        specs["labels"] = jax.ShapeDtypeStruct(tok_shape, i32)
        sh["labels"] = sh["tokens"]
    if cfg.family == "vlm" and shape.kind != "decode":
        im = (B, cfg.n_image_tokens, cfg.d_model)
        specs["image_embeds"] = jax.ShapeDtypeStruct(im, cfg.jdtype)
        sh["image_embeds"] = rules.sharding(("act_batch", None, "act_embed"),
                                            mesh, im)
    return specs, sh


def _param_shardings(cfg, mesh, rules, params_abs):
    return tree_shardings(param_logical_axes(cfg), mesh, rules, params_abs)


def opt_state_shardings(mesh, params_abs, params_sh, opt_abs):
    """Shard optimizer state: leaves structured like params inherit the
    param sharding; everything else (counters, EMA scalars, low-rank
    projections) replicates."""
    rep = NamedSharding(mesh, P())
    p_leaves = jax.tree_util.tree_leaves(params_abs)
    p_sh = jax.tree_util.tree_leaves(params_sh)
    shape_to_sh = {}
    for pl_, ps in zip(p_leaves, p_sh):
        shape_to_sh.setdefault((tuple(pl_.shape), str(pl_.dtype)), ps)
    shape_only = {tuple(pl_.shape): ps for pl_, ps in zip(p_leaves, p_sh)}

    def pick(leaf):
        key = (tuple(leaf.shape), str(leaf.dtype))
        if key in shape_to_sh:
            return shape_to_sh[key]
        if tuple(leaf.shape) in shape_only:
            return shape_only[tuple(leaf.shape)]
        return rep

    return jax.tree_util.tree_map(pick, opt_abs)


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               optimizer: str = "scale", accum: str = "auto",
               extra_overrides=()):
    """Lower + compile one cell; return the result record."""
    cfg, shape = get_cell(arch, shape_name)
    cfg.rule_overrides = tuple(cfg.rule_overrides) + tuple(extra_overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    rules = Rules(cfg.rule_overrides)

    t0 = time.time()
    params_abs = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    params_sh = _param_shardings(cfg, mesh, rules, params_abs)
    specs, specs_sh = input_specs(cfg, shape, mesh, rules)

    if shape.kind == "train":
        data_extent = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
        # per-arch local microbatch: 4 sequences amortizes per-microbatch
        # FSDP weight gathers and grad reductions 4x vs microbatch=1 (§Perf
        # iteration 5); mistral-large drops to 2 to stay inside HBM.
        local_mb = {"mistral-large-123b": 2}.get(arch, 4)
        if accum == "auto":
            n_accum = max(1, shape.global_batch // (data_extent * local_mb))
        else:
            n_accum = int(accum)
        n_total = count_params(param_shapes(cfg))
        accum_dtype = "bfloat16" if n_total > 150e9 else "float32"
        tx = make_optimizer(optimizer, 1e-3)
        step = make_train_step(cfg, tx, grad_accum=n_accum, rules=rules,
                               accum_dtype=accum_dtype, norm_metrics=False)
        opt_abs = jax.eval_shape(lambda: tx.init(params_abs))
        opt_sh = opt_state_shardings(mesh, params_abs, params_sh, opt_abs)
        rep = NamedSharding(mesh, P())
        state_abs = TrainState(jax.ShapeDtypeStruct((), jnp.int32),
                               params_abs, opt_abs)
        state_sh = TrainState(rep, params_sh, opt_sh)
        with jax.set_mesh(mesh):
            jitted = jax.jit(step, in_shardings=(state_sh, specs_sh),
                             out_shardings=(state_sh, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_abs, specs)
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg, max_seq=shape.seq_len, rules=rules)
        with jax.set_mesh(mesh):
            jitted = jax.jit(step, in_shardings=(params_sh, specs_sh["tokens"])
                             if cfg.family != "vlm" else
                             (params_sh, specs_sh["tokens"],
                              specs_sh["image_embeds"]))
            args = ((params_abs, specs["tokens"]) if cfg.family != "vlm" else
                    (params_abs, specs["tokens"], specs["image_embeds"]))
            lowered = jitted.lower(*args)
        n_accum = 1
    else:  # decode
        B = shape.global_batch
        cache_abs = jax.eval_shape(
            lambda: init_cache(cfg, B, shape.seq_len))
        cache_sh = tree_shardings(cache_logical_axes(cfg), mesh, rules,
                                  cache_abs)
        rep = NamedSharding(mesh, P())
        st_abs = ServeState(cache_abs, jax.ShapeDtypeStruct((), jnp.int32))
        st_sh = ServeState(cache_sh, rep)
        step = make_decode_step(cfg, rules=rules)
        with jax.set_mesh(mesh):
            jitted = jax.jit(step, in_shardings=(params_sh, st_sh,
                                                 specs_sh["tokens"]),
                             out_shardings=(st_sh, None),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_abs, st_abs, specs["tokens"])
        n_accum = 1

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    # trip-count-aware recursive analysis of the partitioned module
    # (compiled.cost_analysis() counts while bodies once — useless for
    # scanned-layer models; see hlo_cost.py)
    from repro.launch import hlo_cost as HC
    c = HC.analyze(compiled.as_text())
    cost = {"flops": c.flops, "bytes_accessed": c.bytes_accessed,
            "transcendentals": c.transcendentals}
    coll = H.CollectiveStats(
        {k: int(v) for k, v in c.coll_bytes.items()},
        {k: int(v) for k, v in c.coll_counts.items()})
    xla_cost = H.extract_cost(compiled)  # raw, kept for reference
    mem = H.extract_memory(compiled)
    mf = H.model_flops_for(cfg, shape.kind, shape.seq_len, shape.global_batch)
    roof = H.roofline(cost, coll, model_flops=mf, n_chips=n_chips)
    cost["xla_flops_raw"] = xla_cost["flops"]

    return {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "n_chips": n_chips, "kind": shape.kind,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "optimizer": optimizer, "grad_accum": n_accum,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "cost": cost, "memory": mem, "roofline": roof,
        "hbm_ok": mem.get("temp_size_in_bytes", 0) +
                  mem.get("argument_size_in_bytes", 0) < HW["hbm_bytes"],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--optimizer", default="scale")
    ap.add_argument("--accum", default="auto")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--fail-fast", action="store_true")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    cells = (list(iter_cells()) if args.all
             else [(args.arch, None, True)])

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch, shape, _ in cells:
        shape_name = args.shape if shape is None else shape.name
        for mp in meshes:
            tag = f"{arch}__{shape_name}__{'multi' if mp else 'single'}"
            out_path = os.path.join(args.out, tag + ".json")
            if os.path.exists(out_path):
                print(f"[skip] {tag} (exists)")
                continue
            print(f"[run ] {tag}", flush=True)
            try:
                rec = lower_cell(arch, shape_name, mp,
                                 optimizer=args.optimizer, accum=args.accum)
                with open(out_path, "w") as f:
                    json.dump(rec, f, indent=1)
                r = rec["roofline"]
                print(f"  ok: compile={rec['compile_s']}s "
                      f"bottleneck={r['bottleneck']} "
                      f"compute={r['compute_s']:.3e}s mem={r['memory_s']:.3e}s "
                      f"coll={r['collective_s']:.3e}s "
                      f"useful={r['useful_flop_ratio']:.2f}", flush=True)
            except Exception as e:
                failures.append((tag, repr(e)))
                print(f"  FAIL: {e}\n{traceback.format_exc()}", flush=True)
                if args.fail_fast:
                    raise
    if failures:
        print(f"\n{len(failures)} failures:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print("\nall cells compiled OK")


if __name__ == "__main__":
    main()
