"""Training driver CLI.

Runs a real training loop on the devices JAX finds — one TPU chip or a
four-chip host, or the CPU, where the tests run it with the Pallas kernels
interpreted — with parameters initialised sharded by the model's rules
table, each batch placed along the mesh's batch axes,
deterministic resumable data, periodic checkpointing and auto-resume.

Compile cache: on a TPU, ``main`` keeps JAX's persistent compilation cache
in ``<repo root>/.jax_cache`` — a fixed path, so a later run finds it —
unless ``JAX_COMPILATION_CACHE_DIR`` is set, in which case JAX keeps it
there and nothing here overrides it.

Resilience (PR 8): the step runs under the in-jit anomaly guard
(:mod:`repro.training.resilience`) — non-finite loss/grad-norm or a loss
spike skips the update bitwise; after ``--max-bad-steps`` consecutive bad
steps the driver rolls back to the last verifiable checkpoint and cuts the
learning rate by ``--rollback-lr-cut`` (recompiling the step with the new
peak LR). SIGTERM triggers a final synchronous checkpoint and a clean
exit, so a preempted run under ``--resume auto`` loses at most the current
step. ``REPRO_FAULTS`` (see :mod:`repro.training.faults`) injects
deterministic chaos into all of it.

Telemetry (PR 10, :mod:`repro.obs`): ``--log-dir`` attaches a JSONL
metrics sink (schema ``repro_metrics/v1``, one ``metrics.<host>.jsonl``
per process — never cross-host-written) behind a non-blocking background
logger; records at ``--metrics-every`` cadence carry loss/norm metrics,
step-time breakdown (data wait / blocked step / checkpoint IO), tokens/s,
per-device memory where the backend reports it, and kernel-fallback
*deltas* (``dispatch.fallback_delta``); the run ends with a
``step_program`` record naming the Pallas kernels compiled into the step
and its ``memory_analysis()``. ``--stats-every K`` weaves the
in-jit per-layer-group statistics collector into the step (the paper's
Fig. 4/10 quantities live — see :mod:`repro.obs.stats`); ``--profile-steps
A:B`` wraps those steps in ``jax.profiler`` traces. Console lines are
host-0-only and always flushed; the logger is flushed on SIGTERM and on
rollback so a dying run's tail reaches disk.

Example (end-to-end ~100M-param pretraining driver):
  PYTHONPATH=src python -m repro.launch.train --arch llama-130m \
      --optimizer scale --steps 200 --batch 16 --seq 256 \
      --ckpt-dir /tmp/ckpt --ckpt-every 50 --resume auto \
      --log-dir /tmp/run0 --stats-every 50
"""
from __future__ import annotations

import argparse
import os
import signal
import time
from pathlib import Path

import jax
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.checkpoint import restore_latest, save, save_async
from repro.configs import get_arch
from repro.core import linear_warmup_cosine, make_optimizer
from repro.data import make_dataset
from repro.kernels import dispatch
from repro.launch.hlo_analysis import extract_memory, pallas_kernels
from repro.launch.mesh import make_host_mesh
from repro.models import init_params, param_logical_axes, param_shapes
from repro.models.sharding import Rules, tree_shardings
from repro.obs import (JSONLSink, MetricsLogger, ProfileWindow, StatsPolicy,
                       StepTimer, device_memory, split_stats)
from repro.training import (GuardPolicy, init_guard_state, init_state,
                            make_train_step, resolve_plan)


JAX_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Keep compiled TPU programs in ``JAX_CACHE_DIR`` across runs.

    Leaves the cache to JAX when ``JAX_COMPILATION_CACHE_DIR`` is set, and
    sets none off the TPU (the CPU test runs keep no cache).
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR") \
            or jax.default_backend() != "tpu":
        return
    jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE_DIR))


def place_batch(batch: dict, mesh, rules: Rules) -> dict:
    """Each host batch leaf on the mesh, split along its batch axes."""
    return {k: jax.device_put(v, rules.sharding(
        ("act_batch",) + (None,) * (v.ndim - 1), mesh, v.shape))
        for k, v in batch.items()}


def build(args, lr_scale: float = 1.0):
    """(cfg, tx) for the run. ``lr_scale`` scales the peak LR (rollback cut)."""
    cfg = get_arch(args.arch, smoke=args.smoke)
    if args.seq and cfg.attn_kv_block > args.seq:
        cfg.attn_kv_block = cfg.attn_q_block = max(16, args.seq // 4)
    cfg.loss_chunk = min(cfg.loss_chunk, args.seq)
    if args.dtype:
        cfg.dtype = args.dtype
    if getattr(args, "tie_embeddings", False):
        cfg.tie_embeddings = True
    sched = linear_warmup_cosine(args.lr * lr_scale, args.steps)
    return cfg, make_tx(cfg, args.optimizer, sched)


def make_tx(cfg, optimizer: str, lr):
    """``make_optimizer(optimizer, lr)`` for ``cfg``'s parameter tree."""
    if cfg.tie_embeddings:
        # feature-detect rather than enumerate names: any optimizer whose
        # factory takes LabelRules gets the tied embedding routed to the 'last'
        # group — scale would otherwise hard-error (a tied tree has no
        # lm_head to carry the momentum). Optimizers without a rules
        # kwarg treat every matrix alike, so there is nothing to route;
        # note only scale flips its col/row kind for the (V, D) storage —
        # the fixed-kind sgd_*norm ablations normalize along the storage
        # axis as defined.
        from repro.core import OPTIMIZER_REGISTRY
        from repro.core.labels import LabelRules
        spec = OPTIMIZER_REGISTRY.get(optimizer.lower())
        if spec is not None and "rules" in spec.valid_kwargs():
            return make_optimizer(optimizer, lr, rules=LabelRules.tied())
    return make_optimizer(optimizer, lr)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-130m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config for --arch")
    ap.add_argument("--optimizer", default="scale")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--pack-documents", dest="pack_documents",
                    action="store_true",
                    help="first-fit pack variable-length documents into "
                         "each (batch, seq) row; batches gain segment_ids "
                         "/ positions / loss_weights and attention + loss "
                         "stay within document boundaries")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--clip-norm", type=float, default=1.0)
    ap.add_argument("--dtype", default="")
    ap.add_argument("--tie-embeddings", dest="tie_embeddings",
                    action="store_true",
                    help="tie the LM head to the token embedding (no "
                         "lm_head params; SCALE momentum moves to the "
                         "tied matrix)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", default="none", choices=["none", "auto"])
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--log-dir", default="",
                    help="write schema-versioned JSONL metrics records "
                         "(metrics.<host>.jsonl) under this directory via "
                         "the non-blocking background logger")
    ap.add_argument("--metrics-every", type=int, default=10,
                    help="JSONL record cadence in steps (needs --log-dir)")
    ap.add_argument("--stats-every", type=int, default=0,
                    help="cadence of the in-jit per-layer-group gradient "
                         "statistics (Fig. 4/10 live: grad norms, column-"
                         "norm dispersion, update/param ratios, momentum "
                         "norms); 0 disables the collector entirely")
    ap.add_argument("--profile-steps", default="",
                    help="'A:B' (inclusive) or 'A': wrap those steps in a "
                         "jax.profiler trace written to --profile-dir")
    ap.add_argument("--profile-dir", default="",
                    help="profiler trace directory (default "
                         "<log-dir>/profile)")
    ap.add_argument("--no-guard", action="store_true",
                    help="disable the in-jit anomaly guard (finite checks "
                         "on loss/grad norm, step skipping, rollback)")
    ap.add_argument("--spike-factor", type=float, default=0.0,
                    help="skip steps whose loss exceeds this multiple of "
                         "the accepted-loss EMA (0 disables the spike "
                         "check; finite checks stay on)")
    ap.add_argument("--spike-warmup", type=int, default=20,
                    help="accepted steps before the spike check arms")
    ap.add_argument("--max-bad-steps", type=int, default=10,
                    help="consecutive guard-skipped steps before rolling "
                         "back to the last checkpoint with an LR cut "
                         "(0 = never roll back, skip forever)")
    ap.add_argument("--rollback-lr-cut", type=float, default=0.5,
                    help="multiply the peak LR by this on every rollback")
    ap.add_argument("--max-rollbacks", type=int, default=3,
                    help="abort the run after this many rollbacks (a "
                         "deterministic fault replays identically after "
                         "restore, so an unbounded loop would never "
                         "terminate)")
    args = ap.parse_args(argv)
    use_compile_cache()

    guard = None if args.no_guard else GuardPolicy(
        spike_factor=args.spike_factor, spike_warmup=args.spike_warmup,
        max_bad_steps=args.max_bad_steps)
    faults = resolve_plan()  # REPRO_FAULTS, read once, outside jit

    # ---- telemetry plane: every record/line carries (host, step); the
    # JSONL file is per-host (never cross-host-written) and only host 0
    # speaks on the console (multi-host log hygiene)
    host = jax.process_index()
    sinks = []
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
        sinks.append(JSONLSink(
            os.path.join(args.log_dir, f"metrics.{host}.jsonl")))
    logger = MetricsLogger(sinks, host=host)
    profile = ProfileWindow.parse(
        args.profile_steps,
        args.profile_dir or os.path.join(args.log_dir or ".", "profile"))

    if faults is not None:
        logger.console(f"fault injection active: {faults}")

    cfg, tx = build(args)
    rules = Rules(cfg.rule_overrides)
    n_dev = len(jax.devices())
    mesh = make_host_mesh(data=n_dev)
    stats = StatsPolicy(every_k=args.stats_every,
                        tied=cfg.tie_embeddings) \
        if args.stats_every > 0 else None
    logger.console(f"arch={cfg.name} optimizer={args.optimizer} "
                   f"devices={n_dev} "
                   f"guard={'off' if guard is None else 'on'}"
                   + (f" stats_every={args.stats_every}" if stats else ""))
    logger.log("run_header", 0, arch=cfg.name, optimizer=args.optimizer,
               devices=n_dev, guard=guard is not None,
               stats_every=args.stats_every, steps=args.steps,
               batch=args.batch, seq=args.seq, lr=args.lr,
               grad_accum=args.grad_accum,
               pack_documents=bool(args.pack_documents),
               tie_embeddings=bool(cfg.tie_embeddings))

    # params are born sharded per the rules table (never whole on one
    # device), so the fused optimizer runs sharded from step 0 (its kernels
    # psum norm reductions over the mesh — see repro.kernels.dispatch)
    params = jax.jit(
        lambda key: init_params(key, cfg),
        out_shardings=tree_shardings(param_logical_axes(cfg), mesh, rules,
                                     param_shapes(cfg)),
    )(jax.random.PRNGKey(args.seed))
    state = init_state(params, tx, guard=guard is not None)
    start_step = 0
    if args.resume == "auto" and args.ckpt_dir:
        got = restore_latest(args.ckpt_dir, state)
        if got is not None:
            state, start_step = got
            logger.console(f"resumed from step {start_step}",
                           step=start_step)

    ds = make_dataset(cfg, seq_len=args.seq, global_batch=args.batch,
                      seed=args.seed, pack_documents=args.pack_documents)

    def make_step(tx):
        return make_train_step(cfg, tx, grad_accum=args.grad_accum,
                               clip_norm=args.clip_norm, rules=rules,
                               mesh=mesh if n_dev > 1 else None, donate=True,
                               guard=guard, faults=faults, stats=stats)

    step_fn = make_step(tx)

    # SIGTERM (preemption notice) -> finish the current step, write a final
    # synchronous checkpoint, exit cleanly; --resume auto picks it up
    stop = {"sigterm": False}

    def _on_sigterm(signum, frame):
        del signum, frame
        stop["sigterm"] = True

    try:
        prev_handler = signal.signal(signal.SIGTERM, _on_sigterm)
    except ValueError:  # not the main thread (embedded use): no handler
        prev_handler = None

    t0 = time.time()
    pending = None
    # packed rows carry fewer real tokens than batch*seq; the loss's token
    # weight is the honest numerator for tok/s there
    tokens_per_step = args.batch * args.seq
    eff_tokens = 0.0
    step, done_steps = start_step, 0
    lr_scale, rollbacks = 1.0, 0
    metrics = {"loss": float("nan")}
    timer = StepTimer()
    fb_prev = dispatch.fallback_snapshot()

    last_emitted = -1

    def emit_record(step, tput):
        """One train_step JSONL record: loss/norm metrics, on-cadence
        stats, step-time breakdown deltas, memory, fallback deltas."""
        nonlocal fb_prev, last_emitted
        last_emitted = step
        plain, stat_vals = split_stats(metrics, stats)
        rec = dict(plain)
        rec.update(stat_vals)
        rec.update(timer.snapshot())
        rec.update(device_memory())
        fb = dispatch.fallback_snapshot()
        delta = dispatch.fallback_delta(fb_prev, fb)
        fb_prev = fb
        if delta:
            rec["fallbacks"] = delta
        rec["tokens_per_s"] = tput
        rec["lr_scale"] = lr_scale
        rec["rollbacks"] = rollbacks
        logger.log("train_step", step, rec)

    def roll_back(step, state, skipped):
        """The guard flagged an unrecoverable streak at ``step``; the host
        takes the action jit cannot: restore + LR cut + retrace. Returns
        the step to run next and its state."""
        nonlocal lr_scale, rollbacks, step_fn
        lr_scale *= args.rollback_lr_cut
        rollbacks += 1
        logger.log("event", step, event="rollback", rollbacks=rollbacks,
                   lr_scale=lr_scale, skipped=skipped)
        logger.flush()     # the tail of a sick run must hit disk
        if rollbacks > args.max_rollbacks:
            raise RuntimeError(
                f"giving up after {args.max_rollbacks} rollbacks: the run "
                f"keeps hitting {args.max_bad_steps} consecutive bad steps")
        got = restore_latest(args.ckpt_dir, state) if args.ckpt_dir else None
        if got is not None:
            state, step = got
            logger.console(f"rollback #{rollbacks}: restored step {step}, "
                           f"peak lr x{lr_scale:g}", step=step)
        else:
            # nothing to roll back to: reset the streak and push on with
            # the cut LR (the guard keeps skipping bad steps)
            step += 1
            logger.console(f"rollback #{rollbacks}: no checkpoint in "
                           f"{args.ckpt_dir or '<none>'}; continuing with "
                           f"peak lr x{lr_scale:g}", step=step)
        _, tx = build(args, lr_scale)
        step_fn = make_step(tx)
        return step, state._replace(guard=init_guard_state())

    try:
        while step < args.steps and not stop["sigterm"]:
            if profile is not None:
                profile.maybe_start(step)
            turn = step
            # one numbered step in the profiler per loop turn, so every
            # idle gap of the device lands in a step
            with StepTraceAnnotation("train", step_num=turn):
                with timer.section("data"), TraceAnnotation("data_wait"):
                    batch = place_batch(ds.host_batch_at(step), mesh, rules)
                # "step" is blocked step time: the call through the read of
                # the guard's rollback flag, which waits for the step's
                # outputs (with --no-guard, nothing is read: dispatch time)
                with timer.section("step"):
                    with TraceAnnotation("train_step"):
                        state, metrics = step_fn(state, batch)
                    rollback_flag = False
                    if guard is not None:
                        with TraceAnnotation("sync"):
                            rollback_flag = bool(float(metrics["rollback"]))
                if rollback_flag:
                    step, state = roll_back(step, state, metrics["skipped"])
                else:
                    step += 1
                    done_steps += 1
                    eff_tokens += (
                        float(metrics.get("weight", tokens_per_step))
                        if args.pack_documents else tokens_per_step)
                    tput = eff_tokens / max(time.time() - t0, 1e-9)
                    if step % args.log_every == 0 or done_steps == 1:
                        line = (f"step {step:6d} loss "
                                f"{float(metrics['loss']):.4f} "
                                f"|g| {float(metrics['grad_norm']):.3f} "
                                f"tok/s {tput:,.0f}")
                        if guard is not None:
                            line += (f" skipped {int(metrics['skipped'])}"
                                     f" rollbacks {rollbacks}")
                        fb = dispatch.fallback_counts()
                        if fb:
                            line += f" kernel-fallbacks {sum(fb.values())}"
                        logger.console(line, step=step, raw=True)
                    if args.log_dir and (step % args.metrics_every == 0
                                         or done_steps == 1):
                        emit_record(step, tput)
                    if args.ckpt_dir and step % args.ckpt_every == 0:
                        with timer.section("ckpt"), \
                                TraceAnnotation("checkpoint"):
                            if pending is not None:
                                pending.wait()   # one checkpoint in flight
                            pending = save_async(args.ckpt_dir, step, state)
            if profile is not None:
                profile.maybe_stop(turn)
        with timer.section("ckpt"), TraceAnnotation("checkpoint"):
            if pending is not None:
                pending.wait()
            if args.ckpt_dir:
                save(args.ckpt_dir, step, state)
        if stop["sigterm"]:
            logger.console(f"sigterm: checkpointed step {step}, exiting "
                           "cleanly", step=step)
        else:
            logger.console(f"done: final loss {float(metrics['loss']):.4f}",
                           step=step)
        if args.log_dir and done_steps and step != last_emitted:
            emit_record(step, eff_tokens / max(time.time() - t0, 1e-9))
        if args.log_dir and done_steps:
            # the program the steps ran: same arguments, so the jit's own
            # compile is reused rather than repeated
            compiled = step_fn.lower(state, batch).compile()
            logger.log("step_program", step,
                       kernels=pallas_kernels(compiled.as_text()),
                       **extract_memory(compiled))
        logger.log("run_end", step,
                   reason="sigterm" if stop["sigterm"] else "done",
                   loss=metrics["loss"], rollbacks=rollbacks,
                   fallbacks=dispatch.fallback_counts() or None)
    finally:
        if profile is not None:
            profile.finalize()
        logger.close()
        if prev_handler is not None:
            signal.signal(signal.SIGTERM, prev_handler)
    return float(metrics["loss"])


if __name__ == "__main__":
    main()
