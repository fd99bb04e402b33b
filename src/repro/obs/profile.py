"""Profiler trace hooks: a step window for ``jax.profiler``, and the
named spans the trace viewer groups work under.

``--profile-steps A:B`` on the training CLI parses into a
:class:`ProfileWindow`; the loop calls ``maybe_start(step)`` before and
``maybe_stop(step)`` after each step, so exactly steps ``A..B`` (inclusive,
0-indexed like the log lines) land in the trace. Spans:

  * in-jit work is annotated with ``jax.named_scope`` in the model
    (``embed``, ``layers``, one per sub-layer kind, ``final_norm``,
    ``head``) and the trainer (``fwd``, ``clip``, ``optimizer_update``,
    ``guard``, ``step_metrics``, ``obs_stats``) — those names show up on
    the compiled op metadata, and the backward pass and recomputed forward
    inherit them (see README.md for the metric that reads each);
  * host-side phases of the training loop (``data_wait``, ``train_step``,
    ``sync``, ``checkpoint``) wrap in ``jax.profiler.TraceAnnotation``, and
    each loop turn in a numbered ``jax.profiler.StepTraceAnnotation`` —
    both cost nothing while no trace is being taken, so they stay on
    every step.
"""
from __future__ import annotations

import os
import warnings
from typing import Optional

import jax


class ProfileWindow:
    """Drive ``jax.profiler.start_trace/stop_trace`` over a step range."""

    def __init__(self, start: int, stop: int, logdir: str):
        if stop < start or start < 0:
            raise ValueError(f"profile window must be 0 <= start <= stop, "
                             f"got {start}:{stop}")
        self.start = int(start)
        self.stop = int(stop)
        self.logdir = logdir
        self.active = False
        self.done = False

    @classmethod
    def parse(cls, spec: str, logdir: str) -> Optional["ProfileWindow"]:
        """``"A:B"`` (inclusive) or ``"A"`` (single step) -> window;
        ``""`` -> None."""
        if not spec:
            return None
        parts = spec.split(":")
        if len(parts) not in (1, 2):
            raise ValueError(
                f"--profile-steps wants 'A:B' or 'A', got {spec!r}")
        try:
            a = int(parts[0])
            b = int(parts[1]) if len(parts) == 2 else a
        except ValueError as e:
            raise ValueError(
                f"--profile-steps wants integers, got {spec!r}") from e
        return cls(a, b, logdir)

    def maybe_start(self, step: int) -> bool:
        if self.done or self.active or step < self.start or step > self.stop:
            return False
        try:
            os.makedirs(self.logdir, exist_ok=True)
            jax.profiler.start_trace(self.logdir)
            self.active = True
        except Exception as e:  # profiling must never kill the run
            warnings.warn(f"profiler: start_trace failed ({e}); "
                          "disabling the profile window")
            self.done = True
        return self.active

    def maybe_stop(self, step: int) -> bool:
        """Stop after the last window step (call with the step just run)."""
        if not self.active or step < self.stop:
            return False
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            warnings.warn(f"profiler: stop_trace failed ({e})")
        self.active = False
        self.done = True
        return True

    def finalize(self) -> None:
        """Stop an open trace (run ended inside the window / SIGTERM)."""
        if self.active:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self.active = False
            self.done = True
