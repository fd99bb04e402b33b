"""Host-side step-time breakdown and device-memory accounting.

:class:`StepTimer` accumulates named wall-clock sections (``data`` wait,
blocked ``step`` time, ``ckpt`` IO, ...) between metric emissions;
``snapshot()`` returns seconds-per-section (+ call counts) and resets, so
every emitted record carries the breakdown *since the last record* —
deltas, matching the dispatch fallback-delta semantics.

:func:`device_memory` reads ``jax.local_devices()[i].memory_stats()``
where the backend provides it (TPU/GPU; CPU returns nothing) and reports
live/peak bytes per local device plus totals. A TPU that reports no
stats raises: every TPU has them, so their absence is a fault.
"""
from __future__ import annotations

import time
from contextlib import contextmanager

import jax


class StepTimer:
    """Accumulating wall-clock section timer (not thread-safe: the train
    loop is single-threaded on the host)."""

    def __init__(self):
        self._acc: dict = {}
        self._n: dict = {}
        self._t0 = time.perf_counter()

    @contextmanager
    def section(self, name: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t
            self._acc[name] = self._acc.get(name, 0.0) + dt
            self._n[name] = self._n.get(name, 0) + 1

    def add(self, name: str, seconds: float) -> None:
        self._acc[name] = self._acc.get(name, 0.0) + float(seconds)
        self._n[name] = self._n.get(name, 0) + 1

    def snapshot(self) -> dict:
        """{'time/<name>_s': secs, 'time/<name>_n': calls, 'time/wall_s':
        wall-clock since the previous snapshot}; resets the accumulators."""
        now = time.perf_counter()
        out = {"time/wall_s": now - self._t0}
        for name, secs in self._acc.items():
            out[f"time/{name}_s"] = secs
            out[f"time/{name}_n"] = self._n[name]
        self._acc, self._n, self._t0 = {}, {}, now
        return out


def device_memory() -> dict:
    """Per-local-device live/peak HBM bytes, where the backend exposes it.

    Keys: ``mem/dev<i>/bytes_in_use``, ``mem/dev<i>/peak_bytes`` plus
    ``mem/total_bytes_in_use`` / ``mem/total_peak_bytes``. Empty dict on
    backends without ``memory_stats`` (host CPU). A TPU always reports
    them, so there a missing report raises instead of logging nothing.
    """
    out: dict = {}
    total_live = total_peak = 0
    for i, d in enumerate(jax.local_devices()):
        ms = d.memory_stats()
        if not ms:
            if d.platform == "tpu":
                raise RuntimeError(f"{d} reports no memory_stats()")
            continue
        live = int(ms.get("bytes_in_use", 0))
        peak = int(ms.get("peak_bytes_in_use", live))
        out[f"mem/dev{i}/bytes_in_use"] = live
        out[f"mem/dev{i}/peak_bytes"] = peak
        total_live += live
        total_peak += peak
    if out:
        out["mem/total_bytes_in_use"] = total_live
        out["mem/total_peak_bytes"] = total_peak
    return out
