"""Reduction of a profiler trace to device intervals, and the arithmetic
on them that the per-layer metrics read.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes. Each
``/device:TPU:<n>`` plane has an ``XLA Ops`` line (one event per executed
HLO instruction, named by the instruction's text, ``%name = shape
opcode(...)``; control flow such as ``while`` appears as an event that
contains its body's events) and an ``XLA Modules`` line (one event per
executed program). Host threads are the ``/host:CPU`` plane's lines; the
benchmark's own spans (``TraceAnnotation``) are among their events.

Everything below works on plain ``(start_ns, end_ns, name)`` tuples, so a
hand-built trace tests it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
_NAME = re.compile(r"^%?([^ =]+)")


@dataclasses.dataclass
class Trace:
    ops: dict        # device id -> [(start, end, instruction text)]
    modules: dict    # device id -> [(start, end, program name)]
    host: list       # [(start, end, span name)]


def load(trace_dir: str) -> Trace:
    """The trace that ``jax.profiler.start_trace(trace_dir)`` wrote."""
    import jax
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one xplane.pb under {trace_dir}, "
                           f"found {files}")
    data = jax.profiler.ProfileData.from_file(files[0])
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events]
                    (ops if line.name == "XLA Ops" else modules)[dev] = evs
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in line.events]
    if not ops:
        raise RuntimeError("the trace holds no device operations")
    return Trace(ops, modules, host)


# ------------------------------------------------------------ intervals

def union(intervals) -> list:
    """Merged, sorted, disjoint intervals."""
    out = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def length(intervals) -> float:
    return float(sum(e - s for s, e in union(intervals)))


def subtract(a, b) -> list:
    """Parts of the intervals ``a`` that no interval of ``b`` covers."""
    out = []
    b = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
        if cur < e:
            out.append((cur, e))
    return out


def gaps(intervals) -> list:
    """The holes between consecutive merged intervals."""
    u = union(intervals)
    return [(a[1], b[0]) for a, b in zip(u, u[1:])]


def leaves(events) -> list:
    """Events of positive length that contain no other such event
    (control-flow containers such as ``while`` dropped), so summed times
    count each moment once. Operations on one ``XLA Ops`` line run one at
    a time, so an event that starts inside another is nested in it."""
    evs = sorted((x for x in events if x[1] > x[0]),
                 key=lambda x: (x[0], -x[1]))
    out = []
    for i, (s, e, n) in enumerate(evs):
        if i + 1 < len(evs) and evs[i + 1][0] < e and evs[i + 1][1] <= e:
            continue
        out.append((s, e, n))
    return out


def op_name(text: str) -> str:
    """``%attn_fwd.16 = ...`` -> ``attn_fwd.16``."""
    m = _NAME.match(text)
    return m.group(1) if m else text


def op_kind(text: str) -> str:
    """Instruction name without its ``.N`` suffix (``attn_fwd``)."""
    return re.sub(r"(\.\d+)+$", "", op_name(text))


def is_collective(text: str) -> bool:
    return op_kind(text).startswith(COLLECTIVES)


# -------------------------------------------------------------- readings

def busy_ns(tr: Trace) -> float:
    """Device time with some operation running, averaged over devices."""
    return sum(length(v) for v in tr.ops.values()) / len(tr.ops)


def kernel_ns(tr: Trace, prefixes: tuple) -> float:
    """Summed time of operations named with one of ``prefixes``, averaged
    over devices; 0 where none ran."""
    tot = 0.0
    for evs in tr.ops.values():
        tot += sum(e - s for s, e, n in leaves(evs)
                   if op_name(n).startswith(prefixes))
    return tot / len(tr.ops)


def scope_ns(tr: Trace, scopes: dict, scope: str) -> float:
    """Summed time of operations whose HLO ``op_name`` holds ``/scope/``,
    averaged over devices. ``scopes`` maps instruction names to op_name."""
    tag = f"/{scope}/"
    tot = 0.0
    for evs in tr.ops.values():
        tot += sum(e - s for s, e, n in leaves(evs)
                   if tag in scopes.get(op_name(n), ""))
    return tot / len(tr.ops)


def step_gaps_ns(tr: Trace, program: str) -> list:
    """Idle time between consecutive runs of ``program`` on device 0: from
    the end of one run's last operation to the start of the next one's
    first."""
    dev = min(tr.modules)
    runs = sorted((s, e) for s, e, n in tr.modules[dev] if program in n)
    return [max(0.0, b[0] - a[1]) for a, b in zip(runs, runs[1:])]


def program_runs(tr: Trace, program: str) -> int:
    dev = min(tr.modules)
    return sum(1 for *_, n in tr.modules[dev] if program in n)


def exposed_collective_ns(tr: Trace) -> float:
    """Worst device's collective time during which no other operation
    runs on it."""
    worst = 0.0
    for evs in tr.ops.values():
        lv = leaves(evs)
        coll = [x for x in lv if is_collective(x[2])]
        other = [x for x in lv if not is_collective(x[2])]
        worst = max(worst, length(subtract(coll, other)))
    return worst


# -------------------------------------------------------------- breakdown

def scope_of(op_name_meta: str) -> str:
    """First named scope under the program: ``jit(train_step)/jvp(fwd)/..``
    -> ``jvp(fwd)``."""
    parts = [p for p in op_name_meta.split("/") if p]
    return parts[1] if len(parts) > 1 else (parts[0] if parts else "?")


def top_ops(tr: Trace, scopes: dict, kernels: tuple, n: int = 10) -> list:
    """[[name, seconds]]: device time by kernel, else by instruction kind
    and scope, summed over devices, longest first."""
    tot = defaultdict(float)
    for evs in tr.ops.values():
        for s, e, text in leaves(evs):
            name = op_name(text)
            if name.startswith(kernels):
                key = op_kind(text)
            else:
                key = f"{op_kind(text)} ({scope_of(scopes.get(name, ''))})"
            tot[key] += (e - s) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda x: -x[1])[:n]]


def idle_by_host(tr: Trace, spans: tuple, n: int = 10) -> list:
    """[[host span, seconds]]: device 0's idle gaps, each charged to the
    benchmark span (of ``spans``) that overlaps it most, else "other"."""
    dev = min(tr.ops)
    host = [x for x in tr.host if x[2] in spans]
    tot = defaultdict(float)
    for gs, ge in gaps(tr.ops[dev]):
        best, over = "other", 0.0
        for hs, he, name in host:
            o = min(ge, he) - max(gs, hs)
            if o > over:
                best, over = name, o
        tot[best] += (ge - gs) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda x: -x[1])[:n]]


def hlo_scopes(hlo_text: str) -> dict:
    """{instruction name: op_name metadata} from a compiled program's
    text."""
    out = {}
    for m in re.finditer(r"^\s*%([^ =]+) = .*?op_name=\"([^\"]*)\"",
                         hlo_text, re.M):
        out[m.group(1)] = m.group(2)
    return out
