"""Roofline analysis from compiled dry-run artifacts.

``collective_bytes`` is not exposed by ``cost_analysis()``; we parse the
SPMD-partitioned HLO (per-device view, so printed shapes are local shards)
and sum the moved bytes of every collective:

  all-gather          -> out_bytes                (received per device)
  reduce-scatter      -> out_bytes * (group - 1)  (ring sends n-1 shards)
  all-reduce          -> 2 * out_bytes * (g-1)/g  (RS + AG ring)
  all-to-all          -> out_bytes
  collective-permute  -> out_bytes
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional


from .mesh import HW

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.:  %all-reduce.5 = f32[1024,512]{1,0} all-reduce(...), replica_groups=...
_OP_RE = re.compile(
    r"=\s*(?:\(?)([a-z0-9\[\],\s{}]*?)\)?\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", re.IGNORECASE)
_SHAPE_RE = re.compile(r"(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64)"
                       r"\[([0-9,]*)\]")
_GROUP_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUP2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUP_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUP2_RE.search(line)
    if m:  # replica_groups=[G,S]<=[N]: G groups of size S
        return int(m.group(2))
    return 2


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: Dict[str, int]
    count_by_kind: Dict[str, int]

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def collective_stats(hlo_text: str) -> CollectiveStats:
    bytes_by = {k: 0 for k in _COLLECTIVES}
    count_by = {k: 0 for k in _COLLECTIVES}
    seen_start = set()
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        type_str, kind = m.group(1), m.group(2).lower()
        # async ops appear as -start/-done pairs: count -start only
        if "-done(" in line:
            continue
        out_bytes = _shape_bytes(type_str)
        g = _group_size(line)
        if kind == "all-reduce":
            moved = int(2 * out_bytes * (g - 1) / max(g, 1))
        elif kind == "reduce-scatter":
            moved = out_bytes * (g - 1)
        else:
            moved = out_bytes
        bytes_by[kind] += moved
        count_by[kind] += 1
    return CollectiveStats(bytes_by, count_by)


def extract_cost(compiled) -> dict:
    """FLOPs / bytes-accessed from compiled.cost_analysis() (per device)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
    }


def extract_memory(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


_KERNEL_RE = re.compile(r'op_name="(?:[^"]*/)?([^"/]+)/pallas_call"')


def pallas_kernels(hlo_text: str) -> Dict[str, int]:
    """{kernel name: count} of the compiled Pallas calls in a TPU program.

    Each kernel is a ``tpu_custom_call`` whose op metadata ends in
    ``<name>/pallas_call`` (the ``name=`` every kernel under
    ``repro.kernels`` passes). Off the TPU the kernels are interpreted and
    none appear.
    """
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = _KERNEL_RE.search(line)
            name = m.group(1) if m else "unnamed"
            out[name] = out.get(name, 0) + 1
    return out


def roofline(cost: dict, coll: CollectiveStats, *, model_flops: float,
             n_chips: int, hw: Optional[dict] = None) -> dict:
    """The three roofline terms (seconds) + bottleneck + usefulness ratio.

    ``cost`` comes from the SPMD-partitioned module, i.e. per-device values.
    """
    hw = hw or HW
    compute_s = cost["flops"] / hw["peak_flops_bf16"]
    memory_s = cost["bytes_accessed"] / hw["hbm_bw"]
    collective_s = coll.total_bytes / hw["ici_bw"]
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    bottleneck = max(terms, key=terms.get)
    step_s = max(terms.values())
    useful = model_flops / max(cost["flops"] * n_chips, 1.0)
    mfu = (model_flops / n_chips / max(step_s, 1e-30)) / hw["peak_flops_bf16"]
    return {
        **terms,
        "bottleneck": bottleneck,
        "model_flops_total": model_flops,
        "hlo_flops_per_chip": cost["flops"],
        "useful_flop_ratio": useful,
        "roofline_step_s": step_s,
        "mfu_at_roofline": mfu,
        "collective_bytes": coll.total_bytes,
        "collective_breakdown": coll.bytes_by_kind,
        "collective_counts": coll.count_by_kind,
    }


def model_flops_for(cfg, shape_kind: str, seq_len: int, global_batch: int) -> float:
    """6*N_active*D for training, 2*N_active*D for inference (per step)."""
    from repro.models import count_params, param_shapes
    n_active = count_params(param_shapes(cfg), cfg=cfg, active_only=True)
    if shape_kind == "train":
        tokens = seq_len * global_batch
        return 6.0 * n_active * tokens
    if shape_kind == "prefill":
        tokens = seq_len * global_batch
        return 2.0 * n_active * tokens
    # decode: one token per sequence per step
    return 2.0 * n_active * global_batch
