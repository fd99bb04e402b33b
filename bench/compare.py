"""The comparison that decides ``correct``.

Three numbers are compared, each against a limit of its own that the
cell's configuration file states (``limits``), with the readings each limit
was set from in ``PERF.md``:

* ``loss_gap``: the widest relative gap between the program's and the
  reference's loss over the first steps;
* ``grad_gap``: the first gradient as the optimizer got it, worked out from
  the optimizer's state after one step (the LM head's momentum, the Adam
  first moments of the vectors), by the worst leaf;
* ``delta_gap``: the norm of each leaf's change over the first steps, by
  the worst leaf;
* ``delta_diff``: the distance between the program's parameters and the
  reference's after the first steps, ``|prog - ref|``, by the worst matrix
  leaf. SCALE moves every column of a matrix by ``lr`` whatever its
  gradient, so a matrix's change has the same norm whether its direction
  is right or wrong: only this number sees the direction.

A gap of norms is ``| |prog| - |ref| |`` over the larger of the reference's
norm of that leaf and the median leaf's, since some leaves barely move;
``delta_diff`` takes the reference's change of the leaf and of the median
matrix leaf in the same place. Stacked layers count each layer's slice as
a leaf (``path[l]``). Leaves whose reference gradient is under
``DEAD_SHARE`` of the median leaf's move by round-off alone and are left
out of ``delta_gap``. Matrices (leaves named ``w*``) step by their column
norms; the other leaves (norm scales, biases) by Adam.
"""
from __future__ import annotations

import statistics

import numpy as np

DEAD_SHARE = 1e-3
NAMES = ("loss_gap", "grad_gap", "delta_gap", "delta_diff")


def named_norms(flat: dict) -> dict:
    """{path: array} -> {name: norm}: one norm per layer for the leaves of
    stacked layers (``layers/...``, layer on axis 0), else one per leaf.
    Arrays already reduced to those norms pass through unchanged."""
    out = {}
    for path, x in flat.items():
        x = np.asarray(x, np.float64)
        if path.startswith("layers/"):
            n = x if x.ndim == 1 else np.sqrt(
                np.sum(x.reshape(x.shape[0], -1) ** 2, axis=1))
            for l, v in enumerate(n):
                out[f"{path}[{l}]"] = float(v)
        else:
            out[path] = float(x if x.ndim == 0 else np.sqrt(np.sum(x * x)))
    return out


def norm_gap(prog: dict, ref: dict, keep=None) -> tuple:
    """(worst gap, its leaf) of two {name: norm} readings."""
    names = [k for k in ref if keep is None or k in keep]
    missing = [k for k in names if k not in prog]
    if missing:
        raise KeyError(f"program readings lack {missing[:5]}")
    med = statistics.median(ref[k] for k in names)
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in names}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def is_matrix(name: str) -> bool:
    return name.split("[")[0].rsplit("/", 1)[-1].startswith("w")


def distance(diff: dict, ref_delta: dict) -> tuple:
    """(worst, its leaf) of each matrix leaf's ``|prog - ref|`` over the
    larger of the reference's change of that leaf and of the median
    matrix leaf."""
    names = [k for k in ref_delta if is_matrix(k)]
    missing = [k for k in names if k not in diff]
    if missing:
        raise KeyError(f"program readings lack {missing[:5]}")
    med = statistics.median(ref_delta[k] for k in names)
    gaps = {k: diff[k] / max(ref_delta[k], med, 1e-30) for k in names}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def live_leaves(ref_grad_all: dict) -> set:
    med = statistics.median(ref_grad_all.values())
    return {k for k, v in ref_grad_all.items() if v >= DEAD_SHARE * med}


def detail(prog: dict, ref: dict) -> dict:
    """Every per-step and per-leaf gap, for setting limits."""
    med = statistics.median(ref["grad_all"].values())
    out = {"loss": [abs(a - b) / abs(b) for a, b in zip(prog["loss"],
                                                       ref["loss"])],
           "grad_share": {k: v / med for k, v in ref["grad_all"].items()}}
    for key in ("grad", "delta"):
        m = statistics.median(ref[key].values())
        out[key] = {k: abs(prog[key][k] - v) / max(v, m, 1e-30)
                    for k, v in ref[key].items()}
    m = statistics.median(v for k, v in ref["delta"].items() if is_matrix(k))
    out["diff"] = {k: prog["diff"][k] / max(v, m, 1e-30)
                   for k, v in ref["delta"].items()}
    return out


def readings(prog: dict, ref: dict) -> dict:
    """{name: (value, detail)} of the compared numbers."""
    lg = [abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"])]
    if len(prog["loss"]) != len(ref["loss"]) or not all(
            np.isfinite(prog["loss"])):
        lg = [float("inf")]
    live = live_leaves(ref["grad_all"])
    g, gk = norm_gap(prog["grad"], ref["grad"])
    d, dk = norm_gap(prog["delta"], ref["delta"], keep=live)
    x, xk = distance(prog["diff"], ref["delta"])
    return {"loss_gap": (max(lg), f"step {int(np.argmax(lg)) + 1}"),
            "grad_gap": (g, gk), "delta_gap": (d, dk), "delta_diff": (x, xk)}


def judge(values: dict, limits: dict) -> bool:
    """True when every compared number is finite and under its limit."""
    return all(np.isfinite(values[k]) and values[k] <= limits[k]
               for k in limits)
