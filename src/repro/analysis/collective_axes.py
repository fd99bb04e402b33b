"""CX: shard_map / collective-axis contract checks.

Rules
-----
CX001  hard-coded axis-name string literal passed directly to a
       collective (``psum``/``pmax``/...). Mesh axis names are caller
       config; kernels must take them from the sharding-derived plan.
CX002  collective axis that resolves to a constant string (a module- or
       function-level ``AXIS = "data"``) — same bug, one assignment
       removed.
CX003  ``shard_map`` ``in_specs``/``out_specs`` arity vs the wrapped
       function's positional parameters / returned tuple.
CX004  (dynamic) the dispatch reduce-axis derivation: ``_red_axes`` must
       return exactly the plan axes that shard the *reduce* dimension
       (rows for col-norms, columns for row-norms). Runs by importing
       ``repro.kernels.dispatch`` and probing a synthetic plan.
"""
from __future__ import annotations

import ast

from .astutil import (ModuleInfo, Resolver, call_name, iter_calls, kwarg,
                      positional_arity)
from .findings import Finding

_COLLECTIVES = {"psum", "pmax", "pmin", "pmean", "all_gather", "all_to_all",
                "ppermute", "axis_index"}


def run(modules, resolver=None, rel=None):
    resolver = resolver or Resolver()
    for mi in modules:
        resolver.add(mi)
    rel = rel or (lambda p: str(p))
    out = []
    for mi in modules:
        path = rel(mi.path)
        out.extend(_check_collectives(mi, resolver, path))
        out.extend(_check_shard_map(mi, resolver, path))
    return out


def _axis_arg(call, last):
    if last == "axis_index":
        pos = 0
    else:
        pos = 1
    if len(call.args) > pos:
        return call.args[pos]
    return kwarg(call, "axis_name")


def _axis_strings(node):
    """Axis-name string literals at the *top level* of an axis argument:
    a bare string, or elements of a tuple/list of axis names. Strings
    buried deeper (e.g. a ``"col"`` comparison inside a subscript that
    selects the plan axes) are not axis names."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [el.value for el in node.elts
                if isinstance(el, ast.Constant)
                and isinstance(el.value, str)]
    return []


def _check_collectives(mi, resolver, path):
    out = []
    for call in ast.walk(mi.tree):
        name = call_name(call)
        if not name:
            continue
        parts = name.split(".")
        last = parts[-1]
        if last not in _COLLECTIVES:
            continue
        if len(parts) > 1 and parts[-2] not in ("lax", "jax"):
            continue  # some other object's method, not a jax collective
        axis = _axis_arg(call, last)
        if axis is None:
            continue
        lits = _axis_strings(axis)
        if lits:
            out.append(Finding(
                "CX001", path, call.lineno,
                f"{last} over hard-coded axis name "
                f"{lits[0]!r}; derive collective axes from the "
                f"sharding plan, not string literals"))
            continue
        ctx = resolver.ctx_for(call, mi)
        for val, _ in resolver.resolve(axis, ctx):
            if val is axis:
                continue
            lits = _axis_strings(val)
            if lits:
                out.append(Finding(
                    "CX002", path, call.lineno,
                    f"{last} axis resolves to constant "
                    f"{lits[0]!r}; derive collective axes from "
                    f"the sharding plan, not module constants"))
                break
    return out


def _return_arities(fn):
    """Possible return-tuple arities of a FunctionDef/Lambda body."""
    arities = set()
    if isinstance(fn, ast.Lambda):
        body = fn.body
        arities.add(len(body.elts) if isinstance(body, ast.Tuple) else 1)
        return arities, True
    resolvable = True
    for node in ast.walk(fn):
        if isinstance(node, ast.Return) and node.value is not None:
            if isinstance(node.value, ast.Tuple):
                arities.add(len(node.value.elts))
            elif isinstance(node.value, (ast.Name, ast.Constant,
                                         ast.Attribute, ast.Subscript,
                                         ast.BinOp, ast.UnaryOp)):
                arities.add(1)
            elif isinstance(node.value, ast.Call):
                nm = call_name(node.value) or ""
                # x.reshape(...) / x.astype(...) return one array
                if nm.split(".")[-1] in ("reshape", "astype", "sum", "mean",
                                         "transpose"):
                    arities.add(1)
                else:
                    resolvable = False
            else:
                resolvable = False
    return arities, resolvable


def _check_shard_map(mi, resolver, path):
    out = []
    for call in iter_calls(mi.tree, "shard_map"):
        if not call.args:
            continue
        ctx = resolver.ctx_for(call, mi)
        fns = resolver.resolve_function(call.args[0], ctx)
        in_specs = kwarg(call, "in_specs")
        out_specs = kwarg(call, "out_specs")
        n_in = None
        if isinstance(in_specs, (ast.Tuple, ast.List)):
            n_in = len(in_specs.elts)
        n_out = None
        if isinstance(out_specs, (ast.Tuple, ast.List)):
            n_out = len(out_specs.elts)
        for fn, _ in fns:
            if getattr(fn, "args", None) is not None and (
                    fn.args.vararg is not None):
                continue
            arity = positional_arity(fn)
            fname = getattr(fn, "name", "<lambda>")
            if n_in is not None and arity != n_in:
                out.append(Finding(
                    "CX003", path, call.lineno,
                    f"shard_map in_specs has {n_in} entries but wrapped "
                    f"fn {fname} takes {arity} positional args"))
            if n_out is not None and not isinstance(fn, ast.Lambda):
                rets, resolvable = _return_arities(fn)
                if resolvable and rets and n_out not in rets:
                    out.append(Finding(
                        "CX003", path, call.lineno,
                        f"shard_map out_specs has {n_out} entries but "
                        f"wrapped fn {fname} returns "
                        f"{sorted(rets)} value(s)"))
    return out


def check_dispatch_contract():
    """CX004: executable probe of the reduce-axis derivation.

    Col-kind norms reduce over rows, so the cross-shard psum must run
    over the axes sharding the ``rows`` role of the padded (L, m, n)
    layout; row-kind over ``cols``. A synthetic plan makes the mapping
    observable without any mesh.
    """
    out = []
    try:
        from repro.kernels import dispatch as _d
    except Exception as e:  # pragma: no cover - import env problems
        return [Finding("CX004", "src/repro/kernels/dispatch.py", 0,
                        f"could not import dispatch for the dynamic "
                        f"reduce-axis probe: {e!r}")]
    red = getattr(_d, "_red_axes", None)
    plan_cls = getattr(_d, "Plan", None)
    if red is None or plan_cls is None:
        return [Finding("CX004", "src/repro/kernels/dispatch.py", 0,
                        "dispatch no longer exposes _red_axes/Plan; "
                        "update the CX004 probe alongside the refactor")]
    try:
        plan = plan_cls(None, (("rows", ("row_ax",)),
                               ("cols", ("col_ax",))))
        got_col = tuple(red(plan, "col"))
        got_row = tuple(red(plan, "row"))
    except Exception as e:
        return [Finding("CX004", "src/repro/kernels/dispatch.py", 0,
                        f"_red_axes probe raised {e!r}")]
    if got_col != ("row_ax",):
        out.append(Finding(
            "CX004", "src/repro/kernels/dispatch.py", 0,
            f"col-kind reduce axes must be the row-dim sharding axes "
            f"(role 'rows'); got {got_col!r}"))
    if got_row != ("col_ax",):
        out.append(Finding(
            "CX004", "src/repro/kernels/dispatch.py", 0,
            f"row-kind reduce axes must be the col-dim sharding axes "
            f"(role 'cols'); got {got_row!r}"))
    return out


def analyze_source(path, source):
    """Convenience for tests: analyze one synthetic module."""
    return run([ModuleInfo(path, source)])
