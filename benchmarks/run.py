"""Benchmark entrypoint: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. ``--full`` runs the long versions
(Table-scale step counts); default is a quick pass suitable for CI.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma list: norms,memory,pretrain,optimizers,"
                         "throughput,variance,fused,xent")
    args = ap.parse_args()
    quick = not args.full

    from . import (fused_update, memory_table, norm_timing, optimizer_bench,
                   pretrain_proxy, throughput, variance_analysis, xent_fused)
    sections = {
        "norms": norm_timing,
        "memory": memory_table,
        "pretrain": pretrain_proxy,
        "optimizers": optimizer_bench,
        "throughput": throughput,
        "variance": variance_analysis,
        "fused": fused_update,
        "xent": xent_fused,
    }
    only = set(args.only.split(",")) if args.only else set(sections)

    print("name,us_per_call,derived")
    for name, mod in sections.items():
        if name not in only:
            continue
        t0 = time.time()
        try:
            rows = mod.run(quick=quick)
        except Exception as e:  # a failing section must not hide the rest
            rows = [(f"{name}/ERROR", None, repr(e))]
        for r in rows:
            print(f"{r[0]},{r[1] if r[1] is not None else ''},{r[2]}")
        print(f"# section {name} took {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
