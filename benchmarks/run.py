"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Scale knobs via env:
  REPRO_BENCH_FAST=1  -> kernel microbenches only (CI mode; skips the
                         index-build figure benchmarks).

Usage: PYTHONPATH=src python -m benchmarks.run [--only substr] [--json PATH]

``--json PATH`` additionally writes ``{"rows": [{name, us, derived}, ...]}``
— the machine-readable form CI uploads as a per-PR build artifact so hot-path
regressions (e.g. the fused serving kernel) are visible in review.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run benchmarks whose name contains this")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as JSON (CI artifact)")
    ap.add_argument("--audit", action="store_true",
                    help="stamp repro.analysis.audit per-route gather/"
                         "collective counts into the JSON artifact")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}", file=sys.stderr)
    from . import kernels_bench, paper_figs
    benches = list(kernels_bench.ALL)
    if os.environ.get("REPRO_BENCH_FAST") != "1":
        benches += list(paper_figs.ALL)

    rows = []

    def emit(name, us, derived=""):
        rows.append({"name": name, "us": round(us, 1), "derived": derived})
        print(f"{name},{us:.1f},{derived}", flush=True)

    print("name,us_per_call,derived")
    t0 = time.time()
    for bench in benches:
        if args.only and args.only not in bench.__name__:
            continue
        try:
            bench(emit)
        except Exception:
            traceback.print_exc()
            emit(f"{bench.__name__}/ERROR", 0.0, "see stderr")
    print(f"# total {time.time() - t0:.0f}s, {len(rows)} rows",
          file=sys.stderr)
    out = {"rows": rows, "total_s": round(time.time() - t0, 1)}
    if args.audit:
        from repro.analysis.audit import audit_stamp
        out["audit"] = audit_stamp()
        print(f"# audit stamp: {len(out['audit'])} routes",
              file=sys.stderr)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(out, fh, indent=1)


if __name__ == "__main__":
    main()
