#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark (see ``BENCHMARK.json``).

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout. Exits non-zero, with no result line,
when JAX sees no TPU or fewer chips than the cell asks for. With
``--trace 0`` the result's metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace.
"""
import sys

from benchlib.harness import main

if __name__ == "__main__":
    sys.exit(main())
