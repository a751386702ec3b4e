"""Shared fixtures of the benchmark's own tests (run by hand, on the CPU:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests``)."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchlib import harness  # noqa: E402

RANGE_LEVELS = [1.0, 0.1, 0.01, 0.001, 0.0001, 0.00001]

# every cell's code path at a size the CPU holds in seconds; widths (d)
# stay as the configurations state them
TINY = {
    "config": {"rows": 2048,
               "index": {"degree": 16, "ls_build": 32, "alpha": 1.2,
                         "threshold_quantiles": [1.0, 0.01, 0.0],
                         "batch_size": 128, "cand_pool": 64},
               "search": {"k": 10, "ls": 96, "max_iters": 192,
                          "mode": "per_query"}},
    "traffic": {"batch": 64, "pool_batches": 2, "trace_batches": 2,
                "insert_rows": 128,
                "check": {"batch": 64, "pool_batches": 2,
                          "readback_per_batch": 8,
                          "filter": {"kind": "range", "levels": RANGE_LEVELS,
                                     "stratified": True}}},
}
# every cell of BENCHMARK.json, and the loop its traffic picks
KIND = {w["name"]: harness.load_cell(w["name"]).mix["kind"]
        for w in json.loads((harness.ROOT / "BENCHMARK.json").read_text())
        ["workloads"]}
CELLS = list(KIND)


@pytest.fixture(autouse=True)
def cpu_cache(tmp_path_factory, monkeypatch):
    """Built indexes in a temporary directory shared by the test run, and
    no persistent compile cache (CPU programs are not what is measured)."""
    cache = tmp_path_factory.getbasetemp() / "bench-cache"
    monkeypatch.setattr(harness, "CACHE", cache)
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")


def tiny_run(cell: str, seed: int = 2**31 + 11, trace: bool = False):
    """One run of ``cell`` at the tiny size, without the chip check."""
    import io
    buf = io.StringIO()
    return harness.run(cell, seed, 1.0, trace, overrides=TINY,
                       chip_check=False, out=buf)
