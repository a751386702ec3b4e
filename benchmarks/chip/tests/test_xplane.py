"""The reduction from a profiler trace to device metrics, checked on a
hand-made trace with known answers and on a small trace recorded on a TPU
v5e (``data/``)."""
import gzip
import json
from pathlib import Path

import pytest

from benchlib import peaks, work, xplane

DATA = Path(__file__).with_name("data")
DEV = "/device:TPU:0"


def hand_trace():
    """Window [100, 1100) ns; ops overlap and stick out of the window."""
    return {
        "device": {DEV: {
            "XLA Modules": [["jit_run(7)", 150, 300], ["jit_take", 500, 50],
                            ["jit_run(7)", 700, 200], ["jit_run(9)", 1200, 10]],
            "XLA Ops": [["fusion.1", 50, 100], ["fusion.2", 150, 200],
                        ["custom-call.3", 300, 150], ["copy", 500, 50],
                        ["fusion.2", 700, 200], ["fusion.1", 1050, 100]]},
            "/device:CPU:0": {"XLA Ops": [["x", 0, 5000]]}},
        "host": [["bench.window", 100, 1000], ["bench.batch", 120, 500],
                 ["bench.batch", 650, 400]],
    }


def brute_busy(trace, plane):
    lo, hi = xplane.window(trace)
    busy = set()
    for _, s, d in xplane.op_events(trace, plane):
        busy.update(t for t in range(s, s + d) if lo <= t < hi)
    return len(busy)


def test_union_matches_brute_force():
    tr = hand_trace()
    assert xplane.device_planes(tr) == [DEV]
    # [100,150) + [150,450) + [500,550) + [700,900) + [1050,1100)
    assert xplane.busy_ns(tr) == 50 + 300 + 50 + 200 + 50
    assert xplane.busy_ns(tr) == brute_busy(tr, DEV)


def test_gaps_and_host_spans():
    tr = hand_trace()
    g = xplane.gaps(tr, DEV)
    assert g[0] == [550, 700] and sorted(map(tuple, g)) == [
        (450, 500), (550, 700), (900, 1050)]
    assert xplane.host_span_at(tr, 600) == "bench.batch"
    assert xplane.host_span_at(tr, 640) == "bench.window"


def test_programs_pair_in_launch_order():
    tr = hand_trace()
    mods = xplane.modules_in_window(tr)
    assert [m[0] for m in mods] == ["jit_run", "jit_take", "jit_run"]
    progs = [{"module": "jit_run", "route": "prefilter"},
             {"module": "jit_run", "route": "graph"}]
    out = xplane.match_programs(mods, progs)
    assert [(p["route"], p["start_ns"], p["dur_ns"]) for p in out] == [
        ("prefilter", 150, 300), ("graph", 700, 200)]
    with pytest.raises(ValueError):
        xplane.match_programs(mods, progs[:1])


def test_roofline_arithmetic():
    """171 queries over 2^17 rows of d=100 with 4-byte attributes: 52.9 MB
    at 819 GB/s bounds it, about 64.6 us, above the 22.7 us of flops."""
    w = work.prefilter_scan(171, 1 << 17, 100, 4)
    assert w["flops"] == 2 * 171 * (1 << 17) * 100
    assert w["bytes"] == 4 * (1 << 17) * 100 + 4 * (1 << 17) + 4 * 171 * 100
    p = peaks.peaks("TPU v5 lite")
    assert work.least_seconds(w, p) == pytest.approx(w["bytes"] / 819e9)
    assert work.least_seconds(w, p) == pytest.approx(64.6e-6, rel=1e-2)
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def recorded():
    """Two batches of range-mixed on one TPU v5e (2^17 rows, d=100, B=256),
    cut from a ``--trace 1`` run; op names shortened to the HLO
    instruction."""
    with gzip.open(DATA / "range-mixed-2-batches.json.gz", "rt") as f:
        return json.load(f)


def recorded_ctx():
    """Each batch launches the planner's probe, then the prefilter (170
    queries), graph and postfilter programs, in that order."""
    shapes = [(1 << 17, 100), (), (170, 100), ()]
    batch = [{"route": "estimate", "module": "jit_estimate_selectivity",
              "shapes": []},
             {"route": "prefilter", "module": "jit_run", "shapes": shapes},
             {"route": "graph", "module": "jit_run", "shapes": []},
             {"route": "postfilter", "module": "jit_run", "shapes": []}]
    return {"trace": recorded(), "programs": batch * 2, "batches": 2,
            "peaks": peaks.peaks("TPU v5 lite"), "compiles": [],
            "cfg": {"dataset": {"attribute": {"bytes_per_row": 4}}}}


def test_recorded_trace_busy_and_routes():
    ctx = recorded_ctx()
    tr = ctx["trace"]
    lo, hi = xplane.window(tr)
    busy = xplane.busy_ns(tr)
    mods = xplane.modules_in_window(tr)
    # one TPU core runs one program at a time: ops lie inside programs
    assert 0 < busy <= xplane.union_ns(mods, lo, hi) <= hi - lo
    assert sum(d for _, _, d in mods) == xplane.union_ns(mods, lo, hi)
    execs = xplane.route_execs(ctx)
    ms = {r: [e["dur_ns"] / 1e6 for e in execs if e["route"] == r]
          for r in ("prefilter", "graph", "postfilter")}
    assert ms["prefilter"] == pytest.approx([89.5, 89.5], abs=0.1)
    assert ms["graph"][0] == pytest.approx(18.8, abs=0.1)
    assert ms["postfilter"][0] == pytest.approx(15.6, abs=0.1)


def test_recorded_trace_metric_readers():
    """The readers of BENCHMARK.json's per-layer metrics on the recorded
    trace: device time per batch by route, and the scan's roofline share
    = least time of 170 x 2^17 x 100 over 89.5 ms."""
    from benchlib.harness import reader
    ctx = recorded_ctx()
    assert reader("route.prefilter.device_ms")(ctx) == pytest.approx(
        89.5, abs=0.1)
    assert reader("route.graph.device_ms")(ctx) == pytest.approx(
        18.9, abs=0.2)
    least = work.least_seconds(work.prefilter_scan(170, 1 << 17, 100, 4),
                               peaks.peaks("TPU v5 lite"))
    share = reader("prefilter.scan_roofline")(ctx)
    assert share == pytest.approx(100 * least / 89.5e-3, rel=2e-3)
    idle = reader("device.idle_share.search")(ctx)
    assert 0 < idle < 1
    assert reader("executor.compiles_in_window")(ctx) == 0.0
    assert reader("build.insert_step_ms")(ctx) is None
