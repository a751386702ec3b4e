"""Telemetry subsystem tests: traces, metrics, hooks, drift/recal, realized
routes, quality observability (shadow oracle, introspection, spans,
health), and the jagstat CLI.

The index fixtures here are tiny (N=400) — telemetry is host-side
bookkeeping, so the assertions are about record/counter correctness and
policy (hysteresis, exactly-once miss accounting), not performance; the
<5% overhead bar lives in ``benchmarks/obs_bench.py`` under CI.
"""
import importlib.util
import json
import os
from dataclasses import asdict

import numpy as np
import pytest

from repro.core import JAGConfig, JAGIndex, range_filters, range_table
from repro.core.filters import as_filter
from repro.cost.model import BASE_ROUTES, Observation, fit
from repro.obs import Telemetry
from repro.obs.drift import detect_drift, relative_error
from repro.obs.health import (FAIL, PASS, WARN, HealthSLO, health_report,
                              render_health)
from repro.obs.introspect import introspection_summary
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.recal import (heldout_error, observations_from_traces,
                             recalibrate)
from repro.obs.shadow import (ShadowAuditor, cells_from_records,
                              load_shadow_jsonl, sampled_qid, sel_band,
                              wilson_interval)
from repro.obs.spans import SpanRecorder, span
from repro.obs.trace import TraceBuffer, TraceRecord, load_buffer, load_jsonl
from repro.serve.planner import PlannerConfig, explain
from repro.stream import StreamingJAGIndex

N, D, B = 400, 8, 8


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(0)
    xb = rng.normal(size=(N, D)).astype(np.float32)
    vals = rng.uniform(0, 1, N).astype(np.float32)
    q = (xb[rng.integers(0, N, B)] +
         0.05 * rng.normal(size=(B, D))).astype(np.float32)
    cfg = JAGConfig(degree=6, ls_build=8, batch_size=128, cand_pool=16,
                    calib_samples=16, n_seeds=2)
    index = JAGIndex.build(xb, range_table(vals), cfg)
    return index, q


def mixed_filt(b=B):
    his = np.where(np.arange(b) % 2 == 0, 0.01, 0.9).astype(np.float32)
    return range_filters(np.zeros(b, np.float32), his)


def uniform_filt(sel, b=B):
    return range_filters(np.zeros(b, np.float32),
                         np.full(b, sel, np.float32))


# ---------------------------------------------------------------------------
# trace ring buffer
# ---------------------------------------------------------------------------

def _rec(qid, **kw):
    base = dict(qid=qid, ts=0.0, epoch=0, band="graph", route="graph",
                group=0, group_size=1, batch=1, mode="batch", sel=0.1,
                k=10, ls=64, n=1000, d=16, n_clauses=1, delta_n=0,
                shard=None, predicted=None, cost_metric=None,
                observed_us=100.0, n_dist=50, n_expanded=5)
    base.update(kw)
    return TraceRecord(**base)


def test_ring_buffer_bounded_ordered_dropped():
    buf = TraceBuffer(capacity=4)
    for i in range(10):
        buf.append(_rec(i))
    assert len(buf) == 4
    assert [r.qid for r in buf] == [6, 7, 8, 9]     # oldest-first
    assert buf.dropped == 6
    assert [r.qid for r in buf.window(2)] == [8, 9]
    buf.clear()
    assert len(buf) == 0 and buf.dropped == 0


def test_trace_jsonl_roundtrip(tmp_path):
    buf = TraceBuffer(capacity=8)
    buf.append(_rec(0, predicted={"graph": 12.5, "prefilter": 99.0},
                    cost_metric="us", shard=[8, 125]))
    buf.append(_rec(1, route="graph[fused,int8]+delta", delta_n=64))
    path = str(tmp_path / "traces.jsonl")
    assert buf.dump_jsonl(path) == 2
    back = load_jsonl(path)
    assert back == list(buf)


def test_trace_ring_wraparound_roundtrip(tmp_path):
    # overflow the ring, dump, restore: the newest `capacity` records AND
    # the dropped counter must survive the JSONL round-trip
    buf = TraceBuffer(capacity=4)
    for i in range(11):
        buf.append(_rec(i, dead_ends=i, sat_step=i + 1))
    assert buf.dropped == 7
    path = str(tmp_path / "wrap.jsonl")
    assert buf.dump_jsonl(path) == 4
    back = load_buffer(path)
    assert [r.qid for r in back] == [7, 8, 9, 10]
    assert back.capacity == 4
    assert back.dropped == 7
    assert list(back) == list(buf)
    # the restored ring keeps ring semantics: next append evicts oldest
    back.append(_rec(11))
    assert [r.qid for r in back] == [8, 9, 10, 11]
    assert back.dropped == 8
    # line-oriented consumers skip the meta header transparently
    assert [r.qid for r in load_jsonl(path)] == [7, 8, 9, 10]


def test_load_buffer_headerless_backcompat(tmp_path):
    # dumps written before the meta header (and before the introspection
    # fields) existed must still load: capacity = record count, dropped 0
    path = str(tmp_path / "old.jsonl")
    with open(path, "w") as fh:
        for i in range(3):
            raw = asdict(_rec(i))
            del raw["dead_ends"], raw["sat_step"]
            fh.write(json.dumps(raw) + "\n")
    back = load_buffer(path)
    assert [r.qid for r in back] == [0, 1, 2]
    assert back.capacity == 3 and back.dropped == 0
    assert all(r.dead_ends is None and r.sat_step is None for r in back)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counters_and_labels():
    reg = MetricsRegistry()
    reg.counter("jag_x_total", route="graph").inc()
    reg.counter("jag_x_total", route="graph").inc(2)
    reg.counter("jag_x_total", route="prefilter").inc()
    assert reg.value("jag_x_total", route="graph") == 3
    assert reg.value("jag_x_total", route="none") == 0
    assert reg.counter_total("jag_x_total") == 4


def test_histogram_quantiles_log_buckets():
    h = Histogram(lo=1.0, factor=2.0, n_buckets=16)
    for v in range(1, 1001):
        h.observe(float(v))
    # p50 rank is 500 -> bucket upper bound 512; p99 -> 1024
    assert h.quantile(0.5) == 512.0
    assert h.quantile(0.99) == 1024.0
    assert h.count == 1000
    p = h.percentiles()
    assert p["p50"] <= p["p95"] <= p["p99"]
    assert h.quantile(0.0) <= h.quantile(1.0)


def test_histogram_overflow_bucket():
    h = Histogram(lo=1.0, factor=2.0, n_buckets=3)   # bounds 1, 2, 4
    h.observe(1e9)
    assert h.quantile(1.0) == float("inf")


def test_prometheus_render():
    reg = MetricsRegistry()
    reg.counter("jag_call_total", route="graph").inc(5)
    reg.histogram("jag_lat_us", lo=1.0, factor=2.0, n_buckets=4,
                  route="graph").observe(3.0)
    text = reg.render()
    assert 'jag_call_total{route="graph"} 5' in text
    assert 'jag_lat_us_bucket{route="graph",le="4"} 1' in text
    assert 'jag_lat_us_bucket{route="graph",le="+Inf"} 1' in text
    assert 'jag_lat_us_count{route="graph"} 1' in text
    snap = reg.snapshot()
    assert snap["counters"]['jag_call_total{route="graph"}'] == 5


def test_prometheus_label_escaping():
    # the exposition format requires backslash, double quote, and line
    # feed escaped inside label values — route descriptors are free text
    reg = MetricsRegistry()
    reg.counter("jag_x_total", route='a"b\\c\nd').inc()
    text = reg.render()
    assert 'route="a\\"b\\\\c\\nd"' in text
    assert "\n\n" not in text            # the raw newline never leaks
    reg2 = MetricsRegistry()
    reg2.histogram("jag_h", n_buckets=1, route='q"r').observe(1.0)
    assert 'jag_h_count{route="q\\"r"} 1' in reg2.render()


# ---------------------------------------------------------------------------
# attach / trace recording through search_auto
# ---------------------------------------------------------------------------

def test_attach_records_per_query_traces(setup):
    index, q = setup
    tel = index.attach_telemetry()
    try:
        tel.traces.clear()
        index.search_auto(q, mixed_filt(), k=3, ls=8)
        index.search_auto(q, mixed_filt(), k=3, ls=8)
        recs = list(tel.traces)
        assert len(recs) == 2 * B
        assert len({r.qid for r in recs}) == 2 * B
        assert all(r.band in ("prefilter", "graph", "postfilter")
                   for r in recs)
        assert all(r.observed_us > 0 for r in recs)
        assert all(r.n == N and r.d == D and r.batch == B for r in recs)
        assert all(r.shard is None and r.epoch == 0 for r in recs)
        assert all(0.0 <= r.sel <= 1.0 for r in recs)
        # per-query traces cover both bands of the mixed batch
        assert len({r.band for r in recs}) >= 2
        # route counters tick per group, query counters per query
        assert tel.metrics.counter_total("jag_route_query_total") == 2 * B
        assert tel.metrics.value("jag_search_total") == 2
    finally:
        index.attach_telemetry(None)


def test_detach_stops_tracing(setup):
    index, q = setup
    tel = index.attach_telemetry()
    index.search_auto(q, mixed_filt(), k=3, ls=8)
    n0 = len(tel.traces)
    assert n0 > 0
    assert index.attach_telemetry(None) is None
    index.search_auto(q, mixed_filt(), k=3, ls=8)
    assert len(tel.traces) == n0
    assert index.executor.miss_hook is None
    # disabled-but-attached is also off
    tel2 = index.attach_telemetry(Telemetry(enabled=False))
    index.search_auto(q, mixed_filt(), k=3, ls=8)
    assert len(tel2.traces) == 0
    index.attach_telemetry(None)


def _toy_cost(route, f):
    if route == "prefilter":
        return 0.002 * (f["n"] * f["d"]) * f["sel"] ** 0.5
    if route == "graph":
        return 0.3 * (f["ls"] * f["d"]) ** 0.8 * f["sel"] ** -0.2 \
            * f["n"] ** 0.1
    assert route == "postfilter"
    return 0.1 * (f["ls"] * f["d"]) ** 0.9 * f["n"] ** 0.05 \
        * f["sel"] ** 0.3


def _toy_model(scale=1.0):
    """A model whose true costs are exactly in phi's span (exact fit)."""
    obs = []
    for n in (300.0, 600.0, 1200.0):
        for sel in (0.001, 0.01, 0.1, 0.5, 0.9):
            f = dict(sel=sel, n=n, d=8.0, k=5.0, ls=16.0, n_clauses=1.0)
            for route in BASE_ROUTES:
                us = _toy_cost(route, f) * scale
                obs.append(Observation(route, f, us=us, n_dist=us))
    return fit(obs, {"source": "toy"})


def test_traces_carry_predictions_with_cost_model(setup):
    index, q = setup
    index.attach_cost_model(_toy_model(), metric="us")
    tel = index.attach_telemetry()
    try:
        index.search_auto(q, mixed_filt(), k=3, ls=8)
        recs = list(tel.traces)
        assert len(recs) == B
        for r in recs:
            assert r.cost_metric == "us"
            assert set(r.predicted) == set(BASE_ROUTES)
            assert all(c > 0 for c in r.predicted.values())
            assert relative_error(r) is not None
    finally:
        index.attach_telemetry(None)
        index.attach_cost_model(None)


# ---------------------------------------------------------------------------
# executor miss hook + trace_log composition (satellite)
# ---------------------------------------------------------------------------

def test_miss_hook_exactly_once_per_key(setup):
    index, q = setup
    ex = index.executor
    misses = []
    ex.miss_hook = misses.append
    try:
        filt = uniform_filt(0.4)
        index.search(q, filt, k=3, ls=9)      # odd ls -> fresh cache key
        n1 = len(misses)
        assert n1 >= 1
        index.search(q, filt, k=3, ls=9)      # warm: same key, no new miss
        assert len(misses) == n1
        index.search(q, filt, k=4, ls=9)      # distinct key -> one more
        assert len(misses) == n1 + 1
        # exactly once per distinct (epoch,)+key
        assert len(set(misses)) == len(misses)
        assert all(key[0] == ex._cache_epoch for key in misses)
        assert all((key in ex._cache) for key in misses)
    finally:
        ex.miss_hook = None


def test_epoch_roll_hook_and_trace_log_compose(setup):
    index, q = setup
    stream = StreamingJAGIndex(index, compact_frac=10.0)
    tel = stream.attach_telemetry()
    filt = uniform_filt(0.4)
    stream.search_auto(q, filt, k=3, ls=8)
    assert tel.metrics.value("jag_epoch_roll_total") == 0
    m0 = tel.jit_misses()
    assert m0 > 0

    rng = np.random.default_rng(7)
    stream.insert(rng.normal(size=(16, D)).astype(np.float32),
                  range_table(rng.uniform(0, 1, 16).astype(np.float32)))
    # PR 8 analysis capture must compose with telemetry enabled
    stream.executor.trace_log = captured = []
    stream.search_auto(q, filt, k=3, ls=8)
    stream.executor.trace_log = None
    assert captured, "trace_log capture dead with telemetry attached"
    assert tel.metrics.value("jag_epoch_roll_total") == 1
    assert tel.jit_misses() > m0          # rolled caches re-compile
    assert tel.delta_scan_fraction() > 0
    # streaming search traces got the +delta realized suffix
    assert any(t.route.endswith("+delta") for t in tel.traces)
    assert all(t.delta_n == 16 for t in list(tel.traces)[-B:])


def test_compaction_counter(setup):
    index, q = setup
    stream = StreamingJAGIndex(index, compact_frac=10.0)
    tel = stream.attach_telemetry()
    rng = np.random.default_rng(8)
    stream.insert(rng.normal(size=(8, D)).astype(np.float32),
                  range_table(rng.uniform(0, 1, 8).astype(np.float32)))
    assert stream.compact()
    assert tel.metrics.value("jag_compaction_total") == 1
    res, p = stream.search_auto(q, uniform_filt(0.4), k=3, ls=8,
                                return_plan=True)
    # compacted: no delta -> no +delta suffix on realized routes
    assert all(not r.endswith("+delta") for r in p.realized)


# ---------------------------------------------------------------------------
# drift + recalibration (satellite)
# ---------------------------------------------------------------------------

def _trace_window(model, scale, n_traces=240, n=2000.0, noise=0.02, seed=0,
                  bands=None):
    """Traces whose observed cost is ``scale`` x the model's prediction."""
    rng = np.random.default_rng(seed)
    sweep = (0.001, 0.003, 0.01, 0.05, 0.1, 0.3, 0.5, 0.7, 0.9)
    out = []
    for i in range(n_traces):
        sel = sweep[i % len(sweep)]
        f = dict(sel=sel, n=n, d=8.0, k=5.0, ls=16.0, n_clauses=1.0)
        pred = {r: model.predict(r, f) for r in BASE_ROUTES}
        band = (bands[i % len(bands)] if bands
                else min(pred, key=pred.get))
        obs = pred[band] * scale * (1.0 + noise * rng.standard_normal())
        out.append(_rec(i, band=band, route=band, sel=sel, k=5, ls=16,
                        n=int(n), d=8, predicted=pred, cost_metric="us",
                        observed_us=float(obs), n_dist=int(obs) + 1))
    return out


def test_drift_flagged_on_mis_scaled_model():
    model = _toy_model()
    window = _trace_window(model, scale=3.0)
    report = detect_drift(window, threshold=0.5)
    assert report.any_drifted
    # |p - 3p| / 3p = 2/3 for every trace
    for band, med in report.median_rel_err.items():
        assert 0.55 < med < 0.8, (band, med)
        assert report.drifted[band]
    assert "DRIFT" in report.summary()


def test_no_drift_on_unbiased_window():
    model = _toy_model()
    report = detect_drift(_trace_window(model, scale=1.0), threshold=0.5)
    assert not report.any_drifted
    assert report.median_rel_err            # measured, just small
    assert all(m < 0.1 for m in report.median_rel_err.values())


def test_observations_from_traces_roundtrip():
    model = _toy_model()
    window = _trace_window(model, scale=3.0, n_traces=30)
    obs = observations_from_traces(window)
    assert len(obs) == 30
    assert all(o.us > 0 and o.route in BASE_ROUTES for o in obs)
    assert obs[0].features["n"] == 2000.0
    err = heldout_error(model, window)
    assert 0.6 < err < 0.75                 # ~2/3 by construction


def test_recalibrate_swaps_on_drifted_window():
    model = _toy_model()
    # force band coverage so the refit re-learns every route's scale
    window = _trace_window(model, scale=3.0, bands=BASE_ROUTES)
    rep = recalibrate(model, window, metric="us", min_traces=32)
    assert rep.swapped, rep.reason
    assert rep.refit_err < rep.stale_err
    assert rep.model is not model
    assert rep.model.covers(BASE_ROUTES, "us")
    # the refit learned the x3: its predictions track observed costs
    f = dict(sel=0.1, n=2000.0, d=8.0, k=5.0, ls=16.0, n_clauses=1.0)
    for r in BASE_ROUTES:
        ratio = rep.model.predict(r, f) / model.predict(r, f)
        assert 2.5 < ratio < 3.5, (r, ratio)


def test_hysteresis_rejects_unbiased_window_no_oscillation():
    model = _toy_model()
    window = _trace_window(model, scale=1.0)
    for _ in range(3):                      # repeated calls stay rejected
        rep = recalibrate(model, window, metric="us", min_traces=32)
        assert not rep.swapped
        assert rep.reason.startswith("no drift")
        assert rep.model is model


def test_recalibrate_merges_unserved_routes():
    # window only ever served the graph band: the candidate must keep the
    # stale prefilter/postfilter coefficients (coverage never shrinks)
    model = _toy_model()
    window = _trace_window(model, scale=3.0, bands=("graph",))
    rep = recalibrate(model, window, metric="us", min_traces=32)
    assert rep.swapped, rep.reason
    assert rep.model.covers(BASE_ROUTES, "us")
    f = dict(sel=0.1, n=2000.0, d=8.0, k=5.0, ls=16.0, n_clauses=1.0)
    # unserved routes keep stale predictions bit-identically
    for r in ("prefilter", "postfilter"):
        assert rep.model.predict(r, f) == pytest.approx(model.predict(r, f))


def test_recalibrate_window_too_small():
    model = _toy_model()
    rep = recalibrate(model, _trace_window(model, 3.0, n_traces=8),
                      metric="us", min_traces=64)
    assert not rep.swapped and "window too small" in rep.reason


def test_recalibrate_degenerate_windows_decline_deterministically():
    # windows below the held-out split minimum must decline with a
    # logged reason, never swap, and do so identically on every call
    model = _toy_model()
    one = _trace_window(model, scale=3.0, n_traces=1)
    reasons = set()
    for _ in range(3):
        rep = recalibrate(model, one, metric="us", min_traces=1,
                          require_drift=False)
        assert not rep.swapped
        assert rep.model is model
        assert "degenerate holdout split" in rep.reason
        assert rep.stale_err is None and rep.refit_err is None
        reasons.add(rep.reason)
    assert len(reasons) == 1                # decline is deterministic
    # below the window floor the gate names itself too
    for _ in range(2):
        rep = recalibrate(model, _trace_window(model, 3.0, n_traces=4),
                          metric="us", min_traces=8)
        assert not rep.swapped and "window too small" in rep.reason
    # an empty window is the same decline, not an exception
    rep = recalibrate(model, [], metric="us", min_traces=8)
    assert not rep.swapped and "window too small" in rep.reason


def test_maybe_recalibrate_attaches_on_swap(setup):
    index, q = setup
    stale = _toy_model()
    index.attach_cost_model(stale, metric="us")
    tel = index.attach_telemetry(Telemetry(drift_threshold=0.5))
    try:
        for t in _trace_window(stale, scale=3.0, n_traces=128):
            tel.traces.append(t)
        rep = tel.maybe_recalibrate(index)
        assert rep.swapped
        assert index.cost_model is rep.model
        assert tel.metrics.value("jag_recal_swap_total") == 1
        assert tel.last_recal is rep
    finally:
        index.attach_telemetry(None)
        index.attach_cost_model(None)


# ---------------------------------------------------------------------------
# realized-route satellite (bugfix): plans report what actually executed
# ---------------------------------------------------------------------------

def test_realized_routes_default_variant(setup):
    index, q = setup
    res, p = index.search_auto(q, mixed_filt(), k=3, ls=8, return_plan=True)
    assert p.realized == p.routes           # default layout == band names
    assert "executed[" not in explain(p)    # byte-stable when identical


def test_realized_routes_serving_variant(setup):
    index, q = setup
    res, p = index.search_auto(q, uniform_filt(0.4), k=3, ls=8,
                               return_plan=True, layout="fused",
                               dtype="int8")
    assert p.routes == ("graph",) * B
    assert p.realized == ("graph[fused,int8]",) * B
    note = explain(p)
    assert "executed[graph[fused,int8]:8]" in note


def test_realized_route_batch_mode(setup):
    index, q = setup
    res, p = index.search_auto(q, uniform_filt(0.4), k=3, ls=8,
                               return_plan=True, mode="batch",
                               layout="fused")
    assert p.route == "graph"
    assert p.realized == "graph[fused,f32]"
    assert "executed[graph[fused,f32]]" in explain(p)


def test_realized_streaming_delta_suffix(setup):
    index, q = setup
    stream = StreamingJAGIndex(index, compact_frac=10.0)
    rng = np.random.default_rng(9)
    stream.insert(rng.normal(size=(8, D)).astype(np.float32),
                  range_table(rng.uniform(0, 1, 8).astype(np.float32)))
    res, p = stream.search_auto(q, mixed_filt(), k=3, ls=8,
                                return_plan=True)
    assert all(r.endswith("+delta") for r in p.realized)
    assert "executed[" in explain(p)


def test_plan_without_execution_has_no_realized(setup):
    from repro.serve.planner import plan_per_query
    index, q = setup
    p = plan_per_query(mixed_filt(), index.attr, PlannerConfig(),
                       executor=index.executor)
    assert p.realized is None
    assert "executed[" not in explain(p)


# ---------------------------------------------------------------------------
# shadow-oracle recall auditing (tentpole)
# ---------------------------------------------------------------------------

def test_sampled_qid_deterministic_and_proportional():
    picks = [sampled_qid(i, 0.25) for i in range(4096)]
    assert picks == [sampled_qid(i, 0.25) for i in range(4096)]
    assert 0.2 < sum(picks) / 4096 < 0.3
    assert all(sampled_qid(i, 1.0) for i in range(16))
    assert not any(sampled_qid(i, 0.0) for i in range(16))
    # nested: every qid sampled at f stays sampled at any f' > f
    assert all(sampled_qid(i, 0.5)
               for i in range(4096) if sampled_qid(i, 0.25))


def test_wilson_interval_sanity():
    lo, hi = wilson_interval(90, 100)
    assert 0.0 <= lo < 0.9 < hi <= 1.0
    assert wilson_interval(0, 0) == (0.0, 1.0)
    lo_n, hi_n = wilson_interval(900, 1000)
    assert hi_n - lo_n < hi - lo            # tighter with more trials
    lo0, hi0 = wilson_interval(0, 50)
    assert lo0 < 1e-12 and hi0 < 0.15       # sane at p = 0
    lo1, hi1 = wilson_interval(50, 50)
    assert hi1 > 1.0 - 1e-12 and lo1 > 0.85  # ... and p = 1


def test_sel_band_edges():
    assert sel_band(0.0005) == "sel<=0.001"
    assert sel_band(0.001) == "sel<=0.001"
    assert sel_band(0.05) == "sel<=0.1"
    assert sel_band(0.3) == "sel<=0.5"
    assert sel_band(0.7) == "sel>0.5"


def test_shadow_deferred_flush_semantics(setup):
    index, q = setup
    aud = ShadowAuditor(1.0, max_pending=2)
    filt = as_filter(uniform_filt(0.4))
    res = index.search_auto(q, filt, k=3, ls=8)
    aud.audit(index, q, filt, res, k=3, qid0=0, routes=["graph"] * B,
              sels=np.full(B, 0.4))
    # serve time only enqueued — the oracle hasn't run yet
    assert aud.n_pending == B and aud.n_audited == 0
    rows = aud.recall_table()               # reporting accessors flush
    assert aud.n_pending == 0 and aud.n_audited == B
    assert rows and rows[0]["trials"] > 0
    # the pending queue is bounded: max_pending calls flush synchronously
    aud.audit(index, q, filt, res, k=3, qid0=B, routes=["graph"] * B,
              sels=np.full(B, 0.4))
    assert aud.n_pending == B
    aud.audit(index, q, filt, res, k=3, qid0=2 * B, routes=["graph"] * B,
              sels=np.full(B, 0.4))
    assert aud.n_pending == 0 and aud.n_audited == 3 * B
    assert aud.flush() == 0                 # idempotent when drained


def test_shadow_estimates_match_exact_oracle(setup):
    # the honesty property at unit scale: the 0.5-sampled telemetry
    # auditor must agree BIT-FOR-BIT with a fraction-1.0 auditor on
    # every query it sampled (same hits, trials, route, band) — the
    # population-level Wilson-containment acceptance check runs on the
    # bigger sweep in benchmarks/obs_bench.py --quality
    index, q = setup
    tel = index.attach_telemetry(Telemetry(shadow=0.5, capacity=512))
    exact = ShadowAuditor(1.0)
    try:
        qid0 = 0
        for sel in (0.05, 0.4, 0.9, 0.4, 0.05, 0.9):
            filt = as_filter(uniform_filt(sel))
            res, p = index.search_auto(q, filt, k=3, ls=8,
                                       return_plan=True)
            exact.audit(index, q, filt, res, k=3, qid0=qid0,
                        routes=[str(r) for r in p.realized],
                        sels=np.asarray(p.selectivity, np.float64))
            qid0 += B
        tel.shadow.flush()
        exact.flush()
        assert 0 < tel.shadow.n_audited < exact.n_audited == 6 * B
        ex_by_qid = {r.qid: r for r in exact.records}
        for r in tel.shadow.records:
            e = ex_by_qid[r.qid]             # sampled ⊂ exactly-audited
            assert (r.hits, r.trials, r.route, r.band, r.recall) \
                == (e.hits, e.trials, e.route, e.band, e.recall)
        # deterministic sampling: exactly the hash-selected qids audited
        assert sorted(r.qid for r in tel.shadow.records) \
            == [i for i in range(6 * B) if sampled_qid(i, 0.5)]
        # every sampled (route, band) cell exists in the exact census,
        # with a subset of its trials
        assert set(tel.shadow.cells) <= set(exact.cells)
        for key, cell in tel.shadow.cells.items():
            assert cell.trials <= exact.cells[key].trials
        assert tel.metrics.value("jag_shadow_audit_total") \
            == tel.shadow.n_audited
    finally:
        index.attach_telemetry(None)


def test_shadow_records_roundtrip_and_rebuild(tmp_path, setup):
    index, q = setup
    aud = ShadowAuditor(1.0)
    filt = as_filter(uniform_filt(0.4))
    res = index.search_auto(q, filt, k=3, ls=8)
    aud.audit(index, q, filt, res, k=3, qid0=0, routes=["graph"] * B,
              sels=np.full(B, 0.4))
    path = str(tmp_path / "shadow.jsonl")
    assert aud.dump_jsonl(path) == B        # dump flushes first
    back = load_shadow_jsonl(path)
    assert [r.qid for r in back] == list(range(B))
    assert all(r.route == "graph" and r.k == 3 for r in back)
    assert all(0.0 <= r.recall <= 1.0 for r in back)
    # per-cell estimators rebuild exactly from the dumped records
    cells = cells_from_records(back)
    assert set(cells) == set(aud.cells)
    for key, cell in cells.items():
        assert (cell.hits, cell.trials) == \
            (aud.cells[key].hits, aud.cells[key].trials)


def test_shadow_vacuous_filter_counts_no_trials(setup):
    # a filter no row satisfies contributes zero Bernoulli trials
    # (recall_at_k convention) — the cell can then only warn, not fail
    from repro.core.beam_search import SearchResult
    index, q = setup
    aud = ShadowAuditor(1.0)
    empty = as_filter(range_filters(np.full(B, 0.9, np.float32),
                                    np.full(B, 0.1, np.float32)))
    res = SearchResult(
        ids=np.full((B, 3), -1, np.int32),
        primary=np.full((B, 3), np.inf, np.float32),
        secondary=np.full((B, 3), np.inf, np.float32),
        vlog=np.full((B, 4), -1, np.int32),
        n_expanded=np.zeros(B, np.int32),
        n_dist=np.zeros(B, np.int32))
    aud.audit(index, q, empty, res, k=3, qid0=0,
              routes=["prefilter"] * B, sels=np.zeros(B))
    aud.flush()
    (cell,) = aud.cells.values()
    assert cell.trials == 0 and cell.n_queries == B
    assert cell.estimate == 1.0
    assert cell.wilson() == (0.0, 1.0)


def test_streaming_shadow_audits_post_merge_exactly_once(setup):
    # the streaming index audits the FINAL (delta-merged) result, and the
    # inner frozen-graph search must not double-audit the same queries
    index, q = setup
    stream = StreamingJAGIndex(index, compact_frac=10.0)
    tel = stream.attach_telemetry(Telemetry(shadow=1.0))
    rng = np.random.default_rng(3)
    stream.insert(rng.normal(size=(16, D)).astype(np.float32),
                  range_table(rng.uniform(0, 1, 16).astype(np.float32)))
    stream.search_auto(q, uniform_filt(0.4), k=3, ls=8)
    tel.shadow.flush()
    assert tel.shadow.n_audited == B
    # the audited routes are the realized (+delta) ones, and the oracle
    # covered base + delta rows (trials present for a 0.4-selectivity)
    assert all(route.endswith("+delta") for route, _, _ in tel.shadow.cells)
    assert all(c.trials > 0 for c in tel.shadow.cells.values())
    for r in tel.shadow.records:
        assert 0.0 <= r.recall <= 1.0


# ---------------------------------------------------------------------------
# traversal introspection (tentpole)
# ---------------------------------------------------------------------------

def test_introspective_route_bit_identical(setup):
    index, q = setup
    ex = index.executor
    filt = as_filter(uniform_filt(0.4))
    for layout in ("default", "fused"):
        r_std = ex.graph(q, filt, k=3, ls=8, max_iters=16, layout=layout)
        r_int, stats = ex.graph(q, filt, k=3, ls=8, max_iters=16,
                                layout=layout, introspect=True)
        np.testing.assert_array_equal(np.asarray(r_std.ids),
                                      np.asarray(r_int.ids))
        np.testing.assert_array_equal(np.asarray(r_std.primary),
                                      np.asarray(r_int.primary))
        np.testing.assert_array_equal(np.asarray(r_std.secondary),
                                      np.asarray(r_int.secondary))
        hops = np.asarray(stats.hops)
        dead = np.asarray(stats.dead_ends)
        sat = np.asarray(stats.sat_step)
        assert hops.shape == dead.shape == sat.shape == (B,)
        assert (hops >= 1).all()
        assert (dead >= 0).all() and (dead <= hops).all()
        assert (sat >= 0).all() and (sat <= hops).all()


def test_introspect_is_a_cache_key_component(setup):
    index, q = setup
    ex = index.executor
    misses = []
    ex.miss_hook = misses.append
    try:
        filt = as_filter(uniform_filt(0.4))
        ex.graph(q, filt, k=3, ls=11, max_iters=16)      # odd ls: fresh
        ex.graph(q, filt, k=3, ls=11, max_iters=16, introspect=True)
        assert len(misses) == 2                          # distinct entries
        assert any("introspect" in key for key in misses)
        ex.graph(q, filt, k=3, ls=11, max_iters=16, introspect=True)
        assert len(misses) == 2                          # warm second time
    finally:
        ex.miss_hook = None


def test_introspect_traces_stamped_and_summarized(setup):
    index, q = setup
    tel = index.attach_telemetry(Telemetry(introspect=True))
    try:
        index.search_auto(q, uniform_filt(0.4), k=3, ls=8)
        index.search_auto(q, mixed_filt(), k=3, ls=8)
        recs = list(tel.traces)
        graph = [r for r in recs if r.band == "graph"]
        other = [r for r in recs if r.band != "graph"]
        assert graph, "0.4-selectivity batch should route graph"
        assert all(r.dead_ends is not None and r.sat_step is not None
                   for r in graph)
        assert all(r.dead_ends >= 0 and r.sat_step >= 0 for r in graph)
        # non-graph routes have no traversal loop: stamps stay None
        assert all(r.dead_ends is None and r.sat_step is None
                   for r in other)
        rows = introspection_summary(recs)
        assert len(rows) == 1 and rows[0]["queries"] == len(graph)
        assert rows[0]["dead_end_rate"] is not None
        assert 0.0 <= rows[0]["dead_end_rate"]
        assert tel.metrics.counter_total("jag_introspect_query_total") \
            == len(graph)
    finally:
        index.attach_telemetry(None)
    # introspection off (the default): nothing is stamped
    tel2 = index.attach_telemetry()
    try:
        index.search_auto(q, uniform_filt(0.4), k=3, ls=8)
        assert all(r.dead_ends is None and r.sat_step is None
                   for r in tel2.traces)
    finally:
        index.attach_telemetry(None)


# ---------------------------------------------------------------------------
# pipeline spans (tentpole)
# ---------------------------------------------------------------------------

def test_span_recorder_nesting_and_chrome_export():
    sr = SpanRecorder()
    with sr.span("outer", batch=2):
        with sr.span("inner"):
            pass
        with sr.span("inner2"):
            pass
    assert [s.name for s in sr.spans] == ["inner", "inner2", "outer"]
    by_name = {s.name: s for s in sr.spans}
    assert by_name["outer"].depth == 0 and by_name["outer"].parent is None
    assert by_name["inner"].depth == 1
    assert by_name["inner"].parent == "outer"
    # children are contained in the parent's time range
    for child in ("inner", "inner2"):
        assert by_name["outer"].t0 <= by_name[child].t0
        assert by_name[child].t1 <= by_name["outer"].t1
    totals = sr.totals_us()
    assert totals["outer"] >= totals["inner"] + totals["inner2"] - 1e-6
    assert by_name["outer"].args == {"batch": 2}
    assert all(s.duration_us >= 0 for s in sr.spans)
    # span() records in a recorder it is given, under the same name, and
    # nests with the recorder's own spans
    with sr.span("top"):
        with span("leaf", sr, rows=3):
            pass
    leaf = sr.spans[-2]
    assert (leaf.name, leaf.parent, leaf.depth) == ("leaf", "top", 1)
    assert leaf.args == {"rows": 3}


def test_span_recorder_bounded():
    sr = SpanRecorder(capacity=3)
    for i in range(7):
        with sr.span(f"s{i}"):
            pass
    assert len(sr.spans) == 3
    assert sr.dropped == 4
    assert [s.name for s in sr.spans] == ["s4", "s5", "s6"]
    sr.clear()
    assert not sr.spans and sr.dropped == 0


def test_spans_recorded_through_search_auto(setup):
    index, q = setup
    tel = index.attach_telemetry(Telemetry(spans=True))
    try:
        index.search_auto(q, mixed_filt(), k=3, ls=8)
        names = {s.name for s in tel.spans.spans}
        assert "search_auto" in names and "plan" in names
        assert any(n.startswith("execute:") for n in names)
        # execute spans nest under the top-level search span
        ex_spans = [s for s in tel.spans.spans
                    if s.name.startswith("execute:")]
        assert ex_spans and all(s.depth >= 1 for s in ex_spans)
        (top,) = [s for s in tel.spans.spans if s.name == "search_auto"]
        assert top.depth == 0
        assert sum(s.duration_us for s in ex_spans) <= top.duration_us
    finally:
        index.attach_telemetry(None)


def test_streaming_spans_cover_delta_and_merge(setup):
    index, q = setup
    stream = StreamingJAGIndex(index, compact_frac=10.0)
    tel = stream.attach_telemetry(Telemetry(spans=True))
    rng = np.random.default_rng(5)
    stream.insert(rng.normal(size=(16, D)).astype(np.float32),
                  range_table(rng.uniform(0, 1, 16).astype(np.float32)))
    stream.search_auto(q, uniform_filt(0.4), k=3, ls=8)
    names = [s.name for s in tel.spans.spans]
    assert "delta" in names and "merge" in names
    (delta_span,) = [s for s in tel.spans.spans if s.name == "delta"]
    assert delta_span.args.get("rows") == 16


def _profiled(trace_dir, fn):
    """Run ``fn`` under ``jax.profiler.trace``; returns (fn's result, the
    ``jag.*`` host spans [(name, start_ns, end_ns, args)] in start order)."""
    import glob

    import jax
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(trace_dir)):
        out = fn()
    (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                        recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            spans += [(e.name[len("jag."):], e.start_ns,
                       e.start_ns + e.duration_ns, dict(e.stats))
                      for line in plane.lines for e in line.events
                      if e.name.startswith("jag.")]
    return out, sorted(spans, key=lambda e: (e[1], -e[2]))


def _inside(spans, outer):
    return [e for e in spans if outer[1] <= e[1] and e[2] <= outer[2]
            and e is not outer]


def test_search_auto_spans_on_the_profiler_trace(setup, tmp_path):
    """With no telemetry attached, a search writes its spans into the
    profiler trace: one ``jag.search_auto`` per request, with the planner,
    gather, execute and scatter stages inside it; the programs compile in
    the first request only (``jag.jit:*``)."""
    index, q = setup

    def two():          # k and ls no other test compiles
        return [index.search_auto(q, mixed_filt(), k=4, ls=10)
                for _ in range(2)]
    _, spans = _profiled(tmp_path, two)
    tops = [e for e in spans if e[0] == "search_auto"]
    assert len(tops) == 2
    first, second = (e[3]["request"] for e in tops)
    assert second == first + 1
    assert first == index.executor.n_requests - 1
    names = [e[0] for e in _inside(spans, tops[0])]
    for want in ("plan", "plan.probe", "sync:planner", "plan.band",
                 "scatter"):
        assert want in names
    for prefix in ("gather:", "execute:", "jit:"):
        assert any(n.startswith(prefix) for n in names), prefix
    assert {n for n in names if n.startswith("execute:")} == {
        "execute:prefilter", "execute:postfilter"}
    assert not [n for n in (e[0] for e in _inside(spans, tops[1]))
                if n.startswith("jit:")]


def test_compact_spans_on_the_profiler_trace(setup, tmp_path):
    """A compaction writes ``jag.compact`` holding one ``compact.insert``
    per pass and batch, at most one ``jit:insert`` (the call that traces
    the insert program, pass 0 batch 0), and ``finalize_graph``'s degree
    reads and re-prunes."""
    index, _ = setup
    stream = StreamingJAGIndex(index, compact_frac=10.0)
    rng = np.random.default_rng(9)
    m = 200
    stream.insert(rng.normal(size=(m, D)).astype(np.float32),
                  range_table(rng.uniform(0, 1, m).astype(np.float32)))
    bcfg = index.build_cfg
    n_batches = -(-m // bcfg.batch_size)
    assert n_batches == 2
    _, spans = _profiled(tmp_path, stream.compact)
    (top,) = [e for e in spans if e[0] == "compact"]
    assert top[3]["rows"] == m
    inner = _inside(spans, top)
    names = [e[0] for e in inner]
    assert set(names) <= {"compact.prepare", "compact.insert", "jit:insert",
                          "compact.finalize", "compact.reprune",
                          "sync:finalize", "jit:reprune"}
    assert names.count("compact.prepare") == 1
    inserts = [e for e in inner if e[0] == "compact.insert"]
    assert [(e[3]["pass"], e[3]["batch"]) for e in inserts] == [
        (p, i) for p in range(bcfg.n_passes) for i in range(n_batches)]
    jits = [e for e in inner if e[0] == "jit:insert"]
    assert len(jits) <= 1
    assert all(j in _inside(spans, inserts[0]) for j in jits)
    assert names.count("compact.finalize") == bcfg.n_passes
    # each finalize reads the degrees at least once, and re-prunes only
    # between reads
    assert names.count("sync:finalize") >= bcfg.n_passes
    assert names.count("compact.reprune") <= names.count("sync:finalize")
    assert names.count("jit:reprune") <= bcfg.n_passes


def _route_calls(index, stream, q):
    """Each executor route, called once (``route -> thunk``)."""
    ex, sx = index.executor, stream.executor
    f = uniform_filt(0.4)
    res = index.search(q, f, k=3, ls=8)
    return {
        "prefilter": lambda: ex.prefilter(q, f, k=3),
        "graph": lambda: ex.graph(q, f, k=3, ls=8, max_iters=16),
        "graph[fused]": lambda: ex.graph(q, f, k=3, ls=8, max_iters=16,
                                         layout="fused"),
        "graph[int8]": lambda: ex.graph(q, f, k=3, ls=8, max_iters=16,
                                        dtype="int8"),
        "graph[fused,int8]": lambda: ex.graph(q, f, k=3, ls=8, max_iters=16,
                                              layout="fused", dtype="int8"),
        "postfilter": lambda: ex.postfilter(q, f, k=3, ls=8, max_iters=16),
        "unfiltered": lambda: ex.unfiltered(q, k=3, ls=8, max_iters=16),
        "delta": lambda: sx.delta(q, f, k=3),
        "merge": lambda: ex.merge(res, res, k=3),
    }


@pytest.mark.parametrize("variant", [
    "prefilter", "graph", "graph[fused]", "graph[int8]", "graph[fused,int8]",
    "postfilter", "unfiltered", "delta", "merge"])
def test_route_programs_are_named_for_their_route(setup, variant):
    """Every executor route compiles as the program ``jit_<route>``, so a
    device trace names the route without the launch log."""
    import jax
    index, q = setup
    stream = StreamingJAGIndex(index, compact_frac=10.0)
    rng = np.random.default_rng(3)
    stream.insert(rng.normal(size=(8, D)).astype(np.float32),
                  range_table(rng.uniform(0, 1, 8).astype(np.float32)))
    calls = _route_calls(index, stream, q)
    ex = stream.executor if variant == "delta" else index.executor
    ex.trace_log = []
    try:
        calls[variant]()
        (key, make, args), = [t for t in ex.trace_log
                              if t[0][0] not in ("leafval", "estimate")]
    finally:
        ex.trace_log = None
    route = variant.split("[")[0]
    assert key[0] == route
    text = jax.jit(make()).lower(*args).as_text()
    assert text.startswith(f"module @jit_{route} ")


# ---------------------------------------------------------------------------
# health report (tentpole)
# ---------------------------------------------------------------------------

def _shadow_rec(qid, hits, trials, route="graph", band="sel<=0.5",
                epoch=0, sel=0.3, k=5):
    from repro.obs.shadow import ShadowRecord
    return ShadowRecord(qid=qid, ts=0.0, epoch=epoch, route=route,
                        band=band, sel=sel, k=k, hits=hits, trials=trials,
                        recall=(hits / trials) if trials else 1.0)


def test_health_shadow_section_pass_warn_fail():
    slo = HealthSLO(recall=0.9, min_shadow_trials=20)
    # confident pass: high recall, plenty of trials
    good = [_shadow_rec(i, 5, 5) for i in range(10)]
    rep = health_report([], good, slo)
    assert rep["shadow_recall"]["status"] == PASS
    # confident fail: the whole interval sits below the SLO
    bad = [_shadow_rec(i, 2, 5) for i in range(40)]
    rep = health_report([], bad, slo)
    assert rep["shadow_recall"]["status"] == FAIL
    assert rep["status"] == FAIL
    # straddling interval: warn, not fail
    near = [_shadow_rec(i, 8, 10) for i in range(2)]
    rep = health_report([], near, slo)
    assert rep["shadow_recall"]["status"] == WARN
    # too few trials for a confident pass: warn
    thin = [_shadow_rec(0, 5, 5)]
    rep = health_report([], thin, slo)
    assert rep["shadow_recall"]["status"] == WARN
    # no audits at all: warn with a note
    rep = health_report([], [], slo)
    assert rep["shadow_recall"]["status"] == WARN
    assert rep["shadow_recall"]["note"]


def test_health_dead_end_and_latency_sections():
    slo = HealthSLO(dead_end_warn=0.5, dead_end_fail=0.9, p99_us=500.0)
    ok = [_rec(i, dead_ends=1, sat_step=5, n_expanded=10,
               observed_us=100.0) for i in range(8)]
    rep = health_report(ok, [], slo)
    assert rep["dead_ends"]["status"] == PASS
    assert rep["latency"]["status"] == PASS
    # dead-end rate between warn and fail thresholds
    warn = [_rec(i, dead_ends=7, sat_step=2, n_expanded=10,
                 observed_us=100.0) for i in range(8)]
    rep = health_report(warn, [], slo)
    assert rep["dead_ends"]["status"] == WARN
    # p99 above 2x the SLO: latency fails
    slow = [_rec(i, dead_ends=1, sat_step=5, n_expanded=10,
                 observed_us=5000.0) for i in range(8)]
    rep = health_report(slow, [], slo)
    assert rep["latency"]["status"] == FAIL
    assert rep["status"] == FAIL
    # without a p99 SLO latency is informational only
    rep = health_report(slow, [], HealthSLO())
    assert rep["latency"]["status"] == PASS


def test_health_render_and_telemetry_integration(setup):
    index, q = setup
    tel = index.attach_telemetry(Telemetry(shadow=1.0, introspect=True,
                                           spans=True))
    try:
        index.search_auto(q, uniform_filt(0.4), k=3, ls=8)
        rep = tel.health_report()
        assert rep["status"] in (PASS, WARN, FAIL)
        assert rep["n_traces"] == B and rep["n_shadow"] == B
        assert rep["shadow_recall"]["cells"]
        assert rep["dead_ends"]["routes"]
        assert rep["latency"]["routes"]
        text = render_health(rep)
        assert "health:" in text and "shadow recall" in text
        assert "dead ends" in text and "latency" in text
    finally:
        index.attach_telemetry(None)


# ---------------------------------------------------------------------------
# jagstat CLI (exporter satellite)
# ---------------------------------------------------------------------------

def _load_jagstat():
    path = os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                        "jagstat.py")
    spec = importlib.util.spec_from_file_location("jagstat", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_jagstat_renders_summary(tmp_path, capsys, setup):
    index, q = setup
    index.attach_cost_model(_toy_model(), metric="us")
    tel = index.attach_telemetry()
    try:
        index.search_auto(q, mixed_filt(), k=3, ls=8)
        index.search_auto(q, uniform_filt(0.4), k=3, ls=8)
        path = str(tmp_path / "traces.jsonl")
        assert tel.traces.dump_jsonl(path) == 2 * B
    finally:
        index.attach_telemetry(None)
        index.attach_cost_model(None)

    jagstat = _load_jagstat()
    assert jagstat.main([path]) == 0
    out = capsys.readouterr().out
    assert "route" in out and "p50us" in out
    rows = jagstat.summarize(load_jsonl(path))
    assert sum(r["queries"] for r in rows) == 2 * B
    assert abs(sum(r["share_pct"] for r in rows) - 100.0) < 0.5
    assert all(r["p50_us"] > 0 for r in rows)
    # --json mode emits machine-readable rows
    assert jagstat.main([path, "--json"]) == 0
    import json as _json
    assert _json.loads(capsys.readouterr().out)


def test_jagstat_degrades_gracefully_on_empty_dumps(tmp_path, capsys):
    # log rotation racing a dump must not page anyone: explicit
    # "no traces" line, exit 0 — for empty AND missing files
    jagstat = _load_jagstat()
    path = str(tmp_path / "empty.jsonl")
    open(path, "w").close()
    assert jagstat.main([path]) == 0
    assert "no traces" in capsys.readouterr().out
    missing = str(tmp_path / "rotated-away.jsonl")
    assert jagstat.main([missing]) == 0
    assert "no traces" in capsys.readouterr().out


def test_jagstat_single_record(tmp_path, capsys):
    # a one-line dump renders a real table (percentiles of n=1 are fine)
    buf = TraceBuffer(capacity=4)
    buf.append(_rec(0, route="graph[default,f32]"))
    path = str(tmp_path / "one.jsonl")
    buf.dump_jsonl(path)
    jagstat = _load_jagstat()
    assert jagstat.main([path]) == 0
    out = capsys.readouterr().out
    assert "graph[default,f32]" in out and "100.0" in out


def test_jagstat_health_mode(tmp_path, capsys, setup):
    index, q = setup
    tel = index.attach_telemetry(Telemetry(shadow=1.0, introspect=True))
    try:
        index.search_auto(q, uniform_filt(0.4), k=3, ls=8)
        traces = str(tmp_path / "traces.jsonl")
        shadow = str(tmp_path / "shadow.jsonl")
        assert tel.traces.dump_jsonl(traces) == B
        assert tel.shadow.dump_jsonl(shadow) == B
    finally:
        index.attach_telemetry(None)
    jagstat = _load_jagstat()
    # lenient SLO the tiny index can meet: exit 0, render shows the cells
    rc = jagstat.main([traces, "--health", "--shadow", shadow,
                       "--slo-recall", "0.05"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "health:" in out and "shadow recall" in out
    assert "dead ends" in out and "latency" in out
    # impossible p99 SLO: overall fail, exit 1
    rc = jagstat.main([traces, "--health", "--shadow", shadow,
                       "--slo-recall", "0.05", "--slo-p99-us", "0.001"])
    out = capsys.readouterr().out
    assert rc == 1 and "health: FAIL" in out
    # --health --json emits the machine-checkable document
    rc = jagstat.main([traces, "--health", "--shadow", shadow,
                      "--slo-recall", "0.05", "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0 and doc["status"] in ("pass", "warn")
    assert doc["n_shadow"] == B and doc["shadow_recall"]["cells"]
    # health mode works on empty/missing dumps too (warn, exit 0)
    missing = str(tmp_path / "gone.jsonl")
    assert jagstat.main([missing, "--health"]) == 0
    assert "health:" in capsys.readouterr().out
