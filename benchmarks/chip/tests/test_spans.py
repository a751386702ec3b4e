"""The program's spans in a trace (``benchlib/spans.py``) and the per-layer
readers built on them, checked on hand-made traces with known overlaps,
on a trace the profiler writes on the CPU, and on the recorded TPU trace,
which predates the spans."""
import pytest

from benchlib import harness, spans, xplane
from benchlib.harness import reader
from test_xplane import recorded_ctx

DEV = "/device:TPU:0"
T = "/host:CPU/0"
SEARCH = ("planner.idle_ms", "dispatch.host_ms",
          "device.idle_outside_program_ms")
INGEST = ("compact.jit_ms", "compact.host_syncs")


def sp(name, s, e, line=T, **args):
    return (name, s, e, args, line)


def search_ctx():
    """Window [0, 1000) ns, two batches. The device runs [100, 300),
    [500, 700) and [900, 950): idle [0, 100), [300, 500), [700, 900) and
    [950, 1000), 550 ns. One request [50, 800) holds the planner
    [60, 350), then gather, execute (with a compile inside it) and
    scatter."""
    trace = {"device": {DEV: {"XLA Ops": [["a", 100, 200], ["b", 500, 200],
                                          ["c", 900, 50]]}},
             "host": [["bench.window", 0, 1000]]}
    found = [sp("search_auto", 50, 800, request=1),
             sp("plan", 60, 350), sp("plan.probe", 60, 200),
             sp("sync:planner", 150, 200), sp("plan.band", 200, 350),
             sp("gather:prefilter", 350, 400, queries=3),
             sp("execute:prefilter", 400, 600, queries=3),
             sp("jit:prefilter", 420, 520), sp("scatter", 600, 750)]
    return {"trace": trace, "batches": 2, "spans": found}


def ingest_ctx():
    """One compaction [100, 9000): an insert whose first call compiles
    for 1,500 ns, a second insert, and a finalize with three degree reads
    and a re-prune that compiles for 1,150 ns. A compile and a read
    outside the compaction, and a read on another thread inside it, are
    not its own."""
    trace = {"device": {DEV: {"XLA Ops": [["insert", 1800, 200]]}},
             "host": [["bench.window", 0, 10000]]}
    found = [sp("compact", 100, 9000, rows=1024),
             sp("compact.prepare", 100, 300),
             sp("compact.insert", 300, 2000, **{"pass": 0, "batch": 0}),
             sp("jit:insert", 300, 1800),
             sp("compact.insert", 2000, 2200, **{"pass": 0, "batch": 1}),
             sp("compact.finalize", 2200, 4000),
             sp("sync:finalize", 2200, 2250),
             sp("compact.reprune", 2250, 3500, rows=7),
             sp("jit:reprune", 2250, 3400),
             sp("sync:finalize", 3500, 3550),
             sp("compact.reprune", 3550, 3700, rows=2),
             sp("sync:finalize", 3700, 3750),
             sp("sync:other", 5000, 5100, line="/host:CPU/1"),
             sp("jit:graph", 9100, 9400), sp("sync:planner", 9500, 9600)]
    return {"trace": trace, "batches": 1, "spans": found}


def test_nesting_and_self_time():
    ctx = search_ctx()
    found = ctx["spans"]
    top = found[0]
    assert len(spans.inside(found, top)) == len(found) - 1
    (execute,) = spans.named(found, "execute:")
    assert spans.inside(found, execute) == [found[7]]
    assert spans.self_ns(found, execute) == 200 - 100
    assert spans.self_ns(found, found[1]) == 0       # plan: all children
    assert [e[0] for e in spans.named(found, "plan", "scatter")] == [
        "plan", "scatter"]
    other = sp("x", 60, 70, line="/host:CPU/1")
    assert other not in spans.inside(found + [other], top)


def test_search_readers_on_known_overlaps():
    ctx = search_ctx()
    # the planner [60, 350) is on the host while the device idles in
    # [60, 100) and [300, 350): 90 ns over two batches
    assert reader("planner.idle_ms")(ctx) == pytest.approx(45e-6)
    # self time: gather 50, execute 200 - 100 compiling, scatter 150
    assert reader("dispatch.host_ms")(ctx) == pytest.approx(150e-6)
    # idle 550 ns, of which the request [50, 800) covers 50 + 200 + 100
    assert reader("device.idle_outside_program_ms")(ctx) == pytest.approx(
        100e-6)


def test_chip_clock_lead_moves_the_gaps():
    """The prefilter program starts at 300 on the chip's clock, 100 ns
    before its launching span ``execute:prefilter`` [400, 600): the gaps
    move 100 ns later, to [100, 200), [400, 600), [800, 1000) and
    [1050, 1100)."""
    ctx = search_ctx()
    ctx["trace"]["device"][DEV]["XLA Modules"] = [["jit_prefilter(3)", 300,
                                                    200]]
    assert spans.skew_ns(ctx) == 100
    assert spans.idle(ctx)[0] == [400, 600]
    # the planner [60, 350) now meets only [100, 200)
    assert reader("planner.idle_ms")(ctx) == pytest.approx(50e-6)
    # the request [50, 800) covers 100 + 200 of the 550 ns
    assert reader("device.idle_outside_program_ms")(ctx) == pytest.approx(
        125e-6)
    # a program with no span of its own to pair with reads no lead
    ctx["trace"]["device"][DEV]["XLA Modules"].append(["jit_prefilter", 700,
                                                       10])
    assert spans.skew_ns(ctx) == 0


def test_ingest_readers_on_known_overlaps():
    ctx = ingest_ctx()
    assert reader("compact.jit_ms")(ctx) == pytest.approx(2650e-6)
    assert reader("compact.host_syncs")(ctx) == 3.0
    # per compaction: a second one with one read and no compile
    ctx["spans"] += [sp("compact", 11000, 12000),
                     sp("sync:finalize", 11500, 11600)]
    assert reader("compact.jit_ms")(ctx) == pytest.approx(1325e-6)
    assert reader("compact.host_syncs")(ctx) == 2.0


@pytest.mark.parametrize("metric", SEARCH + INGEST)
def test_readers_are_silent_without_program_spans(metric):
    """The recorded TPU trace predates the spans (and no trace file is
    under the cache): every reader returns None, as on the parent of the
    change that added them. A search trace has no compaction, and an
    ingest trace no planner."""
    assert reader(metric)(recorded_ctx()) is None
    other = ingest_ctx() if metric in SEARCH else search_ctx()
    if metric != "device.idle_outside_program_ms":
        assert reader(metric)(other) is None


def test_load_reads_the_matching_trace_only(tmp_path, monkeypatch):
    """``load`` takes the ``jag.*`` spans and their arguments from the
    newest profiler file under the cache, and nothing when that file's
    window is not the one the run reduced."""
    import jax
    from jax.profiler import TraceAnnotation
    monkeypatch.setattr(harness, "CACHE", tmp_path)
    assert spans.load({}) == []
    d = tmp_path / "trace" / "cell"
    jax.profiler.start_trace(str(d))
    with TraceAnnotation(xplane.WINDOW):
        with TraceAnnotation("jag.search_auto", request=7, mode="per_query"):
            with TraceAnnotation("jag.plan"):
                pass
        with TraceAnnotation("bench.batch"):
            pass
    jax.profiler.stop_trace()
    tr = xplane.load(str(d))
    ctx = {"trace": tr}
    found = spans.load(ctx)
    assert [e[0] for e in found] == ["search_auto", "plan"]
    assert found[0][3] == {"request": 7, "mode": "per_query"}
    assert spans.inside(found, found[0]) == [found[1]]
    assert spans.load(ctx) is found                  # cached
    lo, hi = xplane.window(tr)
    moved = dict(tr, host=[[xplane.WINDOW, lo + 1, hi - lo - 1]])
    assert spans.load({"trace": moved}) == []
