"""One run of one benchmark cell: set-up, measured window, check, result.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``configs/<config>.json``, its traffic in
``traffic/<traffic>.json`` (read by the one generator in ``data.py``), and
each per-layer metric in ``metrics/<metric>.py``. A traffic file's
``kind`` picks the loop: ``search`` (closed-loop ``search_auto``
batches replayed from a pool) or ``ingest`` (insert, then compact, from
the cached base).

The last line of standard output is the result object; the numbers that
decide ``correct`` are also the last lines of standard error, each beside
its limit.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from . import data, index_cache, reference, xplane
from .peaks import peaks

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
CACHE = CHIP / "cache"


class NoChip(RuntimeError):
    """JAX sees no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    mix: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, root: Path = ROOT, overrides=None) -> Cell:
    """The cell ``name`` with its configuration and traffic; ``overrides``
    (tests only) is ``{"config": {...}, "traffic": {...}}`` merged over the
    files, key by key at the top level."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = json.loads((CHIP / "configs" / f"{w['config']}.json").read_text())
    mix = json.loads((CHIP / "traffic" / f"{w['traffic']}.json").read_text())
    overrides = overrides or {}
    cfg.update(overrides.get("config", {}))
    mix.update(overrides.get("traffic", {}))

    def mine(m):
        return "workloads" not in m or name in m["workloads"]
    return Cell(name, int(w["chips"]), cfg, mix,
                [m for m in bench["end_to_end"] if mine(m)],
                [m for m in bench["per_layer"] if mine(m)])


def require_chip(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX backend is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"{chips} chips asked, {len(devs)} visible")
    peaks(devs[0].device_kind)      # an unknown device is an error
    return devs[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where it is set, else the fixed ``benchmarks/chip/cache/jax`` inside
    the checkout. Every program is kept, however quick its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts programs compiled (or loaded from the persistent cache)
    while ``on`` — JAX's backend-compile events, by program name."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.on = False
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event == self.EVENT:
            self.names.append(str(kw.get("fun_name", "?")))


def peak_bytes(devs) -> int:
    """Peak device memory on the fullest chip (0 where the backend keeps
    no statistics, as the CPU's does not)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def attr_table(kind: str, values):
    from repro.core import filters as F
    return F.range_table(values) if kind == "range" else F.label_table(values)


def filter_expr(filt: dict):
    from repro import Label, Range
    if "lo" in filt:
        return Range(filt["lo"], filt["hi"])
    return Label(filt["label"])


def jag_config(cfg: dict):
    from repro.core.jag import JAGConfig
    return JAGConfig(**{k: tuple(v) if isinstance(v, list) else v
                        for k, v in cfg["index"].items()})


def open_index(cell: Cell, db: data.Database, root: Path, devs):
    """The configuration's index, loaded from the cache or built into it.
    A configuration with ``shards`` > 1 is a ``ShardedJAGIndex``: shard s
    holds rows [s*N/S, (s+1)*N/S) on chip s, each shard cached on its own
    and all of them built or loaded at once, one thread each."""
    import jax
    from repro.core.jag import JAGIndex
    S = int(cell.cfg.get("shards", 1))
    n_loc = db.xb.shape[0] // S

    def shard(s):
        rows = slice(s * n_loc, (s + 1) * n_loc)

        def build():
            idx = JAGIndex.build(jax.device_put(db.xb[rows], devs[s]),
                                 attr_table(db.spec["kind"], db.attr[rows]),
                                 jag_config(cell.cfg))
            idx.graph.block_until_ready()
            return idx
        with jax.default_device(devs[s]):
            return index_cache.load_or_build(
                root, CACHE, cell.cfg, build, JAGIndex.load,
                part=f".shard{s}" if S > 1 else "")
    if S == 1:
        return shard(0)
    from concurrent.futures import ThreadPoolExecutor
    from repro.distributed.sharding import serve_mesh
    from repro.serve.sharded import ShardedJAGIndex
    with ThreadPoolExecutor(max_workers=S) as pool:
        out = list(pool.map(shard, range(S)))
    return (ShardedJAGIndex.from_shards([o[0] for o in out],
                                        mesh=serve_mesh(S)),
            any(o[1] for o in out))


def serve(index, batch, search: dict):
    """One request: ``search_auto`` until its answer is on the host.
    Returns (ids, reported squared distances, realized route per query).
    A row the program returns with a nonzero primary key is one it marks
    as failing the filter (``repro.core.recall``): it is not served, and
    its id reads -1."""
    res, plan = index.search_auto(
        batch.queries, filter_expr(batch.filt), k=search["k"],
        ls=search["ls"], max_iters=search["max_iters"],
        mode=search["mode"], return_plan=True)
    ids = np.where(np.asarray(res.primary) == 0, np.asarray(res.ids), -1)
    return ids, np.asarray(res.secondary), plan.realized


# ---------------------------------------------------------------------------
# measured loops
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Outcome:
    e2e: dict              # end-to-end metric name -> value
    attempted: int
    numbers: dict          # compared numbers
    ctx: dict              # what per-layer readers read
    peak_bytes: int
    setup_end: float       # perf_counter when the window opened


def check_pool(pool, answers, k, ref_db, exact_routes):
    """Compare the answer to every batch of a pool with the reference over
    ``ref_db`` = (xb, attr, spec)."""
    xb, attr, spec = ref_db
    ref = reference.Reference(xb, attr, spec)
    tally = reference.Tally(exact_routes)
    for b, (ids, d2, routes) in zip(pool, answers):
        rid, rd2 = ref.topk(b, k)
        tally.add(xb, attr, spec, b, ids, d2, routes, rid, rd2)
    log(f"routes served in the check: {tally.routes}")
    return tally


def traced(trace_dir: Path, fn):
    """Run ``fn`` under the profiler inside the ``bench.window`` span;
    returns (fn's result, reduced trace)."""
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation(xplane.WINDOW):
            out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, xplane.load(str(trace_dir))


def search_loop(cell, db, index, seed, seconds, trace, counter, devs):
    import jax
    mix, search = cell.mix, cell.cfg["search"]
    pool = data.search_pool(db, mix, seed)
    B = int(mix["batch"])
    first = [serve(index, b, search) for b in pool]      # warm-up
    setup_end = time.perf_counter()

    replays = 0
    lat = []

    def one(j):
        nonlocal replays
        b = pool[j % len(pool)]
        t = time.perf_counter()
        ids, d2, routes = serve(index, b, search)
        lat.append(time.perf_counter() - t)
        if not (np.array_equal(ids, first[j % len(pool)][0])
                and np.array_equal(d2, first[j % len(pool)][1])):
            replays += 1

    counter.on = True
    t0 = time.perf_counter()
    j = 0
    while time.perf_counter() - t0 < seconds:
        one(j)
        j += 1
    window_s = time.perf_counter() - t0
    e2e = {"qps": j * B / window_s,
           "p95_ms": float(np.percentile(np.asarray(lat) * 1e3, 95))}
    log(f"window: {j} batches of {B} in {window_s:.3f} s; latency ms "
        f"median {np.median(lat) * 1e3:.2f} p95 {e2e['p95_ms']:.2f} "
        f"max {max(lat) * 1e3:.2f}")
    ctx = {}
    if trace:
        n_tr = int(mix["trace_batches"])
        ex = index.executor
        ex.trace_log = []

        def segment():
            for i in range(n_tr):
                with jax.profiler.TraceAnnotation("bench.batch"):
                    one(j + i)
        _, tr = traced(CACHE / "trace" / cell.name, segment)
        log_ = ex.trace_log
        ex.trace_log = None
        ctx = {"trace": tr, "batches": n_tr,
               "programs": [program_entry(key, make, args)
                            for key, make, args in log_]}
        j += n_tr
    counter.on = False
    ctx["compiles"] = list(counter.names)
    peak = peak_bytes(devs)
    del index
    gc.collect()
    tally = check_pool(pool, first, search["k"], (db.xb, db.attr, db.spec),
                       cell.cfg["guarantees"]["exact_routes"])
    e2e["recall_at_10"] = tally.recall_at_k()
    return Outcome(e2e, j * B, tally.numbers(replays), ctx, peak, setup_end)


def program_entry(key, make, args) -> dict:
    """What a reader needs of one executor launch: its route, the module
    name JAX gives its program, and the shapes of its inputs."""
    from jax._src.util import fun_name
    shapes = [tuple(getattr(a, "shape", ())) for a in args]
    return {"route": key[0], "module": "jit_" + fun_name(make()),
            "shapes": shapes}


def ingest_loop(cell, db, index, seed, seconds, trace, counter, devs):
    import jax
    from repro.stream import StreamingJAGIndex
    mix, search = cell.mix, cell.cfg["search"]
    rows, rattr = data.ingest_rows(db, mix, seed)
    table = attr_table(db.spec["kind"], rattr)

    def step():
        st = StreamingJAGIndex(index)
        with jax.profiler.TraceAnnotation("bench.insert"):
            st.insert(rows, table, auto_compact=False)
        with jax.profiler.TraceAnnotation("bench.compact"):
            st.compact()
            st.base.graph.block_until_ready()
        return st

    st = step()                                         # warm-up
    setup_end = time.perf_counter()
    counter.on = True
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < seconds:
        st = step()
        n += 1
    span = time.perf_counter() - t0
    e2e = {"ingest_rate": n * rows.shape[0] / span}
    log(f"window: {n} compactions of {rows.shape[0]} rows in {span:.3f} s")
    ctx = {}
    if trace:
        st, tr = traced(CACHE / "trace" / cell.name, step)
        ctx = {"trace": tr, "batches": 1}
        n += 1
    counter.on = False
    ctx["compiles"] = list(counter.names)
    peak = peak_bytes(devs)

    check = cell.mix["check"]
    pool = data.search_pool(db, check, seed)
    data.plant_readback(pool, rows, rattr, int(check["readback_per_batch"]),
                        seed)
    answers = [serve(st, b, search) for b in pool]
    del st, index
    gc.collect()
    ref_db = (np.concatenate([db.xb, rows]), np.concatenate([db.attr, rattr]),
              db.spec)
    tally = check_pool(pool, answers, search["k"], ref_db,
                       cell.cfg["guarantees"]["exact_routes"])
    e2e["recall_at_10"] = tally.recall_at_k()
    numbers = tally.numbers(0)
    numbers.pop("replays")
    return Outcome(e2e, n * rows.shape[0], numbers, ctx, peak, setup_end)


LOOPS = {"search": search_loop, "ingest": ingest_loop}


# ---------------------------------------------------------------------------
# per-layer metrics and the result line
# ---------------------------------------------------------------------------

def reader(name: str):
    path = CHIP / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer(cell: Cell, ctx: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        value = reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(ctx: dict) -> dict:
    tr = ctx["trace"]
    plane = xplane.device_planes(tr)[0]
    return {"device_ops": xplane.top_ops(tr, plane, xplane.owners(ctx)),
            "idle_gaps": xplane.idle_gaps(ctx)}


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: Path = ROOT, overrides=None, chip_check=True,
        out=sys.stdout) -> dict:
    """One run of a cell; prints and returns the result object. Raises
    :class:`NoChip` before any work when the chip check fails."""
    t_start = time.perf_counter()
    cell = load_cell(workload, root, overrides)
    import jax
    if chip_check:
        devs = require_chip(cell.chips)
    else:
        devs = jax.devices()[:cell.chips]
    sys.path.insert(0, str(root / "src"))
    import repro  # noqa: F401  (the system under test must be there)
    cache = enable_compile_cache()
    counter = CompileCounter()
    log(f"cell {cell.name}: config {cell.cfg['name']}, traffic "
        f"{cell.mix['name']}, seed {seed}, {seconds} s, trace {int(trace)}, "
        f"compile cache {cache}")
    ctx_base = {"cfg": cell.cfg, "mix": cell.mix,
                "peaks": peaks(devs[0].device_kind) if chip_check else None}

    db = data.database(cell.cfg)
    index, built = open_index(cell, db, root, devs)
    log(f"index {'built' if built else 'loaded'} at "
        f"{time.perf_counter() - t_start:.1f} s")
    o = LOOPS[cell.mix["kind"]](cell, db, index, seed, seconds, trace,
                                  counter, devs)
    log(f"programs compiled or loaded in the window: "
        f"{len(o.ctx['compiles'])} {sorted(set(o.ctx['compiles']))}")
    o.e2e["setup_s"] = o.setup_end - t_start
    o.ctx.update(ctx_base)

    limits = cell.cfg["guarantees"]["limits"]
    checks = reference.judge(o.numbers, limits)
    correct = all(c["ok"] for c in checks.values())
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": o.peak_bytes}
    if trace:
        metrics = per_layer(cell, o.ctx)
        tr = o.ctx["trace"]
        lo, hi = xplane.window(tr)
        device["busy_s"] = xplane.busy_ns(tr) / 1e9
        device["window_s"] = (hi - lo) / 1e9
    else:
        metrics = {m["name"]: {"value": o.e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    for name, v in o.e2e.items():
        log(f"end to end: {name} {v!r}")
    # a request that raises ends the run with no result line, so every
    # attempted request that reaches this point was answered
    result = {"correct": bool(correct), "attempted": int(o.attempted),
              "failed": 0, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = breakdown(o.ctx)
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['ok'] else 'FAILED'}")
    print(json.dumps(result), file=out, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    try:
        run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"benchmark: {e}")
        return 2
    return 0
