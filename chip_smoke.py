#!/usr/bin/env python3
"""Chip smoke test: the JAG serving path end to end on a TPU.

One chip (the default) runs the per-chip share of the ``serve_1b``
deployment (``configs/jag_billion.py`` with ``serve_1b``/``build_1b`` in
``configs/shapes.py``): d=128 f32 rows, ``CONFIG`` (R=64, ls_build=96,
cand_pool=192, build batch 128), range filters, k=10, ls=128,
max_iters=192. From ``--seed`` it generates the msturing-range data with a
joint range+subset attribute table, builds a ``JAGIndex``, serves batches of
256 queries through ``search_auto`` (prefilter, graph and postfilter each
serve a group), inserts one batch of 4,096 rows into a
``StreamingJAGIndex`` and searches again (the delta scan), and checks the
answers against a NumPy brute force on the host: exact routes must return
the same ids, approximate routes must reach recall@10 >= 0.90. It also
checks that the compiled prefilter and delta routes carry the Pallas kernel.

``--chips 4`` runs only the sharded path: a ``ShardedJAGIndex`` over four
chips at four times the per-chip rows, checked against the same brute
force over the union, with each device holding only its own shard.

    python3 chip_smoke.py [--seed 0] [--log2n 17]
    python3 chip_smoke.py --chips 4 [--log2n 17]

The default 2^17 rows per chip is a cut from serve_1b's 2^22: the build
runs 0.18 s per 128-row insert step on one TPU v5e, so 2^22 rows (65,536
steps) take hours and 2^20 about 50 minutes, while the whole smoke must
end within 20.

Every earlier line of output is one JSON object naming its phase. The last
line is ``{"ok": true, "device": {"platform", "kind", "count"}}``. Without
a TPU, or when any phase fails, the script exits non-zero and prints no
such line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

LOG2N = 17            # rows per chip: 2^22 is the serve_1b share (see --log2n)
D = 128
BATCH = 256
K, LS, MAX_ITERS = 10, 128, 192
DELTA_ROWS = 4096
N_TAGS = 30           # msturing_subset's Bernoulli(1/2) attributes
N_REF = 32            # queries per batch checked against the brute force
RECALL_FLOOR = 0.90
EXACT_ROUTES = ("prefilter",)


def emit(phase: str, **kw) -> None:
    print(json.dumps({"phase": phase, **kw}), flush=True)


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu(chips: int):
    """The devices to run on; exits non-zero unless JAX sees a TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX backend is {devs[0].platform!r})",
              file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < chips:
        print(f"chip_smoke: {chips} chips asked, {len(devs)} visible",
              file=sys.stderr)
        raise SystemExit(2)
    return devs[:chips]


# ---------------------------------------------------------------------------
# data (host, from the seed) and the NumPy reference
# ---------------------------------------------------------------------------

class Data:
    """Vectors, attributes and query batches, all host-side NumPy."""

    def __init__(self, seed: int, n: int):
        from repro.data import synthetic
        ds = synthetic.msturing_range(n=n, d=D, b=2 * BATCH, seed=seed)
        rng = np.random.default_rng(seed + 1)
        self.xb = ds.xb
        self.vals = np.asarray(ds.attr.data["value"])
        self.tags = rng.random((n, N_TAGS)) < 0.5
        self.queries = ds.queries
        # batch 1: the generator's range mix (selectivity 1 down to 1e-5)
        self.lo = np.array(ds.filt.data["lo"])
        self.hi = np.array(ds.filt.data["hi"])
        # batch 2: narrow ranges (selectivity 1e-2 .. 1e-4, the prefilter's
        # band) AND 1-2 required tags, so the scan runs the bitset kernel
        width = 1_000_000 / rng.choice((100, 1000, 10_000), BATCH)
        lo2 = rng.uniform(0, 1_000_000 - width)
        self.lo[BATCH:] = lo2
        self.hi[BATCH:] = lo2 + width
        self.req = np.zeros((2 * BATCH, N_TAGS), bool)
        for i in range(BATCH, 2 * BATCH):
            self.req[i, rng.choice(N_TAGS, rng.integers(1, 3),
                                   replace=False)] = True
        # rows for the streaming insert, same distribution
        extra = synthetic.msturing_range(n=DELTA_ROWS, d=D, b=1,
                                         seed=seed + 2)
        self.xd = extra.xb
        self.vals_d = np.asarray(extra.attr.data["value"])
        self.tags_d = rng.random((DELTA_ROWS, N_TAGS)) < 0.5

    @staticmethod
    def table(vals, tags):
        from repro.core import filters as F
        return F.joint_table(F.range_table(vals),
                             F.subset_table(tags, N_TAGS))

    def batches(self):
        """(name, queries, filter expression, host filter spec) x 2: the
        range mix alone, then narrow ranges AND required tags."""
        from repro import Range, Subset
        out = []
        for j, name in enumerate(("range", "range&subset")):
            sl = slice(j * BATCH, (j + 1) * BATCH)
            expr = Range(self.lo[sl], self.hi[sl])
            req = None
            if name != "range":
                req = self.req[sl]
                expr = expr & Subset(req)
            out.append((name, self.queries[sl], expr,
                        (self.lo[sl], self.hi[sl], req)))
        return out


def reference_topk(xb, vals, tags, q, spec, rows) -> np.ndarray:
    """Brute-force filtered top-K ids for queries ``q[rows]``: float64
    distances over every filter-passing row, ties to the lower id; -1 pads
    where fewer than K rows pass."""
    lo, hi, req = spec
    x64 = xb.astype(np.float64)
    xn = np.einsum("nd,nd->n", x64, x64)
    out = np.full((len(rows), K), -1, np.int64)
    for j, i in enumerate(rows):
        ok = (vals >= lo[i]) & (vals <= hi[i])
        if req is not None:
            ok &= np.all(tags[:, req[i]], axis=1)
        idx = np.flatnonzero(ok)
        qi = q[i].astype(np.float64)
        d2 = xn[idx] - 2.0 * (x64[idx] @ qi) + qi @ qi
        order = np.lexsort((idx, d2))[:K]
        out[j, :len(order)] = idx[order]
    return out


def pick_rows(routes, rng) -> np.ndarray:
    """N_REF batch positions, spread evenly over the routes served."""
    routes = np.asarray([r.split("+")[0] for r in routes])
    names = sorted(set(routes))
    per = -(-N_REF // len(names))
    rows = []
    for r in names:
        pos = np.flatnonzero(routes == r)
        rows.extend(rng.choice(pos, min(per, pos.size), replace=False))
    rest = np.setdiff1d(np.arange(routes.size), rows)
    rows.extend(rng.choice(rest, max(0, N_REF - len(rows)), replace=False))
    return np.sort(np.asarray(rows[:N_REF]))


def compare(name, ids, routes, ref, rows) -> dict:
    """Exact routes: equal ids. Approximate ones: recall@K >= floor."""
    ids = np.asarray(ids)
    hits, trials, exact = {}, {}, {}
    for j, i in enumerate(rows):
        route = routes[i].split("+")[0]
        want = ref[j]
        if route in EXACT_ROUTES:
            same = np.array_equal(ids[i], want)
            check(same, f"{name}: {routes[i]} query {i} ids {ids[i]} "
                        f"!= reference {want}")
            exact[route] = exact.get(route, 0) + 1
        else:
            w = set(want[want >= 0].tolist())
            hits[route] = hits.get(route, 0) + len(w & set(ids[i].tolist()))
            trials[route] = trials.get(route, 0) + len(w)
    recall = {r: hits[r] / trials[r] for r in trials if trials[r]}
    for r, v in recall.items():
        check(v >= RECALL_FLOOR,
              f"{name}: {r} recall@{K} {v:.4f} < {RECALL_FLOOR}")
    return dict(exact_equal=exact, recall=recall)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def timed(fn):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def mem(dev, key: str = "peak_bytes_in_use") -> int:
    """One counter of the device's ``memory_stats()``."""
    return int(dev.memory_stats()[key])


def serve(index, batches, data_xb, vals, tags, rng, label: str) -> set:
    """Serve every batch twice (cold = compile included, then warm) and
    check it; returns the routes served."""
    served = set()
    for name, q, expr, spec in batches:
        (res, plan), cold = timed(lambda: index.search_auto(
            q, expr, k=K, ls=LS, max_iters=MAX_ITERS, return_plan=True))
        (res2, _), warm = timed(lambda: index.search_auto(
            q, expr, k=K, ls=LS, max_iters=MAX_ITERS, return_plan=True))
        check(np.array_equal(np.asarray(res.ids), np.asarray(res2.ids)),
              f"{label}/{name}: a repeated batch changed its answers")
        routes = plan.realized
        counts = {r: routes.count(r) for r in sorted(set(routes))}
        served |= {r.split("+")[0] for r in routes}
        rows = pick_rows(routes, rng)
        ref = reference_topk(data_xb, vals, tags, q, spec, rows)
        result = compare(f"{label}/{name}", res.ids, routes, ref, rows)
        emit(f"{label}:{name}", queries=len(q), routes=counts,
             cold_s=cold, warm_s=warm, checked=len(rows), **result)
    return served


def kernel_calls(executor, route: str) -> dict:
    """Pallas kernel call sites (``tpu_custom_call``) in the compiled
    program of each distinct ``route`` the executor ran, by filter kind.
    Each must hold one, or the XLA scan, not the kernel, is what ran."""
    import jax
    seen = {}
    for key, make, args in executor.trace_log:
        if key[0] == route and key not in seen:
            text = jax.jit(make()).lower(*args).compile().as_text()
            seen[key] = text.count("tpu_custom_call")
    check(bool(seen), f"no {route} route ran")
    calls = {str(key[6]): n for key, n in seen.items()}
    check(all(calls.values()),
          f"compiled {route} route without a Pallas kernel: {calls}")
    return calls


def one_chip(dev, seed: int, log2n: int, cfg) -> None:
    from repro.core.jag import JAGIndex
    from repro.stream import StreamingJAGIndex
    n = 1 << log2n
    rng = np.random.default_rng(seed + 3)
    data, gen_s = timed(lambda: Data(seed, n))
    emit("data", n=n, d=D, seconds=gen_s)

    table = Data.table(data.vals, data.tags)
    t0 = time.perf_counter()
    index = JAGIndex.build(data.xb, table, cfg)
    index.graph.block_until_ready()
    build_s = time.perf_counter() - t0
    emit("build", n=n, seconds=build_s,
         insert_steps=index.build_cfg.n_passes * (-(-n // cfg.batch_size)),
         peak_bytes=mem(dev))

    index.executor.trace_log = []
    served = serve(index, data.batches(), data.xb, data.vals, data.tags,
                   rng, "serve")
    check(served >= {"prefilter", "graph", "postfilter"},
          f"routes served {sorted(served)}: each of prefilter, graph and "
          f"postfilter must serve a group")
    kernels = kernel_calls(index.executor, "prefilter")

    stream = StreamingJAGIndex(index)
    stream.insert(data.xd, Data.table(data.vals_d, data.tags_d),
                  auto_compact=False)
    stream.executor.trace_log = []
    xb_all = np.concatenate([data.xb, data.xd])
    vals_all = np.concatenate([data.vals, data.vals_d])
    tags_all = np.concatenate([data.tags, data.tags_d])
    served_d = serve(stream, data.batches()[:1], xb_all, vals_all, tags_all,
                     rng, "stream")
    emit("kernels", prefilter_tpu_custom_calls=kernels,
         delta_tpu_custom_calls=kernel_calls(stream.executor, "delta"),
         delta_rows=DELTA_ROWS,
         stream_routes=sorted(served_d), peak_bytes=mem(dev))


def four_chips(devs, seed: int, log2n: int, cfg) -> None:
    from repro.distributed.sharding import serve_mesh
    from repro.serve.sharded import ShardedJAGIndex
    S = len(devs)
    n = S << log2n
    rng = np.random.default_rng(seed + 3)
    data, gen_s = timed(lambda: Data(seed, n))
    emit("data", n=n, shards=S, d=D, seconds=gen_s)

    t0 = time.perf_counter()
    index = ShardedJAGIndex.build(data.xb, Data.table(data.vals, data.tags),
                                  cfg, mesh=serve_mesh(S))
    index.xb.block_until_ready()
    emit("build", n=n, shards=S, seconds=time.perf_counter() - t0)

    n_loc = n // S
    shards = sorted(index.xb.addressable_shards, key=lambda s: s.index[0])
    check([s.device for s in shards] == list(devs),
          f"xb shards sit on {[str(s.device) for s in shards]}")
    for s, sh in enumerate(shards):
        check(sh.data.shape == (1, n_loc, D),
              f"device {s} holds xb block {sh.data.shape}")
        head = np.asarray(sh.data[0, :4])
        check(np.array_equal(head, data.xb[s * n_loc:s * n_loc + 4]),
              f"device {s} does not hold shard {s}'s rows")
    held = [mem(d, "bytes_in_use") for d in devs]
    union_bytes = data.xb.nbytes
    check(max(held) < union_bytes,
          f"a device holds {max(held)} bytes, more than the union's xb "
          f"({union_bytes}): shards are not spread")
    emit("placement", shard_rows=n_loc, bytes_in_use=held,
         union_xb_bytes=union_bytes,
         peak_bytes=[mem(d) for d in devs])

    served = serve(index, data.batches()[:1], data.xb, data.vals, data.tags,
                   rng, "sharded")
    check("prefilter" in served, "no prefilter group on the sharded path")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log2n", type=int, default=LOG2N,
                    help="log2 of the rows per chip")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded path on four chips")
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)
    from repro.configs.jag_billion import CONFIG
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    d0 = devs[0]
    emit("device", platform=d0.platform, kind=d0.device_kind,
         count=len(devs), compile_cache=cache)
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(d0, args.seed, args.log2n, CONFIG)
    else:
        four_chips(devs, args.seed, args.log2n, CONFIG)
    emit("done", seconds=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
