"""Unified search executor: the ONE jit-compilation cache behind every path.

Before this module, each public entry point (``JAGIndex.search``,
``search_int8``, ``search_unfiltered``) carried its own copy-pasted
``@jax.jit`` cache block, and the baselines in core/baselines.py re-created
a fresh ``@jax.jit`` closure on every call (recompiling each time). The
Executor owns a single cache keyed on

    (route, layout, dtype, k, ls, max_iters, filter kind, *route extras)

so every compiled search variant in the process is enumerable
(``cache_keys()``), shared across entry points, and traced exactly once.
``JAGIndex.search/search_int8/search_unfiltered`` are thin shims over the
``graph``/``unfiltered`` routes below and return bit-identical results to
the pre-refactor per-method caches (same traced computation, same key
granularity).

Routes (serve/planner.py owns the router that picks between them):

  prefilter  — masked brute-force scan over filter-passing rows
               (core/ground_truth.py; on TPU the Pallas tile scan via
               kernels/ops.gather_dist_tile). Exact; distance computations
               scale with selectivity * N, so it wins at low selectivity.
  graph      — JAG traversal (core/beam_search.py), default or fused
               serving layout, f32 or int8 vector lanes.
  postfilter — unfiltered traversal with an oversampled beam, the filter
               applied to the survivors (near-1.0 selectivity).
  delta      — exact masked scan over a streaming index's live delta
               segment (ids offset past the graph segment). Only available
               when the executor's index exposes one
               (repro.stream.StreamingJAGIndex); ``merge`` folds its top-k
               into any base route's result, exactly.

Every cache is **epoch-aware**: keys are stored under the index's data
epoch (``JAGIndex.epoch`` is 0 forever; a ``StreamingJAGIndex`` bumps its
counter on every insert batch and compaction), and a rolled epoch evicts
all compiled routes, sample-probe buffers, and engines — a grown index can
never route on a stale-n probe or serve from a pre-compaction layout.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.beam_search import SearchResult, greedy_search
from ..core.distances import INF, query_key_fn, unfiltered_key_fn
from ..core.filters import FilterExpr, matches, n_leaves
from ..core.ground_truth import exact_filtered_knn
from ..core.quantized import make_int8_dist_fn, rerank_exact
from ..kernels.gather_dist import scan_tile
from ..obs.spans import span
from .engine import FusedEngine, make_fetch_fn

LAYOUTS = ("default", "fused")
VEC_DTYPES = ("f32", "int8")


class Executor:
    """Owns the single jit cache + route implementations for one index.

    Instantiated lazily by ``JAGIndex.executor``; holds only references to
    the index's device arrays (graph, vectors, attr table, layouts), never
    copies.
    """

    def __init__(self, index):
        self.index = index
        self._cache: dict = {}
        self._engines: dict = {}
        self._samples: dict = {}
        self._cache_epoch: int = self.epoch
        # analysis hook: when set to a list, run() appends every
        # (key, make, args) it executes so repro.analysis.audit can
        # re-lower the exact programs this cache serves. None in serving.
        self.trace_log: list | None = None
        # telemetry hooks (repro.obs): miss_hook(epoch_key) fires once
        # per distinct compiled cache entry, roll_hook(epoch) once per
        # epoch-driven cache eviction. Host-side only — never traced.
        self.miss_hook: Callable | None = None
        self.roll_hook: Callable | None = None
        # search_auto calls so far: the request id its spans carry
        self.n_requests = 0

    # -- cache plumbing ----------------------------------------------------
    @property
    def epoch(self) -> int:
        """The index's data epoch (0 forever for a frozen JAGIndex)."""
        return getattr(self.index, "epoch", 0)

    def _roll_epoch(self) -> None:
        """Evict every cache built against a previous data epoch.

        Compiled routes, sample-probe device buffers, and fused engines all
        reference epoch-dependent data (live attr table shape, delta
        segment, post-compaction base arrays), so a bumped epoch invalidates
        all three wholesale. Frozen indexes never roll.
        """
        e = self.epoch
        if e != self._cache_epoch:
            self._cache.clear()
            self._samples.clear()
            self._engines.clear()
            self._cache_epoch = e
            if self.roll_hook is not None:
                self.roll_hook(e)

    def sample_ids(self, n: int, n_samples: int, seed: int = 0):
        """Planner probe rows, cached per executor (so per index).

        Replaces the former module-level ``functools.lru_cache`` on
        ``planner.sample_ids``, which pinned device buffers process-wide
        across index lifetimes and test runs; these die with the executor.
        Keys carry the data epoch: when the attr table grows (streaming
        insert), every cached probe buffer is evicted, so a grown index can
        never route on a stale-n sample.
        """
        self._roll_epoch()
        key = (self._cache_epoch, n, n_samples, seed)
        ids = self._samples.get(key)
        if ids is None:
            from .planner import sample_ids
            ids = self._samples[key] = sample_ids(n, n_samples, seed)
        return ids

    def run(self, key: Tuple, make: Callable[[], Callable], *args):
        """Execute the cached compilation for ``key``, tracing on first use.

        ``make()`` must return the pure function to ``jax.jit``; it is only
        invoked on a cache miss, so closure-captured statics (k, ls, ...)
        must be part of ``key``. Keys are stored under the current data
        epoch (``(epoch,) + key``); rolling the epoch evicts them all.
        The function's name is the program's (``jit_<name>`` on the
        device); a miss's call, which traces and compiles it, is the span
        ``jit:<route>``.
        """
        self._roll_epoch()
        if self.trace_log is not None:
            self.trace_log.append((key, make, args))
        epoch_key = (self._cache_epoch,) + key
        fn = self._cache.get(epoch_key)
        if fn is None:
            if self.miss_hook is not None:
                self.miss_hook(epoch_key)
            fn = self._cache[epoch_key] = jax.jit(make())
            with span(f"jit:{key[0]}"):
                return fn(*args)
        return fn(*args)

    def cache_keys(self, full: bool = False) -> Tuple:
        """Route keys of every live compilation (current epoch only).

        ``full=True`` keeps the leading epoch component on each key.
        """
        return tuple(self._cache) if full else tuple(
            k[1:] for k in self._cache)

    def cost_router(self, *, k: int, ls: int, filt=None):
        """The index's calibrated ``cost.CostModelRouter`` for this search
        shape, or None (-> the planner's static thresholds).

        Threads the attached cost model into routing: the router predicts
        every base route's us/query at the live (n, d, k, ls) and folds
        the constant delta-scan tax (``delta_n``/N rows the streaming
        executor scans+merges on EVERY route) into each prediction. A
        model that doesn't cover all three base routes is treated as
        absent — partial calibrations never half-route. ``filt`` threads
        the clause count of a compound expression into the prefilter
        feature vector (log(n_clauses); 1 for atomic filters, which keeps
        legacy models' predictions unchanged).
        """
        model = getattr(self.index, "cost_model", None)
        if model is None:
            return None
        from ..cost.model import BASE_ROUTES, CostModelRouter
        metric = getattr(self.index, "cost_metric", "us")
        if not model.covers(BASE_ROUTES, metric):
            return None
        idx = self.index
        delta_n = idx.delta.n if hasattr(idx, "delta_arrays") else 0
        clauses = 1 if filt is None else n_leaves(filt)
        return CostModelRouter(model, n=int(idx.xb.shape[0]),
                               d=int(idx.xb.shape[1]), k=k, ls=ls,
                               delta_n=delta_n, metric=metric,
                               n_leaves=clauses)

    def engine(self, vec_dtype: str = "f32", **kw) -> FusedEngine:
        """FusedEngine over the index's packed layout (metadata + fetch)."""
        self._roll_epoch()
        key = (vec_dtype, tuple(sorted(kw.items())))
        if key not in self._engines:
            self._engines[key] = FusedEngine(
                self.index.fused_layout(vec_dtype), **kw)
        return self._engines[key]

    # -- graph route (JAG traversal; Algorithm 2) --------------------------
    def graph(self, queries, filt, *, k: int, ls: int,
              max_iters: int, layout: str = "default",
              dtype: str = "f32", introspect: bool = False):
        """JAG traversal. ``introspect=True`` compiles the introspective
        variant (its own cache-key component — the standard program is
        untouched) and returns ``(SearchResult, TraversalStats)`` with
        per-query hops / frontier-saturation step / dead-end counters as
        extra jit outputs: zero host callbacks, zero collectives, and
        (ids, primary, secondary) bit-identical to the standard route.
        """
        if layout not in LAYOUTS:
            raise ValueError(f"layout must be 'default' or 'fused', "
                             f"got {layout!r}")
        if dtype not in VEC_DTYPES:
            raise ValueError(f"dtype must be 'f32' or 'int8', got {dtype!r}")
        idx = self.index
        key = ("graph", layout, dtype, k, ls, max_iters, filt.kind)
        if introspect:
            key = key + ("introspect",)
        q = jnp.asarray(queries)

        if dtype == "f32" and layout == "default":
            def make():
                def graph(graph, xb, xb_norm, attr, q, filt, entry):
                    return greedy_search(graph, xb, xb_norm, attr, q, entry,
                                         query_key_fn(filt), ls=ls, k=k,
                                         max_iters=max_iters,
                                         introspect=introspect)
                return graph
            return self.run(key, make, idx.graph, idx.xb, idx.xb_norm,
                            idx.attr, q, filt, idx.entry)

        if dtype == "f32":  # fused layout, full precision
            lay = idx.fused_layout("f32")

            def make():
                def graph(graph, xb, xb_norm, attr, lay, q, filt, entry):
                    return greedy_search(graph, xb, xb_norm, attr, q, entry,
                                         query_key_fn(filt), ls=ls, k=k,
                                         max_iters=max_iters,
                                         fetch_fn=make_fetch_fn(lay),
                                         introspect=introspect)
                return graph
            return self.run(key, make, idx.graph, idx.xb, idx.xb_norm,
                            idx.attr, lay, q, filt, idx.entry)

        if layout == "fused":  # int8 lanes, one-gather expansion + re-rank
            lay = idx.fused_layout("int8")

            def make():
                def graph(graph, xb, xb_norm, attr, lay, q, filt, entry):
                    out = greedy_search(graph, xb, xb_norm, attr, q, entry,
                                        query_key_fn(filt), ls=ls, k=ls,
                                        max_iters=max_iters,
                                        fetch_fn=make_fetch_fn(lay),
                                        introspect=introspect)
                    res, stats = out if introspect else (out, None)
                    i, p, s = rerank_exact(xb, xb_norm, res.ids,
                                           res.primary, q, k)
                    res = SearchResult(i, p, s, res.vlog, res.n_expanded,
                                       res.n_dist)
                    return (res, stats) if introspect else res
                return graph
            return self.run(key, make, idx.graph, idx.xb, idx.xb_norm,
                            idx.attr, lay, q, filt, idx.entry)

        xq, scale, xq_norm = idx.quantized()  # int8, split layout

        def make():
            def graph(graph, xq, xq_norm, scale, xb, xb_norm, attr, q, filt,
                      entry):
                out = greedy_search(
                    graph, xq, xq_norm, attr, q, entry,
                    query_key_fn(filt), ls=ls, k=ls, max_iters=max_iters,
                    dist_fn=make_int8_dist_fn(scale), introspect=introspect)
                res, stats = out if introspect else (out, None)
                i, p, s = rerank_exact(xb, xb_norm, res.ids, res.primary,
                                       q, k)
                res = SearchResult(i, p, s, res.vlog, res.n_expanded,
                                   res.n_dist)
                return (res, stats) if introspect else res
            return graph
        return self.run(key, make, idx.graph, xq, xq_norm, scale, idx.xb,
                        idx.xb_norm, idx.attr, q, filt, idx.entry)

    # -- unfiltered traversal (feeds the postfilter route) -----------------
    def unfiltered(self, queries, *, k: int, ls: int,
                   max_iters: int) -> SearchResult:
        idx = self.index
        key = ("unfiltered", "default", "f32", k, ls, max_iters, None)

        def make():
            def unfiltered(graph, xb, xb_norm, attr, q, entry):
                return greedy_search(graph, xb, xb_norm, attr, q, entry,
                                     unfiltered_key_fn(), ls=ls, k=k,
                                     max_iters=max_iters)
            return unfiltered
        return self.run(key, make, idx.graph, idx.xb, idx.xb_norm, idx.attr,
                        jnp.asarray(queries), idx.entry)

    # -- prefilter route (masked exact scan) -------------------------------
    def _scan(self, key: Tuple, xb, attr, queries, filt, *,
              k: int, block: int | None, use_kernel: bool,
              offset: int = 0) -> SearchResult:
        """Exact masked scan adapted to the SearchResult contract — the one
        adapter behind both scan routes (prefilter over the base rows,
        delta over the streaming segment with an id offset).

        primary is 0 where a valid neighbor was found (the scan only ever
        returns filter-passing points), INF on -1 padding; n_dist counts
        valid points scanned, matching the paper's DC metric. vlog is the
        honest width-0 ``[B, 0]`` — there is no traversal to log — per the
        normalized contract (SearchResult.vlog may be any width; the
        per-query dispatcher pads groups to a common width when it
        regroups routes).
        """
        def make():
            def scan(xb, attr, q, filt):
                gt = exact_filtered_knn(xb, attr, q, filt, k=k, block=block,
                                        use_kernel=use_kernel)
                B = q.shape[0]
                ids = (gt.ids if offset == 0
                       else jnp.where(gt.ids >= 0, gt.ids + offset, -1))
                prim = jnp.where(gt.ids >= 0, jnp.float32(0.0), INF)
                return SearchResult(ids, prim, gt.d2,
                                    jnp.zeros((B, 0), jnp.int32),
                                    jnp.zeros((B,), jnp.int32), gt.n_dist)
            scan.__name__ = key[0]          # the program: jit_<route>
            return scan
        return self.run(key, make, xb, attr, jnp.asarray(queries), filt)

    def _reorder_compound(self, filt):
        """Short-circuit-optimal clause order for a compound expression.

        Probes each leaf's boolean validity over the executor's cached
        sample rows (one compiled probe per tree signature) and asks the
        planner for the cheapest-most-selective-first order; the boolean
        vectors let the greedy ordering condition each pick on the clauses
        already placed, so correlated clauses rank by their true joint
        filtering power rather than an independence estimate. Host-side
        and static: the reordered tree is result-identical (connectives
        commute), it only changes which clauses the scan's short-circuit
        accounting charges (``GroundTruth.n_feval``). Atomic filters and
        single-leaf trees pass through untouched.
        """
        if not isinstance(filt, FilterExpr) or n_leaves(filt) < 2:
            return filt
        from .planner import leaf_validity, reorder_clauses
        ids = self.sample_ids(self.index.attr.n, 1024, 0)
        key = ("leafval", "default", "bool", 0, 0, 0, filt.kind,
               int(ids.shape[0]))
        valid = self.run(key, lambda: leaf_validity,
                         filt, self.index.attr, ids)
        # [L, B, S] -> per-leaf sample vectors pooled over the query batch
        # (clause order is static for the whole batch, like the old median)
        with span("sync:reorder"):
            v = np.asarray(valid)
        return reorder_clauses(filt, list(v.reshape(v.shape[0], -1)))

    def prefilter(self, queries, filt, *, k: int,
                  block: int | None = None, use_kernel: bool | None = None
                  ) -> SearchResult:
        """Masked exact scan over the index's (graph-segment) rows.

        ``use_kernel`` defaults by backend (the Pallas tile scan on TPU,
        the XLA matmul scan elsewhere), matching the kernels convention.
        ``block`` defaults to the widest tile the chip's VMEM holds at the
        index's d (``kernels.gather_dist.scan_tile``).
        Compound expressions are clause-reordered (cheapest most-selective
        clause first) before the scan compiles.
        """
        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        filt = self._reorder_compound(filt)
        idx = self.index
        key = ("prefilter", "default", "f32", k, 0, 0, filt.kind, block,
               use_kernel)
        return self._scan(key, idx.xb, idx.attr, queries, filt, k=k,
                          block=block, use_kernel=use_kernel)

    # -- delta route (streaming: exact scan over the live delta segment) ---
    def delta(self, queries, filt, *, k: int,
              block: int | None = None, use_kernel: bool | None = None
              ) -> SearchResult:
        """Exact masked scan over the index's delta segment, ids offset.

        The streaming layer's fourth route: the delta segment is small (it
        is compacted into the graph before it exceeds a fraction of N), so
        a brute-force scan — the same primitive as the prefilter route —
        is both exact and cheap. Returned ids live past the graph segment
        (``+ base_n``), so ``merge`` can fold them into any base route's
        top-k as if the concatenated database had been searched.

        Requires the index to expose ``delta_arrays() -> (xv, attr, offset)``
        (repro.stream.StreamingJAGIndex); frozen indexes have no delta.
        """
        if not hasattr(self.index, "delta_arrays"):
            raise TypeError("delta route needs a streaming index exposing "
                            "delta_arrays(); JAGIndex is frozen")
        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        xv, dattr, offset = self.index.delta_arrays()
        # the scan pads to whole blocks — cap at the (small) delta row count
        # so a 60-row delta never pays a 4096-wide distance matrix
        block = max(1, min(block or scan_tile(int(xv.shape[1])),
                           int(xv.shape[0])))
        key = ("delta", "default", "f32", k, 0, 0, filt.kind, block,
               use_kernel, offset)
        return self._scan(key, xv, dattr, queries, filt, k=k, block=block,
                          use_kernel=use_kernel, offset=offset)

    def merge(self, base: SearchResult, extra: SearchResult, *,
              k: int) -> SearchResult:
        """Fold two per-query top-k results into one exact top-k.

        Compiled through the same cache as every route; see
        ``serve.dispatch.merge_topk`` for the ordering contract (stable on
        the (primary, secondary) key, ``base`` winning ties — matching a
        brute-force scan of base rows before delta rows).
        """
        from .dispatch import merge_topk
        key = ("merge", "default", "f32", k, 0, 0, None)

        def make():
            def merge(base, extra):
                return merge_topk(base, extra, k=k)
            return merge
        return self.run(key, make, base, extra)

    # -- postfilter route (oversampled unfiltered beam + filter) -----------
    def postfilter(self, queries, filt, *, k: int, ls: int,
                   max_iters: int) -> SearchResult:
        """Unfiltered traversal keeping the ls-beam, then keep the k best
        filter-passing survivors (the Post-Filtering baseline, fused into
        one compiled program).

        n_dist counts the traversal's distance computations PLUS the filter
        evaluations applied to the surviving beam entries — the paper's DC
        metric compares this route against prefilter/graph, both of which
        charge every point their comparator touches, so omitting the
        survivor evaluations undercounted this route.
        """
        idx = self.index
        key = ("postfilter", "default", "f32", k, ls, max_iters, filt.kind)

        def make():
            def postfilter(graph, xb, xb_norm, attr, q, filt, entry):
                res = greedy_search(graph, xb, xb_norm, attr, q, entry,
                                    unfiltered_key_fn(), ls=ls, k=ls,
                                    max_iters=max_iters)
                ids = res.ids
                ok = matches(filt, attr.gather(jnp.maximum(ids, 0)))
                ok = ok & (ids >= 0)
                prim = jnp.where(ok, 0.0, INF)
                sec = jnp.where(ok, res.secondary, INF)
                idsm = jnp.where(ok, ids, -1)
                prim, sec, idsm = jax.lax.sort((prim, sec, idsm), num_keys=2)
                n_dist = res.n_dist + jnp.sum(ids >= 0, axis=1,
                                              dtype=jnp.int32)
                return SearchResult(idsm[:, :k], prim[:, :k], sec[:, :k],
                                    res.vlog, res.n_expanded, n_dist)
            return postfilter
        return self.run(key, make, idx.graph, idx.xb, idx.xb_norm, idx.attr,
                        jnp.asarray(queries), filt, idx.entry)
