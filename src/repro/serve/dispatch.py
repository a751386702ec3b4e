"""Per-query route dispatch: group-gather, execute each route, scatter back.

The whole-batch planner routes every query down the route the *median*
selectivity picks — a batch mixing 0.1% and 90% filters sends half its
queries down the wrong path, exactly the regime where single-strategy
systems collapse (FAVOR, arXiv:2605.07770; the CUHK study,
arXiv:2508.16263). This module closes that gap:

  1. ``planner.plan_per_query`` bands the [B] selectivity vector into
     route groups (original-batch positions, ascending within a group);
  2. :func:`dispatch_per_query` gathers each group's queries AND filter
     lanes (``FilterBatch.take``) into a contiguous sub-batch and runs it
     through its executor route;
  3. :func:`regroup` scatters the per-group ``SearchResult``s back into
     original query order via one inverse-permutation gather per field.

:func:`merge_topk` is the streaming layer's segment merge: a base route's
top-k over the graph segment folds with the delta scan's (id-offset) top-k
into one exact top-k per query — bit-identical to scanning the
concatenated base+delta database with the base route exact on its segment.

Regrouping relies on the normalized SearchResult contract: every field is
leading-dim-[B] and ``vlog`` may be ANY width (the prefilter scan has no
traversal and emits ``[B, 0]``; graph/postfilter emit ``[B, max_iters]``)
— groups are padded with ``-1`` holes to the widest vlog before the
scatter. Per-query results are bit-identical to running each query alone
through its own route: routes apply per-row ops and batch-invariant
distance computations (every gathered candidate dot goes through
``distances.gathered_dot``), so group composition never leaks into a
query's lane. One caveat: the prefilter scan's block distances are a
``[B, d] @ [d, block]`` GEMM (a batch-invariant mul+sum there measures
~70x slower) — row-invariant on CPU (measured), and per-row by
construction in the TPU tile kernel, which DMAs each block once per call
and runs one fixed 128-row query block per product (groups under 128 rows
take one 8-aligned block), so a row's bits do not depend on its group;
an untested GPU GEMM could in principle tile low-order float bits
differently per batch size.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core.beam_search import SearchResult
from ..obs.spans import span
from .planner import PerQueryPlan

__all__ = ["dispatch_per_query", "fold_topk", "merge_topk", "regroup",
           "route_descriptor", "run_route"]


def route_descriptor(route: str, layout: str = "default",
                     dtype: str = "f32") -> str:
    """The realized-route name: which compiled variant actually serves.

    Only the graph route has serving variants (layout x dtype); the other
    routes ignore those options, so their descriptor is the band name —
    ``route_descriptor("graph", "fused", "int8") == "graph[fused,int8]"``
    and everything at the defaults collapses back to the plain name.
    """
    if route == "graph" and (layout != "default" or dtype != "f32"):
        return f"graph[{layout},{dtype}]"
    return route


def run_route(executor, route: str, queries, filt, *, k: int,
              ls: int, max_iters: int, layout: str = "default",
              dtype: str = "f32", introspect: bool = False):
    """Execute one executor route by name with the serving options it takes.

    ``filt`` may be an atomic FilterBatch or a compound FilterExpr — both
    carry the same lane/take/kind surface, so every route accepts either.
    ``layout``/``dtype`` select the graph route's serving variant; the
    prefilter scan is exact f32 by construction and the postfilter
    traversal runs the default layout, so both ignore them.

    ``introspect=True`` changes the return to ``(result, stats)`` where
    ``stats`` is the graph route's per-query ``TraversalStats`` (an extra
    jit output of the introspective compilation) and None on the scan /
    postfilter routes, which have no traversal to introspect.
    """
    if route == "prefilter":
        res = executor.prefilter(queries, filt, k=k)
        return (res, None) if introspect else res
    if route == "graph":
        return executor.graph(queries, filt, k=k, ls=ls,
                              max_iters=max_iters, layout=layout,
                              dtype=dtype, introspect=introspect)
    if route == "postfilter":
        res = executor.postfilter(queries, filt, k=k, ls=ls,
                                  max_iters=max_iters)
        return (res, None) if introspect else res
    raise ValueError(f"unknown route {route!r}")


def merge_topk(base: SearchResult, extra: SearchResult, *,
               k: int) -> SearchResult:
    """Exact per-query merge of two top-k lists over disjoint id segments.

    The streaming layer's segment merge: ``base`` holds a route's top-k over
    the graph segment, ``extra`` the delta scan's top-k (ids already offset
    past the graph segment). Both order valid entries by the lexicographic
    (primary, secondary) key with -1 padding at (INF, INF), so one stable
    sort over the concatenation yields the exact top-k of the union —
    ties (primary, secondary) resolve to ``base`` entries first, matching a
    brute-force scan that visits base rows before delta rows.

    Traversal telemetry composes: ``vlog``/``n_expanded`` come from ``base``
    plus any expansions ``extra`` logged (the delta scan logs none), and
    ``n_dist`` sums — both segments' distance computations are real work.
    """
    prim = jnp.concatenate([base.primary, extra.primary], axis=1)
    sec = jnp.concatenate([base.secondary, extra.secondary], axis=1)
    ids = jnp.concatenate([base.ids, extra.ids], axis=1)
    prim, sec, ids = jax.lax.sort((prim, sec, ids), num_keys=2)
    return SearchResult(ids[:, :k], prim[:, :k], sec[:, :k], base.vlog,
                        base.n_expanded + extra.n_expanded,
                        base.n_dist + extra.n_dist)


def fold_topk(parts, *, k: int) -> SearchResult:
    """N-way :func:`merge_topk` fold over per-segment results, in order.

    The sharded executor's cross-shard reduction: ``parts[i]`` holds shard
    i's top-k with ids already globalized onto disjoint segments, and the
    fold runs in segment order, so ties on the (primary, secondary) key
    resolve to the LOWEST segment — and within a segment the lowest id —
    exactly like one brute-force scan over the concatenated database.
    ``jax.lax.sort`` is stable and the fold is left-associative, so the
    result (including telemetry sums) is identical whether segments arrive
    pre-merged or one at a time: merge_topk keeps base-side entries on
    equal keys and every later segment enters as ``extra``.
    """
    if not parts:
        raise ValueError("fold_topk needs at least one part")
    out = parts[0]
    for p in parts[1:]:
        out = merge_topk(out, p, k=k)
    return out


def regroup(parts, groups, batch: int) -> SearchResult:
    """Scatter per-group SearchResults back into original query order.

    ``parts[i]`` holds the results for the queries at original-batch
    positions ``groups[i].ids``. Fields are concatenated in group order and
    un-permuted with one gather; vlogs are -1-padded to the widest group
    first so heterogeneous route shapes concatenate cleanly.
    """
    width = max(int(r.vlog.shape[1]) for r in parts)
    parts = [r._replace(vlog=jnp.pad(r.vlog,
                                     ((0, 0), (0, width - r.vlog.shape[1])),
                                     constant_values=-1))
             if r.vlog.shape[1] != width else r for r in parts]
    order = np.concatenate([g.ids for g in groups])
    inv = np.empty(batch, np.int32)
    inv[order] = np.arange(batch, dtype=np.int32)
    inv = jnp.asarray(inv)
    return SearchResult(*(jnp.take(jnp.concatenate([getattr(r, f)
                                                    for r in parts], axis=0),
                                   inv, axis=0)
                          for f in SearchResult._fields))


def dispatch_per_query(executor, queries, filt,
                       pq: PerQueryPlan, *, k: int, ls: int, max_iters: int,
                       layout: str = "default", dtype: str = "f32",
                       on_group=None, introspect: bool = False,
                       spans=None) -> SearchResult:
    """Run each route group through its executor route; regroup per query.

    Each group's sub-batch shape keys its own executor compilation, so a
    workload with recurring group sizes reuses the cache like any other
    batch shape would. Compound expressions slice per group through
    ``FilterExpr.take`` (every leaf's lanes gathered in lockstep), so a
    group sees exactly its queries' filter lanes regardless of tree shape.

    ``on_group(group, result, stats, wall_seconds)`` is the telemetry
    tap: when set, each group's route is blocked on
    (``jax.block_until_ready``) and wall-timed on the host — timestamps
    never enter the compiled routes (JAG006). ``stats`` is the graph
    route's per-query ``TraversalStats`` when ``introspect=True`` (None
    otherwise). Off (None), nothing blocks and dispatch is unchanged.
    The gather → execute → scatter stages are profiler spans
    (``repro.obs.spans``; host-side, around the compiled calls — never
    inside them); ``spans``, an optional ``SpanRecorder``, records them
    too.
    """
    q = jnp.asarray(queries)

    def _run(group, q_g, f_g):
        with span(f"execute:{group.route}", spans,
                  queries=int(np.shape(q_g)[0])):
            if on_group is None:
                out = run_route(executor, group.route, q_g, f_g, k=k,
                                ls=ls, max_iters=max_iters, layout=layout,
                                dtype=dtype, introspect=introspect)
                return out[0] if introspect else out
            t0 = time.perf_counter()
            out = run_route(executor, group.route, q_g, f_g, k=k, ls=ls,
                            max_iters=max_iters, layout=layout,
                            dtype=dtype, introspect=introspect)
            res, stats = out if introspect else (out, None)
            res = jax.block_until_ready(res)
            on_group(group, res, stats, time.perf_counter() - t0)
            return res

    if len(pq.groups) == 1:      # no split -> no gather/scatter round-trip
        return _run(pq.groups[0], q, filt)
    parts = []
    for g in pq.groups:
        with span(f"gather:{g.route}", spans, queries=int(g.ids.size)):
            q_g = jnp.take(q, jnp.asarray(g.ids), axis=0)
            f_g = filt.take(g.ids)
        parts.append(_run(g, q_g, f_g))
    with span("scatter", spans, batch=int(q.shape[0])):
        return regroup(parts, pq.groups, q.shape[0])
