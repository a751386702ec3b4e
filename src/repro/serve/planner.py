"""Selectivity-adaptive query planner: request -> plan -> execute.

JAG's headline claim is robust performance across the entire selectivity
spectrum, but no single execution strategy wins every band (FAVOR,
arXiv:2605.07770; the CUHK experimental study, arXiv:2508.16263): at very
low selectivity an exact masked scan touches fewer points than any graph
walk, and near selectivity 1.0 an unfiltered traversal plus oversampled
filtering matches the filtered walk at lower comparator cost. This module
estimates filter selectivity with a sampled ``matches()`` probe
(jit-compatible, all four filter kinds) and routes to one of the
executor's three routes:

    sel <= prefilter_max_sel   -> "prefilter"   (masked exact scan)
    sel >= postfilter_min_sel  -> "postfilter"  (unfiltered + oversample)
    otherwise                  -> "graph"       (JAG traversal)

Two planning granularities share the probe:

  * :func:`plan` — whole-batch: one route chosen by the *median* estimate
    (``JAGIndex.search_auto(mode="batch")``).
  * :func:`plan_per_query` — the per-query router: bands the [B]
    selectivity vector query-by-query and groups queries by route, so a
    batch mixing 0.1% and 90% filters no longer drags half its queries
    down the wrong path. ``serve/dispatch.py`` gathers each group (queries
    AND filter lanes) into a contiguous sub-batch, runs it through its
    route, and scatters the results back into original query order.

``JAGIndex.search_auto`` is the end-to-end entry point (default
``mode="per_query"``); the static thresholds live in ``PlannerConfig``.
When the index carries a calibrated cost model (``repro.cost``,
``JAGIndex.attach_cost_model``), both planners take a ``router``
(``cost.CostModelRouter``, built per call by ``Executor.cost_router``)
and the threshold ladder is replaced by an argmin over measured-cost
predictions per route — the static thresholds remain the exact fallback
whenever no model is attached or it doesn't cover the base routes.

Compound filters: a FilterExpr tree (core.filters And/Or/Not over the four
atomic leaves) plans exactly like an atomic filter — the probe evaluates
the WHOLE tree on the sampled rows, so the estimate is the joint
selectivity, not an independence composition. Correlated clauses (a label
that implies a range band, a subset mask nested inside the boolean
predicate it encodes) used to be composed as if independent — a
``label & range`` whose clauses coincide was estimated at sel² and
mis-routed to the exact scan; the joint probe costs the same one gather
(every leaf is evaluated on the same rows either way) and is exact on the
sample. Routing — static thresholds or cost-model argmin — stays a
per-query decision over one joint [B] selectivity vector. The prefilter
route additionally asks :func:`reorder_clauses` for the short-circuit-
optimal clause order (cheapest most-selective first, conditioned on the
clauses already placed — :func:`leaf_validity` hands it the per-leaf
boolean vectors, so the ordering also sees the correlations).

Streaming: both planners probe whatever attribute table they are handed —
``StreamingJAGIndex.search_auto`` passes the live base+delta table, so the
selectivity estimate tracks inserted rows immediately. The probe's device
buffers and compilation live in the executor's epoch-aware caches
(``Executor.sample_ids`` / ``Executor.run``): an insert bumps the index
epoch and evicts them, so routing can never consult a stale-n sample.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..core.filters import (AttrTable, FilterBatch, FilterExpr, Leaf, And,
                            Or, Not, _broadcast_rows, describe, matches,
                            matches_sampled)
from ..obs.spans import span

ROUTES = ("prefilter", "graph", "postfilter")


@dataclasses.dataclass(frozen=True)
class PlannerConfig:
    n_samples: int = 1024          # attr rows probed per selectivity estimate
    prefilter_max_sel: float = 0.02
    postfilter_min_sel: float = 0.75
    seed: int = 0                  # sample draw (deterministic per planner)

    def __post_init__(self):
        # inverted thresholds would silently route the whole (0, 1] band
        # to prefilter-or-postfilter with the graph band empty or
        # ill-defined — refuse at construction, where the typo is.
        # Values past 1.0 are legal on purpose: prefilter_max_sel=1.1
        # (with postfilter_min_sel above it) forces the exact scan
        # everywhere, which tests and ground-truth tooling rely on.
        if self.n_samples < 1:
            raise ValueError(f"n_samples must be >= 1, "
                             f"got {self.n_samples}")
        if self.prefilter_max_sel < 0.0:
            raise ValueError(f"prefilter_max_sel must be >= 0, "
                             f"got {self.prefilter_max_sel}")
        if self.prefilter_max_sel >= self.postfilter_min_sel:
            raise ValueError(
                f"inverted thresholds: prefilter_max_sel "
                f"{self.prefilter_max_sel} >= postfilter_min_sel "
                f"{self.postfilter_min_sel} (the graph band would be "
                f"empty and the ladder order-dependent)")


class Plan(NamedTuple):
    """A whole-batch routing decision."""
    route: str                 # one of ROUTES
    selectivity: np.ndarray    # f32 [B] per-query estimates
    batch_selectivity: float   # the median driving the route choice
    n_sampled: int             # probe size actually used (== n for exact)
    # predicted cost/query per route at the batch median when a cost-model
    # router made the decision (in cost_metric units); None under the
    # static thresholds
    costs: Optional[Dict[str, float]] = None
    cost_metric: Optional[str] = None    # "us" | "n_dist" | None (static)
    # the route variant that actually executed (``search_auto`` stamps it
    # post-dispatch): a dispatch.route_descriptor string, e.g.
    # "graph[fused,int8]" or "prefilter+delta". None when the plan never
    # ran (planner-only construction).
    realized: Optional[str] = None


class GroupPlan(NamedTuple):
    """One route group of a per-query plan."""
    route: str                 # one of ROUTES
    ids: np.ndarray            # int32 [G] positions in the original batch
    selectivity: float         # median estimate within the group


class PerQueryPlan(NamedTuple):
    """Per-query routing decisions for one batch.

    ``routes[b]`` is query b's route; ``groups`` lists the non-empty route
    groups in ROUTES order, each with the original-batch positions the
    dispatcher gathers/scatters by. ``route``/``batch_selectivity``
    properties mirror the whole-batch :class:`Plan` so logging and
    benchmarks can treat either plan flavor uniformly.
    """
    routes: Tuple[str, ...]    # per-query route, len B
    selectivity: np.ndarray    # f32 [B] per-query estimates
    groups: Tuple[GroupPlan, ...]
    n_sampled: int
    # predicted cost/query per route at the batch median when a cost-model
    # router banded the queries (in cost_metric units); None under the
    # static thresholds
    costs: Optional[Dict[str, float]] = None
    cost_metric: Optional[str] = None    # "us" | "n_dist" | None (static)
    # per-query realized route descriptors (len B), stamped by
    # ``search_auto`` after dispatch so traces/explain agree with what
    # executed. None when the plan never ran.
    realized: Optional[Tuple[str, ...]] = None

    @property
    def route(self) -> str:
        """The single route when the batch didn't split, else "mixed"."""
        return self.groups[0].route if len(self.groups) == 1 else "mixed"

    @property
    def batch_selectivity(self) -> float:
        return float(np.median(self.selectivity))


def sample_ids(n: int, n_samples: int, seed: int = 0) -> jnp.ndarray:
    """Deterministic sample of attr-table rows; exact (arange) if it fits.

    Deliberately NOT memoized at module level: an ``lru_cache`` here would
    pin JAX device buffers process-wide across index lifetimes and test
    runs. The serving hot path goes through ``Executor.sample_ids``, which
    scopes the cached device arrays to one index's executor.
    """
    if n_samples >= n:
        return jnp.arange(n, dtype=jnp.int32)
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.choice(n, n_samples, replace=False), jnp.int32)


def estimate_selectivity(filt, table: AttrTable,
                         ids: jnp.ndarray) -> jnp.ndarray:
    """Per-query selectivity estimate f32[B] from a sampled matches() probe.

    Pure jnp on registered pytrees, so it traces under ``jax.jit`` for every
    filter kind; the executor caches one compilation per (kind, |sample|) —
    an expression's structural ``kind`` signature keys compound probes the
    same way. Compound estimates evaluate the WHOLE tree on the probe rows,
    so they are JOINT: correlated clauses (a label implying a range band)
    estimate at their true co-occurrence rate, where an independence
    composition of per-leaf means can be off by the full correlation
    factor. Atomic filters keep the identical matches_sampled probe.
    """
    if isinstance(filt, FilterBatch):
        ok = matches_sampled(filt, table, ids)
        return jnp.mean(ok.astype(jnp.float32), axis=-1)
    attrs = _broadcast_rows(table, jnp.asarray(ids, jnp.int32))
    return jnp.mean(matches(filt, attrs).astype(jnp.float32), axis=-1)


def leaf_selectivities(filt, table: AttrTable,
                       ids: jnp.ndarray) -> jnp.ndarray:
    """Per-leaf sampled selectivities f32[L, B], leaves in DFS order.

    One gather of the sample rows feeds every leaf's matches() mean.
    Marginal summaries only — the clause reorderer now probes
    :func:`leaf_validity` so it can see joint structure; this stays the
    cheap per-leaf report for benchmarks and explain-style logging.
    """
    ids = jnp.asarray(ids, jnp.int32)
    attrs = _broadcast_rows(table, ids)
    leaves = filt.leaves() if isinstance(filt, FilterExpr) else [filt]
    return jnp.stack(
        [jnp.mean(matches(f, attrs).astype(jnp.float32), axis=-1)
         for f in leaves])


def leaf_validity(filt, table: AttrTable, ids: jnp.ndarray) -> jnp.ndarray:
    """Per-leaf boolean validity bool[L, B, S] on the probe rows (DFS order).

    The raw material :func:`reorder_clauses` composes internal-node
    selectivities from WITHOUT the independence assumption: every leaf is
    evaluated on the same S sampled rows, so any And/Or node's joint
    validity is just the boolean combination of its children's vectors.
    """
    ids = jnp.asarray(ids, jnp.int32)
    attrs = _broadcast_rows(table, ids)
    leaves = filt.leaves() if isinstance(filt, FilterExpr) else [filt]
    return jnp.stack([matches(f, attrs) for f in leaves])


def _leaf_values(leaf_sels):
    """Normalize reorder inputs: scalars (independence mode) or per-leaf
    boolean arrays such as ``leaf_validity`` rows (joint mode). A mixed
    list degrades every vector to its mean so one mode runs uniformly."""
    out = [np.asarray(v) for v in leaf_sels]
    if any(a.ndim == 0 for a in out):
        return [float(a) if a.ndim == 0 else float(np.mean(a)) for a in out]
    return [a.astype(bool) for a in out]


def _frac(v) -> float:
    """Mass of a validity value: the mean of a boolean vector, or the
    scalar probability itself."""
    return float(np.mean(v)) if isinstance(v, np.ndarray) else float(v)


def _vand(a, b):
    """Conjunction of two validity values (boolean AND, or the
    independence product for scalars)."""
    if isinstance(a, np.ndarray) and isinstance(b, np.ndarray):
        return a & b
    return a * b


def _vnot(v):
    return ~v if isinstance(v, np.ndarray) else 1.0 - v


def _vtrue(like):
    return (np.ones_like(like, dtype=bool)
            if isinstance(like, np.ndarray) else 1.0)


def _order_clauses(filt, leaf_iter, reorder: bool):
    """Recursive (expr, validity, expected_evals_per_point).

    ``validity`` is either a scalar probability (legacy independence mode)
    or a boolean sample vector (joint mode): an internal node's vector is
    the boolean combination of its children's, so selectivities and
    short-circuit live-mass estimates reflect clause correlations exactly
    (on the sample). Ordering is greedy conditional: each next clause is
    the one with the best cost per unit of conditional filtering power
    GIVEN the clauses already placed — which reduces to the classic
    cost/(1-sel) (And) and cost/sel (Or) static sort when clauses are
    independent scalars.
    """
    if isinstance(filt, FilterBatch):
        return filt, next(leaf_iter), 1.0
    if isinstance(filt, Leaf):
        f, v, c = _order_clauses(filt.filt, leaf_iter, reorder)
        return Leaf(f), v, c
    if isinstance(filt, Not):
        ch, v, c = _order_clauses(filt.child, leaf_iter, reorder)
        return Not(ch), _vnot(v), c
    if isinstance(filt, (And, Or)):
        kids = [_order_clauses(c, leaf_iter, reorder)
                for c in filt.children]
        is_and = isinstance(filt, And)
        if reorder:
            ordered, live = [], _vtrue(kids[0][1])
            while kids:
                lm = _frac(live)

                def rank(t):
                    inter = _frac(_vand(live, t[1]))
                    # And: cost per conditionally-killed mass; Or: cost
                    # per conditionally-accepted mass. min() keeps the
                    # first of rank-tied clauses (written order, like the
                    # stable sort it replaces).
                    power = (lm - inter) if is_and else inter
                    return t[2] / max(power, 1e-9)

                i = min(range(len(kids)), key=lambda j: rank(kids[j]))
                t = kids.pop(i)
                ordered.append(t)
                live = _vand(live, t[1] if is_and else _vnot(t[1]))
            kids = ordered
        live, cost = _vtrue(kids[0][1]), 0.0
        for _, v, c in kids:
            cost += _frac(live) * c
            live = _vand(live, v if is_and else _vnot(v))
        val = live if is_and else _vnot(live)
        node = (And if is_and else Or)(*[k[0] for k in kids])
        return node, val, cost
    raise TypeError(f"not a filter: {type(filt)!r}")


def reorder_clauses(filt, leaf_sels):
    """Short-circuit-optimal clause order, cheapest-most-selective first.

    ``leaf_sels``: one value per leaf in DFS order — either scalar
    selectivities (e.g. the medians of :func:`leaf_selectivities`;
    composed under independence) or per-leaf boolean sample vectors (the
    rows of :func:`leaf_validity`; composed JOINTLY, so correlated
    clauses order by their true conditional filtering power). And children
    greedily take the best cost-per-killed-mass next, Or children the best
    cost-per-accepted-mass, each conditioned on the clauses already
    placed; subtree costs are expected short-circuit evals per point, so
    nesting composes. Boolean connectives commute, so the reordered tree
    is result-identical — only ``n_feval`` changes. Atomic filters pass
    through unchanged.
    """
    if not isinstance(filt, FilterExpr):
        return filt
    return _order_clauses(filt, iter(_leaf_values(leaf_sels)), True)[0]


def clause_eval_cost(filt, leaf_sels) -> float:
    """Expected short-circuit leaf evals per scanned point, given the
    tree's CURRENT clause order and per-leaf selectivities or validity
    vectors (DFS order; scalar = independence, boolean vector = joint)."""
    return _order_clauses(filt, iter(_leaf_values(leaf_sels)), False)[2]


def choose_route(sel: float, cfg: PlannerConfig) -> str:
    """Threshold router over one selectivity scalar (the static fallback;
    a calibrated ``cost.CostModelRouter`` replaces this ladder with an
    argmin over predicted per-route cost)."""
    if sel <= cfg.prefilter_max_sel:
        return "prefilter"
    if sel >= cfg.postfilter_min_sel:
        return "postfilter"
    return "graph"


def _route_of(sel: float, cfg: PlannerConfig, router) -> str:
    """One query's route: cost-model argmin when a router is attached,
    else the static threshold ladder."""
    return router.route(sel) if router is not None else choose_route(sel,
                                                                     cfg)


def _estimate(filt, table: AttrTable, cfg: PlannerConfig,
              executor) -> Tuple[np.ndarray, int]:
    """Shared probe: host f32[B] estimates + the probe size used."""
    with span("plan.probe"):
        if executor is not None:
            ids = executor.sample_ids(table.n, cfg.n_samples, cfg.seed)
        else:
            ids = sample_ids(table.n, cfg.n_samples, cfg.seed)
        n_sampled = int(ids.shape[0])
        if executor is not None:
            key = ("estimate", "default", "f32", 0, 0, 0, filt.kind,
                   n_sampled)
            est = executor.run(key, lambda: estimate_selectivity,
                               filt, table, ids)
        else:
            est = estimate_selectivity(filt, table, ids)
        with span("sync:planner"):
            est = np.asarray(est, np.float32)
    return est, n_sampled


def plan(filt, table: AttrTable,
         cfg: PlannerConfig = PlannerConfig(),
         executor=None, router=None) -> Plan:
    """Estimate the batch's selectivity and pick ONE route for all queries.

    When ``executor`` is given, the probe's compilation lives in the
    executor's single jit cache (keyed like every route); otherwise the
    estimate runs as a one-off traced call. When ``router`` (a calibrated
    ``cost.CostModelRouter``) is given, the route is the argmin of
    predicted per-route cost at the batch median instead of the static
    threshold ladder, and ``Plan.costs`` reports those predictions.
    """
    sel, n_sampled = _estimate(filt, table, cfg, executor)
    batch_sel = float(np.median(sel))
    if router is None:
        return Plan(_route_of(batch_sel, cfg, None), sel, batch_sel,
                    n_sampled)
    return Plan(router.route(batch_sel), sel, batch_sel, n_sampled,
                router.costs(batch_sel), router.metric)


def plan_per_query(filt, table: AttrTable,
                   cfg: PlannerConfig = PlannerConfig(),
                   executor=None, router=None) -> PerQueryPlan:
    """Band the per-query selectivity vector into route groups.

    Same probe as :func:`plan`; the [B] estimates are banded query-by-query
    and grouped by route (positions kept in ascending order so the
    dispatcher's gather/scatter is a stable permutation). With a ``router``
    attached, each query's band is the argmin of its predicted per-route
    cost instead of the static thresholds.
    """
    sel, n_sampled = _estimate(filt, table, cfg, executor)
    with span("plan.band"):
        routes = tuple(_route_of(float(s), cfg, router) for s in sel)
        routes_arr = np.asarray(routes)
        groups = []
        for route in ROUTES:
            members = np.flatnonzero(routes_arr == route)
            if members.size:
                groups.append(GroupPlan(route, members.astype(np.int32),
                                        float(np.median(sel[members]))))
        batch_sel = float(np.median(sel))
    if router is None:
        return PerQueryPlan(routes, sel, tuple(groups), n_sampled)
    return PerQueryPlan(routes, sel, tuple(groups), n_sampled,
                        router.costs(batch_sel), router.metric)


def _executed_note(p) -> str:
    """Realized-route summary when it differs from the planned band names.

    Empty when the plan never ran (``realized is None``) or execution was
    exactly the planned route (default layout, no delta) — ``explain``
    stays byte-stable for every pre-existing call site.
    """
    realized = getattr(p, "realized", None)
    if realized is None:
        return ""
    if isinstance(realized, str):
        return "" if realized == p.route else realized
    if tuple(realized) == tuple(getattr(p, "routes", ())):
        return ""
    counts: Dict[str, int] = {}
    for name in realized:
        counts[name] = counts.get(name, 0) + 1
    return " ".join(f"{name}:{c}" for name, c in counts.items())


def explain(p, cfg: PlannerConfig = PlannerConfig(), filt=None) -> str:
    """One-line human-readable routing rationale (benchmarks / logs).

    Pass the planned ``filt`` to prepend the filter expression, e.g.
    ``filter=(label=3 & range[0,0.5])``. Plans returned by
    ``search_auto(return_plan=True)`` carry the realized per-query route
    (serving variant / delta suffix included); when that differs from the
    planned band names, an ``executed[...]`` summary is appended.
    """
    head = f"route={p.route} sel~{p.batch_selectivity:.4f}"
    if filt is not None:
        head = f"filter={describe(filt)} {head}"
    if isinstance(p, PerQueryPlan):
        split = " ".join(f"{g.route}:{g.ids.size}" for g in p.groups)
        head += f" [{split}]"
    executed = _executed_note(p)
    if executed:
        head += f" executed[{executed}]"
    if p.costs is not None:
        unit = {"us": "us", "n_dist": "DC"}.get(p.cost_metric,
                                                p.cost_metric or "")
        pred = " ".join(f"{r}={c:.1f}{unit}" for r, c in p.costs.items())
        return f"{head} (n_sampled={p.n_sampled}, cost-model argmin: {pred})"
    lo, hi = cfg.prefilter_max_sel, cfg.postfilter_min_sel
    return (f"{head} (n_sampled={p.n_sampled}, thresholds: "
            f"prefilter<={lo}, postfilter>={hi})")
