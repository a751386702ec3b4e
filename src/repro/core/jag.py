"""Public JAG index API: Threshold-JAG (default) and Weight-JAG (§3.3, §3.4).

Thresholds/weights are specified as *quantiles* of the empirical dist_A
distribution (paper D.3: sample |V|=500 points, take quantiles from
{100%, 10%, 1%, 0.1%, 0%}) and calibrated to absolute values at build time.

Query execution is delegated to the serving pipeline: every ``search*``
entry point is a thin shim over ``serve.Executor`` (the single
jit-compilation cache — this module contains no ``jax.jit`` of its own),
and ``search_auto`` adds the selectivity-adaptive routing on top
(``serve.planner``: prefilter | graph | postfilter, banded per query and
dispatched as route-group sub-batches by ``serve.dispatch``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.spans import span
from .beam_search import SearchResult
from .build import BuildConfig, build_graph
from .distances import dist_a, sq_norms
from .filters import AttrTable, as_filter


@dataclasses.dataclass(frozen=True)
class JAGConfig:
    degree: int = 32
    ls_build: int = 64
    alpha: float = 1.2
    mode: str = "threshold"                    # "threshold" | "weight"
    # quantiles of dist_A; 1.0 -> pure-vector edges, 0.0 -> strict-attribute
    threshold_quantiles: Tuple[float, ...] = (1.0, 0.01, 0.0)
    # weight multipliers of h = sigma_vec / sigma_attr (paper D.3)
    weight_scales: Tuple[float, ...] = (0.0, 1.0)
    batch_size: int = 128
    cand_pool: int = 192
    calib_samples: int = 512
    seed: int = 0
    ex_slots: int = 16
    ov_max: int = 256
    n_seeds: int = 8                           # multi-seed beam init


def calibrate_thresholds(attr: AttrTable, quantiles: Sequence[float],
                         n_samples: int, seed: int) -> Tuple[float, ...]:
    """Absolute dist_A caps at the requested quantiles (paper D.3)."""
    rng = np.random.default_rng(seed)
    n = attr.n
    ia = jnp.asarray(rng.integers(0, n, n_samples), jnp.int32)
    ib = jnp.asarray(rng.integers(0, n, (n_samples, 64)), jnp.int32)
    da = dist_a(attr.kind, attr.gather(ia), attr.gather(ib))
    da = np.asarray(da).reshape(-1)
    out = []
    for q in quantiles:
        if q >= 1.0:
            out.append(float(da.max()) + 1.0)  # cap above max -> pure vector
        else:
            out.append(float(np.quantile(da, q)))
    return tuple(out)


def calibrate_weight_unit(xb, attr: AttrTable, n_samples: int,
                          seed: int) -> float:
    """h = sigma(dist_vec) / sigma(dist_A) over sampled pairs (paper D.3)."""
    rng = np.random.default_rng(seed)
    n = attr.n
    ia = jnp.asarray(rng.integers(0, n, n_samples), jnp.int32)
    ib = jnp.asarray(rng.integers(0, n, (n_samples, 16)), jnp.int32)
    da = np.asarray(dist_a(attr.kind, attr.gather(ia), attr.gather(ib)))
    va = np.asarray(jnp.take(xb, ia, axis=0), dtype=np.float32)
    vb = np.asarray(jnp.take(xb, ib.reshape(-1), axis=0),
                    dtype=np.float32).reshape(n_samples, 16, -1)
    dv = np.sqrt(np.maximum(
        ((va[:, None, :] - vb) ** 2).sum(-1), 0.0))
    sa = float(np.std(da)) or 1.0
    return float(np.std(dv)) / sa


def _encode_cfg(dc) -> np.ndarray:
    """Dataclass -> uint8 repr buffer (npz-safe, allow_pickle=False)."""
    return np.frombuffer(repr(dataclasses.asdict(dc)).encode(), np.uint8)


def _decode_cfg(buf) -> dict:
    """Inverse of :func:`_encode_cfg`.

    ``repr(float('inf'))`` is ``'inf'`` which ``ast.literal_eval`` rejects;
    rewriting the bare token to the overflowing literal ``2e308`` round-trips
    it. Word-bounded, so names/values merely *containing* 'inf' are safe —
    but a string value holding 'inf' as a standalone word would still be
    rewritten: don't introduce one into JAGConfig/BuildConfig.
    """
    import ast
    import re
    txt = re.sub(r"\binf\b", "2e308", bytes(buf).decode())
    return {k: tuple(v) if isinstance(v, list) else v
            for k, v in ast.literal_eval(txt).items()}


class JAGIndex:
    """A built Joint Attribute Graph over (vectors, attributes)."""

    # Data epoch of a frozen index: never changes. The streaming layer
    # (repro.stream.StreamingJAGIndex) shadows this with a live counter so
    # the executor's epoch-aware caches invalidate as the index grows.
    epoch: int = 0

    def __init__(self, xb, attr: AttrTable, graph, degree, entry,
                 cfg: JAGConfig, build_cfg: BuildConfig):
        self.xb = jnp.asarray(xb)
        self.xb_norm = sq_norms(self.xb)
        self.attr = attr
        self.graph = graph
        self.degree = degree
        self.entry = entry
        self.cfg = cfg
        self.build_cfg = build_cfg
        self._executor = None                # serve.Executor, built lazily
        self._fused = {}                     # vec_dtype -> serve.FusedLayout
        self._q8 = None                      # (codes, scale, norms) cache
        self.cost_model = None               # repro.cost.CostModel | None
        self.cost_metric = "us"              # routing objective: us | n_dist
        self.telemetry = None                # repro.obs.Telemetry | None

    # -- construction ------------------------------------------------------
    @classmethod
    def build(cls, xb, attr: AttrTable, cfg: JAGConfig = JAGConfig(),
              verbose: bool = False) -> "JAGIndex":
        xb = jnp.asarray(xb)
        if cfg.mode == "threshold":
            tvals = calibrate_thresholds(attr, cfg.threshold_quantiles,
                                         cfg.calib_samples, cfg.seed)
            wvals = ()
        else:
            h = calibrate_weight_unit(xb, attr, cfg.calib_samples, cfg.seed)
            wvals = tuple(w * h for w in cfg.weight_scales)
            tvals = ()
        bcfg = BuildConfig(
            degree=cfg.degree, ls_build=cfg.ls_build, alpha=cfg.alpha,
            mode=cfg.mode, thresholds=tvals, weights=wvals,
            batch_size=cfg.batch_size, cand_pool=cfg.cand_pool,
            ex_slots=cfg.ex_slots, ov_max=cfg.ov_max)
        from .build import make_seeds
        seeds = make_seeds(xb, cfg.n_seeds, cfg.seed)
        graph, deg, entry = build_graph(xb, attr, bcfg, seed=cfg.seed,
                                        entry=seeds, verbose=verbose)
        return cls(xb, attr, graph, deg, entry, cfg, bcfg)

    # -- serving state (serve/) ---------------------------------------------
    @property
    def executor(self):
        """The index's ``serve.Executor`` — the one jit cache every search
        entry point (and the baselines) compiles through."""
        if self._executor is None:
            from ..serve.executor import Executor
            self._executor = Executor(self)
        return self._executor

    def fused_layout(self, vec_dtype: str = "f32"):
        """Build (once) and return the packed [vec|norm|attr] serving layout.

        The f32 layout reproduces the default path's (dist_F, dist_vec) keys
        bit-for-bit from ONE row gather per beam expansion; the int8 layout
        additionally shrinks the vector lanes to int8 codes (query-side scale
        folding). Cached per dtype; persisted by :meth:`save`.
        """
        if vec_dtype not in self._fused:
            from ..serve import build_layout
            self._fused[vec_dtype] = build_layout(self.xb, self.attr,
                                                  vec_dtype=vec_dtype)
        return self._fused[vec_dtype]

    def quantized(self):
        """(codes int8 [N,d], scale f32 [d], dequantized norms f32 [N]).

        Computed once and cached; persisted by :meth:`save` so a loaded
        index never re-quantizes the database.
        """
        if self._q8 is None:
            from .quantized import quantize_int8
            xq, scale = quantize_int8(self.xb)
            xq_norm = jnp.sum((xq.astype(jnp.float32) * scale) ** 2, -1)
            self._q8 = (xq, scale, xq_norm)
        return self._q8

    def attach_cost_model(self, model, metric: str = "us") -> None:
        """Attach (or detach, with None) a calibrated ``repro.cost``
        CostModel: ``search_auto`` then routes on the argmin of predicted
        per-route cost instead of the static thresholds, and :meth:`save`
        persists the model inside the archive. Purely a routing-policy
        change — each route's results are unchanged.

        ``metric`` picks the routing objective: ``"us"`` (measured wall
        time — the serving default) or ``"n_dist"`` (the paper's
        hardware-independent distance-computation metric, deterministic
        per route and therefore what benchmarks compare on).
        """
        from ..cost.model import METRICS
        if metric not in METRICS:
            raise ValueError(f"metric must be one of {METRICS}, "
                             f"got {metric!r}")
        self.cost_model = model
        self.cost_metric = metric

    def attach_telemetry(self, telemetry=...):
        """Attach (or detach, with None) a ``repro.obs.Telemetry``.

        With telemetry attached, every :meth:`search_auto` call records
        one per-query :class:`~repro.obs.trace.TraceRecord` (band,
        realized route, selectivity, predicted costs, wall-clock us,
        n_dist/n_expanded) into the telemetry's ring buffer and ticks its
        route counters/latency histograms; the executor additionally
        reports jit-cache misses and epoch rolls. All of it happens on
        the host after routes return — compiled programs are unchanged
        (the audit runs with telemetry attached to prove it).

        Called with no argument a default ``Telemetry()`` is created.
        Returns the attached telemetry (None on detach) so
        ``tel = index.attach_telemetry()`` reads naturally.
        """
        if telemetry is ...:
            from ..obs import Telemetry
            telemetry = Telemetry()
        self.telemetry = telemetry
        ex = self.executor
        ex.miss_hook = None if telemetry is None else telemetry.on_executor_miss
        ex.roll_hook = None if telemetry is None else telemetry.on_epoch_roll
        return telemetry

    # -- query (Algorithm 2) ------------------------------------------------
    def search(self, queries, filt, k: int = 10,
               ls: int = 64, max_iters: int = 0,
               layout: str = "default") -> SearchResult:
        """Filtered top-k search under D_F = (dist_F, dist_vec).

        ``filt`` is a filter expression (``Label``/``Range``/``Subset``/
        ``Boolean`` leaves combined with ``&``/``|``/``~``) or a raw
        per-kind ``FilterBatch``; a single-leaf expression normalizes to
        the atomic path bit-identically. ``layout="fused"`` routes beam
        expansions through the packed serving layout (one gather per
        expansion via greedy_search's ``fetch_fn`` hook) and returns
        identical ids/keys to the default two-gather path.
        """
        return self.executor.graph(queries, as_filter(filt), k=k, ls=ls,
                                   max_iters=max_iters or 2 * ls,
                                   layout=layout, dtype="f32")

    def search_int8(self, queries, filt, k: int = 10,
                    ls: int = 64, max_iters: int = 0,
                    layout: str = "default") -> SearchResult:
        """Quantized traversal + exact re-rank (beyond-paper; §Perf).

        Graph navigation uses the int8 database (4x less HBM pull per beam
        expansion); the beam's survivors are re-ranked with full-precision
        distances so the returned top-k ordering is exact w.r.t. the
        traversed set. ``layout="fused"`` additionally packs
        [int8 vec | norm | attr] so navigation costs ONE gather per
        expansion instead of two.
        """
        return self.executor.graph(queries, as_filter(filt), k=k, ls=ls,
                                   max_iters=max_iters or 2 * ls,
                                   layout=layout, dtype="int8")

    def search_unfiltered(self, queries, k: int = 10, ls: int = 64,
                          max_iters: int = 0) -> SearchResult:
        """Pure vector-distance search (used by post-filtering)."""
        return self.executor.unfiltered(queries, k=k, ls=ls,
                                        max_iters=max_iters or 2 * ls)

    def search_auto(self, queries, filt, k: int = 10,
                    ls: int = 64, max_iters: int = 0,
                    planner=None, return_plan: bool = False,
                    mode: str = "per_query", layout: str = "default",
                    dtype: str = "f32"):
        """Selectivity-adaptive search: plan route(s), then execute.

        A sampled ``matches()`` probe estimates filter selectivity and
        routes to the executor's prefilter (masked exact scan), graph
        (JAG traversal), or postfilter (unfiltered + oversample) route —
        see ``serve/planner.py``.

        ``mode="per_query"`` (default) bands each query individually and
        dispatches every route group as its own contiguous sub-batch
        (``serve/dispatch.py``), scattering results back into original
        query order — a mixed-selectivity batch no longer rides the median
        query's route. ``mode="batch"`` keeps the whole-batch median
        routing. ``layout``/``dtype`` select the graph route's serving
        variant (packed fused rows and/or int8 lanes) in either mode.
        ``planner`` overrides the ``PlannerConfig`` thresholds;
        ``return_plan=True`` returns ``(result, plan)`` — a ``PerQueryPlan``
        reporting the per-group decisions, or a whole-batch ``Plan``;
        either plan's ``realized`` field carries the route variant that
        actually executed (e.g. ``graph[fused,int8]``; the streaming
        subclass appends ``+delta`` when the delta segment was merged).

        When a calibrated cost model is attached
        (:meth:`attach_cost_model`), routing decisions come from the
        argmin of predicted per-route cost (``Executor.cost_router``)
        instead of the thresholds; with no model the static behavior is
        reproduced exactly. An explicit ``planner=`` override always wins
        over the cost model — forced-route configs stay forced.
        """
        from ..serve.dispatch import (dispatch_per_query, route_descriptor,
                                      run_route)
        from ..serve.planner import (GroupPlan, PlannerConfig, plan as _plan,
                                     plan_per_query)
        tel = getattr(self, "telemetry", None)
        if tel is not None and not tel.enabled:
            tel = None
        # telemetry tap: dispatch blocks on each group and hands back
        # (group, result, traversal stats, wall seconds) — all host-side,
        # post-execution. introspect serves graph groups through the
        # executor's introspective compilation (bit-identical results,
        # extra device-side counters); spans time the host pipeline.
        timed = [] if tel is not None else None
        on_group = (None if timed is None
                    else lambda g, r, st, s: timed.append((g, r, st, s)))
        introspect = bool(getattr(tel, "introspect", False))
        rec = getattr(tel, "spans", None)
        ex = self.executor
        ex.n_requests += 1

        with span("search_auto", rec, request=ex.n_requests, mode=mode,
                  batch=int(np.shape(queries)[0])):
            filt = as_filter(filt)
            cfg = planner or PlannerConfig()
            mi = max_iters or 2 * ls
            # an explicit planner= override is an explicit routing
            # instruction (e.g. prefilter_max_sel=1.1 forcing the exact
            # scan everywhere) — an attached cost model must never shadow it
            router = (None if planner is not None
                      else ex.cost_router(k=k, ls=ls, filt=filt))
            if mode == "per_query":
                with span("plan", rec):
                    p = plan_per_query(filt, self.attr, cfg, executor=ex,
                                       router=router)
                res = dispatch_per_query(ex, queries, filt, p,
                                         k=k, ls=ls, max_iters=mi,
                                         layout=layout, dtype=dtype,
                                         on_group=on_group,
                                         introspect=introspect, spans=rec)
                p = p._replace(realized=tuple(
                    route_descriptor(r, layout, dtype) for r in p.routes))
            elif mode == "batch":
                with span("plan", rec):
                    p = _plan(filt, self.attr, cfg, executor=ex,
                              router=router)
                with span(f"execute:{p.route}", rec,
                          queries=int(np.shape(queries)[0])):
                    if timed is None:
                        res = run_route(ex, p.route, queries,
                                        filt, k=k, ls=ls, max_iters=mi,
                                        layout=layout, dtype=dtype)
                    else:
                        t0 = time.perf_counter()
                        out = run_route(ex, p.route, queries,
                                        filt, k=k, ls=ls, max_iters=mi,
                                        layout=layout, dtype=dtype,
                                        introspect=introspect)
                        res, stats = out if introspect else (out, None)
                        res = jax.block_until_ready(res)
                        ids = np.arange(np.asarray(p.selectivity).size,
                                        dtype=np.int32)
                        timed.append(
                            (GroupPlan(p.route, ids, p.batch_selectivity),
                             res, stats, time.perf_counter() - t0))
                p = p._replace(
                    realized=route_descriptor(p.route, layout, dtype))
            else:
                raise ValueError(f"mode must be 'per_query' or 'batch', "
                                 f"got {mode!r}")
        if timed:
            tel.record_call(
                self, p,
                [(g.route, route_descriptor(g.route, layout, dtype),
                  g.ids, r, st, s) for (g, r, st, s) in timed],
                k=k, ls=ls, router=router, filt=filt, mode=mode)
            # shadow-oracle audit of the sampled fraction — for a frozen
            # index the served result is final here; a streaming index
            # audits after its delta merge (stream.index.search_auto)
            if tel.shadow is not None and not hasattr(self, "delta_arrays"):
                tel.shadow_audit(self, queries, filt, res, p, k=k)
        return (res, p) if return_plan else res

    # -- multi-device serving (serve/sharded.py) ----------------------------
    def shard(self, n_shards: int, mesh=None):
        """Re-shard this index row-wise across ``n_shards`` devices.

        Returns a ``serve.ShardedJAGIndex`` serving the same rows behind
        the same ``search_auto`` surface; per-shard sub-graphs are rebuilt
        from this index's rows and config (a built graph's edges cross any
        row split, so an honest reshard is a rebuild). Requires N
        divisible by ``n_shards`` and that many visible devices — fake
        them with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
        """
        from ..serve.sharded import shard_index
        return shard_index(self, n_shards, mesh=mesh)

    # -- persistence ---------------------------------------------------------
    def _save_arrays(self) -> dict:
        """The index as a flat npz-ready dict (shared with repro.stream).

        Packed fused rows are stored as raw uint32 bit patterns
        (``packed_bits``) because the attr lanes are uint32 payloads bitcast
        into f32 — a value-level f32 round-trip could canonicalize NaNs and
        corrupt them. The calibrated ``BuildConfig`` and any computed int8
        quantization are included too, so :meth:`load` restores the exact
        build parameters and never re-quantizes.
        """
        extra = {}
        for dt, lay in self._fused.items():
            extra[f"fused_{dt}__packed_bits"] = (
                np.asarray(lay.packed).view(np.uint32))
            extra[f"fused_{dt}__q_scale"] = np.asarray(lay.q_scale)
            extra[f"fused_{dt}__bit_weights"] = np.asarray(lay.bit_weights)
        if self._q8 is not None:
            xq, scale, xq_norm = self._q8
            extra["q8__codes"] = np.asarray(xq)
            extra["q8__scale"] = np.asarray(scale)
            extra["q8__norms"] = np.asarray(xq_norm)
        if self.cost_model is not None:
            from ..cost.registry import to_json
            extra["cost__model"] = np.frombuffer(
                to_json(self.cost_model).encode(), np.uint8)
            extra["cost__metric"] = self.cost_metric
        return dict(
            xb=np.asarray(self.xb), graph=np.asarray(self.graph),
            degree=np.asarray(self.degree), entry=np.asarray(self.entry),
            attr_kind=self.attr.kind, attr_nbits=self.attr.n_bits,
            cfg=_encode_cfg(self.cfg),
            build_cfg=_encode_cfg(self.build_cfg),
            **{f"attr__{k}": np.asarray(v)
               for k, v in self.attr.data.items()},
            **extra)

    def save(self, path: str) -> None:
        """Persist the index; built serving state rides along losslessly."""
        np.savez_compressed(path, **self._save_arrays())

    @classmethod
    def _from_npz(cls, z) -> "JAGIndex":
        """Rebuild an index from a loaded npz mapping (shared with load and
        the streaming archive format, which adds ``stream__*`` keys)."""
        cfg = JAGConfig(**_decode_cfg(z["cfg"]))
        # archives predating the build_cfg fix fall back to defaults
        bcfg = (BuildConfig(**_decode_cfg(z["build_cfg"]))
                if "build_cfg" in z else BuildConfig())
        attr = AttrTable(str(z["attr_kind"]),
                         {k[len("attr__"):]: jnp.asarray(v)
                          for k, v in z.items() if k.startswith("attr__")},
                         n_bits=int(z["attr_nbits"]))
        idx = cls(jnp.asarray(z["xb"]), attr, jnp.asarray(z["graph"]),
                  jnp.asarray(z["degree"]), jnp.asarray(z["entry"]),
                  cfg, bcfg)
        from ..serve import FusedLayout
        for dt in ("f32", "int8"):
            key = f"fused_{dt}__packed_bits"
            if key in z:
                idx._fused[dt] = FusedLayout(
                    jnp.asarray(z[key].view(np.float32)),
                    jnp.asarray(z[f"fused_{dt}__q_scale"]),
                    jnp.asarray(z[f"fused_{dt}__bit_weights"]),
                    attr.kind, attr.n_bits, int(z["xb"].shape[1]), dt)
        if "q8__codes" in z:
            idx._q8 = (jnp.asarray(z["q8__codes"]),
                       jnp.asarray(z["q8__scale"]),
                       jnp.asarray(z["q8__norms"]))
        if "cost__model" in z:
            from ..cost.registry import from_json
            idx.cost_model = from_json(bytes(z["cost__model"]).decode())
            if "cost__metric" in z:
                idx.cost_metric = str(z["cost__metric"])
        return idx

    @classmethod
    def load(cls, path: str) -> "JAGIndex":
        return cls._from_npz(np.load(path, allow_pickle=False))

    # -- stats ---------------------------------------------------------------
    def degree_stats(self):
        d = np.asarray(jnp.sum(self.graph >= 0, axis=1))
        return dict(mean=float(d.mean()), max=int(d.max()),
                    min=int(d.min()),
                    over_budget=int((d > self.cfg.degree).sum()))
