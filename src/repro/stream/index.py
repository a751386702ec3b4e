"""StreamingJAGIndex: a mutable index layer over the frozen JAG graph.

Architecture (base + delta + epoch, redisvl-style index lifecycle):

  * **base** — a built, frozen :class:`~repro.core.jag.JAGIndex`. Its graph,
    vectors, and serving layouts never mutate in place.
  * **delta** — a :class:`~repro.stream.delta.DeltaSegment`: vectors + attr
    rows appended in O(1) amortized batches, searched exactly by the
    executor's brute-force ``delta`` route (ids offset past the base).
  * **epoch** — a monotonic counter bumped by every insert batch and every
    compaction. The executor's caches (compiled routes, planner sample
    buffers, fused engines) are keyed by it, so serving state can never
    outlive the data it was built against, and the planner's selectivity
    probe always samples the LIVE base+delta attribute table.

Every search merges the base result (any planner route over the graph
segment) with the delta scan into one exact top-k per query
(``serve.dispatch.merge_topk``) — with an exact base route the result is
bit-identical to brute-force filtered k-NN over the concatenated database.
Compaction triggering is cost-driven when a calibrated ``repro.cost``
model is attached (:meth:`attach_cost_model`, or loaded with the
archive): the delta scan is a tax EVERY search pays, so the index
compacts at the break-even point where the predicted tax over the next
``query_horizon`` searches exceeds the predicted total compaction cost.
With no model the static ``compact_frac * base_n`` row-count cutoff is
the exact fallback. Either way :meth:`compact` re-runs the build's
batch-insert primitive (core/build.py, Algorithm 3) to fold the delta
rows into the graph, extends the fused f32 serving layout row-wise,
resets the delta, and bumps the epoch. ``save``/``load`` persist the
delta segment, epoch, and cost model alongside the base archive, so a
restarted server resumes mid-stream bit-for-bit.
"""
from __future__ import annotations

from contextlib import nullcontext
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..core.beam_search import SearchResult
from ..core.build import finalize_graph, make_insert_step
from ..core.distances import sq_norms
from ..core.filters import AttrTable, as_filter
from ..core.jag import JAGConfig, JAGIndex
from ..obs.spans import span
from .delta import DeltaSegment


class StreamingJAGIndex:
    """A live (insertable) view over a frozen JAGIndex + delta segment.

    Mirrors the executor-facing surface of :class:`JAGIndex` (``graph``,
    ``xb``, ``attr``, ``entry``, ``fused_layout``, ...), so
    ``serve.Executor`` runs its routes over the graph segment unchanged —
    except that ``attr`` is the MERGED base+delta table (identical rows for
    base ids; the planner's probe sees inserted rows immediately).
    """

    def __init__(self, base: JAGIndex, delta: Optional[DeltaSegment] = None,
                 *, epoch: int = 0, compact_frac: float = 0.25,
                 n_compactions: int = 0, query_horizon: int = 100_000):
        self.base = base
        self.delta = delta if delta is not None else DeltaSegment.for_table(
            base.attr, int(base.xb.shape[1]))
        self.epoch = int(epoch)
        self.compact_frac = float(compact_frac)
        self.n_compactions = int(n_compactions)
        # cost-driven compaction: the model lives on the WRAPPER (compaction
        # replaces .base with a fresh index, which would drop it), seeded
        # from whatever the base archive carried
        self.cost_model = base.cost_model
        self.cost_metric = base.cost_metric
        # telemetry lives on the WRAPPER too (same compaction-survival
        # argument) and hooks into the wrapper's epoch-aware executor
        self.telemetry = None
        self.query_horizon = int(query_horizon)
        self.delta_tax_us = 0.0      # predicted delta-scan us served so far
        self._last_k = 10            # most recent served k (merge-tax term)
        self._executor = None
        self._merged: Optional[Tuple[int, AttrTable]] = None

    @classmethod
    def build(cls, xb, attr: AttrTable, cfg: JAGConfig = JAGConfig(), *,
              compact_frac: float = 0.25, query_horizon: int = 100_000,
              verbose: bool = False) -> "StreamingJAGIndex":
        """Build the base graph, then serve it live."""
        return cls(JAGIndex.build(xb, attr, cfg, verbose=verbose),
                   compact_frac=compact_frac, query_horizon=query_horizon)

    # -- executor-facing surface (graph segment + live attr table) ---------
    @property
    def xb(self):
        return self.base.xb

    @property
    def xb_norm(self):
        return self.base.xb_norm

    @property
    def graph(self):
        return self.base.graph

    @property
    def degree(self):
        return self.base.degree

    @property
    def entry(self):
        return self.base.entry

    @property
    def cfg(self):
        return self.base.cfg

    @property
    def build_cfg(self):
        return self.base.build_cfg

    @property
    def attr(self) -> AttrTable:
        """The LIVE attribute table: base rows then delta rows.

        Cached per epoch. Base ids index identical rows, so graph-segment
        routes gather the same attributes they would from the frozen table;
        the planner's selectivity probe samples over all ``n`` live rows.
        """
        if self.delta.n == 0:
            return self.base.attr
        if self._merged is None or self._merged[0] != self.epoch:
            _, dattr = self.delta.device()
            self._merged = (self.epoch, self.base.attr.append(dattr))
        return self._merged[1]

    @property
    def n(self) -> int:
        return int(self.base.xb.shape[0]) + self.delta.n

    def fused_layout(self, vec_dtype: str = "f32"):
        return self.base.fused_layout(vec_dtype)

    def quantized(self):
        return self.base.quantized()

    @property
    def executor(self):
        """This index's epoch-aware ``serve.Executor`` (NOT the base's: it
        must see the live attr table and the streaming epoch)."""
        if self._executor is None:
            from ..serve.executor import Executor
            self._executor = Executor(self)
        return self._executor

    def delta_arrays(self) -> Tuple[jnp.ndarray, AttrTable, int]:
        """(delta vectors, delta attr table, id offset) for the delta route."""
        xv, dattr = self.delta.device()
        return xv, dattr, int(self.base.xb.shape[0])

    # -- cost-model plumbing (routing + compaction break-even) -------------
    def attach_cost_model(self, model, metric: str = "us") -> None:
        """Attach (or detach, with None) a calibrated ``repro.cost`` model:
        ``search_auto`` routes on predicted-cost argmin (under ``metric``,
        see ``JAGIndex.attach_cost_model``) and compaction fires on the
        delta-tax break-even instead of ``compact_frac``. Sets the
        WRAPPER's model (validation shared with the base method) — the
        base index is untouched, so compaction can't drop it."""
        JAGIndex.attach_cost_model(self, model, metric)

    def attach_telemetry(self, telemetry=...):
        """Attach (or detach) serving telemetry on the WRAPPER's executor
        (the streaming epoch and jit caches live there) — see
        ``JAGIndex.attach_telemetry``. The streaming-only signals (epoch
        rolls, compactions, delta-scan fraction) tick the same registry.
        """
        return JAGIndex.attach_telemetry(self, telemetry)

    def compaction_break_even(self, k: Optional[int] = None
                              ) -> Optional[Tuple[float, float, bool]]:
        """(delta tax us/query, compaction total us, past break-even) under
        the attached cost model, or None when uncalibrated.

        The delta scan (+ merge) is a constant tax EVERY search pays; the
        predicted tax over the next ``query_horizon`` searches against the
        predicted one-off compaction cost is the row-count-free trigger —
        a slow-compacting build tolerates a bigger delta, a hot query
        stream compacts sooner, with no hand-tuned fraction anywhere.
        ``k`` sizes the merge term of the tax; it defaults to the most
        recently served k (searches record it), so the insert-time trigger
        reasons about the traffic actually being served.
        """
        model = self.cost_model
        if model is None or not model.covers(("delta", "compact")):
            return None
        if self.delta.n == 0:
            return (0.0, 0.0, False)
        from ..cost.model import delta_scan_tax
        n, d = int(self.base.xb.shape[0]), int(self.base.xb.shape[1])
        tax = delta_scan_tax(model, n=n, d=d,
                             k=self._last_k if k is None else int(k),
                             delta_n=self.delta.n)
        cost = model.predict("compact",
                             dict(delta_n=self.delta.n, n=n, d=d))
        return (tax, cost, tax * self.query_horizon >= cost)

    def _should_compact(self) -> bool:
        """Cost break-even when calibrated; ``compact_frac`` fallback.

        ``compact_frac <= 0`` is the explicit auto-compaction OFF switch
        and wins over everything — a calibrated model must not start
        firing multi-second compactions mid-bulk-load on an index whose
        owner disabled them.
        """
        if self.compact_frac <= 0:
            return False
        be = self.compaction_break_even()
        if be is not None:
            return be[2]
        return self.delta.n > self.compact_frac * self.base.xb.shape[0]

    # -- streaming writes --------------------------------------------------
    def insert(self, vectors, attrs: AttrTable, *,
               auto_compact: bool = True) -> dict:
        """Append a batch of (vectors, attr rows); bumps the epoch.

        Amortized O(batch): rows land in the delta segment's growable host
        buffers; no graph work happens until compaction. With
        ``auto_compact`` on, the batch triggers :meth:`compact` before
        returning when the compaction policy says so — the cost-model
        break-even when calibrated, the static ``compact_frac`` row-count
        cutoff otherwise. Returns a report dict (n_added / n_total /
        epoch / compacted).
        """
        n_added = np.asarray(vectors).shape[0]
        self.delta.append(vectors, attrs)
        self.epoch += 1
        compacted = False
        if auto_compact and self._should_compact():
            compacted = self.compact()
        return dict(n_added=int(n_added), n_total=self.n, epoch=self.epoch,
                    delta_rows=self.delta.n, compacted=compacted)

    def compact(self, verbose: bool = False) -> bool:
        """Fold the delta segment into the graph; reset delta, bump epoch.

        Re-runs the build's batch-insert primitive (Algorithm 3) over ONLY
        the delta ids — ``build_cfg.n_passes`` passes, same BuildConfig the
        base was calibrated with (re-insertion passes are dedup-safe; the
        second pass is what closes the recall gap to a from-scratch
        rebuild) — then drains the overflow backlog. Ids are stable: base rows
        keep their ids and delta row j becomes id ``base_n + j``, exactly
        the ids the merged search already returned, so results are
        comparable across a compaction. The fused f32 serving layout
        extends row-wise (``serve.layout.extend_layout``) instead of
        re-packing the base; int8 state is rebuilt lazily on next use
        (its quantization scale is global).
        """
        if self.delta.n == 0:
            return False
        base = self.base
        bcfg = base.build_cfg
        if bcfg.row_width != int(base.graph.shape[1]):
            # a legacy archive (no build_cfg key) loads with DEFAULT build
            # params; folding rows with the wrong degree/row width would
            # corrupt the graph, so refuse loudly — insert/search still work
            raise ValueError(
                f"build_cfg.row_width {bcfg.row_width} != graph row width "
                f"{int(base.graph.shape[1])} (legacy archive loaded with "
                f"default BuildConfig?) — cannot compact; rebuild the base "
                f"index or save a modern archive")
        with span("compact", rows=self.delta.n):
            self.base = self._fold(verbose)
        self.delta.reset()
        self._merged = None
        self.epoch += 1
        self.n_compactions += 1
        if self.telemetry is not None and self.telemetry.enabled:
            self.telemetry.on_compaction()
        return True

    def _fold(self, verbose: bool) -> JAGIndex:
        """The compacted base: the delta rows appended, every insert pass
        over their ids, the overflow re-prune, the f32 layout extended."""
        base = self.base
        bcfg = base.build_cfg
        with span("compact.prepare"):
            xv, dattr = self.delta.device()
            xb_new = jnp.concatenate([jnp.asarray(base.xb), xv], axis=0)
            attr_new = base.attr.append(dattr)
            xb_norm = sq_norms(xb_new)
            n0, m = int(base.xb.shape[0]), self.delta.n
            graph = jnp.concatenate(
                [base.graph,
                 jnp.full((m, bcfg.row_width), -1, jnp.int32)], axis=0)
            degree = jnp.concatenate(
                [jnp.asarray(base.degree, jnp.int32),
                 jnp.zeros((m,), jnp.int32)], axis=0)
        insert = make_insert_step(bcfg)
        bsz = bcfg.batch_size
        new_ids = np.arange(n0, n0 + m, dtype=np.int64)
        n_batches = (m + bsz - 1) // bsz
        for pass_i in range(bcfg.n_passes):
            for i in range(n_batches):
                ids = new_ids[i * bsz:(i + 1) * bsz]
                if len(ids) < bsz:  # pad final batch cyclically (dup-safe)
                    ids = np.resize(ids, bsz)
                # the first call traces the insert program made above
                first = pass_i == 0 and i == 0
                with span("compact.insert", **{"pass": pass_i, "batch": i}), \
                        (span("jit:insert") if first else nullcontext()):
                    graph, degree = insert(graph, degree, xb_new, xb_norm,
                                           attr_new,
                                           jnp.asarray(ids, jnp.int32),
                                           base.entry)
                if verbose:
                    print(f"  compaction pass {pass_i + 1}/{bcfg.n_passes} "
                          f"batch {i + 1}/{n_batches}")
            with span("compact.finalize"):
                graph, degree = finalize_graph(graph, degree, xb_new,
                                               xb_norm, attr_new, bcfg)
        new_base = JAGIndex(xb_new, attr_new, graph, degree, base.entry,
                            base.cfg, bcfg)
        if "f32" in base._fused:
            from ..serve.layout import extend_layout
            new_base._fused["f32"] = extend_layout(base._fused["f32"],
                                                   xv, dattr)
        return new_base

    # -- queries (base route + delta scan, merged exactly) -----------------
    def _spans(self):
        """The attached telemetry's span recorder, if any (host-side)."""
        tel = self.telemetry
        if tel is None or not tel.enabled:
            return None
        return getattr(tel, "spans", None)

    def _with_delta(self, base_res: SearchResult, queries,
                    filt, k: int) -> SearchResult:
        if self.telemetry is not None and self.telemetry.enabled:
            self.telemetry.on_search(delta_scanned=self.delta.n > 0)
        if self.delta.n == 0:
            return base_res
        self._last_k = int(k)
        be = self.compaction_break_even(k)
        if be is not None:          # telemetry: predicted tax actually paid
            self.delta_tax_us += be[0] * int(np.shape(queries)[0])
        rec = self._spans()
        with span("delta", rec, rows=self.delta.n):
            extra = self.executor.delta(queries, filt, k=k)
        with span("merge", rec):
            return self.executor.merge(base_res, extra, k=k)

    def search(self, queries, filt, k: int = 10, ls: int = 64,
               max_iters: int = 0, layout: str = "default") -> SearchResult:
        """JAG traversal over the graph segment + exact delta scan, merged.

        ``filt`` may be a filter expression or a raw FilterBatch; it is
        normalized ONCE here so the base traversal and the delta scan see
        the same object (one jit cache entry each)."""
        filt = as_filter(filt)
        base = JAGIndex.search(self, queries, filt, k=k, ls=ls,
                               max_iters=max_iters, layout=layout)
        return self._with_delta(base, queries, filt, k)

    def search_int8(self, queries, filt, k: int = 10,
                    ls: int = 64, max_iters: int = 0,
                    layout: str = "default") -> SearchResult:
        """int8 traversal + exact re-rank on the graph segment, merged with
        the (always full-precision) delta scan."""
        filt = as_filter(filt)
        base = JAGIndex.search_int8(self, queries, filt, k=k, ls=ls,
                                    max_iters=max_iters, layout=layout)
        return self._with_delta(base, queries, filt, k)

    def search_auto(self, queries, filt, k: int = 10,
                    ls: int = 64, max_iters: int = 0,
                    planner=None, return_plan: bool = False,
                    mode: str = "per_query", layout: str = "default",
                    dtype: str = "f32"):
        """Selectivity-adaptive search over the LIVE base+delta database.

        Delegates to ``JAGIndex.search_auto`` (this class mirrors the
        executor-facing surface it needs — crucially ``self.attr`` is the
        merged live table, so the planner's probe tracks inserted rows),
        then merges the delta scan's top-k in exactly. The delta scan runs
        once for the whole batch regardless of the per-query route split —
        it is a constant (and compaction-bounded) cost that every route
        shares, so routing decisions are unchanged by the delta.
        """
        filt = as_filter(filt)
        base, p = JAGIndex.search_auto(
            self, queries, filt, k=k, ls=ls, max_iters=max_iters,
            planner=planner, return_plan=True, mode=mode, layout=layout,
            dtype=dtype)
        res = self._with_delta(base, queries, filt, k)
        if self.delta.n > 0 and getattr(p, "realized", None) is not None:
            # the realized route includes the merged delta scan
            if isinstance(p.realized, str):
                p = p._replace(realized=p.realized + "+delta")
            else:
                p = p._replace(realized=tuple(r + "+delta"
                                              for r in p.realized))
        # shadow-oracle audit runs HERE, not in the delegated base call
        # (which skips streaming indexes): the audited result must be the
        # final served top-k over base + live delta rows
        tel = self.telemetry
        if (tel is not None and tel.enabled
                and getattr(tel, "shadow", None) is not None):
            tel.shadow_audit(self, queries, filt, res, p, k=k)
        return (res, p) if return_plan else res

    # -- persistence -------------------------------------------------------
    def save(self, path: str) -> None:
        """One archive: the base index's arrays + delta rows + epoch.

        The base half is exactly ``JAGIndex.save``'s format (a plain
        ``JAGIndex.load`` on a streaming archive recovers the graph
        segment); ``stream__*`` keys carry the live state, losslessly —
        delta vectors/attr rows round-trip bit-for-bit.
        """
        arrs = self.base._save_arrays()
        # the WRAPPER's cost-model state is authoritative either way: a
        # post-compaction base carries none (keep the wrapper's), and a
        # wrapper whose model was detached must not resurrect the base
        # archive's on the next load
        arrs.pop("cost__model", None)
        arrs.pop("cost__metric", None)
        if self.cost_model is not None:
            from ..cost.registry import to_json
            arrs["cost__model"] = np.frombuffer(
                to_json(self.cost_model).encode(), np.uint8)
            arrs["cost__metric"] = self.cost_metric
        xv, attrs = self.delta.rows()
        arrs["stream__epoch"] = np.asarray(self.epoch, np.int64)
        arrs["stream__n_compactions"] = np.asarray(self.n_compactions,
                                                   np.int64)
        arrs["stream__compact_frac"] = np.asarray(self.compact_frac,
                                                  np.float64)
        arrs["stream__query_horizon"] = np.asarray(self.query_horizon,
                                                   np.int64)
        arrs["stream__delta_xv"] = xv
        for k, v in attrs.items():
            arrs[f"stream__delta_attr__{k}"] = v
        np.savez_compressed(path, **arrs)

    @classmethod
    def load(cls, path: str) -> "StreamingJAGIndex":
        """Resume mid-stream: epoch, delta rows, and search results are
        preserved bit-for-bit. A plain (frozen) ``JAGIndex`` archive loads
        too — as epoch 0 with an empty delta."""
        z = np.load(path, allow_pickle=False)
        base = JAGIndex._from_npz(z)
        if "stream__epoch" not in z:
            return cls(base)
        idx = cls(base,
                  epoch=int(z["stream__epoch"]),
                  compact_frac=float(z["stream__compact_frac"]),
                  n_compactions=int(z["stream__n_compactions"]),
                  query_horizon=int(z["stream__query_horizon"])
                  if "stream__query_horizon" in z else 100_000)
        xv = z["stream__delta_xv"]
        if xv.shape[0]:
            pre = "stream__delta_attr__"
            rows = AttrTable(base.attr.kind,
                             {k[len(pre):]: jnp.asarray(v)
                              for k, v in z.items() if k.startswith(pre)},
                             base.attr.n_bits)
            idx.delta.append(xv, rows)
        return idx
