"""Sharded serving: shard_map executor + cross-shard exact top-k merge.

The multi-device serving subsystem (ROADMAP "millions of users" north
star): the database — vectors, row norms, attribute table, graph, entry
seeds — is sharded ROW-WISE across the mesh's "data" axis (one
self-contained JAG shard of N_loc = N / S rows per device, placed by the
``distributed.sharding`` ``db_shard`` rule), queries are replicated, and
every executor route runs INSIDE a ``jax.shard_map`` program:

  1. each shard executes the route shard-locally — the prefilter scan over
     its rows, the beam-search graph traversal from its own entry points,
     the postfilter oversampled traversal;
  2. shard-local ids are globalized onto disjoint segments
     (``+ shard_id * N_loc`` — shard s owns [s*N_loc, (s+1)*N_loc));
  3. one ``all_gather`` of the per-shard ``[B, k]`` results over the shard
     axis, then ``serve.dispatch.merge_topk`` folded across shards IN
     SHARD ORDER reduces to the exact global top-k. Collective bytes
     scale with B*k, independent of N.

Exact-merge semantics: ``merge_topk`` sorts stably on the lexicographic
(primary, secondary) key with the lower segment as the tie-winning base,
so the fold resolves equal keys to the lowest global id — exactly how one
brute-force scan over the concatenated database breaks ties. The exact
routes are therefore BIT-identical to a single-device index over the
union of shard rows (the per-shard block GEMM computes each query-row
distance independently of the blocking, measured in the test suite); the
graph route traverses per-shard sub-graphs, so its results match a
single-device index exactly at S=1 and at recall parity for S>1 (each
shard's beam covers N/S rows — the bench asserts parity per selectivity
band).

:class:`ShardedJAGIndex` wraps the stacked per-shard state behind the
same ``search_auto(queries, filt, k, ls)`` surface as ``JAGIndex`` — it
reuses the single-device planner verbatim (the selectivity probe runs on
the replicated union attribute table; per-query route banding dispatches
each route group into its own shard_map program) and the cost model
integration via :class:`ShardedExecutor.cost_router`, which predicts at
the PER-SHARD shape (n = N_loc): attach an
``repro.cost.InterpolatedCostModel`` (``CostRegistry.load_shard_grids``)
and a fresh shard count routes cost-calibrated with no dedicated
calibration pass — predictions interpolate between neighboring (N, d)
grids.

Telemetry across shards: ``n_expanded``/``n_dist`` SUM over shards (all
shards really did that work); ``vlog`` is the width-0 ``[B, 0]`` — the
per-shard traversal logs are shard-local and id-ambiguous after
globalization, so the sharded routes don't expose them (the normalized
SearchResult contract allows any vlog width). The exact-scan route's
single-device vlog is also ``[B, 0]``, so forced-prefilter results stay
bit-identical across EVERY field.

Not yet sharded (recorded in ROADMAP follow-ons): streaming deltas (the
delta route raises, as on any frozen index), int8/fused serving variants,
cross-host dispatch.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..core.beam_search import SearchResult, greedy_search
from ..core.distances import INF, query_key_fn, unfiltered_key_fn
from ..core.filters import AttrTable, as_filter
from ..core.ground_truth import exact_filtered_knn
from ..core.jag import JAGConfig, JAGIndex
from ..distributed.sharding import put_db_sharded, serve_mesh, stack_shards
from .executor import Executor
from .dispatch import fold_topk


def _merge_across_shards(local: SearchResult, *, k: int,
                         n_shards: int) -> SearchResult:
    """Inside-shard_map reduction: ONE all_gather of the packed per-shard
    results over the "data" axis, then merge_topk folded in shard order
    (ties -> lowest segment, matching a union scan). Runs replicated on
    every shard.

    The shard-local result's five live fields (ids/primary/secondary
    [B, k] + n_expanded/n_dist [B]; vlog is dropped — see the module
    docstring) are bitcast to int32 and concatenated into one
    ``[B, 3k + 2]`` payload BEFORE the collective, so each route's whole
    cross-shard traffic is a single all-gather of B*(3k+2)*4 bytes — the
    invariant ``repro.analysis.audit`` asserts per sharded route. The
    f32<->int32 bitcast is exact for every payload (INF sentinels and NaN
    bit patterns round-trip), so the merged result is bit-identical to
    gathering each field separately.
    """
    B = local.ids.shape[0]
    bits = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)  # noqa: E731
    packed = jnp.concatenate(
        [local.ids, bits(local.primary), bits(local.secondary),
         local.n_expanded[:, None], local.n_dist[:, None]], axis=1)
    ag = jax.lax.all_gather(packed, "data")          # [S, B, 3k + 2]
    unbits = lambda x: jax.lax.bitcast_convert_type(  # noqa: E731
        x, jnp.float32)
    parts = [SearchResult(ag[s, :, :k], unbits(ag[s, :, k:2 * k]),
                          unbits(ag[s, :, 2 * k:3 * k]),
                          jnp.zeros((B, 0), jnp.int32),
                          ag[s, :, 3 * k], ag[s, :, 3 * k + 1])
             for s in range(n_shards)]
    return fold_topk(parts, k=k)


class ShardedExecutor(Executor):
    """The executor's route/cache surface over stacked per-shard arrays.

    Subclasses :class:`~repro.serve.executor.Executor`: the jit cache,
    epoch plumbing, planner sample buffers, and compound-clause
    reordering are inherited unchanged (they operate on the replicated
    union attribute table); the three base routes are overridden to
    compile shard_map programs whose results arrive pre-merged across the
    "data" axis. Cache keys reuse the inherited scheme — this executor
    belongs to one :class:`ShardedJAGIndex`, so route names can't collide
    with a single-device cache.
    """

    # -- routing shape: predict at the per-shard grid ----------------------
    def cost_router(self, *, k: int, ls: int, filt=None):
        """Per-shard cost routing: every shard executes the route over its
        own N_loc rows (the merge adds a B*k sort), so predictions use
        n = N_loc — the shard-shape grid an InterpolatedCostModel
        interpolates over — not the union row count."""
        model = getattr(self.index, "cost_model", None)
        if model is None:
            return None
        from ..cost.model import BASE_ROUTES, CostModelRouter
        from ..core.filters import n_leaves
        metric = getattr(self.index, "cost_metric", "us")
        if not model.covers(BASE_ROUTES, metric):
            return None
        idx = self.index
        clauses = 1 if filt is None else n_leaves(filt)
        return CostModelRouter(model, n=idx.n_loc, d=idx.d, k=k, ls=ls,
                               delta_n=0, metric=metric, n_leaves=clauses)

    # -- shard_map route programs ------------------------------------------
    def _sharded(self, key, make_local, db_args, queries, filt, *, k: int):
        """Compile-and-run one sharded route.

        ``make_local(*db_locals, q, filt) -> SearchResult`` is the
        shard-local body (ids still shard-local, any vlog width);
        ``db_args`` are the stacked [S, ...] trees. The wrapper drops the
        leading shard dim, globalizes ids onto the shard's segment, and
        merges across the "data" axis — one program, compiled once per
        key through the inherited cache.
        """
        idx = self.index
        mesh, S, n_loc = idx.mesh, idx.n_shards, idx.n_loc

        def make():
            def shard_fn(*args):
                db = [jax.tree.map(lambda x: x[0], a)
                      for a in args[:len(db_args)]]
                q, f = args[len(db_args)], args[len(db_args) + 1]
                res = make_local(*db, q, f)
                sid = jax.lax.axis_index("data")
                gids = jnp.where(res.ids >= 0, res.ids + sid * n_loc, -1)
                return _merge_across_shards(res._replace(ids=gids), k=k,
                                            n_shards=S)
            shard_fn.__name__ = key[0]      # the program: jit_<route>
            return jax.shard_map(
                shard_fn, mesh=mesh,
                in_specs=(P("data"),) * len(db_args) + (P(), P()),
                out_specs=P(), check_vma=False)
        return self.run(key, make, *db_args, jnp.asarray(queries), filt)

    def prefilter(self, queries, filt, *, k: int,
                  block: Optional[int] = None,
                  use_kernel: Optional[bool] = None) -> SearchResult:
        """Sharded masked exact scan: each shard scans its rows, the merge
        is exact — bit-identical to the single-device union scan."""
        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        filt = self._reorder_compound(filt)
        idx = self.index
        key = ("prefilter", "default", "f32", k, 0, 0, filt.kind, block,
               use_kernel)

        def local(xb, attr_data, q, f):
            attr = AttrTable(idx.attr.kind, attr_data,
                             n_bits=idx.attr.n_bits)
            gt = exact_filtered_knn(xb, attr, q, f, k=k, block=block,
                                    use_kernel=use_kernel)
            B = q.shape[0]
            prim = jnp.where(gt.ids >= 0, jnp.float32(0.0), INF)
            return SearchResult(gt.ids, prim, gt.d2,
                                jnp.zeros((B, 0), jnp.int32),
                                jnp.zeros((B,), jnp.int32), gt.n_dist)
        return self._sharded(key, local, (idx.xb, idx.attr_data), queries,
                             filt, k=k)

    def graph(self, queries, filt, *, k: int, ls: int, max_iters: int,
              layout: str = "default", dtype: str = "f32",
              introspect: bool = False) -> SearchResult:
        """Sharded JAG traversal: each shard walks its own sub-graph from
        its own entry seeds; the exact merge keeps the k best of the S
        shard beams. Only the default f32 variant is sharded today."""
        if introspect:
            raise NotImplementedError(
                "traversal introspection is single-device only — the "
                "cross-shard merge would need per-shard stat reduction "
                "(recorded follow-on); detach Telemetry(introspect=True) "
                "before serving sharded")
        if (layout, dtype) != ("default", "f32"):
            raise NotImplementedError(
                f"sharded graph route serves layout='default', dtype='f32' "
                f"only (got {layout!r}, {dtype!r}) — int8/fused sharding "
                f"is a recorded follow-on")
        idx = self.index
        key = ("graph", layout, dtype, k, ls, max_iters, filt.kind)

        def local(graph, xb, xb_norm, attr_data, entry, q, f):
            attr = AttrTable(idx.attr.kind, attr_data,
                             n_bits=idx.attr.n_bits)
            return greedy_search(graph, xb, xb_norm, attr, q, entry,
                                 query_key_fn(f), ls=ls, k=k,
                                 max_iters=max_iters)
        return self._sharded(key, local,
                             (idx.graph, idx.xb, idx.xb_norm,
                              idx.attr_data, idx.entry),
                             queries, filt, k=k)

    def unfiltered(self, queries, *, k: int, ls: int,
                   max_iters: int) -> SearchResult:
        """Sharded pure vector-distance traversal (no filter comparator);
        per-shard beams merge exactly like the graph route's."""
        idx = self.index
        key = ("unfiltered", "default", "f32", k, ls, max_iters, None)

        def local(graph, xb, xb_norm, attr_data, entry, q, f):
            attr = AttrTable(idx.attr.kind, attr_data,
                             n_bits=idx.attr.n_bits)
            return greedy_search(graph, xb, xb_norm, attr, q, entry,
                                 unfiltered_key_fn(), ls=ls, k=k,
                                 max_iters=max_iters)
        return self._sharded(key, local,
                             (idx.graph, idx.xb, idx.xb_norm,
                              idx.attr_data, idx.entry),
                             queries, None, k=k)

    def postfilter(self, queries, filt, *, k: int, ls: int,
                   max_iters: int) -> SearchResult:
        """Sharded post-filtering: each shard's unfiltered ls-beam is
        filtered against its local attribute rows, then merged."""
        idx = self.index
        key = ("postfilter", "default", "f32", k, ls, max_iters, filt.kind)

        def local(graph, xb, xb_norm, attr_data, entry, q, f):
            from ..core.filters import matches
            attr = AttrTable(idx.attr.kind, attr_data,
                             n_bits=idx.attr.n_bits)
            res = greedy_search(graph, xb, xb_norm, attr, q, entry,
                                unfiltered_key_fn(), ls=ls, k=ls,
                                max_iters=max_iters)
            ids = res.ids
            ok = matches(f, attr.gather(jnp.maximum(ids, 0))) & (ids >= 0)
            prim = jnp.where(ok, 0.0, INF)
            sec = jnp.where(ok, res.secondary, INF)
            idsm = jnp.where(ok, ids, -1)
            prim, sec, idsm = jax.lax.sort((prim, sec, idsm), num_keys=2)
            n_dist = res.n_dist + jnp.sum(ids >= 0, axis=1,
                                          dtype=jnp.int32)
            return SearchResult(idsm[:, :k], prim[:, :k], sec[:, :k],
                                res.vlog, res.n_expanded, n_dist)
        return self._sharded(key, local,
                             (idx.graph, idx.xb, idx.xb_norm,
                              idx.attr_data, idx.entry),
                             queries, filt, k=k)


class ShardedJAGIndex:
    """Row-sharded JAG behind the single-device ``search_auto`` surface.

    Holds the per-shard state STACKED on a leading shard axis split over
    the mesh's "data" axis — device s holds shard s's rows and no others:

        graph     int32 [S, N_loc, R]   shard-local neighbor ids
        xb        f32   [S, N_loc, d]
        xb_norm   f32   [S, N_loc]
        attr_data       {name: [S, N_loc, ...]}
        entry     int32 [S, E]          per-shard entry seeds

    plus the replicated union :class:`AttrTable` (``.attr``) the planner
    probes — so routing decisions see exactly the same selectivity
    estimates as a single-device index over the same rows. Build with
    :meth:`build` (splits rows contiguously, builds one sub-graph per
    shard) or :meth:`from_shards` (adopts existing per-shard indexes);
    ``JAGIndex.shard(n_shards)`` is the one-call migration path.
    """

    epoch: int = 0        # frozen, like JAGIndex — streaming is a follow-on

    def __init__(self, *, mesh: Mesh, graph, xb, xb_norm, attr_data,
                 entry, attr: AttrTable, cfg: JAGConfig):
        if "data" not in mesh.axis_names:
            raise ValueError(f"mesh needs a 'data' axis, got "
                             f"{mesh.axis_names}")
        self.mesh = mesh
        self.n_shards = int(mesh.shape["data"])
        if int(graph.shape[0]) != self.n_shards:
            raise ValueError(
                f"stacked arrays carry {int(graph.shape[0])} shards but "
                f"the mesh 'data' axis is {self.n_shards}-way")
        placed = put_db_sharded(
            dict(graph=graph, xb=xb, xb_norm=xb_norm,
                 attr_data=dict(attr_data), entry=entry), mesh)
        self.graph = placed["graph"]
        self.xb = placed["xb"]
        self.xb_norm = placed["xb_norm"]
        self.attr_data = placed["attr_data"]
        self.entry = placed["entry"]
        self.attr = attr                     # replicated union table
        self.n_loc = int(self.xb.shape[1])
        self.d = int(self.xb.shape[2])
        self.cfg = cfg
        self._executor = None
        self.cost_model = None
        self.cost_metric = "us"
        self.telemetry = None
        if attr.n != self.n_shards * self.n_loc:
            raise ValueError(
                f"union attr table has {attr.n} rows, shards carry "
                f"{self.n_shards} x {self.n_loc}")

    # -- construction ------------------------------------------------------
    @classmethod
    def from_shards(cls, shards: Sequence[JAGIndex],
                    mesh: Optional[Mesh] = None) -> "ShardedJAGIndex":
        """Adopt per-shard JAGIndexes (equal row counts and attr kinds);
        shard i serves global ids [i*N_loc, (i+1)*N_loc) from the i-th mesh
        device. The stacked arrays are assembled from per-device pieces
        (``distributed.sharding.stack_shards``): a shard built on its own
        device stays there."""
        if not shards:
            raise ValueError("need at least one shard")
        n_loc = int(shards[0].xb.shape[0])
        kind, n_bits = shards[0].attr.kind, shards[0].attr.n_bits
        for s in shards[1:]:
            if int(s.xb.shape[0]) != n_loc:
                raise ValueError("all shards must hold the same row count "
                                 f"({n_loc} != {int(s.xb.shape[0])})")
            if s.attr.kind != kind or s.attr.n_bits != n_bits:
                raise ValueError("all shards must share one attr schema")
        mesh = mesh or serve_mesh(len(shards))
        # the planner's probe table, on the default device; gathered via the
        # host because the shards' pieces sit on different devices
        union = AttrTable(
            kind,
            {k: jnp.asarray(np.concatenate(
                [np.asarray(s.attr.data[k]) for s in shards], axis=0))
             for k in shards[0].attr.data},
            n_bits=n_bits)
        st = stack_shards(
            [dict(graph=s.graph, xb=s.xb, xb_norm=s.xb_norm,
                  attr_data=dict(s.attr.data), entry=s.entry)
             for s in shards], mesh)
        return cls(mesh=mesh, attr=union, cfg=shards[0].cfg, **st)

    @classmethod
    def build(cls, xb, attr: AttrTable, cfg: JAGConfig = JAGConfig(),
              *, n_shards: Optional[int] = None, mesh: Optional[Mesh] = None,
              verbose: bool = False) -> "ShardedJAGIndex":
        """Split rows contiguously into S shards and build one sub-graph
        per shard (shard-local entry seeds included). N must divide by S —
        ragged resharding is a cross-host-dispatch follow-on.

        Shard s is built on the s-th mesh device, from rows copied there
        from the host; the S builds run concurrently, one thread each, so
        every device works on its own shard at once."""
        if mesh is None:
            if n_shards is None:
                raise ValueError("pass n_shards or a mesh")
            mesh = serve_mesh(int(n_shards))
        devs = list(mesh.devices.flat)
        S = len(devs)
        xb = np.asarray(xb)
        n = int(xb.shape[0])
        if n % S != 0:
            raise ValueError(f"N={n} rows do not split evenly into "
                             f"{S} shards")
        n_loc = n // S
        host_attr = {k: np.asarray(v) for k, v in attr.data.items()}

        def build_one(s: int) -> JAGIndex:
            lo, hi = s * n_loc, (s + 1) * n_loc
            dev = devs[s]
            with jax.default_device(dev):
                sub = AttrTable(attr.kind,
                                {k: jax.device_put(v[lo:hi], dev)
                                 for k, v in host_attr.items()},
                                n_bits=attr.n_bits)
                return JAGIndex.build(jax.device_put(xb[lo:hi], dev), sub,
                                      cfg, verbose=verbose)

        with ThreadPoolExecutor(max_workers=S) as pool:
            shards = list(pool.map(build_one, range(S)))
        return cls.from_shards(shards, mesh=mesh)

    # -- serving (the JAGIndex surface) ------------------------------------
    @property
    def executor(self) -> ShardedExecutor:
        if self._executor is None:
            self._executor = ShardedExecutor(self)
        return self._executor

    # search_auto/attach_cost_model/attach_telemetry run the single-device
    # implementations verbatim: they only touch self.executor / self.attr /
    # self.cost_* / self.telemetry, so the sharded index IS a drop-in
    # behind the public surface. Telemetry traces record the per-shard
    # view (n = n_loc, shard = [S, n_loc]) — predictions are per-shard too.
    search_auto = JAGIndex.search_auto
    attach_cost_model = JAGIndex.attach_cost_model
    attach_telemetry = JAGIndex.attach_telemetry

    def search(self, queries, filt, k: int = 10, ls: int = 64,
               max_iters: int = 0) -> SearchResult:
        """Sharded filtered traversal (the graph route, default layout)."""
        return self.executor.graph(queries, as_filter(filt), k=k, ls=ls,
                                   max_iters=max_iters or 2 * ls)


def shard_index(index: JAGIndex, n_shards: int,
                mesh: Optional[Mesh] = None) -> ShardedJAGIndex:
    """Re-shard a built single-device index across ``n_shards`` devices.

    Sub-graphs are REBUILT per shard from the index's rows and config —
    a built graph's edges cross any row split, so slicing the adjacency
    would orphan every cross-shard edge; an honest reshard is a rebuild.
    """
    return ShardedJAGIndex.build(
        index.xb, index.attr, index.cfg,
        n_shards=None if mesh is not None else n_shards, mesh=mesh)
