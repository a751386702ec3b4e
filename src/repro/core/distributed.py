"""Distributed JAG: shard-and-merge serving + per-shard builds (shard_map).

Architecture (DESIGN.md §4): every device owns an independent JAG shard
(vectors + sub-graph + attributes over N/n_shards points — the layout used
by production ANN services). Queries are sharded over the "pod" axis and
replicated across shards; each shard runs the batched beam search locally
and the per-shard top-k results are merged with one all-gather over the
shard axes + a local lexicographic sort. Collective bytes therefore scale
with B·k, independent of N.

Fault tolerance: a lost shard removes only its slice of candidates until
the checkpointed shard arrays are restored (graceful recall degradation);
elastic scaling = changing the number of "data"-axis shards (each shard is
self-contained).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .beam_search import greedy_search
from .distances import gathered_dot, query_key_fn
from .filters import AttrTable, FilterBatch


@dataclasses.dataclass(frozen=True)
class ShardedServeConfig:
    k: int = 10
    ls: int = 64
    max_iters: int = 128
    query_chunk: int = 128     # bitmap-bounded query chunking per shard


def shard_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("data", "model") if a in mesh.axis_names)


def query_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod",) if a in mesh.axis_names)


def make_serve_step(mesh: Mesh, cfg: ShardedServeConfig, attr_kind: str,
                    filt_kind: str, n_bits: int = 0,
                    variant: str = "f32", dedup: str = "bitmap"):
    """Returns step(graph, xb, xb_norm, attr_data, entries, queries,
    filt_data[, scale]) -> (global ids [B, k], primary, secondary).

    ``variant``: "f32" (xb as given) | "int8" (xb int8 + trailing ``scale``
    f32[d] arg; row norms gathered) | "int8_reg" (int8, norms recomputed
    in-register from the gathered row — no norm gather). ``dedup``: see
    beam_search.greedy_search. §Perf iterations for the serve_1b cell.

    Sharded layouts (leading shard axis = flattened ("data","model")):
      graph    int32 [S, N_loc, R] (shard-local ids)
      xb             [S, N_loc, d]
      xb_norm  f32   [S, N_loc]
      attr_data      {name: [S, N_loc, ...]}
      entries  int32 [S, n_seeds]      (per-shard entry points)
      queries        [B, d]            sharded over "pod"
      filt_data      {name: [B, ...]}  sharded over "pod"
    """
    sx = shard_axes(mesh)
    qx = query_axes(mesh)
    n_shards = 1
    for a in sx:
        n_shards *= mesh.shape[a]

    def shard_fn(graph, xb, xb_norm, attr_data, entries, queries,
                 filt_data, *rest):
        graph, xb, xb_norm = graph[0], xb[0], xb_norm[0]
        attr_data = jax.tree.map(lambda x: x[0], attr_data)
        entries = entries[0]
        attr = AttrTable(attr_kind, attr_data, n_bits=n_bits)
        shard_id = jnp.int32(0)
        for a in sx:
            shard_id = shard_id * mesh.shape[a] + jax.lax.axis_index(a)

        dist_fn = None
        if variant == "int8":
            from .quantized import make_int8_dist_fn
            dist_fn = make_int8_dist_fn(rest[0])
        elif variant == "int8_reg":
            scale = rest[0]

            def dist_fn(xq, _norm, ids, q32, q_norm):  # noqa: F811
                rows = jnp.take(xq, ids, axis=0,
                                mode="clip").astype(jnp.float32) * scale
                # gathered_dot, not einsum: the batched-dot lowering of
                # einsum("bcd,bd->bc") vectorizes its reduction by batch
                # size, so per-query results drift across query_chunk
                # regroupings — JAG002 (batch-invariance, PR 3 contract)
                d2 = (jnp.sum(rows * rows, -1)
                      - 2.0 * gathered_dot(rows, q32)
                      + q_norm[:, None])
                return jnp.maximum(d2, 0.0)

        def chunk_fn(args):
            q, fd = args
            filt = FilterBatch(filt_kind, fd, n_bits=n_bits)
            kw = {} if dist_fn is None else {"dist_fn": dist_fn}
            res = greedy_search(graph, xb, xb_norm, attr, q, entries,
                                query_key_fn(filt), ls=cfg.ls, k=cfg.k,
                                max_iters=cfg.max_iters, dedup=dedup, **kw)
            return res.ids, res.primary, res.secondary

        B = queries.shape[0]
        nch = max(B // cfg.query_chunk, 1)
        qc = queries.reshape(nch, B // nch, -1)
        fdc = jax.tree.map(
            lambda x: x.reshape((nch, B // nch) + x.shape[1:]), filt_data)
        ids, prim, sec = jax.lax.map(chunk_fn, (qc, fdc))
        ids = ids.reshape(B, cfg.k)
        prim = prim.reshape(B, cfg.k)
        sec = sec.reshape(B, cfg.k)
        gids = jnp.where(ids >= 0, ids + shard_id * xb.shape[0], -1)

        # merge across shards: all_gather (axis 0 = shard) + local sort
        ag_i = jax.lax.all_gather(gids, sx)      # [n_shards, B, k]
        ag_p = jax.lax.all_gather(prim, sx)
        ag_s = jax.lax.all_gather(sec, sx)
        ag_i = jnp.moveaxis(ag_i.reshape(n_shards, B, cfg.k), 0, 1
                            ).reshape(B, -1)
        ag_p = jnp.moveaxis(ag_p.reshape(n_shards, B, cfg.k), 0, 1
                            ).reshape(B, -1)
        ag_s = jnp.moveaxis(ag_s.reshape(n_shards, B, cfg.k), 0, 1
                            ).reshape(B, -1)
        p, s, i = jax.lax.sort((ag_p, ag_s, ag_i), num_keys=2)
        return i[:, :cfg.k], p[:, :cfg.k], s[:, :cfg.k]

    shard_spec = P(sx)
    q_spec = P(qx) if qx else P()
    in_specs = [shard_spec, shard_spec, shard_spec, shard_spec,
                shard_spec, q_spec, q_spec]
    if variant in ("int8", "int8_reg"):
        in_specs.append(P())        # replicated dequant scale
    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(q_spec, q_spec, q_spec),
        check_vma=False)


def make_build_step(mesh: Mesh, build_cfg, attr_kind: str, n_bits: int = 0):
    """Per-shard batched Insert over the full mesh (independent sub-graphs).

    step(graph [S,N,W], degree [S,N], xb [S,N,d], xb_norm [S,N],
         attr_data [S,N,...], batch_ids [S,B], entries [S,E])
    """
    from .build import make_insert_step
    sx = shard_axes(mesh)
    insert = make_insert_step(build_cfg)

    def shard_fn(graph, degree, xb, xb_norm, attr_data, batch_ids, entries):
        attr = AttrTable(attr_kind, jax.tree.map(lambda x: x[0], attr_data),
                         n_bits=n_bits)
        g, d = insert(graph[0], degree[0], xb[0], xb_norm[0], attr,
                      batch_ids[0], entries[0])
        return g[None], d[None]

    spec = P(sx)
    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(spec,) * 7, out_specs=(spec, spec), check_vma=False)
