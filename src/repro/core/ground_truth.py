"""Exact filtered nearest neighbors (= the Pre-Filtering baseline).

Brute force over validity-masked distances, blocked over the database so the
distance matrix stays bounded; the blocked path is also the production
pre-filter (paper Appendix A: isolate valid subset, scan it exactly) — the
query planner (serve/planner.py) routes low-selectivity batches here, and
the executor (serve/executor.py) adapts the result to the SearchResult
contract. ``use_kernel=True`` swaps the per-block distance matmul for the
scalar-prefetch Pallas tile scan (kernels/ops.gather_dist_tile, padded once
up front): one kernel call per database block, which DMAs the block
HBM->VMEM once and scores it against the whole query group in fixed
128-row query blocks, so each query's distances are the same whatever
group it is served in. The block defaults to
``kernels.gather_dist.scan_tile(d)``: 4096 rows up to d=256, fewer at
wider rows, so a double-buffered tile fits the chip's VMEM.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..kernels.gather_dist import scan_tile
from .distances import INF, sq_norms
from .filters import AttrTable, matches_rows


class GroundTruth(NamedTuple):
    ids: jnp.ndarray   # int32 [B, k], -1 where fewer than k valid points
    d2: jnp.ndarray    # f32 [B, k]
    n_dist: jnp.ndarray  # int32 [B]: #valid points scanned (paper Table 1 DC)
    n_feval: jnp.ndarray  # int32 [B]: short-circuit filter-clause evals


@partial(jax.jit, static_argnames=("k", "block", "use_kernel"))
def exact_filtered_knn(xb, attr: AttrTable, queries, filt,
                       k: int = 10, block: int | None = None,
                       use_kernel: bool = False) -> GroundTruth:
    """Exact top-k among filter-satisfying points, blocked scan.

    ``filt`` may be an atomic FilterBatch or a compound FilterExpr; the
    validity scan evaluates the tree per block with left-to-right
    short-circuit accounting (``n_feval`` — what the planner's clause
    reordering minimizes). ``use_kernel`` also routes the subset/boolean
    leaf validity through the Pallas popcount kernel (kernels/bitset.py).
    """
    N, d = xb.shape
    B = queries.shape[0]
    block = block or scan_tile(d)
    xb32 = xb.astype(jnp.float32)
    xn = sq_norms(xb32)
    q32 = queries.astype(jnp.float32)
    qn = sq_norms(q32)
    nblk = (N + block - 1) // block
    if use_kernel:
        # pad ONCE (rows to a block multiple, d to the 8-lane minimum) so
        # the fori_loop body is a bare tile DMA + reduction; padded rows
        # score against the zero vector and are masked by `inb` below
        xb_pad = jnp.pad(xb32, ((0, (-N) % block), (0, (-d) % 8)))
        q_pad = jnp.pad(q32, ((0, 0), (0, (-d) % 8)))

    top_d = jnp.full((B, k), INF)
    top_i = jnp.full((B, k), -1, jnp.int32)
    ndist = jnp.zeros((B,), jnp.int32)
    nfeval = jnp.zeros((B,), jnp.int32)

    def body(bi, carry):
        top_d, top_i, ndist, nfeval = carry
        ids = bi * block + jnp.arange(block)
        inb = ids < N
        idc = jnp.minimum(ids, N - 1)
        if use_kernel:
            from ..kernels import ops
            d2 = ops.gather_dist_tile(xb_pad, bi, q_pad,
                                      tile=block)            # [B, blk]
        else:
            xbl = jnp.take(xb32, idc, axis=0)                # [blk, d]
            d2 = (jnp.take(xn, idc)[None, :] + qn[:, None]
                  - 2.0 * jnp.matmul(q32, xbl.T,             # [B, blk]
                                     precision=jax.lax.Precision.HIGHEST))
        # gather the block's [block] attr rows ONCE and broadcast against
        # the filter batch — the old [B, block] id matrix repeated the same
        # gather B times per block on the prefilter hot path
        ok, ev = matches_rows(filt, attr, idc, use_kernel=use_kernel)
        ok = ok & inb[None, :]
        d2 = jnp.where(ok, jnp.maximum(d2, 0.0), INF)
        ndist = ndist + jnp.sum(ok, axis=1, dtype=jnp.int32)
        nfeval = nfeval + jnp.sum(
            jnp.where(inb[None, :], ev, 0), axis=1, dtype=jnp.int32)
        cd = jnp.concatenate([top_d, d2], axis=1)
        ci = jnp.concatenate(
            [top_i, jnp.where(ok, ids[None, :], -1)], axis=1)
        cd, ci = jax.lax.sort((cd, ci), num_keys=1)
        return cd[:, :k], ci[:, :k], ndist, nfeval

    top_d, top_i, ndist, nfeval = jax.lax.fori_loop(
        0, nblk, body, (top_d, top_i, ndist, nfeval))
    top_i = jnp.where(jnp.isinf(top_d), -1, top_i)
    return GroundTruth(top_i, top_d, ndist, nfeval)
