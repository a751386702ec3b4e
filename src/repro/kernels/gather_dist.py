"""Fused gather + squared-L2 distance Pallas kernel (scalar prefetch).

The TPU-native answer to graph pointer-chasing: neighbor ids are
scalar-prefetched so the ``BlockSpec.index_map`` selects which database
row block the DMA engine fetches HBM->VMEM for each grid step; the distance
reduction runs on the resident tile, so gathered rows never round-trip
through HBM. This is the beam-search expansion hot spot (the paper's
"distance computations" metric, Figs. 10-13).

Two granularities:
  gather_dist      — one grid step per (b, c) id; block = a single row
                     selected by ``ids[g]``. Exact gather semantics.
  gather_dist_tile — one call per contiguous database tile, scored against
                     the whole query group: the tile index is
                     scalar-prefetched and the tile's block index is the
                     same at every grid step, so its (tile, d) rows are
                     DMA'd HBM->VMEM once per call. The grid runs over fixed
                     ``QUERY_BLOCK``-row query blocks, each one
                     ``[bq, d] x [d, tile]`` MXU product, so a query's
                     distances do not depend on which queries share its
                     group (per-row bit-identity by construction).

Single-row operands are viewed as ``[rows, 1, width]`` with the row axis
squeezed out of the block (``None``): Mosaic requires the last two block
dims to be (8, 128)-aligned or equal to the array's, and ``(1, width)``
over a ``[rows, 1, width]`` array is. The kernel bodies see the same
``(1, width)`` tiles either way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# f32 elements one database tile may hold. Per grid step the scan kernel
# keeps in VMEM a double-buffered (tile, d) input (2 x 1.7 MiB at d=104,
# tile=4096), a double-buffered (QUERY_BLOCK, tile) output (2 x 2 MiB) and
# the product's and row norms' temporaries: at most about 11 MiB, inside
# v5e's 16 MiB scoped limit, and so at d=768 with 1,024-row tiles. The TPU
# compiler fits both widths in 5 MiB (8 MiB for a 64-row query block).
TILE_ELEMS = 1 << 20
MAX_TILE = 4096
# query rows per grid step of the tile scan; groups of fewer rows take one
# block of their row count rounded up to 8
QUERY_BLOCK = 128


def scan_tile(d: int) -> int:
    """Rows per scan tile at width ``d``: the largest power of two up to
    ``MAX_TILE`` whose f32 tile (d padded to 8 lanes) fits ``TILE_ELEMS``."""
    d8 = -(-int(d) // 8) * 8
    t = MAX_TILE
    while t > 8 and t * d8 > TILE_ELEMS:
        t //= 2
    return t


def _row_kernel(ids_ref, x_ref, q_ref, o_ref):
    diff = x_ref[...].astype(jnp.float32) - q_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.sum(diff * diff, axis=-1, keepdims=True).T


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_dist(xb: jnp.ndarray, ids: jnp.ndarray, q: jnp.ndarray,
                *, interpret: bool = False) -> jnp.ndarray:
    """xb [N, d], ids int32 [B, C] (pre-clipped to [0, N)), q [B, d]
    -> f32 [B, C]: ||q[b] - xb[ids[b, c]]||^2."""
    N, d = xb.shape
    B, C = ids.shape
    flat = ids.reshape(-1)
    total = flat.shape[0]

    out = pl.pallas_call(
        _row_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(total,),
            in_specs=[
                pl.BlockSpec((None, 1, d), lambda g, ids: (ids[g], 0, 0)),
                pl.BlockSpec((None, 1, d), lambda g, ids: (g // C, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, 1, 1), lambda g, ids: (g, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((total, 1, 1), jnp.float32),
        interpret=interpret,
    )(flat, xb.reshape(N, 1, d), q.reshape(B, 1, d))
    return out.reshape(B, C)


def _tile_kernel(bi_ref, x_ref, q_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)            # [tile, d]
    q = q_ref[...].astype(jnp.float32)            # [bq, d]
    # full-f32 MXU passes: the prefilter is an exact route, so its
    # distances may not drop to a single bf16 pass (the TPU default)
    qx = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST)
    # the norms reduce to a (tile, 1) column and turn into a row: a bare
    # [None, :] of the (tile,) sum asks Mosaic for tens of MiB of VMEM
    xn = jnp.sum(x * x, axis=-1, keepdims=True).T  # [1, tile]
    o_ref[...] = xn - 2.0 * qx + jnp.sum(q * q, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def gather_dist_tile(xb: jnp.ndarray, bi: jnp.ndarray, q: jnp.ndarray,
                     *, tile: int, interpret: bool = False) -> jnp.ndarray:
    """Squared L2 distances from every query to one database tile.

    ``bi`` int32 scalar: the tile index; the rows scored are
    ``[bi*tile, (bi+1)*tile)`` of xb, whose row count must be divisible by
    ``tile`` (``scan_tile(d)`` gives the largest tile the chip's VMEM
    holds). The group is padded to whole query blocks of ``QUERY_BLOCK``
    rows (one block of ``B`` rounded up to 8 when B is smaller). Returns
    f32 [B, tile], clamped at 0.
    """
    N, d = xb.shape
    B = q.shape[0]
    assert N % tile == 0
    bq = min(QUERY_BLOCK, -(-B // 8) * 8)
    qp = jnp.pad(q, ((0, (-B) % bq), (0, 0)))

    out = pl.pallas_call(
        _tile_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(qp.shape[0] // bq,),
            in_specs=[
                pl.BlockSpec((tile, d), lambda j, bi: (bi[0], 0)),
                pl.BlockSpec((bq, d), lambda j, bi: (j, 0)),
            ],
            out_specs=pl.BlockSpec((bq, tile), lambda j, bi: (j, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((qp.shape[0], tile), jnp.float32),
        interpret=interpret,
    )(jnp.reshape(bi, (1,)).astype(jnp.int32), xb, qp)
    return jnp.maximum(out[:B], 0.0)
