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
  gather_dist_tile — one grid step per query lane; the lane's C ids must
                     point into a contiguous [C-aligned] region (used by the
                     sorted/bucketed layouts produced at build time), letting
                     the DMA fetch a (C, d) tile in one shot.

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

# f32 elements one database tile may hold: the scan kernel keeps a
# double-buffered (tile, d) input plus one (tile, d) temporary in VMEM, so
# 2^20 elements (4 MiB) per tile stays inside v5e's 16 MiB scoped limit.
TILE_ELEMS = 1 << 20
MAX_TILE = 4096


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


def _tile_kernel(base_ref, x_ref, q_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)            # [C, d]
    q = q_ref[...].astype(jnp.float32)            # [1, d]
    # full-f32 MXU passes: the prefilter is an exact route, so its
    # distances may not drop to a single bf16 pass (the TPU default)
    qx = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST)
    o_ref[...] = (jnp.sum(x * x, axis=-1)[None, :]
                  - 2.0 * qx
                  + jnp.sum(q * q, axis=-1, keepdims=True))


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def gather_dist_tile(xb: jnp.ndarray, base: jnp.ndarray, q: jnp.ndarray,
                     *, tile: int, interpret: bool = False) -> jnp.ndarray:
    """Tile-granular fused gather+distance.

    ``base`` int32 [B]: tile index per query lane; lane b scores database
    rows [base[b]*tile, (base[b]+1)*tile) against q[b]. xb's row count must
    be divisible by ``tile``; ``scan_tile(d)`` gives the largest tile the
    chip's VMEM holds. Returns f32 [B, tile].
    """
    N, d = xb.shape
    B = base.shape[0]
    assert N % tile == 0

    out = pl.pallas_call(
        _tile_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((tile, d), lambda b, base: (base[b], 0)),
                pl.BlockSpec((None, 1, d), lambda b, base: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, 1, tile),
                                   lambda b, base: (b, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, 1, tile), jnp.float32),
        interpret=interpret,
    )(base, xb, q.reshape(B, 1, d))
    return jnp.maximum(out.reshape(B, tile), 0.0)
