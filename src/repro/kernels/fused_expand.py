"""Fused gather + distance + attribute-fetch Pallas kernel (scalar prefetch).

The serving hot path of JAG is the beam expansion: score C neighbor rows per
query lane per iteration (the paper's "distance computations", Figs. 10-13).
With the default split layout that costs TWO HBM gathers per expansion — one
over the vector matrix (``dist_fn``) and one over the attribute table
(``attr.gather``). The fused serving layout (serve/layout.py) packs each
database row as

    [ vec lanes (f32, or int8 codes widened to f32) | sq-norm | attr words ]

into one contiguous f32 matrix, and this kernel consumes it: neighbor ids are
scalar-prefetched so ``BlockSpec.index_map`` selects which packed row the DMA
engine pulls HBM->VMEM for each grid step (exactly like gather_dist.py), and
the kernel emits BOTH the squared-L2 distance and the raw attr words from the
single resident row — one gather per expansion instead of two.

int8 rows are handled with zero kernel changes: the caller pre-scales the
query (``q_eff = q * scale``) so ``codes . q_eff == dequant(codes) . q``, and
the norm lane already stores the dequantized squared norm.

Attr lanes are opaque bit payloads (filters.pack_attr_words); the kernel only
copies them, so the uint32<->f32 bitcast round-trips exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _row_kernel(d, C, ids_ref, qn_ref, x_ref, q_ref, o_dist, o_attr):
    del ids_ref  # consumed by the index_map (scalar prefetch)
    g = pl.program_id(0)
    row = x_ref[...]                                   # [1, d + 1 + A]
    vec = row[:, :d].astype(jnp.float32)               # [1, d]
    norm = row[:, d:d + 1]                             # [1, 1]
    q = q_ref[...].astype(jnp.float32)                 # [1, d]
    dot = jnp.sum(vec * q, axis=-1, keepdims=True)     # [1, 1]
    o_dist[...] = jnp.maximum(norm - 2.0 * dot + qn_ref[g // C], 0.0)
    o_attr[...] = row[:, d + 1:]                       # bit-preserving copy


@functools.partial(jax.jit, static_argnames=("d", "interpret"))
def fused_expand(packed: jnp.ndarray, ids: jnp.ndarray, q: jnp.ndarray,
                 q_norm: jnp.ndarray, *, d: int,
                 interpret: bool = False) -> tuple[jnp.ndarray, jnp.ndarray]:
    """packed f32 [N, d+1+A], ids int32 [B, C] (pre-clipped), q f32 [B, d]
    (pre-scaled for int8 layouts), q_norm f32 [B]
    -> (d2 f32 [B, C], attr words f32 [B, C, A])."""
    N, row_w = packed.shape
    A = row_w - d - 1
    assert A >= 1, "packed rows must carry at least one attr word"
    B, C = ids.shape
    flat = ids.reshape(-1)
    total = flat.shape[0]

    # single rows ride as [rows, 1, width] with the row axis squeezed out
    # of each block, so every block's last two dims equal the array's
    dist, attrs = pl.pallas_call(
        functools.partial(_row_kernel, d, C),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(total,),
            in_specs=[
                pl.BlockSpec((None, 1, row_w),
                             lambda g, ids, qn: (ids[g], 0, 0)),
                pl.BlockSpec((None, 1, d), lambda g, ids, qn: (g // C, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, 1, 1), lambda g, ids, qn: (g, 0, 0)),
                pl.BlockSpec((None, 1, A), lambda g, ids, qn: (g, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((total, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((total, 1, A), jnp.float32),
        ],
        interpret=interpret,
    )(flat, jnp.asarray(q_norm, jnp.float32), packed.reshape(N, 1, row_w),
      q.reshape(B, 1, d))
    return dist.reshape(B, C), attrs.reshape(B, C, A)
