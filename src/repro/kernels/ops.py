"""Jit'd public wrappers for the Pallas kernels.

Off the chip kernels run in Pallas ``interpret=True`` mode; on a TPU backend
every call compiles to Mosaic, whatever the caller asks for — nothing on the
serving path can fall back to the interpreter there. Wrappers pad inputs to
tile multiples and slice results back.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import bitset as _bitset
from . import fused_expand as _fe
from . import gather_dist as _gd
from . import l2dist as _l2


def _interp(explicit: bool | None) -> bool:
    """False on a TPU backend; elsewhere ``explicit``, defaulting to True."""
    if jax.default_backend() == "tpu":
        return False
    return True if explicit is None else explicit


def _pad_to(x: jnp.ndarray, axis: int, mult: int, value=0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), n


def l2dist(q, xb, *, bq: int = 128, bn: int = 256, bd: int = 128,
           interpret: bool | None = None) -> jnp.ndarray:
    """Padded/sliced blocked distance matrix [B, N] (see l2dist.py)."""
    q = jnp.asarray(q)
    xb = jnp.asarray(xb)
    qp, B = _pad_to(q, 0, min(bq, max(q.shape[0], 1)))
    qp, _ = _pad_to(qp, 1, 8)
    xp, N = _pad_to(xb, 0, min(bn, max(xb.shape[0], 1)))
    xp, _ = _pad_to(xp, 1, 8)
    bq2 = min(bq, qp.shape[0])
    bn2 = min(bn, xp.shape[0])
    bd2 = min(bd, qp.shape[1])
    qp, _ = _pad_to(qp, 0, bq2)
    xp, _ = _pad_to(xp, 0, bn2)
    qp, _ = _pad_to(qp, 1, bd2)
    xp, _ = _pad_to(xp, 1, bd2)
    out = _l2.l2dist(qp, xp, bq=bq2, bn=bn2, bd=bd2,
                     interpret=_interp(interpret))
    return out[:B, :N]


def gather_dist(xb, ids, q, *, interpret: bool | None = None) -> jnp.ndarray:
    """Fused gather+distance [B, C] (ids clipped internally)."""
    ids = jnp.clip(jnp.asarray(ids, jnp.int32), 0, xb.shape[0] - 1)
    return _gd.gather_dist(jnp.asarray(xb), ids, jnp.asarray(q),
                           interpret=_interp(interpret))


def fused_expand(packed, ids, q, q_norm, *, d: int,
                 interpret: bool | None = None):
    """One-gather beam expansion over the fused serving layout.

    ``packed`` f32 [N, d+1+A] rows of [vec | sq-norm | attr words] (see
    serve/layout.py). Returns (d2 [B, C], attr words [B, C, A]) from a single
    row gather — the fetch contract of ``beam_search.greedy_search``'s
    ``fetch_fn`` hook, minus the word decode (filters.unpack_attr_words).
    ids are clipped internally; q must already be scale-folded for int8 rows.
    """
    ids = jnp.clip(jnp.asarray(ids, jnp.int32), 0, packed.shape[0] - 1)
    return _fe.fused_expand(jnp.asarray(packed, jnp.float32), ids,
                            jnp.asarray(q, jnp.float32),
                            jnp.asarray(q_norm, jnp.float32), d=d,
                            interpret=_interp(interpret))


def gather_dist_tile(xb, bi, q, *, tile: int,
                     interpret: bool | None = None) -> jnp.ndarray:
    """One database tile scored against the whole query group: rows
    ``[bi*tile, (bi+1)*tile)`` of xb against every q[b] -> f32 [B, tile].

    The prefilter route's masked-scan inner loop (core/ground_truth.py with
    ``use_kernel=True``): each call DMAs its tile HBM->VMEM once and does
    one ``[bq, d] x [d, tile]`` MXU product per fixed query block, so a
    query's row is bit-identical whatever group it is scored in. xb's row
    count must be a tile multiple and d an 8-lane multiple — callers pad
    once up front (padded rows score against the zero vector and must be
    masked; ``exact_filtered_knn``'s ``inb`` mask does).
    """
    return _gd.gather_dist_tile(jnp.asarray(xb), jnp.asarray(bi, jnp.int32),
                                jnp.asarray(q), tile=tile,
                                interpret=_interp(interpret))


def hamming(a, b, *, interpret: bool | None = None) -> jnp.ndarray:
    """Packed Hamming distance matrix [B, N]."""
    a = jnp.asarray(a, jnp.uint32)
    b = jnp.asarray(b, jnp.uint32)
    ap, B = _pad_to(a, 0, min(128, max(a.shape[0], 1)))
    bp, N = _pad_to(b, 0, min(128, max(b.shape[0], 1)))
    bq = min(128, ap.shape[0])
    bn = min(128, bp.shape[0])
    ap, _ = _pad_to(ap, 0, bq)
    bp, _ = _pad_to(bp, 0, bn)
    return _bitset.bitset_dist(ap, bp, op="xor", bq=bq, bn=bn,
                               interpret=_interp(interpret))[:B, :N]


def subset_deficit(f, a, *, interpret: bool | None = None) -> jnp.ndarray:
    """|f \\ a| matrix [B, N] (subset dist_F)."""
    f = jnp.asarray(f, jnp.uint32)
    a = jnp.asarray(a, jnp.uint32)
    fp, B = _pad_to(f, 0, min(128, max(f.shape[0], 1)))
    ap, N = _pad_to(a, 0, min(128, max(a.shape[0], 1)))
    bq = min(128, fp.shape[0])
    bn = min(128, ap.shape[0])
    fp, _ = _pad_to(fp, 0, bq)
    ap, _ = _pad_to(ap, 0, bn)
    return _bitset.bitset_dist(fp, ap, op="deficit", bq=bq, bn=bn,
                               interpret=_interp(interpret))[:B, :N]
