"""Packed-bitset attribute distance Pallas kernels (popcount on the VPU).

Subset/boolean attribute & filter distances over uint32-packed bitsets:
XOR/ANDN + ``lax.population_count`` on (bq, bn) VMEM tiles, producing the
[B, N] distance matrices used by the subset dist_F (|f \\ a|), the Hamming
dist_A, and the pre-filter validity scans.

The word axis is the innermost grid axis: each step brings one word of the
bq filter rows (a ``(bq, 1)`` column) and of the bn attribute rows (a
``(1, bn)`` row) into VMEM and adds the popcount of their (bq, bn)
broadcast into the resident output tile. Compile time and VMEM therefore
do not grow with W — the boolean kind's truth tables run to 1,024 words.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _make_kernel(op: str):
    def kernel(a_ref, b_ref, o_ref):
        @pl.when(pl.program_id(2) == 0)
        def _():
            o_ref[...] = jnp.zeros(o_ref.shape, jnp.int32)

        a = a_ref[...]                                    # [bq, 1]
        b = b_ref[...]                                    # [1, bn]
        x = (a ^ b) if op == "xor" else (a & ~b)  # "deficit": f & ~a
        o_ref[...] += jax.lax.population_count(x).astype(jnp.int32)
    return kernel


@functools.partial(jax.jit, static_argnames=("op", "bq", "bn", "interpret"))
def bitset_dist(a: jnp.ndarray, b: jnp.ndarray, *, op: str = "xor",
                bq: int = 128, bn: int = 128,
                interpret: bool = False) -> jnp.ndarray:
    """Bitset distance matrix.

    a uint32 [B, W], b uint32 [N, W] -> int32 [B, N].
    op="xor": Hamming (dist_A); op="deficit": popcount(a & ~b) = |a \\ b|
    (dist_F with a=filter bits, b=attribute bits).
    """
    B, W = a.shape
    N, _ = b.shape
    bq, bn = min(bq, B), min(bn, N)
    assert B % bq == 0 and N % bn == 0, (B, N, bq, bn)
    return pl.pallas_call(
        _make_kernel(op),
        grid=(B // bq, N // bn, W),
        in_specs=[
            pl.BlockSpec((None, bq, 1), lambda i, j, w: (w, i, 0)),
            pl.BlockSpec((None, 1, bn), lambda i, j, w: (w, 0, j)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j, w: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.int32),
        interpret=interpret,
    )(a.T[:, :, None], b.T[:, None, :])
