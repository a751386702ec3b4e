"""The work an algorithm needs, counted from shapes.

A kernel's roofline share is the least time the chip could take for this
work, the larger of operations over peak FLOP/s and bytes over peak
bytes/s, divided by the device time measured. The work is that of the
algorithm, not of one implementation's traffic, so a later change to how
the work is done is read against the same numbers.
"""
from __future__ import annotations


def prefilter_scan(queries: int, rows: int, dim: int,
                   attr_bytes_per_row: int) -> dict:
    """Exact filtered scan of ``rows`` rows for ``queries`` queries: one
    multiply-add per query, row and dimension; the float32 rows and their
    attributes read once, the float32 queries read once."""
    return {"flops": 2.0 * queries * rows * dim,
            "bytes": 4.0 * rows * dim + attr_bytes_per_row * rows
            + 4.0 * queries * dim}


def least_seconds(work: dict, peak: dict) -> float:
    return max(work["flops"] / peak["flops_bf16"],
               work["bytes"] / peak["hbm_bytes_per_s"])
