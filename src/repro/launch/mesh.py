"""Production mesh builders. TPU v5e pod = 16x16 = 256 chips; multi-pod adds
a leading "pod" axis (2 pods = 512 chips for the dry-run).

Functions, not module constants: importing this module never touches jax
device state.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_local_mesh(model: int = 1):
    """Debug mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


HW = dict(  # TPU v5e per-chip constants used by the roofline
    peak_flops=197e12,      # bf16
    hbm_bw=819e9,           # bytes/s
    link_bw=50e9,           # bytes/s per ICI link
    hbm_bytes=16 * 2 ** 30,
)
