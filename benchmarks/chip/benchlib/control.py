"""The control and the planted faults, which ``correct`` must reject.

The control is the plain reference put in the program's place and computed
one precision below what the configuration states. The configuration
states float32 vectors and distances, and the program computes them at
``Precision.HIGHEST``; so the control computes the brute-force filtered
top-k on the device with the distance product at ``Precision.HIGH``, the
three-pass step a later change to the scan would be tempted to take.
``DEFAULT`` (one pass over bf16-rounded queries and rows with float32
sums, written out so that the CPU computes the same as the chip) is read
beside it. The CPU computes ``HIGH`` in float32, so a test on the CPU
reads ``DEFAULT`` in its place.

The faults are planted in the program's own answers or steps:

- ``altered``: one returned row of every query replaced by another row,
  where the answer is produced;
- ``half``: half of every batch left out (no rows returned);
- ``unchanged`` (ingest): the insert step returns the index unchanged.

None of this runs in the benchmark's own runs: ``calibrate.py`` reads it
on the chip, and ``tests/test_control.py`` at a size a test run holds.
"""
from __future__ import annotations

import numpy as np

PASSES = {"HIGH": 3, "DEFAULT": 1}


def control_server(xb: np.ndarray, attr: np.ndarray, spec: dict, k: int,
                   precision: str):
    """A ``serve(batch)`` that answers every query exactly, on the device,
    with the distance product at ``precision``."""
    import jax
    import jax.numpy as jnp
    passes = PASSES[precision]
    x = jnp.asarray(xb)
    xn = jnp.sum(x * x, axis=1)
    a = jnp.asarray(attr)

    @jax.jit
    def run(q, f0, f1):
        if passes == 1:
            qx = jnp.matmul(q.astype(jnp.bfloat16), x.astype(jnp.bfloat16).T,
                            preferred_element_type=jnp.float32)
        else:
            qx = jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGH)
        d2 = (xn[None, :] - 2.0 * qx + jnp.sum(q * q, axis=1)[:, None])
        ok = ((a[None, :] >= f0[:, None]) & (a[None, :] <= f1[:, None]))
        d2 = jnp.where(ok, jnp.maximum(d2, 0.0), jnp.inf)
        neg, ids = jax.lax.top_k(-d2, k)
        return jnp.where(jnp.isinf(neg), -1, ids), -neg

    def serve(batch):
        f = batch.filt
        f0, f1 = (f["lo"], f["hi"]) if "lo" in f else (f["label"], f["label"])
        ids, d2 = run(jnp.asarray(batch.queries), jnp.asarray(f0),
                      jnp.asarray(f1))
        ids = np.asarray(ids)
        return ids, np.asarray(d2), ("prefilter",) * ids.shape[0]
    return serve


def altered(answer, n_rows: int, seed: int):
    """One returned row of every query replaced by another row."""
    ids, d2, routes = answer
    ids = ids.copy()
    rng = np.random.default_rng(seed)
    col = rng.integers(0, ids.shape[1], ids.shape[0])
    ids[np.arange(ids.shape[0]), col] = rng.integers(0, n_rows, ids.shape[0])
    return ids, d2, routes


def half(answer, seed: int):
    """Half of the batch left out: those queries return no rows."""
    ids, d2, routes = answer
    ids, d2 = ids.copy(), d2.copy()
    rng = np.random.default_rng(seed)
    out = rng.permutation(ids.shape[0])[:ids.shape[0] // 2]
    ids[out], d2[out] = -1, np.inf
    return ids, d2, routes
