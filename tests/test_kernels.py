"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.l2dist import l2dist as l2_raw
from repro.kernels.gather_dist import gather_dist_tile
from repro.kernels.bitset import bitset_dist


@pytest.mark.parametrize("B,N,d,dtype", [
    (8, 32, 16, np.float32),
    (128, 256, 128, np.float32),
    (64, 100, 48, np.float32),     # padding path
    (33, 257, 130, np.float32),    # awkward shapes
    (16, 64, 32, jnp.bfloat16),
])
def test_l2dist_matches_ref(B, N, d, dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, d)), dtype)
    xb = jnp.asarray(rng.normal(size=(N, d)), dtype)
    got = ops.l2dist(q, xb, interpret=True)
    want = ref.l2dist_ref(q, xb)
    tol = 1e-5 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * 10)


def test_l2dist_raw_blocked_grid():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
    xb = jnp.asarray(rng.normal(size=(512, 256)), jnp.float32)
    got = l2_raw(q, xb, bq=128, bn=256, bd=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.l2dist_ref(q, xb)),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("B,C,N,d", [(4, 8, 64, 16), (16, 32, 200, 64),
                                     (2, 5, 33, 128)])
def test_gather_dist_matches_ref(B, C, N, d):
    rng = np.random.default_rng(2)
    xb = jnp.asarray(rng.normal(size=(N, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, N, (B, C)), jnp.int32)
    got = ops.gather_dist(xb, ids, q, interpret=True)
    want = ref.gather_dist_ref(xb, ids, q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("B,N,d,tile", [
    (8, 256, 32, 64),
    (170, 8192, 104, 4096),    # range-mixed's prefilter group, 2 blocks
    (1, 256, 104, 64),         # one query: one 8-row block
    (60, 120, 104, 60),        # the delta route's short unaligned block
])
def test_gather_dist_tile(B, N, d, tile):
    rng = np.random.default_rng(3)
    xb = rng.normal(size=(N, d)).astype(np.float32)
    q = rng.normal(size=(B, d)).astype(np.float32)
    x64, q64 = xb.astype(np.float64), q.astype(np.float64)
    for bi in sorted({0, N // tile - 1}):
        got = gather_dist_tile(jnp.asarray(xb), jnp.int32(bi), jnp.asarray(q),
                               tile=tile, interpret=True)
        assert got.shape == (B, tile) and got.dtype == jnp.float32
        rows = x64[bi * tile:(bi + 1) * tile]
        want = ((q64[:, None, :] - rows[None]) ** 2).sum(-1)
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=1e-5, atol=1e-4)


def test_gather_dist_tile_row_independent_of_group():
    """A query's row is bit-identical scored alone or in a group of 170:
    the per-row property serve/dispatch.py documents."""
    rng = np.random.default_rng(5)
    N, d, tile = 512, 104, 256
    xb = jnp.asarray(rng.normal(size=(N, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(170, d)), jnp.float32)
    group = np.asarray(gather_dist_tile(xb, jnp.int32(1), q, tile=tile,
                                        interpret=True))
    for b in (0, 127, 128, 169):
        alone = np.asarray(gather_dist_tile(xb, jnp.int32(1), q[b:b + 1],
                                            tile=tile, interpret=True))
        np.testing.assert_array_equal(alone[0], group[b])


@pytest.mark.parametrize("B,N,W", [(8, 16, 1), (64, 128, 4), (33, 77, 7)])
@pytest.mark.parametrize("op", ["xor", "deficit"])
def test_bitset_matches_ref(B, N, W, op):
    rng = np.random.default_rng(4)
    a = jnp.asarray(rng.integers(0, 2 ** 32, (B, W), dtype=np.uint64),
                    jnp.uint32)
    b = jnp.asarray(rng.integers(0, 2 ** 32, (N, W), dtype=np.uint64),
                    jnp.uint32)
    if op == "xor":
        got = ops.hamming(a, b, interpret=True)
        want = ref.hamming_ref(a, b)
    else:
        got = ops.subset_deficit(a, b, interpret=True)
        want = ref.subset_deficit_ref(a, b)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bitset_raw_grid():
    rng = np.random.default_rng(5)
    a = jnp.asarray(rng.integers(0, 2 ** 32, (256, 2), dtype=np.uint64),
                    jnp.uint32)
    b = jnp.asarray(rng.integers(0, 2 ** 32, (256, 2), dtype=np.uint64),
                    jnp.uint32)
    got = bitset_dist(a, b, op="xor", bq=128, bn=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.hamming_ref(a, b)))


def _np_popcount_words(w):
    """Independent numpy popcount reference: bytes -> unpackbits -> sum."""
    w = np.asarray(w, np.uint32)
    by = w.view(np.uint8).reshape(w.shape + (4,))
    return np.unpackbits(by, axis=-1).sum(axis=(-1, -2)).astype(np.int32)


@pytest.mark.parametrize("B,N,W", [(3, 5, 1), (130, 257, 3)])
def test_bitset_matches_numpy_popcount(B, N, W):
    """xor/deficit vs a from-scratch numpy unpackbits oracle (the jnp ref
    shares population_count with the kernel; this one shares nothing),
    including shapes that exercise the 128-row padding path."""
    rng = np.random.default_rng(7)
    a = rng.integers(0, 2 ** 32, (B, W), dtype=np.uint64).astype(np.uint32)
    b = rng.integers(0, 2 ** 32, (N, W), dtype=np.uint64).astype(np.uint32)
    want_xor = _np_popcount_words(a[:, None, :] ^ b[None, :, :])
    want_def = _np_popcount_words(a[:, None, :] & ~b[None, :, :])
    got_xor = ops.hamming(jnp.asarray(a), jnp.asarray(b), interpret=True)
    got_def = ops.subset_deficit(jnp.asarray(a), jnp.asarray(b),
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(got_xor), want_xor)
    np.testing.assert_array_equal(np.asarray(got_def), want_def)


@pytest.mark.parametrize("kind", ["subset", "boolean", "compound"])
def test_prefilter_scan_kernel_validity_bit_identical(kind):
    """exact_filtered_knn with use_kernel=True routes subset/boolean leaf
    validity through the bitset kernel — results (ids, d2, n_dist, n_feval)
    must be bit-identical to the dense comparator path."""
    from repro.core import filters as F
    from repro.core.filters import Boolean, Subset
    from repro.core.ground_truth import exact_filtered_knn
    rng = np.random.default_rng(8)
    N, d, B, L = 300, 16, 6, 24
    xb = rng.normal(size=(N, d)).astype(np.float32)
    q = rng.normal(size=(B, d)).astype(np.float32)
    bits = rng.random((N, L)) < 0.5
    assign = rng.integers(0, 1 << 8, N).astype(np.uint32)
    if kind == "subset":
        tab = F.subset_table(bits, L)
        fb = np.zeros((B, L), bool)
        fb[:, :3] = True
        filt = F.subset_filters(fb, L)
    elif kind == "boolean":
        tab = F.boolean_table(assign, 8)
        sat = rng.random((B, 1 << 8)) < 0.3
        filt = F.boolean_filters(sat, 8)
    else:
        L2 = 12          # joint tables share one n_bits across bit kinds
        tab = F.joint_table(F.subset_table(bits[:, :L2], L2),
                            F.boolean_table(assign % (1 << L2), L2))
        fb = np.zeros((B, L2), bool)
        fb[:, :2] = True
        sat = rng.random((B, 1 << L2)) < 0.5
        filt = Subset(fb) & ~Boolean(sat, L2)
    gt0 = exact_filtered_knn(xb, tab, q, filt, k=10, block=128,
                             use_kernel=False)
    gt1 = exact_filtered_knn(xb, tab, q, filt, k=10, block=128,
                             use_kernel=True)
    # validity must be bit-identical (same survivors, same scan counts,
    # same short-circuit evals); d2 comes from a different distance
    # kernel (tile scan vs norms+matmul), so it is allclose, not bitwise
    for f in ("ids", "n_dist", "n_feval"):
        np.testing.assert_array_equal(np.asarray(getattr(gt0, f)),
                                      np.asarray(getattr(gt1, f)),
                                      err_msg=(kind, f))
    np.testing.assert_allclose(np.asarray(gt0.d2), np.asarray(gt1.d2),
                               rtol=1e-4, atol=1e-4)
    assert int(np.asarray(gt0.n_dist).sum()) > 0


def test_kernel_agrees_with_core_distance_path():
    """gather_dist must agree with the beam-search gathered_d2 helper."""
    from repro.core.distances import gathered_d2, sq_norms
    rng = np.random.default_rng(6)
    N, d, B, C = 128, 32, 8, 16
    xb = jnp.asarray(rng.normal(size=(N, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, N, (B, C)), jnp.int32)
    want = gathered_d2(xb, sq_norms(xb), ids, q, sq_norms(q))
    got = ops.gather_dist(xb, ids, q, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
