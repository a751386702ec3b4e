"""Compile the serving path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers a kernel (or the jitted prefilter route) at
the widths the serving path uses and compiles it with the TPU compiler for
one chip of a described ``v5e:2x2`` topology, which refuses what the chip
would refuse (block shapes off the (8, 128) tiling, VMEM over the scoped
limit). Each compiled program must hold the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests; where it cannot be described,
the tests skip.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import filters as F
from repro.kernels import bitset, fused_expand, gather_dist
from repro.kernels.gather_dist import scan_tile

N_SCAN = 1 << 20      # rows of the per-chip prefilter scan
B = 256               # queries per batch


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip cannot be read back from the
    # persistent cache, so keep them out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **kw):
    t0 = time.perf_counter()
    text = fn.lower(*args, **kw).compile().as_text()
    return text, time.perf_counter() - t0


@pytest.mark.parametrize("n_q", [B, 170])
@pytest.mark.parametrize("d", [104, 128, 768])
def test_gather_dist_tile_compiles(one_chip, d, n_q):
    """One tile against a whole query group: the module's batch and the
    range-mixed cell's prefilter group of 170, padded to 128-row blocks."""
    tile = scan_tile(d)
    assert tile == (1024 if d == 768 else 4096)
    text, _ = _compile(
        gather_dist.gather_dist_tile,
        _spec(one_chip, (N_SCAN, d), jnp.float32),
        _spec(one_chip, (), jnp.int32),
        _spec(one_chip, (n_q, d), jnp.float32), tile=tile)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("W", [1, 32, 1024])
def test_bitset_deficit_compiles(one_chip, W):
    """W=1024 is the boolean kind's truth table at msturing_bool's
    n_vars=15; the word loop is a grid axis, so compile time stays flat."""
    text, secs = _compile(
        bitset.bitset_dist,
        _spec(one_chip, (B, W), jnp.uint32),
        _spec(one_chip, (4096, W), jnp.uint32), op="deficit")
    print(f"bitset deficit W={W}: compiled in {secs:.2f}s")
    assert "tpu_custom_call" in text
    assert secs < 30.0, secs


def test_gather_dist_compiles(one_chip):
    text, _ = _compile(
        gather_dist.gather_dist,
        _spec(one_chip, (N_SCAN, 128), jnp.float32),
        _spec(one_chip, (B, 80), jnp.int32),
        _spec(one_chip, (B, 128), jnp.float32))
    assert "tpu_custom_call" in text


def test_fused_expand_compiles(one_chip):
    d, attr_words = 128, 2       # range value + one subset word
    text, _ = _compile(
        fused_expand.fused_expand,
        _spec(one_chip, (N_SCAN, d + 1 + attr_words), jnp.float32),
        _spec(one_chip, (B, 80), jnp.int32),
        _spec(one_chip, (B, d), jnp.float32),
        _spec(one_chip, (B,), jnp.float32), d=d)
    assert "tpu_custom_call" in text


def test_prefilter_route_compiles(one_chip, monkeypatch):
    """The jitted exact scan the prefilter route serves, at the per-chip
    scan size, over a joint range+subset table with a compound filter:
    both the tile scan and the bitset kernel must be in the program."""
    from repro.core.ground_truth import exact_filtered_knn
    from repro.kernels import ops
    # this process's backend is the CPU, whose kernels interpret; the
    # program compiled here is the chip's, so compile them for Mosaic
    monkeypatch.setattr(ops, "_interp", lambda explicit: False)
    rng = np.random.default_rng(0)
    table = F.joint_table(
        F.range_table(rng.uniform(0, 1, 8).astype(np.float32)),
        F.subset_table(rng.random((8, 30)) < 0.5, 30))
    attr = jax.tree.map(
        lambda v: _spec(one_chip, (N_SCAN,) + v.shape[1:], v.dtype), table)
    lo = np.zeros(B, np.float32)
    filt = F.Range(lo, lo + 0.01) & F.Subset(rng.random((B, 30)) < 0.1)
    filt = jax.tree.map(lambda v: _spec(one_chip, v.shape, v.dtype), filt)
    text, secs = _compile(
        exact_filtered_knn,
        _spec(one_chip, (N_SCAN, 128), jnp.float32), attr,
        _spec(one_chip, (B, 128), jnp.float32), filt,
        k=10, block=scan_tile(128), use_kernel=True)
    print(f"prefilter route: compiled in {secs:.2f}s")
    assert text.count("tpu_custom_call") >= 2
