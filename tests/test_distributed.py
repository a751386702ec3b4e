"""Distributed JAG (shard_map) + sharding-rule resolution tests.

Multi-device cases run in a subprocess with faked host devices so the rest
of the suite keeps seeing 1 device (the dry-run sets its own flags)."""
import subprocess
import sys
import os

import jax

from jax.sharding import PartitionSpec as P


def test_resolve_spec_divisibility_and_dedup():
    from types import SimpleNamespace
    from repro.distributed.sharding import Rules, resolve_spec
    mesh = SimpleNamespace(shape={"data": 4})   # resolution is mesh-shape-only
    rules = Rules(mesh, {"a": "data", "b": "data", "c": None})
    # divisible -> bound; non-divisible -> dropped
    assert resolve_spec(("a",), (4,), rules) == P("data")
    assert resolve_spec(("a",), (3,), rules) == P(None)
    # duplicate mesh axis across dims -> later dim replicated
    assert resolve_spec(("a", "b"), (4, 4), rules) == P("data", None)
    assert resolve_spec(("c", "a"), (4, 4), rules) == P(None, "data")


def test_production_rules_cover_all_model_specs():
    from types import SimpleNamespace
    from repro.configs import get
    from repro.distributed.sharding import make_rules, resolve_spec

    # shape-only stand-in for the 512-chip mesh (1 real device here)
    mesh = SimpleNamespace(axis_names=("pod", "data", "model"),
                           shape={"pod": 2, "data": 16, "model": 16})
    rules = make_rules(mesh)
    # every logical name used by the models must resolve without KeyError
    from repro.models import transformer as T
    key = jax.random.PRNGKey(0)
    for arch in ("qwen3-1.7b", "llama4-scout-17b-a16e"):
        cfg = get(arch).make_reduced()
        _, specs = T.init_params(cfg, key)
        for axes in jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(x, tuple)):
            resolve_spec(axes, (8,) * len(axes), rules)


def test_shard_map_serve_and_build_subprocess():
    """End-to-end distributed serve+build on 8 fake devices."""
    code = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from repro.core import JAGConfig, JAGIndex, range_table
from repro.core.distributed import make_serve_step, ShardedServeConfig
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rng = np.random.default_rng(0)
S, Nloc, d = 8, 300, 8
xb = rng.normal(size=(S, Nloc, d)).astype(np.float32)
vals = rng.uniform(0, 100, (S, Nloc)).astype(np.float32)
cfg = JAGConfig(degree=10, ls_build=16, batch_size=128, cand_pool=48)
graphs, entries = [], []
for s in range(S):
    idx = JAGIndex.build(xb[s], range_table(vals[s]), cfg)
    graphs.append(np.asarray(idx.graph))
    entries.append(np.resize(np.atleast_1d(np.asarray(idx.entry)), 4))
graphs = np.stack(graphs); entries = np.stack(entries).astype(np.int32)
xbn = (xb.astype(np.float64)**2).sum(-1).astype(np.float32)
B = 16
q = rng.normal(size=(B, d)).astype(np.float32)
lo = rng.uniform(0, 90, B).astype(np.float32)
step = jax.jit(make_serve_step(mesh, ShardedServeConfig(k=5, ls=24,
    max_iters=48, query_chunk=8), "range", "range"))
with jax.set_mesh(mesh):
    ids, prim, sec = step(jnp.asarray(graphs), jnp.asarray(xb),
        jnp.asarray(xbn), {"value": jnp.asarray(vals)},
        jnp.asarray(entries), jnp.asarray(q),
        {"lo": jnp.asarray(lo), "hi": jnp.asarray(lo + 10)})
ids = np.asarray(ids); prim = np.asarray(prim)
xf = xb.reshape(-1, d); vf = vals.reshape(-1)
d2 = ((q[:, None] - xf[None])**2).sum(-1)
mask = (vf[None] >= lo[:, None]) & (vf[None] <= (lo+10)[:, None])
d2m = np.where(mask, d2, np.inf)
recs = []
for b in range(B):
    gt = [j for j in np.argsort(d2m[b])[:5] if d2m[b, j] < np.inf]
    got = [i for i, p in zip(ids[b], prim[b]) if p == 0 and i >= 0]
    if gt: recs.append(len(set(gt) & set(got)) / len(gt))
rec = float(np.mean(recs))
assert rec > 0.75, rec
print("SUBPROC_OK", rec)
'''
    r = subprocess.run([sys.executable, "-c", code],
                       cwd="/root/repo", capture_output=True, text=True,
                       env=dict(os.environ, PYTHONPATH="src"))
    assert "SUBPROC_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


def test_int8_reg_dist_batch_invariance():
    """JAG002 fix (analysis PR): the int8_reg in-register distance now
    uses distances.gathered_dot, so per-query results are BITWISE
    identical across query_chunk regroupings. The einsum("bcd,bd->bc")
    it replaced lowers to a batched dot whose reduction vectorization
    varies with the chunk batch size — exactly the call-site shape this
    test varies (one 16-query chunk vs two 8-query chunks)."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core import JAGConfig, JAGIndex, range_table
    from repro.core.distributed import ShardedServeConfig, make_serve_step
    from repro.core.quantized import quantize_int8
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rng = np.random.default_rng(3)
    n, d, B = 240, 8, 16
    xb = rng.normal(size=(n, d)).astype(np.float32)
    vals = rng.uniform(0, 100, n).astype(np.float32)
    idx = JAGIndex.build(xb, range_table(vals),
                         JAGConfig(degree=10, ls_build=16, batch_size=128,
                                   cand_pool=48))
    xq, scale = quantize_int8(idx.xb)
    q = rng.normal(size=(B, d)).astype(np.float32)
    lo = rng.uniform(0, 90, B).astype(np.float32)
    args = (jnp.asarray(idx.graph)[None], jnp.asarray(xq)[None],
            jnp.asarray(idx.xb_norm)[None],
            {"value": jnp.asarray(vals)[None]},
            jnp.asarray(np.resize(np.atleast_1d(np.asarray(idx.entry)),
                                  4).astype(np.int32))[None],
            jnp.asarray(q),
            {"lo": jnp.asarray(lo), "hi": jnp.asarray(lo + 10)},
            jnp.asarray(scale))
    outs = []
    with jax.set_mesh(mesh):
        for chunk in (16, 8):  # 1x16 vs 2x8: different GEMM batch sizes
            step = jax.jit(make_serve_step(
                mesh, ShardedServeConfig(k=5, ls=24, max_iters=48,
                                         query_chunk=chunk),
                "range", "range", variant="int8_reg"))
            outs.append([np.asarray(x) for x in step(*args)])
    (i1, p1, s1), (i2, p2, s2) = outs
    np.testing.assert_array_equal(i1, i2)
    assert p1.tobytes() == p2.tobytes()   # bitwise, not approx
    assert s1.tobytes() == s2.tobytes()


def test_hlo_collective_parser():
    from repro.launch.hlo_stats import collective_bytes
    txt = """
  %ar = f32[128,256]{1,0} all-reduce(%x), replica_groups={}
  %ag.1 = bf16[64,64]{1,0} all-gather(%y), dimensions={0}
  %cp = (f32[8,8]{1,0}, f32[8,8]{1,0}) collective-permute(%a, %b)
  %notacoll = f32[4,4]{1,0} add(%p, %q)
"""
    out = collective_bytes(txt)
    assert out["all-reduce"] == 128 * 256 * 4
    assert out["all-gather"] == 64 * 64 * 2
    assert out["collective-permute"] == 2 * 8 * 8 * 4
    assert out["total"] == sum(v for k, v in out.items() if k != "total")
    from repro.launch.hlo_stats import collective_counts
    assert collective_counts(txt) == {"all-reduce": 1, "all-gather": 1,
                                      "collective-permute": 1}
    # operand references and -done halves are not op instances
    assert collective_counts("  ROOT %t = f32[4]{0} tuple(%all-gather.1)\n"
                             "  %d = f32[4]{0} all-gather-done(%s)\n") == {}
