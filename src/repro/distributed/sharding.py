"""Logical-axis sharding: rules map logical axis names -> mesh axes.

MaxText-style indirection: models annotate params/activations with logical
names ("embed", "mlp", "experts", "batch", ...); a rule set binds those to
physical mesh axes per run. Resolution is divisibility-aware: if a tensor
dim is not divisible by the bound mesh-axis product, the binding falls back
to replication for that dim (this is how 40-head attention stays unsharded
on a 16-way model axis while 16-head archs shard — DESIGN.md §4).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class Rules:
    mesh: Mesh
    table: Dict[str, MeshAxes]

    def axis_size(self, binding: MeshAxes) -> int:
        if binding is None:
            return 1
        names = (binding,) if isinstance(binding, str) else binding
        size = 1
        for n in names:
            size *= self.mesh.shape[n]
        return size


def make_rules(mesh: Mesh, overrides: Optional[Dict[str, MeshAxes]] = None
               ) -> Rules:
    """Default binding for the production meshes (DESIGN.md §4)."""
    axes = set(mesh.axis_names)
    dp: MeshAxes = tuple(a for a in ("pod", "data") if a in axes) or None
    tp: MeshAxes = "model" if "model" in axes else None
    fsdp = dp
    table: Dict[str, MeshAxes] = {
        # activations ("seq" -> model = sequence parallelism; decode's T=1
        # falls back to replicated via the divisibility guard)
        "batch": dp, "seq": tp, "act_embed": None,
        "cache_batch": dp if dp else None, "cache_seq": tp,
        "queries": dp, "db_shard": "data" if "data" in axes else None,
        # LM weights: fsdp on embed dim, tensor on mlp/heads/vocab/experts
        "embed": fsdp, "mlp": tp, "vocab": tp,
        "heads": tp, "kv_heads": tp, "head_dim": None,
        "experts": tp, "expert_cap": fsdp, "expert_mlp": None,
        "layers": None, "norm": None,
        # gnn / recsys
        "nodes": dp, "edges": dp, "feat": None,
        "table_rows": (tuple(a for a in ("data", "model") if a in axes)
                       or None),
        "table_dim": None, "fields": None, "mlp_in": fsdp,
        "mlp_hidden": tp, "candidates": tp,
    }
    if overrides:
        table.update(overrides)
    return Rules(mesh, table)


_local = threading.local()


def current_rules() -> Optional[Rules]:
    return getattr(_local, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Optional[Rules]):
    prev = current_rules()
    _local.rules = rules
    try:
        yield rules
    finally:
        _local.rules = prev


def resolve_spec(axes: Sequence[Optional[str]], shape: Sequence[int],
                 rules: Rules) -> P:
    """Logical axes tuple -> PartitionSpec.

    Safety valves: a binding is dropped (replicated) if the dim is not
    divisible by the bound mesh-axis product, or if any of its mesh axes
    was already consumed by an earlier dim of the same tensor.
    """
    parts = []
    used: set = set()
    for dim, name in zip(shape, axes):
        binding = rules.table.get(name) if name else None
        if binding is not None:
            names = (binding,) if isinstance(binding, str) else tuple(binding)
            free = tuple(n for n in names if n not in used)
            binding = (free[0] if len(free) == 1 else free) if free else None
        if binding is not None and dim % rules.axis_size(binding) != 0:
            binding = None  # fall back to replication for this dim
        if binding is not None:
            used.update((binding,) if isinstance(binding, str) else binding)
        parts.append(binding)
    return P(*parts)


def tree_shardings(spec_tree, shape_tree, rules: Rules):
    """Parallel trees of logical-axes tuples + shapes -> NamedShardings."""
    def one(axes, arr):
        shape = arr.shape if hasattr(arr, "shape") else arr
        return NamedSharding(rules.mesh, resolve_spec(axes, shape, rules))
    return jax.tree.map(one, spec_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(e, (str, type(None))) for e in x))


def serve_mesh(n_shards: int) -> Mesh:
    """A ("data",)-axis mesh over the first ``n_shards`` local devices.

    The sharded serving subsystem's mesh shape: row-wise database sharding
    binds to the "data" axis (the ``db_shard`` rule below), queries stay
    replicated. Raises when the host exposes fewer devices — fake more
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    devs = jax.devices()
    if n_shards > len(devs):
        raise ValueError(
            f"n_shards={n_shards} > {len(devs)} visible devices — set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_shards} "
            f"(or lower n_shards)")
    return Mesh(np.asarray(devs[:n_shards]), ("data",))


def put_db_sharded(tree, mesh: Mesh):
    """Place stacked per-shard arrays ([S, ...] leaves) on ``mesh`` with the
    leading dim split over the "data" axis; trailing dims stay whole.

    One ``jax.device_put`` per leaf (a no-op for a leaf already placed so).
    There is no replication fallback: a leading dim that is not the
    data-axis size is an error, never a silent copy of every shard onto
    every device.
    """
    S = int(mesh.shape["data"])

    def one(x):
        if x.shape[0] != S:
            raise ValueError(f"stacked leaf {x.shape} does not carry one "
                             f"row block per shard of the {S}-way mesh")
        return jax.device_put(x, NamedSharding(mesh, P("data")))
    return jax.tree.map(one, tree)


def stack_shards(trees: Sequence, mesh: Mesh):
    """Per-shard pytrees (shard s's leaves ``[n_loc, ...]``) -> stacked
    ``[S, n_loc, ...]`` arrays split over the mesh's "data" axis.

    Each global array is assembled from single-device pieces
    (``jax.make_array_from_single_device_arrays``): shard s's leaf moves to
    the s-th mesh device if it is not already there, and no device ever
    holds another shard's rows — there is no stack on one device first.
    """
    devs = list(mesh.devices.flat)
    if len(trees) != len(devs):
        raise ValueError(f"{len(trees)} shards for a {len(devs)}-device "
                         f"mesh")
    sharding = NamedSharding(mesh, P("data"))

    def assemble(*leaves):
        pieces = [jax.device_put(jnp.expand_dims(x, 0), d)
                  for x, d in zip(leaves, devs)]
        shape = (len(pieces),) + tuple(pieces[0].shape[1:])
        return jax.make_array_from_single_device_arrays(shape, sharding,
                                                        pieces)
    return jax.tree.map(assemble, *trees)


def logical_constraint(x, axes: Sequence[Optional[str]]):
    """with_sharding_constraint via the ambient rule set (no-op if unset)."""
    rules = current_rules()
    if rules is None:
        return x
    spec = resolve_spec(axes, x.shape, rules)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))
