"""Logical-axis → mesh-axis sharding rules (divisibility-aware).

The params / caches carry *logical* axis names ("embed", "heads", "vocab",
"expert", "batch", "seq", ...). This module maps them onto the physical mesh:

  TP    over "model"  — heads / kv_heads / mlp / vocab / expert (EP)
  FSDP  over "data"   — the "embed" axis of weight matrices
  DP    over ("pod","data") — the "batch" axis of inputs/activations/caches
  SP    over "data"   — "seq" fallback when batch doesn't divide (long_500k)

Rules are *candidate chains*: each logical name lists mesh axes to try in
order; a candidate is taken only if (a) the dim divides evenly and (b) the
mesh axis isn't already used by another dim of the same tensor. This is what
lets kv_heads=8 fall through to head_dim sharding on a 16-way model axis,
and batch=1 fall through to sequence sharding.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


# Each logical axis name maps to a list of candidates; a candidate is either
# a mesh-axis name or a tuple of mesh-axis names (sharded jointly).
@dataclasses.dataclass(frozen=True)
class ShardingRules:
    rules: tuple

    def candidates(self, logical: str | None):
        if logical is None:
            return ()
        return dict(self.rules).get(logical, ())


#: mesh axis the sharded search engine partitions *index* data over
#: (graph neighbor lists, quantized codes, attribute bundles). Composes
#: with the batch axis ("data") as a 2-D (batch × index) search mesh.
INDEX_AXIS = "index"

DEFAULT_RULES = ShardingRules(
    rules=(
        ("vocab", ("model",)),
        ("shard", (INDEX_AXIS,)),     # per-shard index data (search scale-out)
        ("embed", ("data",)),         # FSDP
        ("heads", ("model",)),
        ("kv_heads", ("model",)),
        ("head_dim", ("model",)),     # fallback when kv_heads can't take model
        ("mlp", ("model",)),
        ("expert", ("model",)),       # EP
        ("capacity", (("data",),)),   # MoE buffer token dim (EP × DP)
        ("layers", ()),
        ("batch", (("pod", "data"), ("data",),)),
        # seq falls through to "model" when DP consumed the data axis:
        # decode caches become sequence-parallel (flash-decoding style --
        # per-token collectives shrink from cache-sized AG to score-sized AR)
        ("seq", (("pod", "data"), ("data",), ("model",))),
        ("embed2", ()),
    )
)


def _axis_size(mesh: Mesh, cand) -> int:
    if isinstance(cand, tuple):
        return int(np.prod([mesh.shape[a] for a in cand]))
    return mesh.shape[cand]


def _mesh_axes(cand):
    return cand if isinstance(cand, tuple) else (cand,)


def spec_for(mesh: Mesh, shape, logical_axes, rules: ShardingRules = DEFAULT_RULES,
             ) -> PartitionSpec:
    """Build a PartitionSpec for one array given its logical axes."""
    assert len(shape) == len(logical_axes), (shape, logical_axes)
    used: set = set()
    out = []
    for dim, name in zip(shape, logical_axes):
        placed = None
        for cand in rules.candidates(name):
            axes = _mesh_axes(cand)
            if any(a not in mesh.shape for a in axes):
                continue
            if any(a in used for a in axes):
                continue
            if dim % _axis_size(mesh, cand) != 0:
                continue
            # singleton tuples denote the same sharding as the bare axis name
            # but PartitionSpec(('data',)) != PartitionSpec('data') — unwrap.
            placed = cand[0] if isinstance(cand, tuple) and len(cand) == 1 else cand
            used.update(axes)
            break
        out.append(placed)
    # trim trailing Nones for cleanliness
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def tree_shardings(mesh: Mesh, value_tree, axes_tree,
                   rules: ShardingRules = DEFAULT_RULES):
    """Map (values, logical-axes) trees -> NamedSharding tree."""

    def one(v, ax):
        return NamedSharding(mesh, spec_for(mesh, v.shape, ax, rules))

    # value_tree's array leaves define the structure; axes_tree's tuple
    # leaves are matched "up to" that structure by jax.tree.map.
    return jax.tree.map(one, value_tree, axes_tree)


def batch_spec(mesh: Mesh, global_batch: int,
               rules: ShardingRules = DEFAULT_RULES) -> PartitionSpec:
    """Sharding for a [B, ...] input batch dim (replicate if indivisible)."""
    for cand in rules.candidates("batch"):
        axes = _mesh_axes(cand)
        if any(a not in mesh.shape for a in axes):
            continue
        if global_batch % _axis_size(mesh, cand) == 0:
            if isinstance(cand, tuple) and len(cand) == 1:
                cand = cand[0]
            return PartitionSpec(cand)
    return PartitionSpec(None)


def search_mesh_2d(n_shards: int, devices=None) -> Mesh | None:
    """2-D ("data", "index") mesh for index-axis-sharded search.

    The index axis gets the largest device divisor that also divides
    `n_shards` (each index device then owns n_shards/index whole shards);
    the rest of the devices parallelize the batch. Returns None on a
    single device — the sharded engine's loop path needs no mesh.
    """
    from repro.distributed.fault_tolerance import best_search_mesh_shape

    devices = list(jax.devices()) if devices is None else list(devices)
    if len(devices) <= 1:
        return None
    shape, names = best_search_mesh_shape(len(devices), n_shards)
    n_used = int(np.prod(shape))
    return Mesh(np.asarray(devices[:n_used]).reshape(shape), names)


def _ambient_mesh():
    from jax._src import mesh as mesh_lib

    m = mesh_lib.thread_resources.env.physical_mesh
    return None if m.empty else m


def constrain(x, logical_axes, rules: ShardingRules = DEFAULT_RULES):
    """with_sharding_constraint by logical axis names, if a mesh is active.

    No-op outside a `with mesh:` context (CPU smoke tests). This is how the
    model pins activation shardings (batch over DP, seq over SP fallback)
    so GSPMD doesn't drift into replicated-batch weight-stationary layouts.
    """
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    import jax

    spec = spec_for(mesh, x.shape, logical_axes, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
