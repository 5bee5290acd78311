"""Logical-axis sharding rules -> mesh PartitionSpecs.

Default profile (DESIGN.md §5): Megatron-style TP over "model" for
heads/kv/mlp/experts/vocab + FSDP over the data axes ("pod","data") on the
embed dimension of every weight (ZeRO-3: params, grads and optimizer state
all fully sharded). Rules are divisibility-aware: a logical axis whose size
does not divide the mesh axis falls back to replication (recorded so the
dry-run report can flag it).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs.base import MeshConfig


def make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis of type Auto."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh_from_config(mc: MeshConfig) -> Mesh:
    return make_mesh(mc.shape, mc.axes)


def mesh_config_for(mesh: Mesh, **kw) -> MeshConfig:
    """Derive a MeshConfig matching an existing mesh: pure-FSDP when the
    mesh has no "model" axis (small-model data-parallel training), the
    default TP+FSDP profile otherwise. ``kw`` overrides profile knobs."""
    shape = tuple(mesh.shape[a] for a in mesh.axis_names)
    kw.setdefault(
        "profile",
        "tp_fsdp" if "model" in mesh.axis_names else "pure_fsdp")
    return MeshConfig(shape=shape, axes=tuple(mesh.axis_names), **kw)


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def axis_size(mesh: Mesh, axes) -> int:
    """Total number of shards over `axes` (None -> 1)."""
    return _axis_size(mesh, axes)


def data_axes_for(mesh: Mesh) -> Tuple[str, ...]:
    """Default data-parallel axes of a mesh: the conventional ("pod",
    "data") names when present, else every axis (pure-DP meshes)."""
    named = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    return named or tuple(mesh.axis_names)


def pad_leading(arr, multiple: int):
    """Pad ``arr``'s leading axis up to a ``multiple`` by replicating the
    first slice (a real, finite element — padded lanes must run the same
    numerics as live ones so vmapped/shard_mapped batches stay NaN-free).
    Callers slice the result back to the original length."""
    import jax.numpy as jnp
    b = arr.shape[0]
    pad = (-b) % max(multiple, 1)
    if pad == 0:
        return arr
    fill = jnp.broadcast_to(arr[:1], (pad,) + tuple(arr.shape[1:]))
    return jnp.concatenate([arr, fill], axis=0)


def logical_to_pspec(spec: Tuple, shape: Tuple[int, ...], mesh: Mesh,
                     mc: MeshConfig) -> P:
    """Map one leaf's logical axis names to a PartitionSpec."""
    fsdp_axes = tuple(mc.data_axes) if mc.fsdp else None
    if mc.profile == "pure_fsdp":
        # no tensor parallelism: everything replicated except the FSDP
        # (embed) axis, which shards over the whole mesh
        rules: Dict[Optional[str], Any] = {"embed": fsdp_axes}
        out = []
        for dim, name in zip(shape, spec):
            axes = rules.get(name, None)
            if axes is not None and dim % _axis_size(mesh, axes) != 0:
                axes = None
            out.append(axes)
        return P(*out)
    rules: Dict[Optional[str], Any] = {
        None: None,
        "layers": None,
        "vocab": "model",
        "heads": "model",
        "kv": "model",
        "mlp": "model",
        "ssm": "model",
        "ssm_heads": "model",
        "experts": "model",
        "mlp_noshard": None,
        "embed": fsdp_axes,
    }
    out = []
    for dim, name in zip(shape, spec):
        axes = rules.get(name, None)
        if axes is not None and dim % _axis_size(mesh, axes) != 0:
            axes = None  # divisibility fallback -> replicate
        out.append(axes)
    return P(*out)


def param_shardings(mesh: Mesh, mc: MeshConfig, params, specs):
    """Pytree of NamedShardings matching a (params, specs) pair.

    ``specs`` mirrors ``params`` down to the leaves, where it holds a tuple
    of logical axis names (flatten_up_to semantics of tree.map).
    """
    def one(leaf, spec):
        return NamedSharding(mesh, logical_to_pspec(
            tuple(spec), leaf.shape, mesh, mc))

    return jax.tree.map(one, params, specs)


def batch_axes(mesh: Mesh, mc: MeshConfig, batch: int):
    axes = tuple(mc.data_axes)
    if batch % _axis_size(mesh, axes) == 0:
        return axes
    for sub in (axes[:1], ()):
        if not sub or batch % _axis_size(mesh, sub) == 0:
            return sub or None
    return None


def batch_sharding(mesh: Mesh, mc: MeshConfig, batch: int) -> NamedSharding:
    return NamedSharding(mesh, P(batch_axes(mesh, mc, batch)))


def _first_fit(mesh: Mesh, axis: str, dims, candidates):
    """Pick the first dim index (from candidates) divisible by the axis."""
    n = mesh.shape[axis]
    for i in candidates:
        if dims[i] % n == 0 and dims[i] >= n:
            return i
    return None


def cache_shardings(cfg, mesh: Mesh, mc: MeshConfig, cache):
    """Decode-cache shardings: batch over data axes; KV sequence over
    "model" (context-parallel decode) when divisible, else heads/head_dim.
    """
    b_axes = None

    def shard_leaf(path, leaf):
        dims = leaf.shape
        spec = [None] * len(dims)
        if len(dims) >= 2:
            # dim 0 is layers (or scalar pos); dim 1 is batch
            ba = batch_axes(mesh, mc, dims[1]) if len(dims) > 1 else None
            if ba:
                spec[1] = ba
        if len(dims) == 5:  # attn kv cache (L,B,S,H,D) or ssm state (L,B,H,P,N)
            if mc.seq_shard_kv:
                i = _first_fit(mesh, "model", dims, (2, 3))
            else:
                i = _first_fit(mesh, "model", dims, (3, 2))
            if i is None:
                i = _first_fit(mesh, "model", dims, (4,))
            if i is not None:
                spec[i] = "model"
        elif len(dims) == 4:  # ssm conv cache (L,B,K-1,C)
            i = _first_fit(mesh, "model", dims, (3,))
            if i is not None:
                spec[i] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(shard_leaf, cache)
