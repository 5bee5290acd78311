"""Shrink: materialize a ZipLM assignment as a physically smaller model.

Row-structures zeroed in the out-side matrix make twin weights dead;
*which* twins die with which structures is each kind's
``PruneUnit.shrink_layer`` contract (see ``core.structures``):

  * attn:  removed KV groups -> slice q/k/v projection columns + wo rows
  * ffn:   removed FC2 rows  -> slice wg/wu (or wi/bi) columns + wd rows
  * moe:   per-expert as ffn; fully dropped experts keep their router
           column (top-k routing must match the masked model) but carry
           no weights and cost no FLOPs
  * ssm:   removed SSD heads -> slice in_proj (z/x/dt), conv, A/D/dt_bias,
           gated-norm and out_proj rows

A layer whose every unit is at its full-drop level shrinks to an empty
``PrunedLayer`` — the pruned forward passes straight through it (and
``init_cache_pruned`` allocates it no KV cache).

The shrunk model must produce the *same outputs* as the masked model
(verified by tests/test_shrink.py) — the compute simply gets smaller.

``shrink`` and ``shrink_from_stitched`` are one driver over two weight
sources: a host context (numpy fancy-indexing over masked params + DB
snapshots) and a device context (``jnp.take`` over a stitched
``SnapshotCache.apply`` tree, for family servers that must not pull
params off the device).  Both produce equal ``PrunedModel``s (tested).
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..models.pruned import PrunedLayer, PrunedModel
from .database import ModuleDB
from .structures import UNITS, _rows_for_groups, dropped_layers

__all__ = ["shrink", "shrink_from_stitched", "kv_cache_plan",
           "layer_drop_plan", "_rows_for_groups"]


class _HostCtx:
    """Weight source for ``shrink``: masked params + DB snapshots, sliced
    through host numpy (out-side matrices come from ``mdb.weights_at``)."""

    def __init__(self, layers, db, assignment):
        self.layers = layers
        self.db = db
        self.assignment = assignment

    def take(self, a, idx, axis):
        return jnp.asarray(np.take(np.asarray(a), np.asarray(idx),
                                   axis=axis))

    def arr(self, a):
        return jnp.asarray(np.asarray(a))

    def out_mat(self, mdb, removed, leaf):
        return np.asarray(mdb.weights_at(removed)).astype(np.float32)

    def layer_params(self, grp, l):
        return {k: np.asarray(v[l]) for k, v in self.layers[grp].items()}

    def at_layer(self, grp, l):
        return jax.tree.map(lambda a: a[l], self.layers[grp])


class _DeviceCtx(_HostCtx):
    """Weight source for ``shrink_from_stitched``: the stitched tree's
    out-side matrices already hold the per-level snapshots, so every
    slice is a device-side ``jnp.take`` — no host round-trip."""

    def take(self, a, idx, axis):
        return jnp.take(a, jnp.asarray(idx, jnp.int32), axis=axis)

    def arr(self, a):
        return a

    def out_mat(self, mdb, removed, leaf):
        return leaf.astype(jnp.float32)

    def layer_params(self, grp, l):
        return {k: v[l] for k, v in self.layers[grp].items()}


def _shrink_impl(cfg, tree, db, assignment, ctx_cls) -> PrunedModel:
    with jax.profiler.TraceAnnotation("prune.shrink"):
        ctx = ctx_cls(tree["layers"], db, assignment)
        out_layers: List[PrunedLayer] = []
        for l in range(cfg.num_layers):
            lcfg = PrunedLayer()
            lp: Dict = {}
            for unit in UNITS.values():
                unit.shrink_layer(cfg, ctx, l, lcfg, lp)
            lcfg.params = lp
            out_layers.append(lcfg)
        globals_ = {"embed": tree["embed"], "final_norm": tree["final_norm"]}
        if tree.get("head"):
            globals_["head"] = tree["head"]
        return PrunedModel(cfg=cfg, layers=out_layers, globals_=globals_)


def shrink(cfg, params, db: Dict[str, ModuleDB],
           assignment: Dict[str, int]) -> PrunedModel:
    return _shrink_impl(cfg, params, db, assignment, _HostCtx)


def shrink_from_stitched(cfg, stitched, db: Dict[str, ModuleDB],
                         assignment: Dict[str, int]) -> PrunedModel:
    """Device-resident shrink from a ``SnapshotCache.apply`` stitched tree.

    ``shrink`` round-trips every weight through host numpy; this variant
    slices with ``jnp.take`` directly on the stitched tree (whose out-side
    matrices already hold the per-level snapshots), so a family server can
    materialize a member without pulling params off the device. Produces
    the same ``PrunedModel`` as ``shrink`` (tested for equality).
    """
    return _shrink_impl(cfg, stitched, db, assignment, _DeviceCtx)


def kv_cache_plan(cfg, db: Dict[str, ModuleDB],
                  assignment: Dict[str, int]) -> List[int]:
    """Per-layer KV-head counts the shrunk model needs at serving time.

    Feed this to ``transformer.init_cache(kv_heads=...)`` (or let
    ``models.pruned.init_cache_pruned`` derive it) so the KV cache is sized
    by the *pruned* structure — entry 0 means the layer's attention module
    is gone (or the whole layer dropped) and allocates no cache at all.
    Each unit contributes through ``PruneUnit.kv_heads``; only GQA/MHA
    attention holds KV state today, but the plan stays correct if a
    future kind does.
    """
    return [sum(u.kv_heads(cfg, db, assignment, l) for u in UNITS.values())
            for l in range(cfg.num_layers)]


def layer_drop_plan(cfg, assignment: Dict[str, int]) -> List[bool]:
    """Per-layer whole-layer-drop flags for an assignment: True iff every
    prunable unit of the layer sits at its full-drop level, i.e. the
    shrunk model stitches the layer as an identity/passthrough block."""
    return dropped_layers(cfg, assignment)
