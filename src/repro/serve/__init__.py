"""Continuous-batching serving engine for dense and ZipLM-pruned families.

This is the end of the paper's inference-aware story: models pruned for a
concrete inference environment are *served* in one, and the wins show up
as measured tokens/s, per-request latency, and KV-cache bytes.

Slot lifecycle
--------------
The engine owns ``num_slots`` decode slots backed by one batched KV cache
with a per-slot position vector (``cache["pos"]: (B,)``):

1. **admit** — when a slot is free and a request has arrived, its prompt
   is prefilled alone at a power-of-two padded bucket length (bounding
   jit compiles under mixed prompt lengths; padding rows are provably
   never attended);
2. **insert** — the prefilled KV rows and the prompt length land in the
   free slot via one jitted scatter, and the prefill's last-position
   logits yield the request's first token;
3. **decode** — all occupied slots advance together through one jitted
   decode step per token, each slot masking and writing at its own
   absolute position, so requests of different lengths and phases share
   every batched step (continuous batching — no head-of-line blocking on
   the longest request);
4. **retire** — a slot whose request has generated its ``steps`` tokens
   is freed immediately and can be re-filled on the next admit, while the
   other slots keep decoding.

Cache sizing contract
---------------------
``max_len`` bounds ``prompt_len + steps`` for every request; the engine
*rejects* (clear ``RuntimeError``) anything that would decode past it,
because the decode write index clamps at the last cache slot and would
silently corrupt output. Pruned members allocate their cache from the
*shrunk* per-layer structure (``init_cache(kv_heads=[...])``): a layer
that kept ``g`` KV groups pays for ``g`` heads, a dropped attention
module pays nothing — KV bytes, not just FLOPs, shrink with the model
(asserted by ``tests/test_serve_engine.py``'s
``test_pruned_cache_bytes_match_shrunk_structure``).

Model adapter
-------------
One :class:`~repro.serve.engine.ServeModel` owns the jitted prefill, slot
insert and decode step for both runtimes; :class:`DenseServeModel` (the
scanned stack) and :class:`PrunedServeModel` (a shrunk member's unrolled
per-layer list) only build what it serves. Both runtimes run the same
layer body, ``models.transformer.decode_block``.

Family routing
--------------
:class:`~repro.serve.family.FamilyServer` stitches every speedup target
of a ZipLM family device-side from one resident ``SnapshotCache`` (no
parameter reloads) and routes each request by its latency class to the
smallest member target meeting the class's speedup demand — strictest
latency gets the fastest member, relaxed traffic keeps dense quality.

Faults: the decode step donates the slot cache's K/V off the CPU, so a
detected non-finite ``serve.step`` is retried from the failed attempt's
K/V with the pre-step positions, which the step rewrites before reading
(see ``ServeEngine._step_once``); chaos-tier runs recover bit-identically.
"""
from .engine import (DenseServeModel, PrunedServeModel, RequestRecord,
                     ServeEngine, ServeModel, ServeReport)
from .family import DENSE_TARGET, FamilyServer
from .workload import (CLASS_SPEEDUP, LATENCY_CLASSES, Request,
                       synthetic_requests)

__all__ = [
    "ServeModel", "DenseServeModel", "PrunedServeModel", "ServeEngine",
    "ServeReport",
    "RequestRecord", "FamilyServer", "DENSE_TARGET", "Request",
    "synthetic_requests", "CLASS_SPEEDUP", "LATENCY_CLASSES",
]
