"""Persistent cache for measured latency tables.

``build_measured_table`` walks every module kind over its (subsampled)
level grid and wall-clock-times a jitted module at each point — tens of
compile+measure cycles per (cfg, env). ZipLM amortizes that cost across a
whole family of compressed models; this cache amortizes it across *runs*:
repeated ``oneshot_prune``/``gradual_prune`` invocations, the benchmark
suite, and every member of a gradual family re-use one measurement of the
environment.

Cache key
---------
A table is valid only for the exact measurement setup that produced it.
The key is the SHA-256 of the canonical JSON of:

* ``cfg`` — every field of the ``ModelConfig`` dataclass (any
  architecture change re-measures; fingerprinting a subset would silently
  alias configs that time differently);
* ``env`` — every field of the ``InferenceEnv`` including the nested
  ``HardwareSpec`` (batch/seq/mode/tp and the device the analytic model
  would target);
* the measuring device: ``jax.default_backend()`` and the concrete
  ``device_kind`` of device 0 (a table measured on CPU must never serve a
  TPU run and vice versa);
* ``jax.__version__`` — dispatch/compile behaviour shifts between
  releases;
* the measurement parameters (``grid_subsample``, ``reps``, and any other
  kwargs forwarded to ``build_measured_table``).

Invalidation rules
------------------
A lookup is a *miss* (returns None, caller re-measures) when:

* no file exists for the key;
* ``format_version`` differs from ``FORMAT_VERSION`` (schema evolution);
* the stored key dict differs from the recomputed one (hash collision or
  a stale file copied between machines);
* the payload hash does not match (bit-rot / truncation / hand-edits) or
  the JSON does not parse at all.

Corruption therefore can never crash a run or serve wrong numbers — the
worst case is one redundant re-measure, after which ``put`` atomically
overwrites the bad file (tmp + ``os.replace`` via
``checkpoint.manager.atomic_write_json``).

The cache directory resolves to, in order: the ``cache_dir`` argument,
``$ZIPLM_LATENCY_CACHE``, or ``~/.cache/ziplm/latency``. Callers that
need hermetic behaviour (tests) pass an explicit directory.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from dataclasses import dataclass
from typing import Dict, Optional

import jax
import numpy as np

from ..checkpoint.manager import atomic_write_json, load_json
from ..runtime import costmodel as cm
from .latency import LatencyTable

# v2: measured attention modules gained the previously-missing V
# projection (v = k reused the K matmul) — every v1 table undercounts
# dense attention time, so v1 files are misses and get re-measured
FORMAT_VERSION = 2


def _canon(obj) -> str:
    return json.dumps(obj, sort_keys=True, separators=(",", ":"))


def cfg_fingerprint(cfg) -> Dict:
    """ModelConfig as a plain JSON-able dict (full field set), as JSON reads
    it back (a tuple field as a list), so that a stored key compares equal."""
    return json.loads(_canon(dataclasses.asdict(cfg)))


def env_fingerprint(env: cm.InferenceEnv) -> Dict:
    """InferenceEnv (incl. nested HardwareSpec) as a JSON-able dict."""
    return dataclasses.asdict(env)


def device_fingerprint() -> Dict:
    dev = jax.devices()[0]
    return {"backend": jax.default_backend(),
            "device_kind": getattr(dev, "device_kind", str(dev)),
            "jax_version": jax.__version__}


def _resolved_measure_kw(measure_kw: Dict) -> Dict:
    """Measure kwargs with ``build_measured_table``'s current defaults
    folded in: an implicit-default call and an explicit call with the same
    values key identically, and a future default change invalidates
    tables that were measured under the old default."""
    import inspect

    from .latency import build_measured_table
    sig = inspect.signature(build_measured_table)
    out = {name: p.default for name, p in sig.parameters.items()
           if p.default is not inspect.Parameter.empty}
    out.update(measure_kw)
    return out


def cache_key(cfg, env: cm.InferenceEnv, measure_kw: Dict) -> Dict:
    measure_kw = _resolved_measure_kw(measure_kw)
    return {"format_version": FORMAT_VERSION,
            "cfg": cfg_fingerprint(cfg),
            "env": env_fingerprint(env),
            "device": device_fingerprint(),
            "measure": {k: measure_kw[k] for k in sorted(measure_kw)}}


def _key_hash(key: Dict) -> str:
    return hashlib.sha256(_canon(key).encode()).hexdigest()


def _table_payload(tab: LatencyTable) -> Dict:
    return {"base": float(tab.base),
            "grids": {k: np.asarray(v).tolist()
                      for k, v in tab.grids.items()},
            "times": {k: np.asarray(v).tolist()
                      for k, v in tab.times.items()}}


def default_cache_dir() -> str:
    return os.environ.get("ZIPLM_LATENCY_CACHE") \
        or os.path.expanduser("~/.cache/ziplm/latency")


@dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    puts: int = 0


class LatencyCache:
    """Versioned on-disk store of measured ``LatencyTable``s."""

    def __init__(self, cache_dir: Optional[str] = None):
        self.dir = cache_dir or default_cache_dir()
        self.stats = CacheStats()

    def _path(self, key: Dict) -> str:
        return os.path.join(self.dir, f"lat_{_key_hash(key)}.json")

    # ------------------------------------------------------------------
    def get(self, cfg, env: cm.InferenceEnv,
            **measure_kw) -> Optional[LatencyTable]:
        """The cached table for exactly this setup, or None (miss).

        Miss telemetry: a file that exists but is unparseable or fails
        its payload hash counts as ``cache_corrupt`` in
        ``latency.TIMING_STATS``; a parseable file whose format_version
        or key does not match counts as ``cache_foreign``; both append
        the basename to ``cache_flagged``.  The file itself is left in
        place (``put`` atomically overwrites it after the re-measure) —
        renames happen only through :meth:`quarantine`.
        """
        key = cache_key(cfg, env, measure_kw)
        path = self._path(key)
        rec = load_json(path)
        flag = None
        if rec is None:
            if os.path.exists(path):
                flag = "corrupt"  # present but unreadable/unparseable
        elif (rec.get("format_version") != FORMAT_VERSION
                or rec.get("key") != key):
            flag = "foreign"  # stale schema or copied between setups
        elif rec.get("payload_sha256") != hashlib.sha256(
                _canon(rec.get("payload", {})).encode()).hexdigest():
            flag = "corrupt"  # bit-rot / truncation / hand-edit
        if rec is None or flag is not None:
            if flag is not None:
                from .latency import TIMING_STATS
                TIMING_STATS[f"cache_{flag}"] += 1
                TIMING_STATS["cache_flagged"].append(os.path.basename(path))
            self.stats.misses += 1
            return None
        payload = rec["payload"]
        tab = LatencyTable(env=env, base=float(payload["base"]))
        for kind in payload["grids"]:
            tab.grids[kind] = np.asarray(payload["grids"][kind])
            tab.times[kind] = np.asarray(payload["times"][kind])
        self.stats.hits += 1
        return tab

    def put(self, cfg, env: cm.InferenceEnv, tab: LatencyTable,
            **measure_kw) -> str:
        """Persist a measured table; returns the file path."""
        key = cache_key(cfg, env, measure_kw)
        payload = _table_payload(tab)
        rec = {"format_version": FORMAT_VERSION, "key": key,
               "payload": payload,
               "payload_sha256": hashlib.sha256(
                   _canon(payload).encode()).hexdigest()}
        path = self._path(key)
        atomic_write_json(path, rec)
        self.stats.puts += 1
        return path

    def quarantine(self, cfg, env: cm.InferenceEnv,
                   **measure_kw) -> Optional[str]:
        """Rename this key's cache file to ``*.corrupt`` and record it on
        the ambient RobustnessReport (measure-failure demotion path: an
        entry implicated in a failed measurement must not be served
        again).  Returns the quarantine path, or None if there was no
        file / the rename failed."""
        from ..robustness.integrity import quarantine_file
        path = self._path(cache_key(cfg, env, measure_kw))
        if not os.path.exists(path):
            return None
        return quarantine_file(path, site="latency.measure")
