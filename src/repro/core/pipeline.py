"""Gradual structured pruning (paper §4.1) as a stage-checkpointed,
mesh-shardable *family engine*: for each speedup target in ascending
order, ZipLM-prune the *current* model to the target, then finetune with
layer-wise token distillation against the dense teacher, and export. One
run, one set of hyper-parameters, a whole model family — each member
meeting its runtime target by construction.

Fault tolerance / resume semantics
----------------------------------
A family run owns a unique run directory (derived from (cfg name,
targets, seed) unless ``ckpt_dir`` pins the base — and even then the run
nests under a ``<cfg>-<run_key>`` subdirectory, so two concurrent runs
with different seeds can never cross-restore each other's trainer
checkpoints or manifests). Inside it a ``family.json`` manifest — written
atomically via :func:`checkpoint.manager.atomic_write_json` — records
per-target stage progress through the pipeline

    hessians -> db -> search -> finetune -> done

and each completed stage persists its artifact next to the trainer
checkpoints (``t<target>/hessians.npz``, ``t<target>/db.npz``, the SPDY
result inline in the manifest, ``t<target>/ckpt/`` for finetune steps,
``t<target>/params.npz`` with the finished target's final params). A
preempted run re-invoked with the same arguments resumes at the exact
(target, stage): completed targets are reconstructed from their artifacts
(no Hessian collection, database build, or search is redone), the
in-flight target reloads every completed stage's artifact and re-executes
only the in-flight stage, and an in-flight finetune resumes from the
trainer's latest checkpoint. With a deterministic data source (pass
``data`` as a callable ``global_step -> iterator``, e.g. a
``synthetic_stream`` factory) a killed-and-resumed family run is
bit-identical to an uninterrupted one.

Manifest format (``family.json``)::

    {"version": 1,
     "header": {"cfg": ..., "targets": [...], "seed": ...,
                "finetune_steps": ..., "search_steps": ...,
                "search_pop": ..., "run_key": ...},
     "runs": <attempt counter>,
     "targets": {"<target>": {"stage": "pending|hessians|db|search|done",
                              "assignment": {...}, "runtime": ...,
                              "speedup": ..., "score": ..., "coeffs": [...],
                              "n_evals": ..., "loss_before_ft": ...,
                              "loss_after_ft": ...}},
     "executed": [{"run": n, "target": "<t>", "stage": "<s>"}, ...]}

``executed`` is append-only stage bookkeeping: every stage that actually
*computes* (vs. loads its artifact) logs one event tagged with the
attempt counter, so tests can assert a resume re-executed only the
in-flight stage. A header mismatch (same directory, different family
parameters) raises instead of silently mixing state.

``stop_after=(target_idx, stage)`` simulates preemption right after that
stage's artifact is durably persisted; ``(target_idx, "finetune", step)``
kills mid-finetune after ``step`` trainer steps (the trainer's own
``stop_after``), leaving whatever checkpoints ``ckpt_every`` produced.
Both raise :class:`FamilyPreempted`.

Artifact integrity (robustness layer)
-------------------------------------
Every stage artifact's sha256 is recorded in its manifest payload at
write time (``hessians_sha256`` / ``db_sha256`` / ``params_sha256``;
writes go through the ``db.artifact_write`` fault site with bounded
retry on transient OSErrors). On resume each artifact is re-hashed
before use: a corrupt/truncated file is renamed ``*.corrupt``
(quarantined, never deleted — the bytes are the bug report) and the
owning stage re-executes from its still-valid inputs; with a
deterministic setup the rebuilt artifact is bit-identical to the lost
one. A corrupt final ``params.npz`` rolls its target back to the
``search`` stage, where the recorded search result plus the trainer's
own checkpoints repair it. The run's
:class:`~repro.robustness.report.RobustnessReport` (injected/detected/
recovered counts, circuit-breaker demotions, retries, quarantined
paths) is dumped into the manifest under ``"robustness"`` even when
the run is preempted or crashes mid-stage. A fault-free run under
this layer is bit-identical to one without it.

Overlapped scheduler & async artifact streaming
-----------------------------------------------
The family loop is a strict dependency chain per target —
hessians(i) -> db(i) -> search(i) -> finetune(i) — and search(i+1)
re-calibrates on the *post-finetune* params of target i, so stages of
consecutive targets cannot be reordered. What CAN overlap is target i's
**export tail**: the final loss eval, ``params.npz`` serialization,
shrink and variant assembly only *read* the finished params tree.  With
``overlap=True`` (the default) that tail runs on a background thread
concurrent with target i+1's hessians/db/search/finetune; at most one
export is in flight, and every computation in the tail is deterministic
and reads only immutable state, so the produced variants, manifest
payloads and artifacts are bit-identical to the serial
(``overlap=False``) schedule.

Stage artifacts (``hessians.npz``/``db.npz``/``params.npz``) stream
through a :class:`~repro.checkpoint.manager.CheckpointManager` bounded
async queue: bytes are serialized and sha256'd on the producing thread
(:func:`~repro.checkpoint.manager.npz_bytes` is deterministic, so the
digest recorded in the manifest *before* enqueue equals the digest of
the file that later hits disk — the PR-6 integrity/quarantine contract
is unchanged), then written atomically by the worker.  Write failures
surface as :class:`~repro.checkpoint.manager.CheckpointWriteError` at
the next durability barrier.  Barriers (export join + queue drain) run
before every ``FamilyPreempted`` raise and at family completion, so
``stop_after=`` leaves exactly the durable state of a serial run
stopped at the same point, and the manifest never gets *ahead* of disk
across a barrier.  One kill-window exception is handled on resume: a
hard kill can durably record a target as "done" while its streamed
``params.npz`` is still queued — the done-restore path detects the
missing/corrupt file and rolls that target back to its ``search``
stage, where the recorded search result plus trainer checkpoints
repair it deterministically.  Each stage record carries a
``stage_times`` payload (seconds per stage, ``export`` = the tail) so
benchmarks can attribute wall-time to hessians/db/search/finetune
under either schedule; each stage also runs under a ``prune.<stage>``
host span, which a ``jax.profiler`` trace records on the device's clock.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import os
import sys
import tempfile
import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.manager import (CheckpointManager, CheckpointWriteError,
                                  _flatten, atomic_save_npz,
                                  atomic_write_json, load_json, npz_bytes,
                                  restore_pytree, save_pytree)
from ..configs.base import MeshConfig, TrainConfig
from ..models.pruned import PrunedModel
from ..robustness import faults as _faults
from ..robustness.healing import retry_io
from ..robustness.integrity import (checked_npz_load, file_sha256,
                                    quarantine_file)
from ..robustness.report import RobustnessReport, report_scope
from ..train.trainer import Trainer
from .database import (ModuleDB, SnapshotCache, apply_assignment,
                       build_database)
from .hessian import collect_hessians
from .latency import build_table
from .oneshot import batched_calib_loss_fn, calib_loss_fn, make_batched_eval
from .shrink import shrink
from .spdy import SearchResult, search
from .structures import UNITS, registry


def masks_from_assignment(cfg, params, db, assignment):
    """Params-shaped {0,1} mask pytree pinning pruned structures to zero
    during finetuning (gradients would otherwise regrow them)."""
    masks = jax.tree.map(lambda p: jnp.ones(p.shape, jnp.float32), params)
    for name, removed in assignment.items():
        mdb = db[name]
        kept = mdb.kept_structures(removed)
        gs = mdb.mod.group_size
        row_mask = np.zeros(mdb.mod.d_in, np.float32)
        for g in kept:
            row_mask[g * gs:(g + 1) * gs] = 1.0
        mod = mdb.mod
        rm = jnp.asarray(row_mask)[:, None]
        UNITS[mod.kind].mask_rows(masks["layers"], mod, rm)
    return masks


@dataclass
class GradualVariant:
    target: float
    achieved: float
    assignment: Dict[str, int]
    params: dict
    pruned: PrunedModel
    loss_before_ft: float
    loss_after_ft: float


class FamilyPreempted(RuntimeError):
    """Raised at a simulated (``stop_after``) preemption point after the
    in-flight stage's state is durably checkpointed; re-invoking
    ``gradual_prune`` with the same arguments resumes the run."""


# ----------------------------------------------------------------------
# run directory + manifest
# ----------------------------------------------------------------------

STAGES = ("hessians", "db", "search", "done")  # "done" == finetuned


def family_run_key(cfg, targets: Sequence[float], seed: int) -> str:
    """Content key identifying one family run's state: two runs share
    checkpoints iff (cfg name, targets, seed) agree."""
    doc = {"cfg": cfg.name, "targets": [float(t) for t in sorted(targets)],
           "seed": int(seed)}
    return hashlib.sha256(
        json.dumps(doc, sort_keys=True).encode()).hexdigest()[:12]


def family_run_dir(cfg, targets: Sequence[float], seed: int,
                   base: Optional[str] = None) -> str:
    """Unique per-run directory. ``base=None`` -> a tempdir-rooted default;
    an explicit base still nests per run key, so concurrent families
    sharing a base can never cross-restore."""
    base = base or os.path.join(tempfile.gettempdir(), "ziplm_families")
    return os.path.join(base, f"{cfg.name}-{family_run_key(cfg, targets, seed)}")


def _tkey(target: float) -> str:
    return f"{float(target):g}"


def _tree_digest(tree, max_elems: int = 4096) -> str:
    """Content fingerprint of an array pytree (params / calib batches):
    resuming against different inputs must raise, not silently return the
    previous inputs' family. Large leaves hash a deterministic strided
    subsample (device-side gather, tiny host transfer) instead of pulling
    multi-GB sharded params to the host just to build the header."""
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        shape = tuple(getattr(leaf, "shape", ()))
        size = int(np.prod(shape)) if shape else 1
        h.update(str(path).encode())
        h.update(str((shape, str(getattr(leaf, "dtype", type(leaf))))
                     ).encode())
        if size <= max_elems:
            h.update(np.asarray(leaf).tobytes())  # sync: fingerprint pull
        else:
            stride = -(-size // max_elems)
            # sync: strided sample pull, bounded by max_elems per leaf
            h.update(np.asarray(jnp.ravel(leaf)[::stride]).tobytes())
    return h.hexdigest()[:16]


class FamilyRunState:
    """Atomic-JSON manifest of per-target stage progress (format above)."""

    FILE = "family.json"

    def __init__(self, run_dir: str, header: Dict):
        self.path = os.path.join(run_dir, self.FILE)
        # the overlapped scheduler records from two threads (main stage
        # loop + export tail); atomic_write_json's tmp name is only
        # pid-unique, so manifest mutation + save must serialize here
        self._lock = threading.RLock()
        doc = load_json(self.path)
        if doc is not None and doc.get("header") != header:
            raise ValueError(
                f"family manifest at {self.path} belongs to a different "
                f"run (header {doc.get('header')} != {header}); use a "
                f"different ckpt_dir or matching arguments")
        if doc is None:
            doc = {"version": 1, "header": header, "runs": 0,
                   "targets": {}, "executed": []}
        doc["runs"] = int(doc.get("runs", 0)) + 1
        self.doc = doc
        self.run = doc["runs"]
        self._save()

    def _save(self):
        with self._lock:
            atomic_write_json(self.path, self.doc)

    def entry(self, tkey: str) -> Dict:
        with self._lock:
            return self.doc["targets"].setdefault(tkey, {"stage": "pending"})

    def stage_done(self, tkey: str, stage: str) -> bool:
        cur = self.entry(tkey)["stage"]
        if cur == "pending":
            return False
        return STAGES.index(cur) >= STAGES.index(stage)

    def record(self, tkey: str, stage: str, executed: bool = True,
               **payload):
        """Mark ``stage`` complete for ``tkey``; ``executed`` logs a
        stage-execution event (False when an artifact was merely loaded).

        Never regresses the stage pointer: rebuilding an early artifact
        (a quarantined ``db.npz`` under a target already at ``search`` or
        ``done``) refreshes its payload/sha without undoing the later
        stages — deliberate rollbacks write ``entry["stage"]``
        directly."""
        with self._lock:
            e = self.entry(tkey)
            if (e["stage"] == "pending"
                    or STAGES.index(stage) >= STAGES.index(e["stage"])):
                e["stage"] = stage
            e.update(payload)
            if executed:
                self.doc["executed"].append(
                    {"run": self.run, "target": tkey, "stage": stage})
            self._save()

    def log_exec(self, tkey: str, stage: str):
        """Log a stage execution without completing it (mid-stage work
        such as an in-flight finetune)."""
        with self._lock:
            self.doc["executed"].append(
                {"run": self.run, "target": tkey, "stage": stage})
            self._save()

    def executed(self, run: Optional[int] = None) -> List[Dict]:
        ev = self.doc["executed"]
        return ev if run is None else [e for e in ev if e["run"] == run]


# ----------------------------------------------------------------------
# stage artifacts
# ----------------------------------------------------------------------

def _save_artifact(path: str, arrays: Dict[str, np.ndarray]) -> str:
    """Atomic npz write through the ``db.artifact_write`` fault site:
    transient OSErrors retry with backoff; an injected corrupt-mode fault
    flips bytes *after* the write, so the sha recorded in the manifest
    catches it on the next load (the chaos scenario under test).
    Returns the written file's sha256."""
    sha, rule = retry_io(lambda: atomic_save_npz(path, arrays),
                         site="db.artifact_write")
    if rule is not None and rule.mode == "corrupt":
        plan = _faults.active_plan()
        _faults.corrupt_bytes(path, seed=plan.seed if plan else 0)
    return sha


def _stream_artifact(mgr: CheckpointManager, path: str,
                     arrays: Dict[str, np.ndarray]) -> str:
    """Streaming twin of `_save_artifact`: serialize + sha256 on the
    caller's thread, enqueue the bytes on the manager's bounded queue,
    return the digest immediately.  npz serialization is deterministic,
    so the digest recorded in the manifest before the enqueue is by
    construction that of the bytes the worker later writes — the PR-6
    integrity/quarantine contracts verify streamed artifacts unchanged.
    The worker write runs through the same ``db.artifact_write`` fault
    site (bounded retry, corrupt-after-write); persistent failures
    surface at ``mgr.wait()`` — every preemption point and the end of
    the run barrier on it before reporting stages durable."""
    data, sha = npz_bytes(arrays)
    mgr.submit_blob(path, data, site="db.artifact_write")
    return sha


def _hessian_arrays(hessians: Dict[str, jnp.ndarray]
                    ) -> Dict[str, np.ndarray]:
    # sync: artifact persistence — one pull per module Hessian
    return {k: np.asarray(v) for k, v in hessians.items()}


def _save_hessians(path: str, hessians: Dict[str, jnp.ndarray]) -> str:
    """Synchronous twin of the engine's streamed hessian write (kept for
    tools/tests that persist artifacts outside a running manager)."""
    return _save_artifact(path, _hessian_arrays(hessians))


def _load_hessians(path: str, expected_sha: Optional[str] = None
                   ) -> Optional[Dict[str, jnp.ndarray]]:
    data = checked_npz_load(path, expected_sha, site="db.artifact_write")
    if data is None:
        return None
    return {k: jnp.asarray(v) for k, v in data.items()}


_DB_FIELDS = ("snapshots", "errors", "priors", "levels", "order")


def _db_arrays(db: Dict[str, ModuleDB]) -> Dict[str, np.ndarray]:
    arrs = {}
    for name, mdb in db.items():
        for f in _DB_FIELDS:
            # sync: artifact persistence — DB fields are host numpy
            arrs[f"{name}::{f}"] = np.asarray(getattr(mdb, f))
        arrs[f"{name}::base_norm"] = np.float64(mdb.base_norm)
    return arrs


def _save_db(path: str, db: Dict[str, ModuleDB]) -> str:
    """Synchronous twin of the engine's streamed db write (kept for
    tools/tests that persist artifacts outside a running manager)."""
    return _save_artifact(path, _db_arrays(db))


def _load_db(cfg, path: str, expected_sha: Optional[str] = None
             ) -> Optional[Dict[str, ModuleDB]]:
    data = checked_npz_load(path, expected_sha, site="db.artifact_write")
    if data is None:
        return None
    present = {k.split("::")[0] for k in data}
    out = {}
    # registry order, NOT sorted: SPDY's module ordering (and with it the
    # per-module RNG stream alignment) follows db insertion order, and
    # "L10.x" sorts before "L2.x" — a sorted rebuild would silently break
    # resume bit-identity for models with >= 10 layers
    for mod in registry(cfg):
        if mod.name not in present:
            continue
        kw = {f: data[f"{mod.name}::{f}"] for f in _DB_FIELDS}
        out[mod.name] = ModuleDB(
            # sync: npz payload, host data
            mod=mod, base_norm=float(data[f"{mod.name}::base_norm"]), **kw)
    return out


def _result_payload(res: SearchResult) -> Dict:
    return {"assignment": {k: int(v) for k, v in res.assignment.items()},
            "runtime": float(res.runtime), "speedup": float(res.speedup),
            "score": float(res.score),
            "coeffs": np.asarray(res.coeffs, np.float64).tolist(),
            "n_evals": int(res.n_evals)}


def _result_from(entry: Dict) -> SearchResult:
    return SearchResult(
        assignment={k: int(v) for k, v in entry["assignment"].items()},
        runtime=float(entry["runtime"]), speedup=float(entry["speedup"]),
        score=float(entry["score"]),
        coeffs=np.asarray(entry["coeffs"], np.float64),
        n_evals=int(entry.get("n_evals", 0)))


# ----------------------------------------------------------------------
# family engine
# ----------------------------------------------------------------------

DataSource = Union[Iterator[Dict], Callable[[int], Iterator[Dict]]]


def gradual_prune(cfg, params, env, targets: Sequence[float],
                  data: DataSource, calib_batches: List[Dict], *,
                  tcfg: Optional[TrainConfig] = None,
                  finetune_steps: int = 50, search_steps: int = 50,
                  search_pop: int = 16, search_batched: bool = True,
                  latency_backend: str = "costmodel",
                  latency_kw: Optional[Dict] = None,
                  mesh=None, data_axes=None,
                  mc: Optional[MeshConfig] = None, specs=None,
                  ckpt_dir: Optional[str] = None,
                  ckpt_every: Optional[int] = None,
                  seed: int = 0, resume: bool = True,
                  stop_after: Optional[tuple] = None,
                  report: Optional[RobustnessReport] = None,
                  overlap: bool = True,
                  verbose: bool = False) -> List[GradualVariant]:
    """Stage-checkpointed gradual family pruning (module docstring has the
    manifest/resume contract).

    ``latency_kw`` (e.g. ``{"cache_dir": ...}``) routes the measured
    backend through the persistent cache — the table is measured once for
    the whole family. ``mesh``/``data_axes`` shard the per-target
    re-calibration over the mesh's data axes; with ``specs`` (from
    ``model_init``) the distillation finetune also runs mesh-sharded
    through the trainer's ``jit_train_step`` path (``mc`` derived from the
    mesh when omitted), including int8-EF gradient compression when
    ``tcfg.grad_compression`` asks for it.

    ``data`` is an iterator (legacy; resume replays from wherever the
    caller's iterator happens to be) or a callable ``global_step ->
    iterator`` — the engine then draws target ``i``'s batches from global
    steps ``[i*finetune_steps, (i+1)*finetune_steps)``, which makes
    killed-and-resumed runs bit-identical to uninterrupted ones.

    Each target's SPDY search runs through the population-batched engine
    (``search_pop`` candidates stitched+scored per device round); the
    family cannot share one search pass here because every target
    re-calibrates on the just-finetuned model, but per-target RNG streams
    are still fold-in derived from ``seed``.

    ``report`` supplies the run's :class:`RobustnessReport` (a fresh one
    is created otherwise); it is installed as the ambient report for the
    whole run — every layer's fault detections, recoveries, and breaker
    demotions accumulate there — and its dict dump lands in the manifest
    under ``"robustness"``, preempted runs included.

    ``overlap`` runs each finished target's export tail (final loss
    eval, params streaming, shrink) on a background thread, concurrent
    with the next target's hessians/db/search/finetune (module docstring,
    "Overlapped scheduler" section); results are bit-identical either
    way, so the flag is deliberately NOT part of the resume header — a
    serial run may resume an overlapped one and vice versa.
    """
    tcfg = tcfg or TrainConfig(learning_rate=8e-5, warmup_steps=5,
                               total_steps=finetune_steps,
                               distill_logit=1.0, distill_token=0.5)
    if stop_after is not None:
        if stop_after[1] not in ("hessians", "db", "search", "finetune"):
            raise ValueError(f"stop_after stage {stop_after[1]!r} is not a "
                             f"pipeline stage")
        if stop_after[1] == "finetune" and len(stop_after) < 3:
            raise ValueError("stop_after=(i, 'finetune') needs a step "
                             "index: (i, 'finetune', step)")
    targets = [float(t) for t in sorted(targets)]
    ckpt_every = ckpt_every or max(1, min(50, finetune_steps))
    run_dir = family_run_dir(cfg, targets, seed, base=ckpt_dir)
    if not resume:
        import shutil
        shutil.rmtree(run_dir, ignore_errors=True)
    import dataclasses
    lat_kw = {k: repr(v) for k, v in sorted((latency_kw or {}).items())
              if k != "cache_dir"}  # the cache location never changes results
    header = {"cfg": cfg.name, "targets": targets, "seed": int(seed),
              "finetune_steps": int(finetune_steps),
              "search_steps": int(search_steps),
              "search_pop": int(search_pop),
              "search_batched": bool(search_batched),
              "run_key": family_run_key(cfg, targets, seed),
              # every input that changes the results is fingerprinted:
              # resuming a 'done' manifest with a retrained model, new
              # calib set, different env or trainer hyper-parameters must
              # fail loudly instead of handing back stale artifacts
              "inputs": {"params": _tree_digest(params),
                         "calib": _tree_digest(calib_batches),
                         "env": repr(env),
                         "tcfg": dataclasses.asdict(tcfg),
                         "latency": [latency_backend, lat_kw]}}
    frs = FamilyRunState(run_dir, header)
    rep = report if report is not None else RobustnessReport()
    try:
        with report_scope(rep):
            return _family_engine(
                cfg, params, env, targets, data, calib_batches, tcfg=tcfg,
                finetune_steps=finetune_steps, search_steps=search_steps,
                search_pop=search_pop, search_batched=search_batched,
                latency_backend=latency_backend, latency_kw=latency_kw,
                mesh=mesh, data_axes=data_axes, mc=mc, specs=specs,
                ckpt_every=ckpt_every, seed=seed, stop_after=stop_after,
                overlap=overlap, verbose=verbose, run_dir=run_dir, frs=frs)
    finally:
        # the run's robustness telemetry rides in the manifest even when
        # the run was preempted or crashed mid-stage
        frs.doc["robustness"] = rep.as_dict()
        frs._save()


@contextlib.contextmanager
def _stage(name: str, stage_t: Dict[str, float]):
    """Host seconds of stage ``name`` into ``stage_t``, under a
    ``prune.<name>`` span."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(f"prune.{name}"):
        yield
    stage_t[name] = time.perf_counter() - t0


def _family_engine(cfg, params, env, targets, data, calib_batches, *, tcfg,
                   finetune_steps, search_steps, search_pop, search_batched,
                   latency_backend, latency_kw, mesh, data_axes, mc, specs,
                   ckpt_every, seed, stop_after, overlap, verbose, run_dir,
                   frs) -> List[GradualVariant]:
    """The family loop proper, run under an installed report scope
    (``gradual_prune`` is the argument-validating, manifest-owning
    wrapper)."""
    teacher = jax.tree.map(lambda a: a, params)  # dense teacher
    with jax.profiler.TraceAnnotation("prune.latency_table"):
        table = build_table(cfg, env, backend=latency_backend,
                            **(latency_kw or {}))
    loss_eval = calib_loss_fn(cfg, calib_batches[:1])
    devices = list(mesh.devices.flat) if mesh is not None else None

    # async artifact stream: hessians/db/params npz bytes are serialized
    # + sha'd on the producing thread, then drained by the manager's
    # worker (bounded queue -> backpressure); _barrier() is the only
    # place that declares them durable
    mgr = CheckpointManager(run_dir, async_save=True)
    exports: List[threading.Thread] = []   # at most one in flight
    export_err: List[BaseException] = []

    def _join_exports(raise_errors: bool = True):
        while exports:
            exports.pop(0).join()
        if export_err and raise_errors:
            raise export_err.pop(0)

    def _barrier():
        """Durability barrier: join the in-flight export tail, then
        drain the artifact queue (raising any persistent write failure
        as CheckpointWriteError).  After this returns, every stage the
        manifest calls complete is durably on disk."""
        _join_exports()
        mgr.wait()

    def make_trainer(tdir, masks=None):
        # the trainer mesh path needs the logical-axis specs; mesh without
        # specs keeps the documented calibration-only sharding instead of
        # blowing up after hours of hessians/db/search work
        use_mesh = mesh if specs is not None else None
        return Trainer(cfg, tcfg, ckpt_dir=os.path.join(tdir, "ckpt"),
                       teacher_params=teacher, masks=masks,
                       ckpt_every=ckpt_every, mesh=use_mesh,
                       mc=mc if use_mesh is not None else None,
                       specs=specs)

    def preempt_at(i, stage):
        if stop_after is not None and tuple(stop_after[:2]) == (i, stage):
            # the documented semantics — "preemption right after that
            # stage's artifact is durably persisted" — survive overlap:
            # barrier first, so the manifest + artifacts the resuming run
            # sees are exactly those of a serial run stopped here
            _barrier()
            raise FamilyPreempted(
                f"simulated preemption after {stage} of target index {i} "
                f"(run dir {run_dir})")

    current = params
    out: Dict[int, GradualVariant] = {}
    seeds = np.random.SeedSequence(seed).spawn(len(targets))
    loss_b = None  # one compiled batched loss for the whole family

    def load_or_build_db(i, tkey, tdir, entry, stage_t):
        """Sha-verified db load with fall-through rebuild: a corrupt
        (quarantined) or missing ``db.npz`` re-executes the db stage from
        the hessians artifact; a corrupt hessians artifact likewise falls
        back to re-collection on the current model — bit-identical to the
        original build with a deterministic setup.  Hessians stay
        unloaded when the db artifact is valid (dead weight)."""
        dpath = os.path.join(tdir, "db.npz")
        if frs.stage_done(tkey, "db"):
            db = _load_db(cfg, dpath, expected_sha=entry.get("db_sha256"))
            if db is not None:
                return db
        hpath = os.path.join(tdir, "hessians.npz")
        hessians = None
        if frs.stage_done(tkey, "hessians"):
            hessians = _load_hessians(
                hpath, expected_sha=entry.get("hessians_sha256"))
        if hessians is None:
            with _stage("hessians", stage_t):
                hessians = collect_hessians(cfg, current, calib_batches,
                                            mesh=mesh, data_axes=data_axes)
                hsha = _stream_artifact(mgr, hpath,
                                        _hessian_arrays(hessians))
            frs.record(tkey, "hessians", hessians_sha256=hsha,
                       stage_times=dict(stage_t))
            preempt_at(i, "hessians")
        with _stage("db", stage_t):
            db = build_database(cfg, current, hessians, mesh=mesh,
                                shard_axes=data_axes)
            dsha = _stream_artifact(mgr, dpath, _db_arrays(db))
        frs.record(tkey, "db", db_sha256=dsha, stage_times=dict(stage_t))
        preempt_at(i, "db")
        return db

    def export_tail(i, target, tkey, tdir, db, res, loss_before, cur,
                    stage_t):
        """Target ``i``'s read-only completion work: final loss eval,
        params streaming (sha-before-enqueue), shrink, "done" record and
        variant assembly.  Under ``overlap`` this runs on a background
        thread concurrent with target ``i+1``'s stages; everything it
        touches is immutable (``cur`` is the finished params tree) and
        deterministic, so the scheduler cannot change a single bit."""
        with _stage("export", stage_t):
            loss_after = loss_eval(cur)
            data_b, psha = npz_bytes(_flatten(cur))
            mgr.submit_blob(os.path.join(tdir, "params.npz"), data_b,
                            site="db.artifact_write")
            pm = shrink(cfg, cur, db, res.assignment)
        frs.record(tkey, "done", executed=False, loss_after_ft=loss_after,
                   params_sha256=psha, stage_times=dict(stage_t))
        out[i] = GradualVariant(
            target=target, achieved=res.speedup, assignment=res.assignment,
            params=cur, pruned=pm, loss_before_ft=loss_before,
            loss_after_ft=loss_after)
        if verbose:
            print(f"[gradual] {target}x -> {res.speedup:.2f}x  "
                  f"loss {loss_before:.4f} -> {loss_after:.4f}  "
                  f"stack params {pm.encoder_params()/1e6:.2f}M")

    def export_tail_bg(*args):
        try:
            export_tail(*args)
        except BaseException as e:   # surfaced at the next _barrier()
            export_err.append(e)

    try:
        for i, target in enumerate(targets):
            tkey = _tkey(target)
            tdir = os.path.join(run_dir, f"t{tkey}")
            entry = frs.entry(tkey)
            stage_t: Dict[str, float] = dict(entry.get("stage_times", {}))

            if entry["stage"] == "done":
                # completed target: reconstruct the variant from artifacts
                # — no Hessians, no DB build, no search, no finetune. The
                # final params ride in their own params.npz (written at
                # completion) so this path never pays for restoring
                # optimizer/EF state.
                ppath = os.path.join(tdir, "params.npz")
                want = entry.get("params_sha256")
                if not os.path.exists(ppath):
                    # a kill can outrun the async params stream: "done"
                    # was durably recorded while params.npz died in the
                    # write queue. Roll back to "search" — the recorded
                    # search result plus the trainer's own checkpoints
                    # repair it below (deliberate stage regression,
                    # written directly because record() never regresses)
                    entry["stage"] = "search"
                    frs._save()
                elif want is not None and file_sha256(ppath) != want:
                    # final params rotted on disk: quarantine + the same
                    # search-stage rollback
                    quarantine_file(ppath, site="db.artifact_write")
                    entry["stage"] = "search"
                    frs._save()
                else:
                    db = load_or_build_db(i, tkey, tdir, entry, stage_t)
                    res = _result_from(entry)
                    current = restore_pytree(current, ppath)
                    pm = shrink(cfg, current, db, res.assignment)
                    out[i] = GradualVariant(
                        target=target, achieved=res.speedup,
                        assignment=res.assignment, params=current,
                        pruned=pm,
                        # sync: manifest floats, host data
                        loss_before_ft=float(entry["loss_before_ft"]),
                        # sync: manifest floats, host data
                        loss_after_ft=float(entry["loss_after_ft"]))
                    if verbose:
                        print(f"[gradual] {target}x restored (stage done)")
                    continue

            # ---- stages: hessians (re-calibrate on the *current* model —
            # Hessians drift as we prune) + database, both sha-verified
            # with quarantine-and-rebuild on corruption. ----
            db = load_or_build_db(i, tkey, tdir, entry, stage_t)
            cache = SnapshotCache(cfg, db)

            # ---- stage: SPDY search ----
            if frs.stage_done(tkey, "search"):
                res = _result_from(entry)
                masked = apply_assignment(cfg, current, db, res.assignment,
                                          cache=cache)
                loss_before = float(entry["loss_before_ft"])  # sync: manifest
            else:
                with _stage("search", stage_t):
                    if loss_b is None:
                        loss_b = batched_calib_loss_fn(
                            cfg, calib_batches[:1],
                            cache.batch_axes(current))
                    res = search(db, table, target, steps=search_steps,
                                 pop=search_pop, batched=search_batched,
                                 seed=seeds[i], devices=devices,
                                 eval_fn=lambda a: loss_eval(
                                     apply_assignment(cfg, current, db, a,
                                                      cache=cache)),
                                 eval_batched=make_batched_eval(
                                     cfg, current, cache,
                                     calib_batches[:1], loss_b=loss_b))
                    masked = apply_assignment(cfg, current, db,
                                              res.assignment, cache=cache)
                    loss_before = loss_eval(masked)
                frs.record(tkey, "search", loss_before_ft=loss_before,
                           stage_times=dict(stage_t),
                           **_result_payload(res))
                preempt_at(i, "search")

            # ---- stage: distillation finetune ----
            with _stage("finetune", stage_t):
                masks = masks_from_assignment(cfg, masked, db,
                                              res.assignment)
                trainer = make_trainer(tdir, masks=masks)
                state = trainer.init_or_restore(masked)
                start = int(state.step)
                data_iter = (data(i * finetune_steps + start)
                             if callable(data) else data)
                fit_stop = None
                if stop_after is not None and tuple(stop_after[:2]) == \
                        (i, "finetune") and len(stop_after) > 2:
                    fit_stop = int(stop_after[2])
                if start < finetune_steps:
                    frs.log_exec(tkey, "finetune")
                state = trainer.fit(state, data_iter,
                                    steps=finetune_steps,
                                    stop_after=fit_stop)
                if int(state.step) < finetune_steps:
                    # simulated stop_after kill or a real SIGTERM
                    # preemption — the trainer checkpointed; re-invoking
                    # resumes from that step (barrier: the previous
                    # target's export must be as durable as a serial
                    # run's before we report preempted)
                    _barrier()
                    raise FamilyPreempted(
                        f"preempted mid-finetune of target {target} at step "
                        f"{int(state.step)} (run dir {run_dir})")
                current = state.params

            # ---- export tail: overlapped with the next target's stages
            # (only reads the finished `current`), or inline when serial
            tail_args = (i, target, tkey, tdir, db, res, loss_before,
                         current, stage_t)
            if overlap:
                _join_exports()          # at most one export in flight
                th = threading.Thread(target=export_tail_bg,
                                      args=tail_args, daemon=True)
                exports.append(th)
                th.start()
            else:
                export_tail(*tail_args)
        _barrier()
        return [out[i] for i in range(len(targets))]
    finally:
        _join_exports(raise_errors=False)
        try:
            mgr.close()
        except CheckpointWriteError:
            # on an exception path the original error wins (a preempting
            # _barrier() already surfaced write failures); re-raise only
            # when nothing else is propagating
            if sys.exc_info()[0] is None:
                raise
