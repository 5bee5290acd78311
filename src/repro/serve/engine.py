"""Slot-based continuous-batching engine over jitted prefill/decode steps.

See the package docstring (``repro.serve``) for the slot lifecycle and the
cache sizing contract. One model adapter, :class:`ServeModel`, owns the
jitted prefill, insert and decode step; what it serves is built by

* :class:`DenseServeModel` — stock params, ``transformer.decode_step``
  (a scan over the stacked layers and their stacked cache);
* :class:`PrunedServeModel` — a ZipLM-shrunk :class:`PrunedModel`,
  ``models.pruned.decode_step_pruned`` (an unrolled loop over the layers
  and their per-layer cache, whose KV bytes follow the shrunk structure).

Both loops run ``transformer``'s own layer body.
"""
from __future__ import annotations

import functools
import gc
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as _span

from ..models import model as model_mod
from ..models.pruned import (PrunedModel, _check_decodable,
                             decode_step_pruned, init_cache_pruned,
                             prefill_pruned)
from ..models.transformer import decode_step, forward, init_cache
from ..robustness import faults as _faults
from ..robustness.report import current_report
from .workload import Request

_STEP_RETRIES = 4  # bounded serve.step retry budget per decode step


def _bucket(s: int, max_len: int) -> int:
    """Next power-of-two prompt bucket (>=8), capped at max_len — bounds
    the number of prefill compilations under mixed prompt lengths."""
    b = 8
    while b < s:
        b *= 2
    return min(b, max_len)


def serve_sample(logits):
    """The engine's greedy sampler and finite check, on the device: (B, 1,
    V) logits -> (B,) int32, each row's first index of its largest value
    (as ``np.argmax``), or -1 where the row holds a nan or an inf. Its
    executable is ``jit_serve_sample``."""
    x = logits[:, 0]
    ids = jnp.argmax(x, axis=-1).astype(jnp.int32)
    return jnp.where(jnp.isfinite(x).all(axis=-1), ids, -1)


_sample = jax.jit(serve_sample)


def _donate_kv() -> bool:
    """Whether the decode step donates the slot cache's K/V, so that the
    step's output K/V are written in place: off the CPU only, like
    ``hessian._donate``."""
    return jax.default_backend() != "cpu"


def _kv_consumed(cache) -> bool:
    """Whether a dispatch consumed ``cache``'s K/V in place (donated)."""
    leaves = jax.tree.leaves(cache.get("attn"))
    return bool(leaves) and leaves[0].is_deleted()


def _kv_bytes(cache) -> int:
    """Total KV byte footprint of a slot cache (dense stack or pruned
    per-layer list; ``None`` entries of dropped layers cost nothing)."""
    return sum(int(x.size) * x.dtype.itemsize
               for x in jax.tree.leaves(cache.get("attn")))


class ServeModel:
    """The engine's model adapter: the jitted prefill, insert and decode
    step over one model's pieces.

    * ``weights``: the tree each executable takes as its first argument;
    * ``decode(weights, cache, tokens) -> (logits, cache)``: one step of
      the slot cache, whose entries other than ``attn`` and ``pos`` are the
      step's state that is not donated (held experts' routing counter);
    * ``prefill(weights, tokens, last) -> (logits, cache)``: logits at
      every position of a (1, bucket) prompt whose last real token is at
      ``last``, and its one-row cache;
    * ``init_slots(nslots)``: an empty slot cache;
    * ``slot_axis``: the slot axis of each K/V buffer (1 in a stack over
      layers, 0 in a per-layer buffer).

    The jitted functions' names are the executables' names in a profiler
    trace: ``jit_serve_decode``, ``jit_serve_prefill`` (compiled once per
    prompt bucket) and ``jit_serve_insert``.
    """

    # the argument of jit_serve_decode that holds the slot cache's K/V,
    # donated off the CPU (``_donate_kv``)
    kv_argnums = (1,)

    def __init__(self, cfg, weights, max_len: int, *, decode, prefill,
                 init_slots, slot_axis: int):
        self.cfg, self.weights, self.max_len = cfg, weights, max_len
        self.init_slots = init_slots
        at = (slice(None),) * slot_axis

        def serve_decode(p, kv, pos, tokens, state):
            return decode(p, {"pos": pos, "attn": kv, **state}, tokens)

        def serve_prefill(p, tokens, last):
            logits, cache = prefill(p, tokens, last)
            return jax.lax.dynamic_slice_in_dim(logits, last, 1, axis=1), \
                cache

        def serve_insert(cache, row, slot, pos):
            # the row holds the prompt's K/V as the prefill placed it: the
            # full layers' from position 0, the rings from the true length;
            # a dropped layer's None passes through
            return dict(cache, pos=cache["pos"].at[slot].set(pos),
                        attn=jax.tree.map(lambda c, r: c.at[at + (slot,)].set(
                            r[at + (0,)]), cache["attn"], row["attn"]))

        self._step = jax.jit(serve_decode, donate_argnums=(
            self.kv_argnums if _donate_kv() else ()))
        self._prefill = jax.jit(serve_prefill)     # one program per bucket
        self._insert = jax.jit(serve_insert)

    def prefill(self, tokens: np.ndarray):
        """(s,) prompt -> (last-token logits (1,1,V), single-row cache).

        Runs at the padded bucket length; rows past the true length hold
        garbage k/v but are provably never attended (causal mask during
        prefill; during decode every position <= pos has been overwritten
        by a real token before the mask admits it).
        """
        s = int(tokens.shape[0])
        padded = np.zeros((1, _bucket(s, self.max_len)), np.int64)
        padded[0, :s] = tokens
        return self._prefill(self.weights, jnp.asarray(padded),
                             jnp.asarray(s - 1, jnp.int32))

    def insert(self, cache, row_cache, slot: int, pos: int):
        return self._insert(cache, row_cache, jnp.asarray(slot, jnp.int32),
                            jnp.asarray(pos, jnp.int32))

    def step(self, cache, tokens):
        state = {k: v for k, v in cache.items() if k not in ("attn", "pos")}
        return self._step(self.weights, cache["attn"], cache["pos"], tokens,
                          state)


def _check_servable(cfg, kinds):
    """Refuse a configuration other than a causal text decoder whose
    layers' attention is of ``kinds``."""
    if (not cfg.causal or cfg.frontend != "none"
            or not set(cfg.attention_period) <= set(kinds)):
        raise NotImplementedError(
            "serving engine covers causal text decoders of "
            f"{' and '.join(kinds)} attention")
    _check_decodable(cfg)


class DenseServeModel(ServeModel):
    """A :class:`ServeModel` of stock (unpruned) params.

    Each layer's attention is of its kind in ``cfg.attention_period``
    (full, or a ring of the window); the slot cache holds one K/V stack per
    kind. With held experts (``cfg.experts_held``) the slot cache also
    carries the routing counter ``route`` that ``transformer.decode_step``
    adds to, over the slots the engine marks ``live``.
    """

    def __init__(self, cfg, params, max_len: int):
        _check_servable(cfg, ("full", "sliding_window"))

        def prefill(p, tokens, last):
            out = forward(cfg, p, tokens, mode="prefill")
            return out["logits"], model_mod.assemble_prefill_cache(
                cfg, out, 1, last + 1, max_len)

        def init_slots(nslots: int):
            cache = init_cache(cfg, nslots, max_len, per_slot=True)
            if cfg.experts_held:
                cache["route"] = jnp.zeros((3,), jnp.int32)
            return cache

        super().__init__(cfg, params, max_len,
                         decode=functools.partial(decode_step, cfg),
                         prefill=prefill, init_slots=init_slots, slot_axis=1)


class PrunedServeModel(ServeModel):
    """A :class:`ServeModel` of a ZipLM-shrunk :class:`PrunedModel`; its
    weights are ``pm.weights()``, the structure rebuilt around them inside
    each executable."""

    def __init__(self, pm: PrunedModel, max_len: int):
        _check_servable(pm.cfg, ("full",))

        def decode(w, cache, tokens):
            return decode_step_pruned(pm.with_weights(w), cache, tokens)

        def prefill(w, tokens, last):
            return prefill_pruned(pm.with_weights(w), tokens, max_len,
                                  full_logits=True)

        super().__init__(pm.cfg, pm.weights(), max_len, decode=decode,
                         prefill=prefill, slot_axis=0,
                         init_slots=lambda nslots: init_cache_pruned(
                             pm, nslots, max_len, per_slot=True))


@dataclass
class RequestRecord:
    """One request's tokens and times. ``prefill_ms`` and
    ``decode_step_ms`` bracket the dispatches; ``t_admit``, ``t_first`` and
    ``t_done`` are host-clock seconds since the start of ``run``: its
    admission began, its first token was on the host, its last one was.
    Its later tokens came at ``ServeReport.step_end[first_step]`` through
    ``step_end[last_step]`` (both None for a one-token request)."""
    rid: int
    prompt_len: int
    steps: int
    arrival: float
    latency_class: str
    tokens: List[int] = field(default_factory=list)
    prefill_ms: float = 0.0
    decode_step_ms: List[float] = field(default_factory=list)
    t_admit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    first_step: Optional[int] = None
    last_step: Optional[int] = None

    @property
    def _t_due(self) -> float:
        # arrivals are admitted on the virtual clock, which can run ahead
        # of the host's: a request admitted before its arrival on the host
        # clock waited for nothing
        return min(self.arrival, self.t_admit)

    @property
    def latency_s(self) -> float:
        """Queueing + service time of the whole request (host clock)."""
        return self.t_done - self._t_due

    @property
    def ttft_s(self) -> float:
        """Time to the first token (host clock)."""
        return self.t_first - self._t_due

    @property
    def decode_ms_per_token(self) -> float:
        return float(np.mean(self.decode_step_ms)) \
            if self.decode_step_ms else 0.0


def _percentiles(name: str, xs, qs) -> Dict[str, float]:
    return {f"{name}p{q}_ms": float(np.percentile(xs, q)) if len(xs)
            else float("nan") for q in qs}


@dataclass
class ServeReport:
    records: List[RequestRecord]
    wall_s: float                 # busy wall-clock (prefills + steps)
    steps: int                    # decode steps executed
    kv_cache_bytes: int
    # decode steps whose K/V input the step consumed in place (donated)
    donated_steps: int = 0
    # host-clock seconds since the start of ``run`` at which each decode
    # step's token ids were on the host
    step_end: List[float] = field(default_factory=list)

    @property
    def total_tokens(self) -> int:
        return sum(len(r.tokens) for r in self.records)

    @property
    def tokens_per_s(self) -> float:
        return self.total_tokens / max(self.wall_s, 1e-12)

    def token_times(self, rec: RequestRecord) -> np.ndarray:
        """Host-clock stamp of each of ``rec``'s tokens."""
        later = (self.step_end[rec.first_step:rec.last_step + 1]
                 if rec.first_step is not None else [])
        return np.array([rec.t_first, *later])

    def latency_percentiles(self, qs=(50, 99)) -> Dict[str, float]:
        """Request latency (``p<q>_ms``), time to first token
        (``ttft_p<q>_ms``) and inter-token gaps (``itl_p<q>_ms``), all
        from the host-clock stamps."""
        itl = np.concatenate([np.diff(self.token_times(r))
                              for r in self.records] or [[]])
        d = _percentiles("", [r.latency_s * 1e3 for r in self.records], qs)
        d.update(_percentiles("ttft_", [r.ttft_s * 1e3
                                        for r in self.records], qs))
        d.update(_percentiles("itl_", itl * 1e3, qs))
        return d

    @property
    def prefill_ms_mean(self) -> float:
        return float(np.mean([r.prefill_ms for r in self.records]))

    @property
    def decode_ms_per_token_mean(self) -> float:
        return float(np.mean([r.decode_ms_per_token
                              for r in self.records if r.decode_step_ms]))

    def as_dict(self) -> Dict[str, Any]:
        d = {"requests": len(self.records),
             "total_tokens": self.total_tokens,
             "tokens_per_s": self.tokens_per_s,
             "wall_s": self.wall_s,
             "prefill_ms_mean": self.prefill_ms_mean,
             "decode_ms_per_token_mean": self.decode_ms_per_token_mean,
             "kv_cache_bytes": self.kv_cache_bytes,
             "donated_steps": self.donated_steps}
        d.update(self.latency_percentiles())
        return d


class _GCSpans:
    """While registered in ``gc.callbacks``, a ``serve.gc`` span over each
    garbage collection."""

    def __init__(self):
        self._open = None

    def __call__(self, phase, info):
        if phase == "start":
            self._open = _span("serve.gc", generation=info["generation"])
            self._open.__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)
        self(None, None)


class ServeEngine:
    """Continuous batching over ``num_slots`` decode slots.

    ``clock`` is injectable (tests script it). It is read once at the start
    of :meth:`run` and then only around jit dispatches, so measured
    prefill/decode latencies are the compute, not the host bookkeeping;
    the same readings stamp each request and decode step. Call
    :meth:`warmup` before timing runs so reported latencies are warm
    (compiles excluded).

    Under a ``jax.profiler`` session, ``run`` writes host spans named
    ``serve.*`` onto the trace's clock: ``serve.run``; per admission
    ``serve.admit`` (stats ``rid``, ``slot``, ``prompt_len``, ``bucket``)
    over ``serve.prefill``, ``serve.insert``, ``serve.wait`` and
    ``serve.sample``; per decode step ``serve.step`` (stats ``step``,
    ``active``) over ``serve.dispatch`` (the decode step and
    :func:`serve_sample`), ``serve.wait``, ``serve.pull`` (stat ``bytes``,
    the token ids copied to the host), ``serve.check`` (repeated on a
    retry), ``serve.sample`` and ``serve.bookkeep``; ``serve.gc`` (stat
    ``generation``) over each garbage collection; and, where the model's
    slot cache carries a routing counter (held experts), ``serve.route``
    once after the drain (see :meth:`_route_span`). Without a session each
    span costs about a microsecond.
    """

    def __init__(self, model, num_slots: int = 4,
                 clock: Callable[[], float] = time.perf_counter):
        self.model = model
        self.num_slots = num_slots
        self.clock = clock
        self.cache = model.init_slots(num_slots)
        self.kv_cache_bytes = _kv_bytes(self.cache)
        # the last run's routing counter (see _route_span), where the
        # model counts its routing
        self.route: Optional[Dict[str, int]] = None
        self.max_len = model.max_len

    def warmup(self, prompt_lens=(8,)):
        """Compile the prefill buckets, the insert, the decode step and the
        sampler."""
        for s in prompt_lens:
            s = min(int(s), self.max_len - 1)
            logits, row = self.model.prefill(np.zeros((s,), np.int64))
            cache = self._live(self.model.insert(self.cache, row, 0, s), [0])
            toks = jnp.zeros((self.num_slots, 1), jnp.int32)
            # sync: warmup barrier — wait for each bucket's compile
            jax.block_until_ready(_sample(self.model.step(cache, toks)[0]))
        # warmup state is discarded; self.cache was never mutated (the
        # insert does not donate, so a step consumes only the insert's
        # output)

    def _live(self, cache, active_slots):
        """``cache`` with the mask ``live`` of the active slots, whose
        tokens the routing counter counts, where the model counts its
        routing (``route``)."""
        if "route" not in cache:
            return cache
        live = np.zeros(self.num_slots, bool)
        live[active_slots] = True
        return dict(cache, live=jnp.asarray(live))

    # ------------------------------------------------------------------
    # fault-handled decode step (site: serve.step)
    # ------------------------------------------------------------------

    def _step_once(self, tokens: np.ndarray, active_slots: List[int]):
        """One decode step with bounded retries; returns the host token ids,
        ``(num_slots,)`` int32 from :func:`serve_sample` (-1 on a row that
        is not finite), and whether the step consumed its K/V input in
        place.

        Off the CPU the step donates the cache's K/V (``_donate_kv``), so
        the previous cache is gone once the step is dispatched. A detected
        non-finite step (nan/inf poison on an active slot) is retried from
        the candidate's K/V with the pre-step positions, which are never
        donated. That is exact: a step writes only position ``pos[s]`` of
        each slot ``s``, before any read of it, so the retry overwrites the
        failed attempt's rows with the same values and recovered runs are
        bit-identical to clean ones. An injected raise/OSError comes before
        the dispatch and donates nothing. ``delay`` faults are absorbed
        into the measured step latency.
        """
        rep = current_report()
        for attempt in range(_STEP_RETRIES):
            try:
                mult = _faults.poison_scalar("serve.step")
            except _faults.INJECTED:
                rep.count("detected", "serve.step")
                rep.count("retries", "serve.step")
                continue
            cache = self.cache
            with _span("serve.dispatch"):
                cache = self._live(cache, active_slots)
                toks = jnp.asarray(tokens.reshape(-1, 1), jnp.int32)
                logits, new_cache = self.model.step(cache, toks)
                if mult != 1.0:
                    logits = logits * mult
                ids = _sample(logits)
            donated = _kv_consumed(cache)
            with _span("serve.wait"):
                # sync: one pull per decode step — the next step's tokens
                # are this step's ids
                jax.block_until_ready(ids)
            with _span("serve.pull", bytes=ids.nbytes):
                host_ids = np.asarray(ids)  # sync: the copy of ready ids
            with _span("serve.check"):
                finite = (host_ids[active_slots] >= 0).all()
            if not finite:
                rep.count("detected", "serve.step")
                rep.count("retries", "serve.step")
                # a retry restarts from the pre-step positions and counts
                self.cache = dict(new_cache, pos=cache["pos"])
                if "route" in cache:
                    self.cache["route"] = cache["route"]
                continue
            if attempt:
                rep.count("recovered", "serve.step")
            self.cache = new_cache
            return host_ids, donated
        raise RuntimeError(
            f"serve.step produced unusable output {_STEP_RETRIES} times "
            "in a row — fault is not transient")

    # ------------------------------------------------------------------
    # the serving loop
    # ------------------------------------------------------------------

    def run(self, requests: List[Request]) -> ServeReport:
        """Serve a request stream to completion; returns per-request and
        aggregate metrics.

        Arrivals are admitted on a virtual clock: it advances by the
        measured wall-clock of each prefill/decode dispatch and
        fast-forwards across idle gaps to the next arrival, so a seeded
        Poisson stream yields deterministic tokens. Latencies are read
        from the host-clock stamps.
        """
        for r in requests:
            if r.prompt_len + r.steps > self.max_len:
                raise RuntimeError(
                    f"request {r.rid} overflows the KV cache: prompt_len="
                    f"{r.prompt_len} + steps={r.steps} > max_len="
                    f"{self.max_len}; decoding past capacity would "
                    "overwrite the last cache slot and corrupt output")
        with _span("serve.run", requests=len(requests)), _GCSpans():
            report = self._serve(requests)
            if "route" in self.cache:
                self._route_span()
            return report

    def _route_span(self):
        """A ``serve.route`` span whose stats are the run's routing counter
        (``transformer.decode_step``), copied to the host once, after the
        drain: ``assignments`` (held token-expert pairs), ``experts_hit``
        (held experts with a token, summed over layers and steps) and
        ``max_expert_tokens`` (most tokens on one held expert in one layer
        and step)."""
        route = self.cache["route"]
        # sync: once per run, after the last step
        a, hit, most = (int(v) for v in np.asarray(route))
        self.route = {"assignments": a, "experts_hit": hit,
                      "max_expert_tokens": most}
        with _span("serve.route", **self.route):
            # the next run counts from zero
            self.cache = dict(self.cache, route=jnp.zeros_like(route))

    def _serve(self, requests: List[Request]) -> ServeReport:
        t_run = self.clock()
        pending = sorted(requests, key=lambda r: (r.arrival, r.rid))
        records = {r.rid: RequestRecord(
            rid=r.rid, prompt_len=r.prompt_len, steps=r.steps,
            arrival=r.arrival, latency_class=r.latency_class)
            for r in requests}
        free = list(range(self.num_slots - 1, -1, -1))
        active: Dict[int, RequestRecord] = {}
        last_tok = np.zeros(self.num_slots, np.int64)
        remaining: Dict[int, int] = {}
        step_end: List[float] = []
        donated_steps = 0
        t = 0.0
        busy = 0.0

        while pending or active:
            # admit arrived requests into free slots (prefill + insert)
            while pending and free and pending[0].arrival <= t:
                req = pending.pop(0)
                slot = free.pop()
                with _span("serve.admit", rid=req.rid, slot=slot,
                           prompt_len=req.prompt_len,
                           bucket=_bucket(req.prompt_len, self.max_len)):
                    t0 = self.clock()
                    with _span("serve.prefill"):
                        logits, row = self.model.prefill(req.tokens)
                    with _span("serve.insert"):
                        self.cache = self.model.insert(
                            self.cache, row, slot, req.prompt_len)
                    with _span("serve.wait"):
                        # sync: one pull per admission — the first token
                        # gates whether the request enters the decode
                        # batch at all
                        jax.block_until_ready(logits)
                    with _span("serve.sample"):
                        # sync: the copy of ready logits
                        tok = int(np.argmax(np.asarray(logits),
                                            axis=-1)[0, 0])
                    t1 = self.clock()
                    dt = t1 - t0
                    t += dt
                    busy += dt
                    rec = records[req.rid]
                    rec.prefill_ms = dt * 1e3
                    rec.t_admit, rec.t_first = t0 - t_run, t1 - t_run
                    rec.tokens.append(tok)
                    last_tok[slot] = tok
                    if req.steps > 1:
                        active[slot] = rec
                        remaining[slot] = req.steps - 1
                        rec.first_step = len(step_end)
                    else:
                        rec.t_done = rec.t_first
                        free.append(slot)

            if not active:
                if pending:
                    t = max(t, pending[0].arrival)
                continue

            # one batched decode step over all slots
            slots = sorted(active)
            step = len(step_end)
            with _span("serve.step", step=step, active=len(slots)):
                t0 = self.clock()
                ids, donated = self._step_once(last_tok, slots)
                t1 = self.clock()
                dt = t1 - t0
                t += dt
                busy += dt
                step_end.append(t1 - t_run)
                donated_steps += donated
                with _span("serve.sample"):
                    toks = ids[slots].tolist()
                with _span("serve.bookkeep"):
                    for slot, tok in zip(slots, toks):
                        rec = active[slot]
                        rec.tokens.append(tok)
                        rec.decode_step_ms.append(dt * 1e3)
                        last_tok[slot] = tok
                        remaining[slot] -= 1
                        if remaining[slot] == 0:
                            rec.t_done, rec.last_step = step_end[-1], step
                            del active[slot]
                            del remaining[slot]
                            free.append(slot)

        return ServeReport(records=[records[r.rid] for r in requests],
                           wall_s=busy, steps=len(step_end),
                           kv_cache_bytes=self.kv_cache_bytes,
                           donated_steps=donated_steps, step_end=step_end)
