"""The serving job: an offline backlog through ``repro.serve.ServeEngine``.

Set-up makes the weights from the seed, builds the engine (64 slots of
``max_len`` positions by default) and warms every prompt length of the mix
through ``ServeEngine.warmup``. The window then runs streams back to back:
each stream is one ``ServeEngine.run`` over the mix's whole backlog, all of
it queued at the stream's start, timed by the host clock with its drain.
A stream starts only while the window is open; the last one runs to its
end. ``tokens_per_s`` is the output tokens of all streams over their summed
seconds.

Correctness, once the window has closed, the peak memory has been read and
the engine is released: a seeded sample of the finished requests, the
longest among them, is replayed through the plain float32 reference with
the served tokens as input. At each served position the reference's best
logit minus its logit of the served token is a gap; the widest gap is held
to the configuration's limit. Every request must also have returned as many
tokens as it asked for, no breaker may open and nothing may be demoted,
retried or compiled inside the window.

With ``ctx.control`` the fp8 control takes the program's place in that
comparison: at each position of the same prompts and served tokens, the gap
is that of the token the control puts first, held to the same limit, so a
control run comes out not correct. The program's own widest gap is kept in
the notes.
"""
from __future__ import annotations

import gc
import time
from contextlib import nullcontext
from typing import Dict, List

import numpy as np

import harness
import trace_reduce as T
import traffic

STREAM_SPAN = "bench.stream"
WARM_STREAM = 2**20    # stream index of the warm-up requests


class Recorder:
    """The engine's model adapter, passed through; it counts decode steps,
    records each admission's step index, prompt length and slot, and in a
    traced run names each call with a host span."""

    def __init__(self, model, traced: bool):
        import jax
        self.model = model
        self.max_len = model.max_len
        self._span = (jax.profiler.TraceAnnotation if traced
                      else lambda name: nullcontext())
        self.reset()

    def reset(self):
        self.steps = 0
        # (decode steps before, prompt_len, slot); the slot comes with the
        # insert that follows each prefill
        self.admissions: List = []
        self.t_last_admit = None

    def init_slots(self, nslots: int):
        return self.model.init_slots(nslots)

    def prefill(self, tokens):
        self.admissions.append((self.steps, int(tokens.shape[0]), None))
        self.t_last_admit = time.perf_counter()
        with self._span("bench.prefill"):
            return self.model.prefill(tokens)

    def insert(self, cache, row, slot, pos):
        a, s, _ = self.admissions[-1]
        self.admissions[-1] = (a, s, int(slot))
        with self._span("bench.insert"):
            return self.model.insert(cache, row, slot, pos)

    def step(self, cache, tokens):
        self.steps += 1
        with self._span("bench.step"):
            return self.model.step(cache, tokens)


def step_positions(admissions, reqs, nsteps: int, slots: int) -> np.ndarray:
    """The position each slot feeds at each decode step, (nsteps, slots),
    -1 where the slot is empty, rebuilt from the admissions: request r,
    admitted into slot c_r after a_r steps with a prompt of s_r tokens,
    feeds position s_r + j at step a_r + j for j < steps_r - 1 (the engine
    admits in request order)."""
    pos = np.full((nsteps, slots), -1, np.int64)
    for (a, s, c), r in zip(admissions, reqs):
        k = min(r.steps - 1, nsteps - a)
        if k > 0:
            pos[a:a + k, c] = s + np.arange(k)
    return pos


def _requests(reqs):
    from repro.serve.workload import Request
    return [Request(rid=r.rid, tokens=r.tokens, steps=r.steps, arrival=0.0)
            for r in reqs]


def check_sample(reqs, recs, mix, seed: int) -> List[int]:
    """Indices of the requests the reference replays: the longest, then a
    seeded draw until the sample holds ``check_tokens`` served tokens."""
    total = np.array([r.prompt_len + r.steps for r in reqs])
    order = list(np.random.default_rng(traffic.seed_words(seed, 4))
                 .permutation(len(reqs)))
    first = int(np.argmax(total))
    picked, served = [first], len(recs[first].tokens)
    for i in order:
        if served >= mix["check_tokens"]:
            break
        if i != first:
            picked.append(int(i))
            served += len(recs[i].tokens)
    return picked


def replay_gaps(gaps_fn, w, reqs, recs, idx, max_len: int, control=False):
    """The reference's gap at every served position of the sampled
    requests (and the control's, with ``control``), as flat arrays."""
    import jax.numpy as jnp
    out, low = [], []
    for i in idx:
        r, served = reqs[i], np.asarray(recs[i].tokens, np.int64)
        s, n = r.prompt_len, len(served)
        seq = np.zeros(max_len, np.int64)
        seq[:s] = r.tokens
        seq[s:s + n - 1] = served[:-1]
        tgt = np.zeros(max_len, np.int64)
        tgt[s - 1:s - 1 + n] = served
        score = np.zeros(max_len, bool)
        score[s - 1:s - 1 + n] = True
        g = gaps_fn(w, jnp.asarray(seq, jnp.int32), jnp.asarray(tgt, jnp.int32),
                    jnp.asarray(score))
        if control:
            g, c = g
            low.append(np.asarray(c)[score])
        out.append(np.asarray(g)[score])
    gaps = np.concatenate(out)
    return (gaps, np.concatenate(low)) if control else gaps


def run(ctx) -> Dict:
    """One run of a serving cell; returns the result (without checks) and
    the checks. ``ctx`` carries the parsed arguments, the configuration,
    the mix, the family, reference and counts modules, peaks and the start
    time. Of the configuration it reads only ``vocab_size`` and ``check``;
    the three modules get the whole dict."""
    import jax

    from repro.robustness.report import report_scope
    from repro.serve import ServeEngine

    cfg, mix, seed = ctx.cfg, ctx.mix, ctx.seed
    traffic.check_mix(mix)
    max_len, slots = int(mix["max_len"]), int(mix["slots"])
    model, plain_weights = ctx.family.serve_model(cfg, seed, max_len)
    rec = Recorder(model, ctx.trace)
    engine = ServeEngine(rec, num_slots=slots)
    # the prefill is the one call whose shapes follow the prompt's length:
    # each of the mix's lengths goes through it once; the insert and the
    # decode step have one shape each
    lengths = traffic.prompt_lengths(mix)
    for s in lengths:
        jax.block_until_ready(rec.prefill(np.zeros((s,), np.int64)))
    engine.warmup(lengths[:1])
    # one short stream through ServeEngine.run warms what the loop itself
    # converts and copies on the host side
    engine.run(_requests(traffic.stream(
        dict(mix, requests_per_stream=2, prompt=dict(mix["prompt"], sigma=0),
             output=dict(mix["output"], median=2, sigma=0, min=2)),
        cfg["vocab_size"], seed, WARM_STREAM)))
    jax.block_until_ready(engine.cache)
    table = traffic.markov_table(cfg["vocab_size"])

    def next_stream(index):
        reqs = traffic.stream(mix, cfg["vocab_size"], seed, index, table)
        return reqs, _requests(reqs)

    nxt = next_stream(0)
    watch = harness.CompileWatch()
    rec.reset()

    streams = []      # (reqs, report, seconds, steps, admissions)
    profiler = ctx.profiler() if ctx.trace else nullcontext()
    setup_s = None
    with report_scope() as rep, profiler:
        t_window = time.perf_counter()
        setup_s = time.time() - ctx.t_start
        watch.open = True
        elapsed = drain = 0.0
        while elapsed < ctx.seconds:
            reqs, requests = nxt
            rec.reset()
            with rec._span(STREAM_SPAN):
                t0 = time.perf_counter()
                report = engine.run(requests)
                jax.block_until_ready(engine.cache)
                dt = time.perf_counter() - t0
            watch.open = False
            # the drain: from the last admission to the stream's end
            drain += t0 + dt - rec.t_last_admit
            streams.append((reqs, report, dt, rec.steps, rec.admissions))
            elapsed += dt
            nxt = next_stream(len(streams))
            watch.open = True
        watch.open = False
        window_wall = time.perf_counter() - t_window
    mem_peak = harness.memory_peak_bytes(ctx.chips)

    reqs = [r for s in streams for r in s[0]]
    recs = [x for s in streams for x in s[1].records]
    tokens = sum(len(x.tokens) for x in recs)
    seconds = sum(s[2] for s in streams)
    incomplete = sum(len(x.tokens) != r.steps for r, x in zip(reqs, recs))
    robust = rep.as_dict()
    faults = (len(robust["breakers_open"]) + rep.total("demotions")
              + rep.total("retries") + rep.total("detected"))

    # the program's state goes before the reference runs
    meta = [(s[0], s[3], s[4]) for s in streams]
    stream_s = [s[2] for s in streams]
    del engine, rec, model, streams
    gc.collect()
    w = plain_weights()
    gaps_fn = ctx.reference.make_gaps(cfg, control=ctx.control)
    done = [i for i, (r, x) in enumerate(zip(reqs, recs))
            if len(x.tokens) == r.steps]
    idx = [done[i] for i in check_sample([reqs[i] for i in done],
                                         [recs[i] for i in done], mix, seed)]
    t_ref = time.perf_counter()
    gaps = replay_gaps(gaps_fn, w, reqs, recs, idx, max_len, ctx.control)
    if ctx.control:
        # the control in the program's place
        program_gaps, gaps = gaps
    ref_s = time.perf_counter() - t_ref
    limit = float(cfg["check"]["max_logit_gap"])
    # a sampled request fails where any of its gaps passes the limit
    per_req = np.split(gaps, np.cumsum([len(recs[i].tokens) for i in idx])[:-1])
    wrong = sum(float(g.max()) > limit for g in per_req)
    checks = {
        "max_logit_gap": {"value": float(gaps.max()), "limit": limit},
        "requests_short": {"value": int(incomplete), "limit": 0},
        "compiles_in_window": {"value": int(watch.count), "limit": 0},
        "fallbacks": {"value": int(faults), "limit": 0},
    }
    notes = {"streams": len(meta), "stream_s": stream_s,
             "decode_steps": [m[1] for m in meta],
             "drain_share": drain / seconds,
             "window_wall_s": window_wall,
             "reference_s": ref_s, "sampled_requests": len(idx),
             "sampled_tokens": int(len(gaps)),
             "compiled_in_window": watch.names[:5], "robustness": robust}
    if ctx.control:
        notes["program_max_logit_gap"] = float(program_gaps.max())
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(reqs), "failed": int(incomplete + wrong),
        "metrics": {"tokens_per_s": {"value": tokens / seconds,
                                     "unit": "tokens/s"},
                    "setup_s": {"value": setup_s, "unit": "s"}},
        "device": {"memory_peak_bytes": mem_peak},
        "notes": notes,
    }
    if ctx.trace:
        result["run"] = traced = serve_run(ctx, meta, seconds)
        notes["decode_least_s"] = traced["decode_least_s"]
        notes["required_flops"] = traced["required_flops"]
        # what the readers chose from: the costliest executables, each with
        # its device seconds and executions, against the decode calls
        mods = T.module_counts(traced["reduced"], traced["windows"])
        notes["decode_calls"] = traced["steps"]
        notes["executables"] = [[n, v[0] / 1e9, v[1]] for n, v in sorted(
            mods.items(), key=lambda kv: -kv[1][0])[:6]]
    return result, checks


def serve_run(ctx, meta, seconds: float):
    """What the per-layer readers of a traced serving run read. Besides the
    trace and the harness's own sums, a reader that counts a kernel's
    least work of its own finds the configuration's counts module
    (``counts``), each stream's positions, (steps, slots) with -1 for an
    empty slot (``positions``), and each stream's prompt lengths in
    admission order (``prompts``)."""
    cfg, peaks, counts = ctx.cfg, ctx.peaks_dev, ctx.counts
    reduced = T.reduce_xplane(T.latest_xplane(ctx.trace_dir))
    windows = T.windows_of(reduced, STREAM_SPAN)
    steps = sum(m[1] for m in meta)
    admissions = sum(len(m[2]) for m in meta)
    slots = int(ctx.mix["slots"])
    positions = [step_positions(adm, reqs, nsteps, slots)
                 for reqs, nsteps, adm in meta]
    prompts = [np.array([s for _, s, _ in adm], np.int64)
               for _, _, adm in meta]
    least, bound_s, flops = 0.0, {"compute": 0.0, "memory": 0.0}, 0
    for pos, lens in zip(positions, prompts):
        f, b = counts.decode_steps(cfg, pos)
        tc, tm = f / peaks["bf16_flops"], b / peaks["hbm_bytes_per_s"]
        least += float(np.maximum(tc, tm).sum())
        bound_s["compute"] += float(tc[tc >= tm].sum())
        bound_s["memory"] += float(tm[tm > tc].sum())
        flops += int(f.sum()) + sum(counts.prefill_flops(cfg, int(s))
                                    for s in lens)
    return {"reduced": reduced, "windows": windows, "steps": steps,
            "admissions": admissions, "decode_least_s": least,
            "decode_bound": max(bound_s, key=bound_s.get),
            "required_flops": flops, "host_window_s": seconds,
            "peaks": peaks, "counts": counts, "positions": positions,
            "prompts": prompts}
