"""Smoke run of the ZipLM compression pipeline and serving engine on a TPU.

  python chip_smoke.py            # one chip: every phase below
  python chip_smoke.py --chips 4  # four chips: the sharded family build only

The model is GPT2-small at its published widths (12 layers, d_model 768,
d_ff 3072, 12 heads, vocab 50257) with random weights made from
``--seed`` and synthetic calibration data. Everything runs in this one
process and goes through the entry points a user calls:

  device    platform, device kind and device count; fails unless the
            platform is ``tpu`` and the kind has a peak entry in
            ``runtime.costmodel.HARDWARE``
  compress  ``core.oneshot.oneshot_prune`` for targets 1.5x and 2.0x in a
            (batch 16, seq 128) prefill environment, latency table timed
            on the chip (``latency_backend="measure"``)
  shrink    ``core.shrink.shrink`` of each member; its logits match the
            stitched (masked) model's within a bf16 tolerance
  serve     ``serve.ServeEngine`` for the dense model and the 2.0x member;
            in fp32, the dense engine's greedy tokens equal
            ``models.generate``'s; then ``launch.serve.main`` for gpt2-small
  kernels   each Pallas op of ``kernels.ops`` compiled for the chip
            (``interpret=False``) against its ``kernels.ref`` twin
  end       fails if any robustness breaker opened or any demotion was
            counted: on the chip a fallback is a failure, not a recovery.
            Damping retries of Algorithm 1 are printed, not failed: they
            are the algorithm's own answer to an ill-conditioned Hessian

``--chips 4`` runs only the ``oneshot_prune`` family build (targets 1.25x
and 1.5x) on a 4-device data mesh (depth cut to 4 layers), against the
``mesh=None`` build in the same process: Hessians within 1e-5 relative,
identical pruning orders from the same Hessians, identical placed and
unplaced SPDY assignments.

Each line names the device; each phase ends with the peak bytes in use.
Times are smoke readings of one run (compile excluded where stated), not
benchmark numbers. The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``; any failure exits non-zero before it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

TARGETS = (1.5, 2.0)
# at the mesh phase's 4 layers the embedding and LM head, which no target
# can prune, take 0.88 of the dense 1.55 ms in the analytic table: no
# member gets past 1.75x
MESH_TARGETS = (1.25, 1.5)
MAX_LEN = 128
SLOTS = 4
REQUESTS = 8
# logits of a shrunk member vs its masked twin, relative to max |logit|:
# the two sum the same bf16 products in different orders
SHRINK_TOL = 3e-2
# kernel vs ref twin, relative to max |ref|
KERNEL_TOL = {"bfloat16": 2e-2, "float32": 1e-2}
MESH_HESSIAN_TOL = 1e-5


class Failed(Exception):
    """A phase's check did not hold."""


class Log:
    """Phase lines, each naming the device."""

    def __init__(self, devices, count: int):
        self.devices = devices
        self.count = count  # len(jax.devices())
        d = devices[0]
        self.tag = f"{d.platform}/{d.device_kind}"

    def __call__(self, phase: str, msg: str):
        print(f"[{phase}] {self.tag}: {msg}", flush=True)

    def peak(self, phase: str):
        peaks = [d.memory_stats()["peak_bytes_in_use"] for d in self.devices]
        self(phase, "peak_bytes_in_use " + " ".join(
            f"dev{i}={p}" for i, p in enumerate(peaks)))


def _rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-30))


def device_phase(chips: int):
    import jax

    from repro.runtime.costmodel import hardware_for
    devices = jax.devices()
    d = devices[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}", flush=True)
    if d.platform != "tpu":
        raise Failed(f"no TPU: JAX found platform {d.platform!r}")
    hw = hardware_for(d.device_kind)
    if len(devices) < chips:
        raise Failed(f"{chips} chips asked for, {len(devices)} found")
    log = Log(devices[:chips], len(devices))
    log("device", f"count={len(devices)} peak {hw.peak_flops:.3g} FLOP/s "
                  f"bf16, {hw.hbm_bw:.3g} B/s HBM ({hw.name})")
    return log


def compress_phase(log, cfg, seed: int):
    import jax
    import numpy as np

    from repro.core.oneshot import oneshot_prune
    from repro.data import calibration_batches
    from repro.models import model_init
    from repro.runtime.costmodel import InferenceEnv

    params, _ = model_init(cfg, jax.random.key(seed))
    calib = calibration_batches(cfg, 32, 128, batch=8, seed=seed)
    env = InferenceEnv(batch=16, seq=128, mode="prefill")
    t0 = time.perf_counter()
    res = oneshot_prune(cfg, params, calib, env, targets=TARGETS,
                        latency_backend="measure",
                        latency_kw={"grid_subsample": 8},
                        search_steps=32, search_pop=16,
                        eval_batches=calib[:1], seed=seed)
    log("compress", f"{cfg.name}: {cfg.num_params() / 1e6:.1f}M params, "
                    f"{len(calib)} calibration batches of 8x128, family "
                    f"built in {time.perf_counter() - t0:.1f}s "
                    f"(compile included)")
    log("compress", f"dense calib loss {res.dense_loss:.4f}, "
                    f"table dense runtime {res.dense_runtime * 1e3:.3f} ms")
    if not np.isfinite(res.dense_loss):
        raise Failed("dense calibration loss is not finite")
    for t, v in sorted(res.variants.items()):
        log("compress", f"target {t}x: table speedup {v.speedup:.3f}x, "
                        f"calib loss {v.calib_loss:.4f}")
        if not (v.speedup >= t and np.isfinite(v.calib_loss)):
            raise Failed(f"member {t}x: speedup {v.speedup} or loss "
                         f"{v.calib_loss} breaks the family's guarantee")
    return params, calib, res


def _pruned_logits_fn(pm):
    """jit of ``forward_pruned`` with the member's weights as arguments
    (closing over them would bake them into the executable)."""
    import jax

    from repro.models.pruned import forward_pruned
    jf = jax.jit(lambda w, tokens: forward_pruned(pm.with_weights(w), tokens))
    return lambda tokens: jf(pm.weights(), tokens)


def shrink_phase(log, cfg, res, calib):
    import jax

    from repro.core.shrink import shrink
    from repro.models.transformer import forward
    masked_logits = jax.jit(lambda p, t: forward(cfg, p, t)["logits"])
    tokens = calib[0]["tokens"]
    members = {}
    for t, v in sorted(res.variants.items()):
        pm = shrink(cfg, v.params, res.db, v.assignment)
        err = _rel_err(_pruned_logits_fn(pm)(tokens),
                       masked_logits(v.params, tokens))
        log("shrink", f"{t}x: {pm.encoder_params() / 1e6:.2f}M stack "
                      f"params; logits vs masked model rel err {err:.2e} "
                      f"(tol {SHRINK_TOL:g})")
        if not err <= SHRINK_TOL:
            raise Failed(f"shrunk {t}x member departs from its masked twin")
        members[t] = pm
    return members


def _serve(log, name, model, reqs):
    from repro.serve import ServeEngine
    engine = ServeEngine(model, num_slots=SLOTS)
    t0 = time.perf_counter()
    engine.warmup(sorted({r.prompt_len for r in reqs}))
    warm = time.perf_counter() - t0
    report = engine.run(reqs)
    m = report.as_dict()
    log("serve", f"{name}: {m['requests']} requests, {m['total_tokens']} "
                 f"tokens, {SLOTS} slots: {m['tokens_per_s']:.1f} tokens/s, "
                 f"prefill {m['prefill_ms_mean']:.3f} ms, decode "
                 f"{m['decode_ms_per_token_mean']:.3f} ms/token (warm; "
                 f"warmup with compile {warm:.1f}s), KV cache "
                 f"{m['kv_cache_bytes']} B")
    return report


def serve_phase(log, cfg, params, member, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve as serve_launcher
    from repro.models import generate
    from repro.serve import (DenseServeModel, PrunedServeModel,
                             synthetic_requests)

    # prompt lengths equal to prefill buckets: the engine prefills at the
    # shape generate() uses, so the two must agree token for token
    reqs = synthetic_requests(cfg, REQUESTS, seed=seed, rate=100.0,
                              prompt_lens=(16, 32), steps_range=(8, 32))
    _serve(log, "dense", DenseServeModel(cfg, params, MAX_LEN), reqs)
    # token equality is checked in fp32: with random weights the top logits
    # lie within bf16 rounding of each other, and on a v5e the bf16 engine
    # (batched decode, per-slot positions) and generate() (one row) parted
    # at the second token of a request
    cfg32 = cfg.replace(dtype="float32")
    with jax.default_matmul_precision("highest"):
        exact = _serve(log, "dense fp32", DenseServeModel(cfg32, params,
                                                          MAX_LEN), reqs)
        for req, rec in zip(reqs[:2], exact.records[:2]):
            ref = generate(cfg32, params, jnp.asarray(req.tokens[None, :]),
                           steps=req.steps, max_len=MAX_LEN)
            ref = [int(x) for x in np.asarray(ref[0])]
            if rec.tokens != ref:
                raise Failed(f"request {req.rid}: engine {rec.tokens} != "
                             f"generate {ref}")
    log("serve", "dense fp32 engine greedy tokens == models.generate for "
                 f"requests {reqs[0].rid} and {reqs[1].rid}")
    _serve(log, "2.0x member", PrunedServeModel(member, MAX_LEN), reqs)
    m = serve_launcher.main(["--arch", "gpt2-small", "--slots", str(SLOTS),
                             "--requests", str(REQUESTS),
                             "--max-len", str(MAX_LEN)])
    log("serve", f"launch.serve gpt2-small: {m['tokens_per_s']:.1f} "
                 f"tokens/s, prefill {m['prefill_ms_mean']:.3f} ms, decode "
                 f"{m['decode_ms_per_token_mean']:.3f} ms/token")
    if not m["total_tokens"] > 0:
        raise Failed("launch.serve produced no tokens")


def kernels_phase(log, rep, seed: int, interpret: bool = False):
    """Each Pallas op at GPT2-small widths (ssd at a small mamba2 shape)
    against its jnp twin. ``interpret`` stays False on the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    rng = np.random.default_rng(seed)

    def arr(shape, dtype=jnp.float32, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    def check(op, got, want, dtype):
        if rep.breaker_open(f"kernel.pallas:{op}"):
            raise Failed(f"{op}: the kernel failed and was demoted: "
                         f"{rep.notes[-1]}")
        err = max(_rel_err(g, w) for g, w in
                  zip(jax.tree.leaves(got), jax.tree.leaves(want)))
        tol = KERNEL_TOL[dtype]
        log("kernels", f"{op}: rel err vs ref {err:.2e} (tol {tol:g})")
        if not err <= tol:
            raise Failed(f"{op}: kernel departs from its ref twin")

    # refs at full fp32 matmul precision: the twins are the ground truth
    hi = jax.default_matmul_precision("highest")
    q, k, v = (arr((1, 1024, 12, 64), jnp.bfloat16) for _ in range(3))
    got = ops.flash_attention(q, k, v, causal=True, interpret=interpret)
    with hi:
        want = ops._flash_attention_ref(q, k, v, True, 0)
    check("flash_attention", got, want, "bfloat16")

    x = arr((1024, 3072))
    got = ops.hessian_accum(x, interpret=interpret)
    with hi:
        want = ops._hessian_accum_ref(x)
    check("hessian_accum", got, want, "float32")

    for d_in, d_out, gs in ((3072, 768, 1), (768, 768, 64)):
        a = arr((d_in, d_in), scale=d_in ** -0.5)
        args = (arr((d_in, d_out)), a @ a.T, arr((d_in, gs)),
                arr((gs, d_out)), arr((gs, d_in)),
                jnp.asarray(rng.random(d_in) > 0.3, jnp.float32))
        got = ops.obs_downdate(*args, interpret=interpret)
        with hi:
            want = ops._obs_downdate_ref(*args)
        check("obs_downdate", got, want, "float32")

    b, s, h, p, n = 1, 512, 8, 64, 128  # mamba2 head_dim/state, 8 heads
    args = (arr((b, s, h, p), scale=0.5),
            jax.nn.softplus(arr((b, s, h))),
            -jnp.exp(arr((h,), scale=0.3)),
            arr((b, s, n), scale=0.5), arr((b, s, n), scale=0.5))
    got = ops.ssd_chunked_kernel(*args, chunk=128, interpret=interpret)
    with hi:
        want = ops._ssd_ref(*args)
    check("ssd", got, want, "float32")


def mesh_phase(log, cfg, seed: int):
    """The family build on a 4-device data mesh against mesh=None."""
    import jax
    import numpy as np

    from repro.core.database import SnapshotCache, build_database
    from repro.core.oneshot import make_batched_eval, oneshot_prune
    from repro.core.spdy import search_family
    from repro.data import calibration_batches
    from repro.distributed.sharding import make_mesh
    from repro.models import model_init
    from repro.runtime.costmodel import InferenceEnv

    devices = log.devices
    mesh = make_mesh((len(devices),), ("data",))
    params, _ = model_init(cfg, jax.random.key(seed))
    calib = calibration_batches(cfg, 32, 128, batch=8, seed=seed)
    env = InferenceEnv(batch=16, seq=128, mode="prefill")
    # the analytic table: both builds price members identically, so only
    # the device-parallel paths differ between them
    kw = dict(latency_backend="costmodel", search_steps=16, search_pop=8,
              eval_batches=calib[:1], seed=seed)
    t0 = time.perf_counter()
    sh = oneshot_prune(cfg, params, calib, env, MESH_TARGETS, mesh=mesh,
                       **kw)
    t_sh = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref = oneshot_prune(cfg, params, calib, env, MESH_TARGETS, mesh=None,
                        **kw)
    t_ref = time.perf_counter() - t0
    log("mesh", f"{cfg.name} at {cfg.num_layers} layers: family on "
                f"{len(devices)}-device mesh {t_sh:.1f}s, mesh=None "
                f"{t_ref:.1f}s (compile included)")
    for t in MESH_TARGETS:
        log("mesh", f"target {t}x: mesh speedup {sh.variants[t].speedup:.3f}x "
                    f"loss {sh.variants[t].calib_loss:.4f}; mesh=None "
                    f"{ref.variants[t].speedup:.3f}x "
                    f"loss {ref.variants[t].calib_loss:.4f}")

    # every comparison runs and prints before the phase fails
    failed = []
    spread = {len(h.sharding.device_set) for h in sh.hessians.values()}
    if spread != {len(devices)}:
        failed.append(f"calibration did not take the sharded path: "
                      f"Hessians live on {spread} devices")
    err = max(_rel_err(sh.hessians[k], ref.hessians[k])
              for k in ref.hessians)
    log("mesh", f"sharded vs single-device Hessians: max rel err {err:.2e} "
                f"(tol {MESH_HESSIAN_TOL:g}), on {sorted(spread)} devices")
    if not err <= MESH_HESSIAN_TOL:
        failed.append("sharded Hessians depart from the single-device ones")

    db_sh = build_database(cfg, params, ref.hessians, mesh=mesh)
    same = [n for n in ref.db
            if np.array_equal(db_sh[n].order, ref.db[n].order)]
    log("mesh", f"sharded DB build from the same Hessians: "
                f"{len(same)}/{len(ref.db)} modules with identical orders")
    if len(same) != len(ref.db):
        failed.append("sharded DB build changed pruning orders")

    ev = make_batched_eval(cfg, params, SnapshotCache(cfg, ref.db),
                           calib[:1])
    placed = search_family(ref.db, ref.table, MESH_TARGETS, steps=16,
                           pop=8,
                           eval_batched=ev, seed=seed, devices=devices)
    for t in MESH_TARGETS:
        ok = placed[t].assignment == ref.variants[t].assignment
        log("mesh", f"target {t}x: placed SPDY on {len(devices)} devices "
                    f"{'==' if ok else '!='} unplaced assignment")
        if not ok:
            failed.append(f"placed SPDY changed the {t}x assignment")
    if failed:
        raise Failed("; ".join(failed))


def end_phase(log, rep):
    d = rep.as_dict()
    demotions, retries = d["counts"]["demotions"], d["counts"]["retries"]
    log("end", f"breakers open {d['breakers_open']}, demotions {demotions}, "
               f"retries {retries}")
    if d["breakers_open"] or demotions:
        raise Failed("a fallback hid the device: " + "; ".join(d["notes"]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.runtime.device import use_compile_cache
    cache_dir = use_compile_cache()
    t_start = time.perf_counter()
    log = device_phase(args.chips)
    log("device", f"compile cache {cache_dir}")
    log.peak("device")

    from repro.configs import GPT2_SMALL
    from repro.robustness.report import report_scope

    with report_scope() as rep:
        if args.chips == 4:
            mesh_phase(log, GPT2_SMALL.replace(num_layers=4), args.seed)
            log.peak("mesh")
        else:
            params, calib, res = compress_phase(log, GPT2_SMALL, args.seed)
            log.peak("compress")
            members = shrink_phase(log, GPT2_SMALL, res, calib)
            log.peak("shrink")
            serve_phase(log, GPT2_SMALL, params, members[2.0], args.seed)
            log.peak("serve")
            kernels_phase(log, rep, args.seed)
            log.peak("kernels")
    end_phase(log, rep)
    log("end", f"all phases passed in {time.perf_counter() - t_start:.1f}s")
    d = log.devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": log.count}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Failed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
