"""Run one cell of the benchmark once, on the machine it is started on.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` at the checkout's root; see
``bench/harness.py`` for how its files are found. The run makes its inputs
and weights from ``--seed``, warms up every shape the cell uses (set-up),
measures for ``--seconds`` and then checks what the timed path produced
against the plain reference. With ``--trace 0`` it reports the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window. The last line of standard output is the
result as JSON; the compared numbers and their limits are the last lines of
standard error. Without a TPU, or with fewer chips than the cell asks for,
it prints no result and exits non-zero.

``--control 1`` puts the fp8 control in the program's place in the
comparison that decides ``correct``, on the same sample, so that the run
comes out not correct (for setting limits; the benchmark's own runs never
pass it).
"""
from __future__ import annotations

import time

T_WALL0 = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import harness  # noqa: E402

def process_start() -> float:
    """Wall-clock time at which this process started."""
    try:
        import psutil
        return psutil.Process().create_time()
    except Exception:
        return T_WALL0


class Ctx:
    """What a job's ``run`` reads."""


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def make_ctx(args) -> Ctx:
    """Everything the cell's job needs; refuses before any JAX work where a
    file is missing or the device is not the chip the cell asks for."""
    manifest = harness.load_manifest(ROOT)
    cell = harness.cell_of(manifest, args.workload)
    ctx = Ctx()
    ctx.manifest, ctx.cell, ctx.chips = manifest, cell, int(cell["chips"])
    ctx.cfg = harness.load_json(BENCH, "configs", cell["config"])
    ctx.mix = harness.load_json(BENCH, "traffic", cell["traffic"])
    ctx.family = harness.load_module(BENCH, "families", ctx.cfg["family"])
    # the architecture's yardstick: its reference and its counts share the
    # configuration's ``reference`` name
    ctx.reference = harness.load_module(BENCH, "references",
                                        ctx.cfg["reference"])
    ctx.counts = harness.load_module(BENCH, "counts", ctx.cfg["reference"])
    ctx.job = harness.load_module(BENCH, ".", ctx.mix["job"])
    ctx.peaks = harness.load_peaks(BENCH)
    ctx.seed, ctx.seconds = args.seed, args.seconds
    ctx.trace, ctx.control = bool(args.trace), bool(args.control)
    ctx.t_start = process_start()
    ctx.trace_dir = os.path.join(ROOT, harness.TRACE_DIR, args.workload)
    try:
        import repro  # noqa: F401  the system under test
    except ImportError as e:
        raise harness.Refused(f"the program is not in this checkout: {e}")
    ctx.device = harness.require_device(ctx.chips, ctx.peaks)
    # only once the chip is found: a cache written on another platform is
    # no use to it
    harness.use_compile_cache(ROOT)
    ctx.peaks_dev = ctx.peaks["devices"].get(ctx.device["kind"])

    def profiler():
        import jax
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # host spans only, not every call
        opts.enable_hlo_proto = False
        return jax.profiler.trace(ctx.trace_dir, profiler_options=opts)

    ctx.profiler = profiler
    return ctx


class Run:
    """The traced run as the per-layer readers see it."""

    def __init__(self, ctx, data):
        self.cfg, self.mix, self.cell = ctx.cfg, ctx.mix, ctx.cell
        self.__dict__.update(data)


def finish(ctx, result) -> dict:
    """The result line: the cell's metrics for this kind of run, the
    device, and for a traced run its busy time and breakdown."""
    import trace_reduce as T
    out = {k: result[k] for k in ("correct", "attempted", "failed")}
    device = dict(ctx.device)
    device.update(result["device"])
    cell = ctx.cell["name"]
    if ctx.trace:
        run = Run(ctx, result.pop("run"))
        metrics = harness.per_layer_of(ctx.manifest, cell)
        out["metrics"] = harness.read_metrics(BENCH, metrics, run)
        device["busy_s"] = T.busy_ns(run.reduced, run.windows) / 1e9
        device["window_s"] = T.window_ns(run.windows) / 1e9
        out["breakdown"] = {
            "device_ops": T.top_ops(run.reduced, run.windows),
            "idle_gaps": T.idle_gaps(run.reduced, run.windows)}
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    else:
        names = {m["name"] for m in harness.end_to_end_of(ctx.manifest, cell)}
        out["metrics"] = {k: v for k, v in result["metrics"].items()
                          if k in names}
    out["device"] = device
    out["notes"] = result.get("notes", {})
    return out


def main(argv=None) -> int:
    args = parse(argv)
    try:
        ctx = make_ctx(args)
        result, checks = ctx.job.run(ctx)
    except harness.Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return 2
    out = finish(ctx, result)
    print("bench: notes " + json.dumps(out.pop("notes"), default=str),
          file=sys.stderr)
    harness.print_result(out, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
