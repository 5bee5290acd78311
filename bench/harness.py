"""What every cell shares: the manifest, files found by name, the device
check, the compile cache and the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; they
are ``bench/configs/<config>.json`` and ``bench/traffic/<traffic>.json``.
The mix names its job (``bench/<job>.py``), and the configuration its
family (``bench/families/<family>.py``: the program's side) and its
reference, the architecture's yardstick, in two files of one name: the
plain reference (``bench/references/<reference>.py``) and the least-work
counts (``bench/counts/<reference>.py``). Each of the three gets the
configuration's dict whole, so a new architecture joins by new files
alone. A per-layer metric is ``bench/metrics/<name>.py`` with a
``read(run)`` that returns a number, or None where the run holds nothing
for it to read.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Dict, List, Optional

CACHE_DIR = ".bench_cache/jax"    # fixed, inside the checkout
TRACE_DIR = ".bench_cache/trace"


class Refused(Exception):
    """The run cannot measure: no result is printed, the exit is non-zero."""


def load_json(bench_dir: str, kind: str, name: str) -> Dict:
    path = os.path.join(bench_dir, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise Refused(f"no {kind[:-1] if kind.endswith('s') else kind} "
                      f"file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(bench_dir: str, kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise Refused(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_manifest(root: str) -> Dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise Refused(f"no manifest {path}")
    with open(path) as f:
        return json.load(f)


def cell_of(manifest: Dict, workload: str) -> Dict:
    for c in manifest["workloads"]:
        if c["name"] == workload:
            return c
    raise Refused(f"no workload {workload!r} in BENCHMARK.json")


def _lists(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def end_to_end_of(manifest: Dict, cell: str) -> List[Dict]:
    return [m for m in manifest["end_to_end"] if _lists(m, cell)]


def per_layer_of(manifest: Dict, cell: str) -> List[Dict]:
    """Per-layer metrics the cell reports: those that list it, and those
    without a list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_of(manifest, cell)}
    return [m for m in manifest["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def use_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at the fixed checkout path."""
    import jax
    path = os.path.join(root, CACHE_DIR)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def load_peaks(bench_dir: str) -> Dict:
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        return json.load(f)


def require_device(chips: int, peaks: Dict) -> Dict:
    """The device record of the run; refuses anything but a TPU with a
    peaks entry and at least ``chips`` chips."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise Refused(f"no TPU: JAX found platform {d.platform!r}")
    if d.device_kind not in peaks["devices"]:
        raise Refused(f"device kind {d.device_kind!r} has no peaks entry")
    if len(devices) < chips:
        raise Refused(f"{chips} chips asked for, {len(devices)} found")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(chips: int) -> Optional[int]:
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileWatch:
    """Counts JAX traces and backend compiles while ``open`` is set."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.open = False
        self.count = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.open and event in self.EVENTS:
            self.count += 1
            self.names.append(str(kw.get("fun_name", event)))


def read_metrics(bench_dir: str, metrics: List[Dict], run) -> Dict:
    """Each per-layer metric's reader over the run; silent ones are left
    out."""
    out = {}
    for m in metrics:
        value = load_module(bench_dir, "metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def print_result(result: Dict, checks: Dict) -> None:
    """The compared numbers as the last lines of standard error, then the
    result as the last line of standard output, with ``checks`` last."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = checks
    print(json.dumps(result), flush=True)
