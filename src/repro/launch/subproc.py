"""Forced-multi-device subprocess harness.

XLA fixes the host-platform device count at first jax import, so mesh
code can only be driven from a single-device parent (tests, benchmarks)
by re-launching in a subprocess with ``XLA_FLAGS`` set first. This is the
one copy of that pattern — tests/test_sharding.py,
tests/test_sharded_calibration.py and benchmarks/run.py all route through
it. The driven script reports by printing ``"RESULT" + json.dumps(...)``
as its last RESULT line.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict

_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def run_forced_devices(script: str, n_devices: int, *,
                       timeout: float = 900) -> Dict:
    """Run ``script`` in a fresh interpreter with ``n_devices`` forced
    host-platform devices; returns the parsed RESULT-line JSON.

    The child's ``XLA_FLAGS`` is overwritten (the forced count must win)
    and its ``JAX_PLATFORMS`` is ``cpu``: the forced devices are host
    devices, and a child must never reach for an accelerator its parent
    may already hold. ``PYTHONPATH`` is prepended to, not replaced.
    Raises RuntimeError with stdout/stderr tails on a non-zero exit, a
    missing RESULT line, or a timeout — the timeout case includes whatever partial output the
    child produced before the kill (a bare TimeoutExpired hid the
    hung child's last prints, which are exactly the debugging signal).
    """
    preamble = ("import os\n"
                "os.environ['XLA_FLAGS'] = "
                f"'--xla_force_host_platform_device_count={n_devices}'\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "") \
        if env.get("PYTHONPATH") else _SRC
    try:
        r = subprocess.run([sys.executable, "-c", preamble + script],
                           env=env, capture_output=True, text=True,
                           timeout=timeout)
    except subprocess.TimeoutExpired as e:
        def _txt(b):
            return (b.decode(errors="replace") if isinstance(b, bytes)
                    else (b or ""))
        tail = _txt(e.stdout)[-3000:] + _txt(e.stderr)[-3000:]
        raise RuntimeError(
            f"forced-device subprocess timed out after {timeout}s; "
            f"partial output:\n{tail or '<none captured>'}") from e
    tail = r.stdout[-3000:] + r.stderr[-3000:]
    if r.returncode != 0:
        raise RuntimeError(f"forced-device subprocess failed "
                           f"(rc={r.returncode}):\n{tail}")
    lines = [l for l in r.stdout.splitlines() if l.startswith("RESULT")]
    if not lines:
        raise RuntimeError(f"no RESULT line in subprocess output:\n{tail}")
    return json.loads(lines[-1][len("RESULT"):])
