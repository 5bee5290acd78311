"""Trace-level audit of jitted entry points.

Given a jitted callable plus example arguments, trace it (JAX AOT API:
``jitted.trace(*args)``) and walk the ClosedJaxpr to flag hazards that
never show up in unit tests but eat the hot path:

* ``jaxpr.host-callback`` — host callback primitives (``pure_callback``,
  ``io_callback``, ``debug_callback``) reachable from the entry point;
  counted trip-weighted, and separately when they sit inside a
  ``scan``/``while`` body (a device→host sync *per iteration*).
* ``jaxpr.large-const`` — closed-over constants above a byte threshold:
  these are baked into every compiled executable (one copy per jit
  cache entry — the serve prefill buckets multiply them by the number
  of buckets) instead of being passed as arguments.
* ``jaxpr.undonated`` — arguments declared in ``donate_argnums`` whose
  buffers the compiled module did not actually alias to an output
  (parsed from the ``input_output_alias`` attribute of the compiled
  HLO), i.e. donation that silently buys nothing.
* ``jaxpr.weak-type`` — weakly-typed inputs / constants (python scalar
  leakage), which fork the jit cache per Python literal.
* FLOP/byte cross-check — per-primitive ``dot_general`` FLOPs counted
  from the jaxpr (trip-weighted through ``scan``) are compared against
  ``runtime.hlo_analysis.analyze_hlo_text`` on the compiled module; the
  ratio is budgeted as a band. Together with the ``LatencyTable``
  prediction this is the "third column" of the predicted-vs-achieved
  latency loop.
"""
from __future__ import annotations

import math
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.extend import core as jcore

from repro.analysis.findings import Finding
from repro.runtime.hlo_analysis import analyze_hlo_text

CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback", "callback")

# Consts smaller than this are treated as scalars/epsilon tables, not
# baked-in tensors. 16 KiB = a (64, 64) float32.
CONST_BYTE_THRESHOLD = 16 * 1024

# one alias entry: `{out_index}: (param_number, {param_index}, kind)`
_ALIAS_ENTRY_RE = re.compile(r"\}\s*:\s*\(\s*(\d+)\s*,")


def _as_jaxprs(v) -> List[jcore.Jaxpr]:
    if isinstance(v, jcore.ClosedJaxpr):
        return [v.jaxpr]
    if isinstance(v, jcore.Jaxpr):
        return [v]
    if isinstance(v, (tuple, list)):
        out = []
        for x in v:
            out.extend(_as_jaxprs(x))
        return out
    return []


def _sub_jaxprs(eqn) -> List[Tuple[jcore.Jaxpr, int, bool]]:
    """(sub_jaxpr, trip_multiplier, enters_loop) for one equation."""
    prim = eqn.primitive.name
    p = eqn.params
    if prim == "scan":
        length = int(p.get("length") or 1)
        return [(p["jaxpr"].jaxpr, length, True)]
    if prim == "while":
        # Trip count is dynamic; weight 1 but mark as loop body.
        return [(p["body_jaxpr"].jaxpr, 1, True),
                (p["cond_jaxpr"].jaxpr, 1, True)]
    if prim == "cond":
        return [(j, 1, False) for br in p["branches"] for j in _as_jaxprs(br)]
    out = []
    for v in p.values():
        out.extend((j, 1, False) for j in _as_jaxprs(v))
    return out


def iter_eqns(jaxpr: jcore.Jaxpr, mult: int = 1, in_loop: bool = False):
    """Yield (eqn, trip_multiplier, inside_loop) over all nested jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn, mult, in_loop
        for sub, m, loop in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, mult * m, in_loop or loop)


def _dot_flops(eqn) -> float:
    (lhs_c, _rhs_c), _batch = eqn.params["dimension_numbers"]
    lhs_shape = eqn.invars[0].aval.shape
    contract = 1
    for d in lhs_c:
        contract *= lhs_shape[d]
    out = 1
    for d in eqn.outvars[0].aval.shape:
        out *= d
    return 2.0 * out * contract


def _nbytes(x) -> int:
    n = getattr(x, "nbytes", None)
    if n is not None:
        return int(n)
    return int(np.asarray(x).nbytes)


def count_declared_donated(args: Sequence[Any], donate_argnums: Sequence[int]
                           ) -> int:
    n = 0
    for i in donate_argnums:
        n += len(jax.tree_util.tree_leaves(args[i]))
    return n


def count_hlo_aliases(hlo_text: str) -> int:
    """Number of parameter buffers the compiled module aliases to outputs.

    The attribute nests braces — ``input_output_alias={ {0}: (0, {},
    may-alias), ... }`` — so the block is extracted by brace matching,
    not a lazy regex.
    """
    start = hlo_text.find("input_output_alias={")
    if start < 0:
        return 0
    i = hlo_text.index("{", start)
    depth = 0
    for j in range(i, len(hlo_text)):
        if hlo_text[j] == "{":
            depth += 1
        elif hlo_text[j] == "}":
            depth -= 1
            if depth == 0:
                break
    else:
        return 0
    return len(_ALIAS_ENTRY_RE.findall(hlo_text[i:j + 1]))


def audit_traced(name: str, closed: jcore.ClosedJaxpr,
                 *, const_threshold: int = CONST_BYTE_THRESHOLD
                 ) -> Tuple[Dict[str, Any], List[Finding]]:
    """Walk one ClosedJaxpr; pure function of the trace (no compile)."""
    findings: List[Finding] = []
    cb_total = 0
    cb_in_loop = 0
    dot_flops = 0.0
    n_eqns = 0
    for eqn, mult, in_loop in iter_eqns(closed.jaxpr):
        n_eqns += 1
        prim = eqn.primitive.name
        if prim in CALLBACK_PRIMS:
            cb_total += mult
            if in_loop:
                cb_in_loop += mult
            findings.append(Finding(
                rule="jaxpr.host-callback",
                severity="error" if in_loop else "warning",
                where=name,
                message=(f"host callback `{prim}` "
                         + ("inside a device loop body (one device->host "
                            "sync per iteration)" if in_loop else
                            "reachable from this entry point")
                         + " — hoist it out or annotate the host-side "
                           "caller with `# sync:`"),
                detail={"primitive": prim, "trip_weight": mult,
                        "in_loop": in_loop}))
        elif prim == "dot_general":
            dot_flops += mult * _dot_flops(eqn)

    large_consts = []
    weak_consts = 0
    for c in closed.consts:
        nb = _nbytes(c)
        if getattr(c, "weak_type", False):
            weak_consts += 1
        if nb > const_threshold:
            shape = tuple(getattr(c, "shape", ()))
            dtype = str(getattr(c, "dtype", type(c).__name__))
            large_consts.append({"shape": shape, "dtype": dtype, "bytes": nb})
            findings.append(Finding(
                rule="jaxpr.large-const", severity="error", where=name,
                message=(f"closed-over constant {dtype}{shape} ({nb} B) is "
                         "baked into the executable (one copy per jit cache "
                         "entry) — pass it as an argument instead"),
                detail={"shape": list(shape), "dtype": dtype, "bytes": nb}))

    weak_invars = sum(
        1 for v in closed.jaxpr.invars
        if getattr(getattr(v, "aval", None), "weak_type", False))
    if weak_invars or weak_consts:
        findings.append(Finding(
            rule="jaxpr.weak-type", severity="warning", where=name,
            message=(f"{weak_invars + weak_consts} weakly-typed "
                     "inputs/constants (python scalar leakage) — each "
                     "distinct literal forks the jit cache; wrap in "
                     "jnp.asarray with an explicit dtype"),
            detail={"invars": weak_invars, "consts": weak_consts}))

    arg_bytes = sum(
        int(math.prod(v.aval.shape)) * v.aval.dtype.itemsize
        for v in closed.jaxpr.invars if hasattr(v.aval, "shape"))
    out_bytes = sum(
        int(math.prod(v.aval.shape)) * v.aval.dtype.itemsize
        for v in closed.jaxpr.outvars if hasattr(v.aval, "shape"))

    metrics: Dict[str, Any] = {
        "host_callbacks": int(cb_total),
        "host_callbacks_in_loop": int(cb_in_loop),
        "large_consts": len(large_consts),
        "large_const_bytes": int(sum(c["bytes"] for c in large_consts)),
        "weak_invars": int(weak_invars + weak_consts),
        "dot_flops": float(dot_flops),
        "n_eqns": int(n_eqns),
        "arg_bytes": int(arg_bytes),
        "out_bytes": int(out_bytes),
    }
    return metrics, findings


def audit_jitted(name: str, jitted, args: Sequence[Any],
                 *, kwargs: Optional[Dict[str, Any]] = None,
                 donate_argnums: Sequence[int] = (),
                 const_threshold: int = CONST_BYTE_THRESHOLD,
                 compile_check: bool = True,
                 ) -> Tuple[Dict[str, Any], List[Finding]]:
    """Full audit of one jitted entry point: trace walk + compiled HLO.

    ``kwargs`` is forwarded to ``jitted.trace`` (entry points jitted with
    ``static_argnames`` must be traced with those passed by keyword).
    ``donate_argnums`` restates what the jit declaration donates so the
    audit can compare declared leaves against the aliases the compiled
    module actually materialized. On CPU most paths declare ``()`` (the
    repo gates donation on backend), so 0/0 is a clean pass there.
    """
    traced = jitted.trace(*args, **(kwargs or {}))
    metrics, findings = audit_traced(name, traced.jaxpr,
                                     const_threshold=const_threshold)

    declared = count_declared_donated(args, donate_argnums)
    metrics["donated_declared"] = int(declared)
    if compile_check:
        text = traced.lower().compile().as_text()
        consumed = count_hlo_aliases(text)
        metrics["donated_consumed"] = int(consumed)
        metrics["donated_unconsumed"] = int(max(0, declared - consumed))
        if declared > consumed:
            findings.append(Finding(
                rule="jaxpr.undonated", severity="error", where=name,
                message=(f"{declared} buffers declared in donate_argnums "
                         f"but only {consumed} aliased by the compiled "
                         "module — donation is silently buying nothing "
                         "(shape/dtype mismatch between input and output?)"),
                detail={"declared": declared, "consumed": consumed}))
        costs = analyze_hlo_text(text, total_devices=1)
        metrics["hlo_flops"] = float(costs.flops)
        metrics["hlo_bytes"] = float(costs.bytes)
        if costs.flops > 0 and metrics["dot_flops"] > 0:
            metrics["flops_ratio"] = float(metrics["dot_flops"] / costs.flops)
        else:
            metrics["flops_ratio"] = None
    else:
        metrics["donated_consumed"] = 0
        metrics["donated_unconsumed"] = int(declared)
        metrics["hlo_flops"] = None
        metrics["hlo_bytes"] = None
        metrics["flops_ratio"] = None
    return metrics, findings


def roofline_seconds(flops: float, bytes_: float, hw) -> float:
    """Third-column latency prediction from audited HLO costs."""
    return max(flops / hw.peak_flops, bytes_ / hw.hbm_bw) + hw.op_overhead
