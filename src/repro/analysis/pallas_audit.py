"""Kernel-contract audit for everything under ``kernels/``.

The degradation ladder (PR 6) assumes each Pallas kernel has a ref twin
it can fall back to bit-safely; this module checks that contract
*statically* — by signature inspection, abstract evaluation, and jaxpr
introspection of the ``pallas_call`` equations — never by executing the
kernels:

* ``pallas.twin-missing`` / ``pallas.twin-drift`` — two-way check
  between the ops guarded by ``ops._run_guarded`` (extracted from the
  AST) and this module's audit registry.
* ``pallas.signature`` — every positional parameter of the ref twin
  exists on the kernel impl (a renamed/reordered arg would make the
  ladder's fallback call the ref with swapped operands).
* ``pallas.abstract-mismatch`` — ``jax.eval_shape`` of the kernel path
  and the ref path disagree on the output pytree (shape or dtype): the
  fallback would change downstream avals.
* ``pallas.grid-coverage`` — evaluating every BlockSpec index map over
  the full grid, some array dimension is not covered [0, dim): part of
  an operand would never be read / part of an output never written.
* ``pallas.tile-alignment`` — a block dimension is neither a multiple
  of the TPU tile (8 second-minor, 128 minor for f32) nor the full
  array dimension (which the compiler pads); masked-tail ops
  (``obs_downdate``'s ``d_live`` prefix) declare the exemption in the
  registry.
* ``pallas.interpret-hardcoded`` — a ``pl.pallas_call`` in ``kernels/``
  passes ``interpret=`` as a literal (or not at all) instead of
  threading the caller's flag; a hardcoded ``True`` would silently run
  interpret-mode on TPU.
"""
from __future__ import annotations

import ast
import functools
import inspect
import math
import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

from repro.analysis.findings import Finding
from repro.analysis.jaxpr_audit import iter_eqns

TILE_SECOND_MINOR = 8
TILE_MINOR = 128
MAX_GRID_POINTS = 65536


@dataclass
class KernelSpec:
    op: str
    kernel: Callable            # ops._*_impl (jitted, interpret kwarg)
    ref: Callable               # ops._*_ref
    make_args: Callable[[], Tuple]
    kernel_kwargs: Dict[str, Any] = field(default_factory=dict)
    ref_extra_args: Tuple = ()  # positional tail (causal, window, ...)
    masked_tail: bool = False   # explicit d_live-style tail handling


def _mk(shape, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype)


def build_registry() -> Dict[str, KernelSpec]:
    from repro.kernels import ops

    def flash_args():
        return (_mk((1, 128, 4, 64), seed=0), _mk((1, 128, 4, 64), seed=1),
                _mk((1, 128, 4, 64), seed=2))

    def hessian_args():
        return (_mk((1024, 256), seed=3), _mk((256, 256), seed=4))

    def obs_args():
        d_in, d_out, gs = 128, 16, 4
        return (_mk((d_in, d_out), seed=5), _mk((d_in, d_in), seed=6),
                _mk((d_in, gs), seed=7), _mk((gs, d_out), seed=8),
                _mk((gs, d_in), seed=9),
                jnp.asarray(np.random.default_rng(10).random(d_in) > 0.3,
                            jnp.float32))

    def ssd_args():
        b, s, h, p, n = 1, 64, 8, 32, 16
        return (_mk((b, s, h, p), seed=11) * 0.5,
                jax.nn.softplus(_mk((b, s, h), seed=12)),
                -jnp.exp(_mk((h,), seed=13) * 0.3),
                _mk((b, s, n), seed=14) * 0.5, _mk((b, s, n), seed=15) * 0.5)

    return {
        "flash_attention": KernelSpec(
            op="flash_attention", kernel=ops._flash_attention_impl,
            ref=ops._flash_attention_ref, make_args=flash_args,
            kernel_kwargs=dict(causal=True, window=0, block_q=64,
                               block_k=64, interpret=True),
            ref_extra_args=(True, 0)),
        "hessian_accum": KernelSpec(
            op="hessian_accum", kernel=ops._hessian_accum_impl,
            ref=ops._hessian_accum_ref, make_args=hessian_args,
            kernel_kwargs=dict(block_d=256, block_n=512, interpret=True)),
        "obs_downdate": KernelSpec(
            op="obs_downdate", kernel=ops._obs_downdate_impl,
            ref=ops._obs_downdate_ref, make_args=obs_args,
            kernel_kwargs=dict(block_d=64, interpret=True),
            masked_tail=True),
        "ssd": KernelSpec(
            op="ssd", kernel=ops._ssd_chunked_impl,
            ref=ops._ssd_ref, make_args=ssd_args,
            kernel_kwargs=dict(chunk=32, head_block=8, interpret=True)),
    }


# ------------------------------------------------------------ twin checks

def extract_guarded_ops(source: str) -> set:
    """Op-name strings passed as first arg to ``_run_guarded`` in ops.py."""
    out = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Call):
            fname = node.func.id if isinstance(node.func, ast.Name) else \
                getattr(node.func, "attr", None)
            if fname == "_run_guarded" and node.args and \
                    isinstance(node.args[0], ast.Constant) and \
                    isinstance(node.args[0].value, str):
                out.add(node.args[0].value)
    return out


def check_twin_registry(ops_source: str, registry: Dict[str, KernelSpec]
                        ) -> List[Finding]:
    guarded = extract_guarded_ops(ops_source)
    audited = set(registry)
    findings = []
    for op in sorted(guarded - audited):
        findings.append(Finding(
            rule="pallas.twin-drift", severity="error",
            where="kernels/ops.py",
            message=(f"op {op!r} is guarded by _run_guarded but has no "
                     "entry in the pallas audit registry — its ref-twin "
                     "contract is unchecked"),
            detail={"op": op}))
    for op in sorted(audited - guarded):
        findings.append(Finding(
            rule="pallas.twin-missing", severity="error",
            where="analysis/pallas_audit.py",
            message=(f"audit registry op {op!r} is not guarded by "
                     "_run_guarded in kernels/ops.py — stale registry "
                     "entry or a kernel that lost its ladder guard"),
            detail={"op": op}))
    return findings


def check_signature(spec: KernelSpec) -> List[Finding]:
    ksig = inspect.signature(
        inspect.unwrap(getattr(spec.kernel, "__wrapped__", spec.kernel)))
    kpos = [p.name for p in ksig.parameters.values()
            if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]
    # required positional ref params are the operand slots the ladder's
    # fallback call fills; defaulted ref params (d_live, initial_state)
    # are allowed extras the guarded wrapper never passes
    rpos = [p.name for p in inspect.signature(spec.ref).parameters.values()
            if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
            and p.default is inspect.Parameter.empty]
    findings = []
    n = min(len(kpos), len(rpos))
    extra = [p for p in rpos[n:] if p not in ksig.parameters]
    if kpos[:n] != rpos[:n] or extra:
        findings.append(Finding(
            rule="pallas.signature", severity="error", where=spec.op,
            message=(f"operand drift: kernel positional params {kpos} vs "
                     f"ref required params {rpos} — the degradation "
                     "ladder's fallback would mis-bind operands"),
            detail={"kernel": kpos, "ref": rpos,
                    "unmatched": extra}))
    return findings


def check_abstract(spec: KernelSpec) -> List[Finding]:
    args = spec.make_args()
    k_out = jax.eval_shape(
        functools.partial(spec.kernel, **spec.kernel_kwargs), *args)
    r_out = jax.eval_shape(lambda *a: spec.ref(*a, *spec.ref_extra_args),
                           *args)
    k_leaves = [(l.shape, str(l.dtype))
                for l in jax.tree_util.tree_leaves(k_out)]
    r_leaves = [(l.shape, str(l.dtype))
                for l in jax.tree_util.tree_leaves(r_out)]
    if k_leaves != r_leaves:
        return [Finding(
            rule="pallas.abstract-mismatch", severity="error", where=spec.op,
            message=(f"kernel and ref outputs disagree under abstract eval: "
                     f"{k_leaves} vs {r_leaves} — the ladder fallback would "
                     "change downstream avals"),
            detail={"kernel": [list(map(str, t)) for t in k_leaves],
                    "ref": [list(map(str, t)) for t in r_leaves]})]
    return []


# ---------------------------------------------------------- grid checks

def _pallas_eqns(spec: KernelSpec):
    args = spec.make_args()
    closed = jax.make_jaxpr(
        functools.partial(spec.kernel, **spec.kernel_kwargs))(*args)
    return [e for e, _m, _l in iter_eqns(closed.jaxpr)
            if e.primitive.name == "pallas_call"]


def _check_one_mapping(spec: KernelSpec, grid, bm) -> List[Finding]:
    findings = []
    arr_shape = tuple(bm.array_aval.shape)
    # Blocked/Element carry block_size; a Squeezed dim is one element
    block = tuple(getattr(d, "block_size", 1) for d in bm.block_shape)
    # tile alignment (minor two dims)
    for off, tile in ((1, TILE_MINOR), (2, TILE_SECOND_MINOR)):
        if len(block) >= off:
            b, a = block[-off], arr_shape[-off]
            if b % tile != 0 and b != a and not spec.masked_tail:
                findings.append(Finding(
                    rule="pallas.tile-alignment", severity="error",
                    where=spec.op,
                    message=(f"block dim {b} (array dim {a}) is neither a "
                             f"multiple of the TPU tile ({tile}) nor the "
                             "full dimension — add padding or a masked "
                             "tail like obs_downdate's d_live"),
                    detail={"block": list(block), "array": list(arr_shape),
                            "tile": tile}))
    # index-map coverage, projected per dimension
    if math.prod(grid) > MAX_GRID_POINTS:
        return findings + [Finding(
            rule="pallas.grid-coverage", severity="info", where=spec.op,
            message=f"grid {grid} too large to enumerate; coverage skipped",
        )]
    cj = bm.index_map_jaxpr
    starts: List[set] = [set() for _ in arr_shape]
    import itertools
    for point in itertools.product(*(range(g) for g in grid)):
        idx = jcore.jaxpr_as_fun(cj)(*(jnp.int32(p) for p in point))
        for d, (i, b) in enumerate(zip(idx, block)):
            starts[d].add(int(i) * b)
    for d, (a, b) in enumerate(zip(arr_shape, block)):
        need = set(range(0, a, b)) if b else set()
        missing = sorted(need - starts[d])
        if missing:
            findings.append(Finding(
                rule="pallas.grid-coverage", severity="error", where=spec.op,
                message=(f"dimension {d} of a {arr_shape} operand is not "
                         f"fully covered: block starts {sorted(starts[d])} "
                         f"miss offsets {missing[:8]} — part of the array "
                         "is never touched by the grid"),
                detail={"dim": d, "array": list(arr_shape),
                        "block": list(block), "missing": missing[:32]}))
    return findings


def check_grid(spec: KernelSpec) -> Tuple[Dict[str, Any], List[Finding]]:
    findings: List[Finding] = []
    eqns = _pallas_eqns(spec)
    for e in eqns:
        gm = e.params["grid_mapping"]
        for bm in gm.block_mappings:
            findings.extend(_check_one_mapping(spec, tuple(gm.grid), bm))
    return {"n_pallas_calls": len(eqns)}, findings


# ------------------------------------------------------- interpret check

def check_interpret_literals(files: Dict[str, str]) -> List[Finding]:
    findings = []
    for rel, src in files.items():
        for node in ast.walk(ast.parse(src)):
            if not (isinstance(node, ast.Call) and
                    isinstance(node.func, ast.Attribute) and
                    node.func.attr == "pallas_call"):
                continue
            kw = {k.arg: k.value for k in node.keywords}
            has_splat = any(k.arg is None for k in node.keywords)
            if "interpret" not in kw:
                if has_splat:
                    continue   # threaded through a **kwargs dict
                findings.append(Finding(
                    rule="pallas.interpret-hardcoded", severity="error",
                    where=f"{rel}:{node.lineno}",
                    message=("pallas_call without interpret= silently "
                             "defaults to compiled mode — thread the "
                             "caller's flag through"),
                ))
            elif isinstance(kw["interpret"], ast.Constant):
                findings.append(Finding(
                    rule="pallas.interpret-hardcoded", severity="error",
                    where=f"{rel}:{node.lineno}",
                    message=(f"interpret={kw['interpret'].value!r} is "
                             "hardcoded — a TPU run would silently "
                             "interpret (or a CPU run silently compile); "
                             "thread the flag from the public wrapper"),
                ))
    return findings


# --------------------------------------------------------------- driver

def audit_kernels(root: str) -> Tuple[Dict[str, Any], List[Finding]]:
    registry = build_registry()
    findings: List[Finding] = []
    kdir = os.path.join(root, "src", "repro", "kernels")
    files = {}
    for n in sorted(os.listdir(kdir)):
        if n.endswith(".py"):
            with open(os.path.join(kdir, n)) as f:
                files[os.path.join("src", "repro", "kernels", n)] = f.read()

    ops_src = next(v for k, v in files.items() if k.endswith("ops.py"))
    findings.extend(check_twin_registry(ops_src, registry))
    findings.extend(check_interpret_literals(files))

    metrics: Dict[str, Any] = {"ops_audited": sorted(registry)}
    total_calls = 0
    for op, spec in sorted(registry.items()):
        findings.extend(check_signature(spec))
        findings.extend(check_abstract(spec))
        m, fs = check_grid(spec)
        findings.extend(fs)
        total_calls += m["n_pallas_calls"]
    metrics["n_pallas_calls"] = total_calls
    for rule in ("pallas.twin-drift", "pallas.twin-missing",
                 "pallas.signature", "pallas.abstract-mismatch",
                 "pallas.grid-coverage", "pallas.tile-alignment",
                 "pallas.interpret-hardcoded"):
        metrics[f"count.{rule}"] = sum(
            1 for f in findings if f.rule == rule and f.severity == "error")
    return metrics, findings
