"""AST-level repo invariants.

Every rule here encodes a convention an earlier PR paid for the hard
way; the linter makes them contracts. Rules (see ``analysis``
docstring for the catalog):

* ``ast.host-sync-in-loop`` — in hot files (``core/*.py``,
  ``serve/engine.py``) a ``float()`` / ``.item()`` / ``np.asarray()`` /
  ``np.array()`` / ``jax.device_get()`` / ``.block_until_ready()``
  inside a ``for``/``while`` body is a device→host sync per iteration.
  Intentional syncs carry a ``# sync: <reason>`` comment on the call
  line (or the line above); host-only files are allowlisted whole.
* ``ast.linalg-inv`` — ``*.linalg.inv`` is banned (PR 1: explicit
  inverses are numerically worse and slower than the Cholesky solves
  the OBS path uses).
* ``ast.tmp-literal`` — bare ``"/tmp..."`` path literals (PR 5: they
  collide across concurrent runs; use ``tempfile`` or a run dir).
* ``ast.atomic-writer`` — ``json.dump`` / ``np.savez*`` / ``np.save``
  outside ``checkpoint/manager.py``: all persistence goes through
  ``atomic_write_json`` / ``atomic_save_npz`` (torn files poisoned the
  chaos tier until PR 6 made writers atomic).
* ``ast.fault-site-drift`` — two-way check between the fault-site
  strings used at injection points (``_faults.hit(...)``,
  ``poison_*``, ``corrupt_file``, ``site=`` kwargs, breaker-key
  prefixes) and ``robustness.faults.SITES``.
* ``ast.bench-key-drift`` — two-way check between the keys written to
  ``BENCH_db.json`` via ``_write_bench_db`` and the declared
  ``BENCH_KEYS`` tuple in ``benchmarks/run.py``.

All ``lint_*`` functions take ``(path, source)`` so tests can feed
synthetic snippets; ``lint_repo`` walks the tree.
"""
from __future__ import annotations

import ast
import os
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from repro.analysis.findings import Finding

SYNC_ANNOTATION = "# sync:"

HOT_DIRS = ("core",)
HOT_FILES = ("serve/engine.py",)

SYNC_NAME_CALLS = {"float"}
SYNC_ATTR_CALLS = {"item", "block_until_ready", "device_get"}
SYNC_NP_CALLS = {"asarray", "array"}
NP_NAMES = {"np", "numpy", "onp"}


@dataclass(frozen=True)
class Allow:
    path_suffix: str     # matched against the file's repo-relative path
    match: str           # "*" = whole file, else substring of the line
    reason: str


# Per-rule allowlists. Keep entries narrow and justified — an entry is
# a reviewed exception, not an escape hatch.
ALLOWLIST: Dict[str, Tuple[Allow, ...]] = {
    "ast.host-sync-in-loop": (
        Allow("core/spdy.py", "*",
              "host-side numpy knapsack-DP engine: the loops run on "
              "host arrays, there is no device value to sync"),
        Allow("core/latency.py", "*",
              "timing harness: block_until_ready IS the measurement"),
        Allow("core/latency_cache.py", "*",
              "cache (de)serialization: loops over JSON payload lists, "
              "host-only"),
        Allow("core/magnitude.py", "*",
              "host-side magnitude baseline: materializes each module's "
              "weights once per module by design"),
    ),
    "ast.linalg-inv": (
        Allow("core/database.py", "np.linalg.inv(np.asarray(H, np.float64))",
              "Algorithm 1 consumes the full inverse Hessian (entries and "
              "columns), built once per module per damping rung outside "
              "the structure loop, in float64 on the host: the TPU's fp32 "
              "inverse is too coarse for ill-conditioned Hessians"),
        Allow("benchmarks/run.py", "linalg.inv",
              "frozen seed reference path, kept bit-identical for the "
              "db_build benchmark comparison"),
    ),
    "ast.tmp-literal": (
        Allow("analysis/astlint.py", "startswith",
              "the rule's own match pattern"),
    ),
    "ast.atomic-writer": (),
}


def _is_hot(rel_path: str) -> bool:
    rel = rel_path.replace(os.sep, "/")
    if any(rel.endswith(h) for h in HOT_FILES):
        return True
    parts = rel.split("/")
    return any(d in parts[:-1] and parts[-1].endswith(".py") for d in HOT_DIRS)


def _allowed(rule: str, rel_path: str, line_text: str) -> Optional[Allow]:
    rel = rel_path.replace(os.sep, "/")
    for a in ALLOWLIST.get(rule, ()):
        if rel.endswith(a.path_suffix):
            if a.match == "*" or a.match in line_text:
                return a
    return None


def _annotated(lines: Sequence[str], lineno: int) -> bool:
    """True if the call line, or the contiguous comment block directly
    above it, carries ``# sync:``."""
    if 1 <= lineno <= len(lines) and SYNC_ANNOTATION in lines[lineno - 1]:
        return True
    ln = lineno - 1
    while ln >= 1:
        t = lines[ln - 1].strip()
        if not t.startswith("#"):
            return False
        if SYNC_ANNOTATION in t:
            return True
        ln -= 1
    return False


def _docstring_nodes(tree: ast.AST) -> Set[int]:
    out: Set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            body = node.body
            if body and isinstance(body[0], ast.Expr) and \
                    isinstance(body[0].value, ast.Constant) and \
                    isinstance(body[0].value.value, str):
                out.add(id(body[0].value))
    return out


_HOST_DISPLAYS = (ast.List, ast.Tuple, ast.ListComp, ast.GeneratorExp,
                  ast.Dict, ast.DictComp, ast.Constant)


def _is_sync_call(node: ast.Call) -> Optional[str]:
    f = node.func
    if isinstance(f, ast.Name) and f.id in SYNC_NAME_CALLS:
        return f.id + "()"
    if isinstance(f, ast.Attribute):
        if f.attr in SYNC_ATTR_CALLS:
            return "." + f.attr + "()"
        if f.attr in SYNC_NP_CALLS and isinstance(f.value, ast.Name) \
                and f.value.id in NP_NAMES:
            # np.asarray on a list/tuple display or comprehension builds
            # from host data — no device value involved, not a sync
            if node.args and isinstance(node.args[0], _HOST_DISPLAYS):
                return None
            return f"{f.value.id}.{f.attr}()"
    return None


class _SyncVisitor(ast.NodeVisitor):
    def __init__(self):
        self.loop_depth = 0
        self.hits: List[Tuple[int, str]] = []   # (lineno, call repr)

    def _loop(self, node):
        self.loop_depth += 1
        for child in ast.iter_child_nodes(node):
            self.visit(child)
        self.loop_depth -= 1

    visit_For = visit_While = visit_AsyncFor = _loop

    def visit_Call(self, node: ast.Call):
        if self.loop_depth > 0:
            what = _is_sync_call(node)
            if what is not None:
                self.hits.append((node.lineno, what))
        self.generic_visit(node)


def lint_source(rel_path: str, source: str) -> List[Finding]:
    """All single-file rules over one source blob."""
    findings: List[Finding] = []
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding(rule="ast.parse-error", severity="error",
                        where=f"{rel_path}:{e.lineno}", message=str(e))]
    lines = source.splitlines()

    def line(n: int) -> str:
        return lines[n - 1] if 1 <= n <= len(lines) else ""

    if _is_hot(rel_path):
        v = _SyncVisitor()
        v.visit(tree)
        for lineno, what in v.hits:
            if _annotated(lines, lineno):
                continue
            if _allowed("ast.host-sync-in-loop", rel_path, line(lineno)):
                continue
            findings.append(Finding(
                rule="ast.host-sync-in-loop", severity="error",
                where=f"{rel_path}:{lineno}",
                message=(f"{what} inside a loop body in a hot file is a "
                         "device->host sync per iteration — hoist it, or "
                         "annotate the line with `# sync: <reason>` if the "
                         "sync is the point"),
                detail={"call": what}))

    doc_ids = _docstring_nodes(tree)
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr == "inv" and \
                isinstance(node.value, ast.Attribute) and \
                node.value.attr == "linalg":
            if not _allowed("ast.linalg-inv", rel_path, line(node.lineno)):
                findings.append(Finding(
                    rule="ast.linalg-inv", severity="error",
                    where=f"{rel_path}:{node.lineno}",
                    message=("explicit matrix inverse is banned — use the "
                             "Cholesky solve helpers (see core/obs.py)"),
                ))
        elif isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and node.value.startswith("/tmp") and id(node) not in doc_ids:
            if not _allowed("ast.tmp-literal", rel_path, line(node.lineno)):
                findings.append(Finding(
                    rule="ast.tmp-literal", severity="error",
                    where=f"{rel_path}:{node.lineno}",
                    message=("bare /tmp path literal — use tempfile or a "
                             "run directory (concurrent runs collide)"),
                    detail={"literal": node.value}))
        elif isinstance(node, ast.Call) and isinstance(node.func,
                                                       ast.Attribute):
            f = node.func
            writer = None
            if f.attr == "dump" and isinstance(f.value, ast.Name) and \
                    f.value.id == "json":
                writer = "json.dump"
            elif f.attr in ("savez", "savez_compressed", "save") and \
                    isinstance(f.value, ast.Name) and f.value.id in NP_NAMES:
                writer = f"{f.value.id}.{f.attr}"
            if writer is not None and \
                    not rel_path.replace(os.sep, "/").endswith(
                        "checkpoint/manager.py") and \
                    not _allowed("ast.atomic-writer", rel_path,
                                 line(node.lineno)):
                findings.append(Finding(
                    rule="ast.atomic-writer", severity="error",
                    where=f"{rel_path}:{node.lineno}",
                    message=(f"{writer} writes non-atomically — route "
                             "through checkpoint.manager.atomic_write_json "
                             "/ atomic_save_npz (torn files break resume)"),
                    detail={"writer": writer}))
    return findings


# ---------------------------------------------------------------- drift

FAULT_CALL_NAMES = ("hit", "poison_scalar", "poison_array", "corrupt_file")


def _site_from_node(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        s = node.value
        return s.split(":", 1)[0] if ":" in s else s
    if isinstance(node, ast.JoinedStr) and node.values and \
            isinstance(node.values[0], ast.Constant) and \
            isinstance(node.values[0].value, str):
        # breaker keys like f"kernel.pallas:{op}" -> literal prefix
        return node.values[0].value.split(":", 1)[0].rstrip(":")
    return None


def extract_fault_sites(source: str) -> Set[Tuple[str, int]]:
    """(site, lineno) for every fault-API call site in one file."""
    out: Set[Tuple[str, int]] = set()
    tree = ast.parse(source)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fname = None
        if isinstance(node.func, ast.Attribute):
            fname = node.func.attr
        elif isinstance(node.func, ast.Name):
            fname = node.func.id
        if fname in FAULT_CALL_NAMES and node.args:
            s = _site_from_node(node.args[0])
            if s is not None:
                out.add((s, node.lineno))
        for kw in node.keywords:
            if kw.arg == "site":
                s = _site_from_node(kw.value)
                if s is not None:
                    out.add((s, node.lineno))
    return out


def check_fault_sites(files: Dict[str, str],
                      declared_sites: Iterable[str]) -> List[Finding]:
    """Two-way drift between fault-call sites in ``files`` and SITES."""
    declared = set(declared_sites)
    used: Dict[str, List[str]] = {}
    findings: List[Finding] = []
    for rel, src in files.items():
        if rel.replace(os.sep, "/").endswith("robustness/faults.py"):
            continue   # the registry itself demos the API in docstrings
        for site, lineno in extract_fault_sites(src):
            used.setdefault(site, []).append(f"{rel}:{lineno}")
    for site, wheres in sorted(used.items()):
        if site not in declared:
            findings.append(Finding(
                rule="ast.fault-site-drift", severity="error",
                where=wheres[0],
                message=(f"fault site {site!r} is used at an injection "
                         "point but not declared in "
                         "robustness.faults.SITES — plans can never "
                         "target it"),
                detail={"site": site, "uses": wheres}))
    for site in sorted(declared - set(used)):
        findings.append(Finding(
            rule="ast.fault-site-drift", severity="error",
            where="robustness/faults.py",
            message=(f"fault site {site!r} is declared in SITES but no "
                     "injection point uses it — dead registry entry or a "
                     "misspelled call site"),
            detail={"site": site, "uses": []}))
    return findings


def extract_bench_keys(source: str) -> Tuple[Set[str], Set[str]]:
    """(written_keys, declared_keys) from benchmarks/run.py source."""
    tree = ast.parse(source)
    written: Set[str] = set()
    declared: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fname = node.func.id if isinstance(node.func, ast.Name) else \
                getattr(node.func, "attr", None)
            if fname == "_write_bench_db" and node.args and \
                    isinstance(node.args[0], ast.Dict):
                # only the TOP-level dict keys are BENCH_db records;
                # walk each key expr for constants to catch IfExp keys
                # like ("chaos_smoke" if smoke else "chaos")
                for k in node.args[0].keys:
                    if k is None:
                        continue
                    for c in ast.walk(k):
                        if isinstance(c, ast.Constant) and \
                                isinstance(c.value, str):
                            written.add(c.value)
        elif isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and tgt.id == "BENCH_KEYS":
                    for c in ast.walk(node.value):
                        if isinstance(c, ast.Constant) and \
                                isinstance(c.value, str):
                            declared.add(c.value)
    return written, declared


def check_bench_keys(rel_path: str, source: str) -> List[Finding]:
    written, declared = extract_bench_keys(source)
    findings: List[Finding] = []
    if written and not declared:
        return [Finding(
            rule="ast.bench-key-drift", severity="error", where=rel_path,
            message=("bench keys are written but no BENCH_KEYS declaration "
                     "exists — declare the full key set so drift is "
                     "reviewable"),
            detail={"written": sorted(written)})]
    for k in sorted(written - declared):
        findings.append(Finding(
            rule="ast.bench-key-drift", severity="error", where=rel_path,
            message=(f"bench key {k!r} is written to BENCH_db.json but not "
                     "declared in BENCH_KEYS"),
            detail={"key": k}))
    for k in sorted(declared - written):
        findings.append(Finding(
            rule="ast.bench-key-drift", severity="error", where=rel_path,
            message=(f"bench key {k!r} is declared in BENCH_KEYS but never "
                     "written — stale declaration or a lost bench"),
            detail={"key": k}))
    return findings


# ---------------------------------------------------------------- repo walk

def _iter_py(root: str, sub: str) -> Iterable[Tuple[str, str]]:
    base = os.path.join(root, sub)
    for dirpath, _dirs, names in os.walk(base):
        for n in sorted(names):
            if n.endswith(".py"):
                p = os.path.join(dirpath, n)
                yield os.path.relpath(p, root), p


def lint_repo(root: str) -> Tuple[Dict[str, int], List[Finding]]:
    """Run every AST rule over src/repro + benchmarks."""
    findings: List[Finding] = []
    files: Dict[str, str] = {}
    for rel, p in list(_iter_py(root, os.path.join("src", "repro"))) + \
            list(_iter_py(root, "benchmarks")):
        with open(p, "r") as f:
            src = f.read()
        files[rel] = src
        findings.extend(lint_source(rel, src))

    from repro.robustness.faults import SITES
    findings.extend(check_fault_sites(files, SITES))

    bench_rel = os.path.join("benchmarks", "run.py")
    if bench_rel in files:
        findings.extend(check_bench_keys(bench_rel, files[bench_rel]))

    by_rule: Dict[str, int] = {}
    for f in findings:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    metrics = {"files_scanned": len(files), **{
        f"count.{r}": c for r, c in sorted(by_rule.items())}}
    return metrics, findings
