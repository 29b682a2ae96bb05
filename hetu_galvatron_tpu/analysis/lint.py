"""Pass 3 — custom AST lint over the package (stdlib ``ast`` only).

Six rules encode repo invariants that no off-the-shelf linter knows:

* **GAL001 host-sync-in-hot-path** — ``.item()`` / ``np.asarray`` /
  ``jax.device_get`` in the step-path modules (trainer, both pipeline
  engines, the SPMD assembly, the serving engine). Each one is a
  device->host sync that serializes async dispatch; the "no float() in the
  step loop" contract the CPU smoke test pins, made static.
* **GAL002 jit-in-loop** — ``jax.jit``/``.lower`` calls inside a
  ``for``/``while`` body: a recompile (or retrace) hazard when the loop is
  a step loop. Init-time loops are baselined with a justification.
* **GAL003 mesh-axis canon** — mesh axis-name string literals outside the
  ``runtime/mesh.py`` canon (``pp`` and the binary ``d0..dk``) in
  collective/PartitionSpec positions: a typo'd axis name fails at trace
  time with an opaque error, or silently shards nothing.
* **GAL004 dynamic named_scope** — f-strings/computed names in
  ``jax.named_scope``: trace attribution (``observability/
  trace_analysis.py``) matches markers by exact substring, so a dynamic
  scope name silently breaks permute billing.
* **GAL005 silent exception swallowing** — bare ``except:`` anywhere, and
  ``except Exception`` whose body is only ``pass``/``continue``: the audit
  path (crash-path ``finally`` blocks) must log what it swallows.
* **GAL006 env-read outside the schema** — ``os.environ[...]`` /
  ``os.environ.get`` / ``os.getenv`` anywhere but ``core/args_schema.py``
  and ``cli/``: configuration must flow through the validated schema, not
  ambient process state a run cannot reproduce from its config file.
  (Test/tool code is outside the package walk, so it is exempt by
  construction; audited legitimate hits — retry knobs, launcher env
  contracts — stay baselined with one-line justifications.)

Findings are identified by a line-number-free fingerprint
(rule:file:function:snippet#occurrence), so the committed baseline
(``analysis/lint_baseline.json`` — fingerprint -> one-line justification)
survives unrelated edits. The CI gate is ZERO NEW findings, not zero
findings: legitimate host-boundary syncs stay baselined, each with its
justification.
"""

from __future__ import annotations

import ast
import json
import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

# step-path modules for GAL001 (relative to the package root).
# serving/scheduler.py and the observability event/recorder modules are
# included so request-lifecycle event emission can never quietly grow a
# host sync into the serving hot loop — events are host-side dicts by
# contract.
HOT_PATH_MODULES = (
    "runtime/trainer.py",
    "runtime/pipeline.py",
    "runtime/compiled_pipeline.py",
    "parallel/spmd.py",
    "serving/engine.py",
    "serving/scheduler.py",
    "observability/events.py",
    "observability/recorder.py",
)

# mesh axis-name canon (runtime/mesh.py): 'pp' + binary d-axes — any
# other hand-rolled axis literal is a finding
_AXIS_CANON = re.compile(r"^(pp|d\d+)$")

# modules where GAL006 permits ambient-environment reads: the schema is
# where config is DEFINED, and cli/ is the process boundary that feeds it
_ENV_EXEMPT_PREFIXES = ("cli/",)
_ENV_EXEMPT_FILES = ("core/args_schema.py",)

# collective calls whose axis-name argument is checked by GAL003:
# {callee name: positional index of the axis-name arg}
_AXIS_ARG_CALLS = {
    "ppermute": 1, "psum": 1, "pmax": 1, "pmin": 1, "pmean": 1,
    "all_gather": 1, "psum_scatter": 1, "all_to_all": 1, "axis_index": 0,
}
# calls whose EVERY string argument is an axis name
_SPEC_CALLS = ("PartitionSpec", "P")

DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "lint_baseline.json")


@dataclass
class Finding:
    rule: str
    path: str          # package-relative, '/'-separated
    line: int
    func: str          # enclosing function ('<module>' at top level)
    snippet: str       # normalized source of the offending expression
    message: str
    occurrence: int = 0  # index among same-snippet findings in one func

    @property
    def fingerprint(self) -> str:
        return (f"{self.rule}:{self.path}:{self.func}:{self.snippet}"
                f"#{self.occurrence}")

    def __str__(self) -> str:
        return (f"{self.path}:{self.line} [{self.rule}] {self.message} "
                f"(in {self.func})")


def _callee(node: ast.Call) -> str:
    """Dotted name of a call target ('jax.jit', 'np.asarray', 'item')."""
    f = node.func
    parts: List[str] = []
    while isinstance(f, ast.Attribute):
        parts.append(f.attr)
        f = f.value
    if isinstance(f, ast.Name):
        parts.append(f.id)
    return ".".join(reversed(parts))


def _snippet(node: ast.AST, src_lines: List[str]) -> str:
    line = src_lines[node.lineno - 1].strip() if node.lineno <= \
        len(src_lines) else ""
    return line[:120]


class _Visitor(ast.NodeVisitor):
    def __init__(self, path: str, src: str, hot_path: bool):
        self.path = path
        self.src_lines = src.splitlines()
        self.hot_path = hot_path
        self.env_exempt = (path in _ENV_EXEMPT_FILES
                           or path.startswith(_ENV_EXEMPT_PREFIXES))
        self.findings: List[Finding] = []
        self._func_stack: List[str] = []
        self._loop_depth = 0

    # -- helpers ----------------------------------------------------------

    @property
    def func(self) -> str:
        return self._func_stack[-1] if self._func_stack else "<module>"

    def _add(self, rule: str, node: ast.AST, message: str) -> None:
        self.findings.append(Finding(
            rule=rule, path=self.path, line=getattr(node, "lineno", 0),
            func=self.func, snippet=_snippet(node, self.src_lines),
            message=message))

    # -- scope / loop tracking -------------------------------------------

    def visit_FunctionDef(self, node):
        self._func_stack.append(node.name)
        # a def nested inside a loop runs its body only when CALLED, so
        # the enclosing loop must not taint jit-in-loop detection inside it
        outer_depth, self._loop_depth = self._loop_depth, 0
        self.generic_visit(node)
        self._loop_depth = outer_depth
        self._func_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def _visit_loop(self, node):
        self._loop_depth += 1
        self.generic_visit(node)
        self._loop_depth -= 1

    # comprehensions ARE loops: jax.jit inside one is built per element
    visit_For = visit_While = _visit_loop
    visit_ListComp = visit_SetComp = visit_DictComp = _visit_loop
    visit_GeneratorExp = _visit_loop

    # -- the rules --------------------------------------------------------

    def visit_Call(self, node: ast.Call):
        callee = _callee(node)
        # GAL001: host syncs in step-path modules
        if self.hot_path:
            if ((callee == "item" or callee.endswith(".item"))
                    and isinstance(node.func, ast.Attribute)
                    and not node.args):
                self._add("GAL001", node,
                          ".item() forces a device->host sync")
            elif callee in ("np.asarray", "numpy.asarray", "onp.asarray"):
                self._add("GAL001", node,
                          "np.asarray on a device value pulls it to host")
            elif callee.endswith("device_get"):
                self._add("GAL001", node,
                          "jax.device_get forces a device->host transfer")
        # GAL002: jit construction / lowering inside a loop. The .lower
        # arm requires ARGUMENTS so jit AOT lowering (fn.lower(*avals))
        # matches but str.lower() — zero-arg by definition — never does.
        if self._loop_depth > 0 and (
                callee in ("jax.jit", "jit", "pjit", "jax.pjit")
                or (callee.endswith(".lower")
                    and bool(node.args or node.keywords))):
            self._add("GAL002", node,
                      f"{callee}() inside a loop is a recompile/retrace "
                      "hazard")
        # GAL003: axis-name literals outside the mesh canon
        short = callee.rsplit(".", 1)[-1]
        if short in _AXIS_ARG_CALLS:
            idx = _AXIS_ARG_CALLS[short]
            if idx < len(node.args):
                self._check_axis_literals(node.args[idx])
        elif short in _SPEC_CALLS:
            for a in node.args:
                self._check_axis_literals(a)
        # GAL006: ambient-environment reads outside the schema/CLI boundary
        if not self.env_exempt:
            if callee in ("os.getenv", "getenv"):
                self._add("GAL006", node,
                          "os.getenv outside core/args_schema.py / cli/ — "
                          "config must flow through the schema")
            elif callee in ("os.environ.get", "environ.get"):
                self._add("GAL006", node,
                          "os.environ.get outside core/args_schema.py / "
                          "cli/ — config must flow through the schema")
        # GAL004: dynamic named_scope names
        if short == "named_scope" and node.args:
            a = node.args[0]
            if isinstance(a, ast.JoinedStr):
                self._add("GAL004", node,
                          "f-string named_scope breaks trace-marker "
                          "matching (use a module-level constant)")
            elif not isinstance(a, (ast.Constant, ast.Name, ast.Attribute)):
                self._add("GAL004", node,
                          "computed named_scope name breaks trace-marker "
                          "matching (use a module-level constant)")
        self.generic_visit(node)

    def _check_axis_literals(self, node: ast.AST) -> None:
        lits: List[Tuple[ast.AST, str]] = []
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            lits.append((node, node.value))
        elif isinstance(node, (ast.Tuple, ast.List)):
            for e in node.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    lits.append((e, e.value))
        for n, v in lits:
            if not _AXIS_CANON.match(v):
                self._add("GAL003", n,
                          f"mesh axis literal {v!r} is not in the "
                          "runtime/mesh.py canon (pp, d0..dk)")

    def visit_Subscript(self, node: ast.Subscript):
        # GAL006: os.environ["X"] reads (and writes — mutating the
        # process environment outside the CLI boundary is worse)
        if not self.env_exempt:
            v = node.value
            if (isinstance(v, ast.Attribute) and v.attr == "environ"
                    and isinstance(v.value, ast.Name)
                    and v.value.id == "os"):
                self._add("GAL006", node,
                          "os.environ[...] outside core/args_schema.py / "
                          "cli/ — config must flow through the schema")
        self.generic_visit(node)

    def visit_ExceptHandler(self, node: ast.ExceptHandler):
        if node.type is None:
            self._add("GAL005", node,
                      "bare 'except:' swallows KeyboardInterrupt/"
                      "SystemExit too — name the exception")
        elif (isinstance(node.type, ast.Name)
              and node.type.id in ("Exception", "BaseException")
              and all(isinstance(s, (ast.Pass, ast.Continue))
                      for s in node.body)):
            self._add("GAL005", node,
                      f"except {node.type.id} with a silent body hides "
                      "the audit trail — log what is swallowed")
        self.generic_visit(node)


def lint_file(path: str, rel: str, hot_path: bool) -> List[Finding]:
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [Finding(rule="GAL000", path=rel, line=e.lineno or 0,
                        func="<module>", snippet=str(e),
                        message=f"syntax error: {e.msg}")]
    v = _Visitor(rel, src, hot_path)
    v.visit(tree)
    _number_occurrences(v.findings)
    return v.findings


def _number_occurrences(findings: List[Finding]) -> None:
    seen: Dict[str, int] = {}
    for f in findings:
        key = f"{f.rule}:{f.path}:{f.func}:{f.snippet}"
        f.occurrence = seen.get(key, 0)
        seen[key] = f.occurrence + 1


def lint_package(root: Optional[str] = None) -> List[Finding]:
    """Lint every .py file of the installed package (``root`` defaults to
    the hetu_galvatron_tpu package directory). The canon source
    ``runtime/mesh.py`` is exempt from GAL003 (it DEFINES the axis names);
    this module and the baseline are data, not subjects."""
    if root is None:
        import hetu_galvatron_tpu

        root = os.path.dirname(os.path.abspath(hetu_galvatron_tpu.__file__))
    findings: List[Finding] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            full = os.path.join(dirpath, fn)
            rel = os.path.relpath(full, root).replace(os.sep, "/")
            fs = lint_file(full, rel, hot_path=rel in HOT_PATH_MODULES)
            if rel == "runtime/mesh.py":
                fs = [f for f in fs if f.rule != "GAL003"]
            # occurrence numbering is per-file (lint_file owns it; the
            # fingerprint key includes the path, so no cross-file renumber)
            findings.extend(fs)
    return findings


# ---------------------------------------------------------------------------
# baseline (committed accepted findings, each with a justification)
# ---------------------------------------------------------------------------


def load_baseline(path: Optional[str] = None) -> Dict[str, str]:
    path = path or DEFAULT_BASELINE  # resolved at call time (testable)
    if not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as f:
        obj = json.load(f)
    return {k: str(v) for k, v in obj.get("findings", obj).items()}


def save_baseline(findings: List[Finding], path: Optional[str] = None,
                  keep: Optional[Dict[str, str]] = None) -> None:
    """Write the baseline for the CURRENT findings, preserving existing
    justifications; new entries get a TODO placeholder a human must
    replace (the gate treats TODO entries as accepted — the review
    happens at commit time, on the diff)."""
    path = path or DEFAULT_BASELINE
    keep = keep or {}
    out = {f.fingerprint: keep.get(f.fingerprint,
                                   "TODO: justify or fix")
           for f in findings}
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"findings": dict(sorted(out.items()))}, f, indent=1)
        f.write("\n")


def new_findings(findings: List[Finding],
                 baseline: Dict[str, str]) -> List[Finding]:
    return [f for f in findings if f.fingerprint not in baseline]


def stale_baseline(findings: List[Finding],
                   baseline: Dict[str, str]) -> List[str]:
    """Baselined fingerprints that no longer occur (fixed code — prune
    them so the baseline only ever shrinks in meaning)."""
    live = {f.fingerprint for f in findings}
    return [k for k in baseline if k not in live]


def prune_baseline(findings: List[Finding], path: Optional[str] = None
                   ) -> List[str]:
    """Drop the stale entries from the committed baseline IN PLACE and
    return the removed fingerprints. Unlike ``save_baseline`` (which
    rewrites the file from the CURRENT findings, adding TODO entries for
    new ones), this only ever REMOVES: live entries keep their
    justifications untouched and no new finding is auto-accepted — the
    safe way to clear a red stale-baseline gate after deleting code
    (``cli/check.py --prune-baseline``)."""
    path = path or DEFAULT_BASELINE
    baseline = load_baseline(path)
    stale = stale_baseline(findings, baseline)
    if not stale:
        return []
    kept = {k: v for k, v in baseline.items() if k not in stale}
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"findings": dict(sorted(kept.items()))}, f, indent=1)
        f.write("\n")
    return stale
