#!/usr/bin/env python
"""AST linter for spark_tpu codebase invariants.

Seven rules the engine relies on but Python cannot enforce:

1. **conf-keys** — every string key passed to ``conf.get(...)`` /
   ``conf.set(...)`` (and builder ``.config(...)``) that looks like a
   config key (``spark.`` / ``spark_tpu.`` prefix) must be a registered
   ConfigEntry or match a registered prefix (conf.register_prefix).
   Unregistered keys silently read as KeyError at runtime and dodge the
   analysis-level gate.

2. **fault-points** — every string literal passed to
   ``faults.inject("<point>", ...)`` must be one of ``faults.POINTS``;
   a typo'd point would make a fault-injection site unreachable while
   tests believe it is covered.

6. **span-names** — every string literal passed to
   ``trace.span("<name>", ...)`` must be declared in
   ``spark_tpu.trace.SPAN_NAMES`` (same discipline as conf keys and
   fault points); an undeclared span name fragments the waterfall and
   the host/device attribution that key off the registry. Likewise
   every ``trace.built("<kind>", ...)`` literal (the build events
   ``seg_sum`` / ``join`` / ``sort`` / ``group_by``) against
   ``trace.BUILD_EVENTS``, and every ``trace.inner_scope("<name>")``
   literal (``GroupSort`` / ``GroupSum``) against ``trace.INNER_SCOPES``.

3. **fingerprint-purity** — functions on the structural-fingerprint
   path (compile/store.py and planner's two _adaptive_snapshot functions) must
   not call ``hash()`` or ``id()`` (process-seeded / address-based:
   both break cross-session executable reuse) and must not iterate a
   dict's ``.items()/.keys()/.values()`` unless wrapped in
   ``sorted(...)`` (dict order is insertion order — a semantically
   equal plan built in a different order would fingerprint
   differently).

4. **metrics-lock** — in spark_tpu/metrics.py every mutation of the
   module-level state (_EVENTS, _GAUGES, _COMPILE_CACHE, ...) must be
   lexically inside ``with _LOCK:`` (``_PATH_CACHE`` under
   ``_IO_LOCK``); the concurrent scheduler serves queries from many
   threads and an unlocked append corrupts the ring.

5. **dead-fault-points** — the converse of rule 2: every point
   declared in ``faults.POINTS`` must have at least one
   ``faults.inject("<point>", ...)`` call site under the linted
   paths. A declared-but-never-injected point registers a conf key
   and documents a recovery seam that does not exist — fault suites
   arming it would silently test nothing.

7. **retry-budget** — every bounded retry loop (a ``for ... in
   range(...)`` whose target or bound names attempts/retries) must
   draw from the unified per-query retry budget: the enclosing
   function has to reference ``recovery.retry_allowed`` /
   ``RetryBudget`` / ``.draw(...)``. A loop that retries on its own
   private counter multiplies with every other layer's counter —
   exactly the attempt amplification the unified budget exists to
   kill. Exemptions: ``retry_loop_allow = ["path.py:function"]`` in
   ``[tool.lint-invariants]``.

Run as a CLI (exit 0 clean / 1 findings) or import ``run_lint()``;
tests/test_analysis.py runs it as a test so CI enforces it. Optional
overrides live in ``[tool.lint-invariants]`` in pyproject.toml.
"""

from __future__ import annotations

import ast
import os
import sys
from dataclasses import dataclass
from typing import Dict, List, Optional, Set, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: defaults; [tool.lint-invariants] in pyproject.toml may override
DEFAULT_CONFIG = {
    "paths": ["spark_tpu"],
    "key_prefixes": ["spark.", "spark_tpu."],
    # file -> functions on the fingerprint path ([] = every function)
    "fingerprint_paths": {
        os.path.join("spark_tpu", "compile", "store.py"): [],
        os.path.join("spark_tpu", "physical", "planner.py"):
            ["_adaptive_snapshot", "_stable_adaptive_snapshot"],
    },
    "locked_modules": [os.path.join("spark_tpu", "metrics.py")],
    # module state -> lock that must guard its mutations
    "lock_map": {"_PATH_CACHE": "_IO_LOCK", "_LOG_BUF": "_IO_LOCK",
                 "_LOG_BUF_PATH": "_IO_LOCK",
                 "_LOG_LAST_FLUSH": "_IO_LOCK"},
    "default_lock": "_LOCK",
    # "path.py:function" entries exempt from rule 7 (retry-budget);
    # recovery.py itself IMPLEMENTS the budget so its own draw loop
    # is the mechanism, not a violator
    "retry_loop_allow": [],
}


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    message: str

    def format(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def _load_config() -> dict:
    cfg = {k: v for k, v in DEFAULT_CONFIG.items()}
    pyproject = os.path.join(REPO_ROOT, "pyproject.toml")
    try:
        import tomllib
    except ImportError:  # py<3.11: tomli is API-compatible
        try:
            import tomli as tomllib
        except ImportError:
            return cfg
    try:
        with open(pyproject, "rb") as f:
            data = tomllib.load(f)
    except OSError:
        return cfg
    user = data.get("tool", {}).get("lint-invariants", {})
    for k in ("paths", "key_prefixes", "locked_modules",
              "retry_loop_allow"):
        if k in user:
            cfg[k] = list(user[k])
    return cfg


def _iter_py_files(cfg: dict):
    for rel in cfg["paths"]:
        base = os.path.join(REPO_ROOT, rel)
        if os.path.isfile(base):
            yield base
            continue
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = sorted(d for d in dirnames
                                 if d != "__pycache__")
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def _const_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


# ---- rule 1: conf keys ------------------------------------------------------


def _check_conf_keys(tree: ast.AST, rel: str, cfg: dict,
                     out: List[Finding]) -> None:
    from spark_tpu import conf as CF

    prefixes = tuple(cfg["key_prefixes"])
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("get", "set", "config")
                and node.args):
            continue
        key = _const_str(node.args[0])
        if key is None or not key.startswith(prefixes):
            continue
        if not CF.is_registered(key):
            out.append(Finding(
                "conf-keys", rel, node.lineno,
                f"config key {key!r} is not a registered ConfigEntry "
                "or prefix (register it in spark_tpu/conf.py)"))


# ---- rule 2: fault points ---------------------------------------------------


def _check_fault_points(tree: ast.AST, rel: str, out: List[Finding],
                        seen: Optional[Set[str]] = None) -> None:
    from spark_tpu import faults

    valid: Set[str] = set(faults.POINTS)
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and node.args):
            continue
        fn = node.func
        name = fn.attr if isinstance(fn, ast.Attribute) else \
            fn.id if isinstance(fn, ast.Name) else None
        if name != "inject":
            continue
        point = _const_str(node.args[0])
        if point is None:
            continue
        if point not in valid:
            out.append(Finding(
                "fault-points", rel, node.lineno,
                f"fault point {point!r} is not in faults.POINTS — "
                "this injection site can never fire"))
        elif seen is not None:
            seen.add(point)


def _check_dead_fault_points(seen: Set[str],
                             out: List[Finding]) -> None:
    """Rule 5: every declared point must be injectable somewhere."""
    from spark_tpu import faults

    for point in sorted(set(faults.POINTS) - seen):
        out.append(Finding(
            "dead-fault-points",
            os.path.join("spark_tpu", "faults.py"), 0,
            f"fault point {point!r} is declared in faults.POINTS but "
            "has no faults.inject(...) call site under the linted "
            "paths — arming it would silently test nothing"))


# ---- rule 6: span names -----------------------------------------------------


def _check_span_names(tree: ast.AST, rel: str,
                      out: List[Finding]) -> None:
    """Every literal span name opened via ``trace.span("<name>", ...)``
    (or a bare imported ``span("<name>", ...)``) must be declared in
    the central ``spark_tpu.trace.SPAN_NAMES`` registry, every
    ``trace.built("<kind>", ...)`` kind in ``trace.BUILD_EVENTS`` and
    every ``trace.inner_scope("<name>")`` in ``trace.INNER_SCOPES``."""
    from spark_tpu import trace

    registries = {"span": ("SPAN_NAMES", set(trace.SPAN_NAMES)),
                  "built": ("BUILD_EVENTS", set(trace.BUILD_EVENTS)),
                  "inner_scope": ("INNER_SCOPES", set(trace.INNER_SCOPES))}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and node.args):
            continue
        fn = node.func
        if isinstance(fn, ast.Attribute):
            base = fn.value
            if not (fn.attr in registries and isinstance(base, ast.Name)
                    and base.id in ("trace", "_trace")):
                continue
            registry, valid = registries[fn.attr]
        elif isinstance(fn, ast.Name) and fn.id == "span":
            registry, valid = registries["span"]
        else:
            continue
        name = _const_str(node.args[0])
        if name is not None and name not in valid:
            out.append(Finding(
                "span-names", rel, node.lineno,
                f"name {name!r} is not declared in "
                f"spark_tpu.trace.{registry} — register it so the "
                "waterfall/attribution rollups and the benchmark's "
                "readers see it"))


# ---- rule 7: bounded retry loops draw from the unified budget ---------------

#: a loop is retry-shaped when its target or range bound names one of
#: these (``for attempt in range(retries + 1)`` and friends)
_RETRY_HINTS = ("attempt", "retry", "retries")

#: the enclosing function satisfies the rule by referencing any of the
#: unified-budget API surface
_BUDGET_MARKERS = ("retry_allowed", "RetryBudget", "draw",
                   "retry_budget", "bind_budget")


def _check_retry_budget(tree: ast.AST, rel: str, cfg: dict,
                        out: List[Finding]) -> None:
    allow = set(cfg.get("retry_loop_allow", []))

    def _hinted(name: str) -> bool:
        low = name.lower()
        return any(h in low for h in _RETRY_HINTS)

    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        draws = False
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) \
                    and node.id in _BUDGET_MARKERS:
                draws = True
                break
            if isinstance(node, ast.Attribute) \
                    and node.attr in _BUDGET_MARKERS:
                draws = True
                break
        if draws or f"{rel}:{fn.name}" in allow:
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.For):
                continue
            it = node.iter
            if not (isinstance(it, ast.Call)
                    and isinstance(it.func, ast.Name)
                    and it.func.id == "range"):
                continue
            tgt = node.target
            shaped = isinstance(tgt, ast.Name) and _hinted(tgt.id)
            if not shaped:
                for sub in ast.walk(it):
                    nm = sub.id if isinstance(sub, ast.Name) else \
                        sub.attr if isinstance(sub, ast.Attribute) \
                        else None
                    if nm is not None and _hinted(nm):
                        shaped = True
                        break
            if shaped:
                out.append(Finding(
                    "retry-budget", rel, node.lineno,
                    f"retry loop in {fn.name}() never draws from the "
                    "unified RetryBudget (recovery.retry_allowed / "
                    "budget.draw) — a private attempt counter "
                    "multiplies with every other layer's; exempt via "
                    "retry_loop_allow in [tool.lint-invariants] only "
                    "if the loop genuinely is not a retry"))


# ---- rule 3: fingerprint purity ---------------------------------------------


def _check_fingerprint_purity(tree: ast.AST, rel: str,
                              only_functions: List[str],
                              out: List[Finding]) -> None:
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if only_functions and fn.name not in only_functions:
            continue
        sorted_spans: List[Tuple[int, int]] = []
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id == "sorted":
                sorted_spans.append(
                    (node.lineno, node.end_lineno or node.lineno))
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id in ("hash", "id"):
                out.append(Finding(
                    "fingerprint-purity", rel, node.lineno,
                    f"{node.func.id}() inside fingerprint function "
                    f"{fn.name}(): process-seeded/address-based values "
                    "break cross-session executable reuse"))
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("items", "keys", "values") \
                    and not node.args:
                inside_sorted = any(
                    lo <= node.lineno <= hi for lo, hi in sorted_spans)
                if not inside_sorted:
                    out.append(Finding(
                        "fingerprint-purity", rel, node.lineno,
                        f".{node.func.attr}() iteration inside "
                        f"fingerprint function {fn.name}() is dict-"
                        "order-dependent; wrap in sorted(...)"))


# ---- rule 4: metrics mutations under the lock -------------------------------

_MUTATORS = ("append", "pop", "popleft", "clear", "update", "extend",
             "setdefault", "insert", "remove")


def _check_metrics_locks(tree: ast.AST, rel: str, cfg: dict,
                         out: List[Finding]) -> None:
    module_state: Set[str] = set()
    for node in tree.body:
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
            targets = [node.target]
        for t in targets:
            if isinstance(t, ast.Name) and t.id.startswith("_"):
                module_state.add(t.id)
    locks = {cfg["default_lock"]} | set(cfg["lock_map"].values())
    module_state -= locks

    def required_lock(name: str) -> str:
        return cfg["lock_map"].get(name, cfg["default_lock"])

    def base_name(node: ast.AST) -> Optional[str]:
        while isinstance(node, (ast.Subscript, ast.Attribute)):
            node = node.value
        if isinstance(node, ast.Name):
            return node.id
        return None

    def walk(node: ast.AST, held: Set[str], depth: int) -> None:
        if isinstance(node, ast.With):
            got = set(held)
            for item in node.items:
                n = base_name(item.context_expr)
                if n in locks:
                    got.add(n)
            for child in node.body:
                walk(child, got, depth)
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for child in node.body:
                walk(child, set(), depth + 1)
            return

        mutated: List[Tuple[str, int]] = []
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.Delete)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else getattr(node, "targets", None) or [node.target]
            for t in targets:
                n = base_name(t)
                if n in module_state:
                    if depth > 0 or not isinstance(t, ast.Name):
                        mutated.append((n, node.lineno))
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) \
                    and isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr in _MUTATORS:
                n = base_name(sub.func.value)
                if n in module_state and depth > 0:
                    mutated.append((n, sub.lineno))
        for name, line in mutated:
            need = required_lock(name)
            # the recursive walk revisits nested statements; report
            # each (state, line) once
            if need not in held and (name, line) not in reported:
                reported.add((name, line))
                out.append(Finding(
                    "metrics-lock", rel, line,
                    f"mutation of {name} outside `with {need}:` — "
                    "the concurrent scheduler mutates metrics from "
                    "many threads"))
        for child in ast.iter_child_nodes(node):
            walk(child, held, depth)

    reported: Set[Tuple[str, int]] = set()
    for top in tree.body:
        walk(top, set(), 0)


# ---- driver -----------------------------------------------------------------


def _import_all_modules() -> None:
    """ConfigEntry / fault-point registration happens at import time of
    whichever module owns the entry (recovery.py registers
    spark.checkpoint.dir, ...), so the ground-truth registry is only
    complete once every spark_tpu module is imported. Failures are
    tolerated per-module (optional deps may be stubbed out)."""
    import importlib
    import pkgutil

    import spark_tpu

    for info in pkgutil.walk_packages(spark_tpu.__path__,
                                      prefix="spark_tpu."):
        try:
            importlib.import_module(info.name)
        except Exception:
            pass


def run_lint(config: Optional[dict] = None) -> List[Finding]:
    sys.path.insert(0, REPO_ROOT)
    cfg = config or _load_config()
    _import_all_modules()
    findings: List[Finding] = []
    fingerprint: Dict[str, List[str]] = dict(cfg["fingerprint_paths"])
    locked = set(cfg["locked_modules"])
    injected_points: Set[str] = set()
    for path in _iter_py_files(cfg):
        rel = os.path.relpath(path, REPO_ROOT)
        with open(path, "r") as f:
            src = f.read()
        try:
            tree = ast.parse(src, filename=rel)
        except SyntaxError as e:
            findings.append(Finding("parse", rel, e.lineno or 0,
                                    f"syntax error: {e.msg}"))
            continue
        _check_conf_keys(tree, rel, cfg, findings)
        _check_fault_points(tree, rel, findings, injected_points)
        _check_span_names(tree, rel, findings)
        _check_retry_budget(tree, rel, cfg, findings)
        if rel in fingerprint:
            _check_fingerprint_purity(tree, rel, fingerprint[rel],
                                      findings)
        if rel in locked:
            _check_metrics_locks(tree, rel, cfg, findings)
    _check_dead_fault_points(injected_points, findings)
    return findings


def main(argv: Optional[List[str]] = None) -> int:
    findings = run_lint()
    for f in findings:
        print(f.format())
    n = len(findings)
    print(f"lint_invariants: {n} finding(s)"
          if n else "lint_invariants: clean")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
