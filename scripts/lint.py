#!/usr/bin/env python
"""Lint step for scripts/verify.sh.

Prefers ruff, then pyflakes (whichever the environment provides); when
neither is installed it degrades — visibly — to a built-in check that
still catches the common breakage classes a refactor leaves behind:
syntax errors (via compile()) and unused imports (via ast).

    python scripts/lint.py [paths...]       # default: src tests benchmarks
                                            #          examples scripts
"""

from __future__ import annotations

import ast
import pathlib
import re
import subprocess
import sys

_IDENT = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")

DEFAULT_PATHS = ("src", "tests", "benchmarks", "examples", "scripts")


def _external(tool_args: list[str], paths: list[str]) -> int | None:
    """Run an external linter if importable; None means unavailable."""
    probe = subprocess.run([sys.executable, "-m", tool_args[0], "--version"],
                           capture_output=True)
    if probe.returncode != 0:
        return None
    print(f"lint: using {' '.join(tool_args)}")
    return subprocess.run([sys.executable, "-m", *tool_args, *paths]).returncode


def _py_files(paths: list[str]) -> list[pathlib.Path]:
    out: list[pathlib.Path] = []
    for p in paths:
        path = pathlib.Path(p)
        if path.is_dir():
            out.extend(sorted(path.rglob("*.py")))
        elif path.suffix == ".py":
            out.append(path)
    return [p for p in out if "__pycache__" not in p.parts]


def _unused_imports(tree: ast.Module) -> list[tuple[int, str]]:
    """Names imported at module level but never referenced.  Conservative:
    re-export modules (``__all__`` present or __init__-style) and
    ``import x as x`` re-export idiom are exempted by the caller."""
    imported: dict[str, int] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                name = (a.asname or a.name).split(".")[0]
                imported[name] = node.lineno
        elif isinstance(node, ast.ImportFrom):
            if node.module == "__future__":
                continue  # compiler directive, not a binding to "use"
            for a in node.names:
                if a.name == "*":
                    continue
                imported[a.asname or a.name] = node.lineno
    used: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            base = node
            while isinstance(base, ast.Attribute):
                base = base.value
            if isinstance(base, ast.Name):
                used.add(base.id)
        elif isinstance(node, ast.Constant) and isinstance(node.value, str):
            # string annotations / doctest snippets reference names
            # textually — treat any identifier-ish token inside as a use
            used.update(_IDENT.findall(node.value))
    return [(ln, name) for name, ln in sorted(imported.items(),
                                              key=lambda kv: kv[1])
            if name not in used]


def _clock_discipline(paths: list[str]) -> int:
    """Forbid raw ``time.perf_counter()`` in the serving tier outside
    ``observability.py``.  The serving tier must take timestamps through
    the injectable ``Observability`` clock (``service.obs``) so tests can
    drive spans with a fake clock and the no-tracing path stays free of
    clock reads; a raw ``perf_counter`` bypasses both.  (``time
    .monotonic`` stays legal: the scheduler's formation-window deadline
    is a real-time ``Condition.wait`` bound that a frozen fake clock must
    never be able to hang.)  Always runs, even when ruff/pyflakes handle
    the general lint."""
    failures = 0
    for f in _py_files(paths):
        parts = f.parts
        if "service" not in parts or "repro" not in parts:
            continue
        if f.name == "observability.py":
            continue
        for ln, line in enumerate(f.read_text().splitlines(), start=1):
            if "perf_counter" in line.split("#")[0]:
                print(f"{f}:{ln}: raw perf_counter in the serving tier — "
                      "use the injectable Observability clock "
                      "(service.obs) instead")
                failures += 1
    return 1 if failures else 0


def _shard_map_discipline(paths: list[str]) -> int:
    """Forbid ``shard_map`` imports in ``src/`` outside
    ``core/distributed.py``.  The mesh lowering is ONE place — the graph
    interpreter's ring evaluators behind ``DistributedExecutor`` — so
    every other layer (service, planner, tables) stays
    topology-agnostic and single-device code never grows a second,
    subtly-different collective path.  Tests and benchmarks are exempt
    (they exercise the public surface).  Always runs, even when
    ruff/pyflakes handle the general lint."""
    failures = 0
    pat = re.compile(r"import\s+shard_map|shard_map\s*=|"
                     r"from\s+\S*shard_map|jax\.experimental\.shard_map")
    for f in _py_files(paths):
        parts = f.parts
        if "src" not in parts or f.name == "distributed.py":
            continue
        for ln, line in enumerate(f.read_text().splitlines(), start=1):
            if pat.search(line.split("#")[0]):
                print(f"{f}:{ln}: shard_map outside core/distributed.py — "
                      "mesh lowering lives in DistributedExecutor only")
                failures += 1
    return 1 if failures else 0


def _stats_threshold_discipline(paths: list[str]) -> int:
    """Forbid cardinality/selectivity policy constants outside
    ``src/repro/core/stats.py``.  Cost-calibrated planning has ONE home
    for its thresholds (``FK_ELIM_MAX_ORPHANS``,
    ``PREFILTER_MAX_SELECTIVITY``, ``FUSION_COST_DISPARITY``, the
    demotion/EWMA knobs, …): a second copy in a pass or the engine drifts
    from the calibrated value and the decision traces stop telling the
    truth about which gate was applied.  Callers import the constant or
    accept a parameter defaulting to it.  Tests are exempt (they pin
    thresholds on purpose to exercise the gates).  Always runs, even when
    ruff/pyflakes handle the general lint."""
    failures = 0
    pat = re.compile(
        r"^\s*[A-Z0-9_]*(SELECTIVITY|CARDINALITY|DISPARITY|ORPHANS?"
        r"|DEMOTION|EWMA)[A-Z0-9_]*\s*(?::[^=]+)?=[^=]")
    for f in _py_files(paths):
        parts = f.parts
        if "tests" in parts or f.name == "lint.py":
            continue
        if f.name == "stats.py" and "repro" in parts and "core" in parts:
            continue
        for ln, line in enumerate(f.read_text().splitlines(), start=1):
            if pat.search(line.split("#")[0]):
                print(f"{f}:{ln}: cardinality/selectivity threshold "
                      "constant outside src/repro/core/stats.py — planner "
                      "policy knobs live there; import the constant "
                      "instead")
                failures += 1
    return 1 if failures else 0


def _future_resolution_discipline(paths: list[str]) -> int:
    """Forbid direct ``Future.set_result``/``set_exception`` calls in
    ``src/repro/service/`` outside ``scheduler._resolve``.  ``_resolve``
    is the single sanctioned resolution path: it tolerates the
    caller-side cancel race (``InvalidStateError``), so a raw call
    elsewhere reintroduces the crash a cancelled future causes mid-serve.
    Tests are exempt (they resolve throwaway futures to build fixtures).
    Always runs, even when ruff/pyflakes handle the general lint."""
    failures = 0
    for f in _py_files(paths):
        parts = f.parts
        if "service" not in parts or "repro" not in parts:
            continue
        try:
            tree = ast.parse(f.read_text(), filename=str(f))
        except SyntaxError:
            continue  # the builtin lint reports syntax errors
        allowed: list[tuple[int, int]] = []  # _resolve line ranges
        if f.name == "scheduler.py":
            allowed = [(n.lineno, n.end_lineno or n.lineno)
                       for n in ast.walk(tree)
                       if isinstance(n, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))
                       and n.name == "_resolve"]
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("set_result", "set_exception")):
                continue
            if any(lo <= node.lineno <= hi for lo, hi in allowed):
                continue
            print(f"{f}:{node.lineno}: direct Future."
                  f"{node.func.attr} in the serving tier — resolve "
                  "futures through scheduler._resolve (the cancel-race "
                  "guard must stay the single resolution path)")
            failures += 1
    return 1 if failures else 0


def _builtin_lint(paths: list[str]) -> int:
    print("lint: ruff/pyflakes not installed — built-in syntax + "
          "unused-import check")
    failures = 0
    for f in _py_files(paths):
        src = f.read_text()
        try:
            tree = ast.parse(src, filename=str(f))
            compile(src, str(f), "exec")
        except SyntaxError as e:
            print(f"{f}:{e.lineno}: syntax error: {e.msg}")
            failures += 1
            continue
        has_all = any(isinstance(n, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == "__all__" for t in n.targets)
            for n in tree.body)
        if f.name == "__init__.py" or has_all:
            continue  # re-export surface: unused-import check not meaningful
        for ln, name in _unused_imports(tree):
            print(f"{f}:{ln}: unused import {name!r}")
            failures += 1
    return 1 if failures else 0


def main(argv: list[str]) -> int:
    paths = argv or [p for p in DEFAULT_PATHS if pathlib.Path(p).exists()]
    clock_rc = _clock_discipline(paths)
    shard_rc = _shard_map_discipline(paths)
    stats_rc = _stats_threshold_discipline(paths)
    future_rc = _future_resolution_discipline(paths)
    rc = _external(["ruff", "check"], paths)
    if rc is None:
        rc = _external(["pyflakes"], paths)
    if rc is None:
        rc = _builtin_lint(paths)
    rc = rc or clock_rc or shard_rc or stats_rc or future_rc
    print("lint: OK" if rc == 0 else "lint: FAIL")
    return rc


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
