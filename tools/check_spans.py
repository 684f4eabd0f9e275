#!/usr/bin/env python
"""Lint: every ExecPlan subclass must execute under a tracing span, and
the query-phase decomposition must stay canonical and complete.

The tracing contract (doc/observability.md) is that ``ExecPlan.execute`` is
the ONE place spans wrap plan-node execution — subclasses implement
``do_execute`` and inherit the instrumented template method. A subclass that
overrides ``execute`` without opening a span silently drops its subtree out
of every trace, EXPLAIN ANALYZE rendering, and the slow-query log.

This check walks the package AST (no imports — runs without jax):

- collects every class transitively subclassing ``ExecPlan``;
- flags any that define ``execute`` unless that override visibly opens a
  span (calls ``span(``) or delegates to ``super().execute``;
- asserts the base ``ExecPlan.execute`` itself opens a span.

Phase-coverage lint (the query observatory, doc/observability.md "Query
observatory" — mirroring check_metrics.py's fused-fallback taxonomy lint):

- every phase literal in the package (``span(..., phase="x")`` kwargs,
  ``rec.phase("x")`` context-manager calls, ``rec.add("x", ...)``) must be
  a member of the canonical ``metrics.QUERY_PHASES`` set — an unknown
  phase name would mint an undashboarded histogram series;
- every ``span(..., part="x")`` literal must be a member of
  ``metrics.STAGE_PARTS``, and every member must be booked somewhere;
- every QueryEngine execution entry (``_query_range_uncoalesced``,
  ``query_instant``, ``execute_plan``) must capture ``parse_plan`` and
  ``admission`` exactly once;
- every fused dispatch path (``span("fused:dispatch...")`` sites in
  ``FusedAggregateExec.do_execute``) must route through
  ``_dispatch_fused``, which must decompose into ``admission`` (queue
  wait) + ``dispatch``; the stage phase must be captured exactly once.

Exit code 0 = clean, 1 = violations (printed one per line).
"""

from __future__ import annotations

import ast
import sys
from pathlib import Path

PKG = Path(__file__).resolve().parent.parent / "filodb_tpu"


def base_names(cls: ast.ClassDef) -> list[str]:
    out = []
    for b in cls.bases:
        if isinstance(b, ast.Name):
            out.append(b.id)
        elif isinstance(b, ast.Attribute):
            out.append(b.attr)
    return out


def method(cls: ast.ClassDef, name: str) -> ast.FunctionDef | None:
    for node in cls.body:
        if isinstance(node, ast.FunctionDef) and node.name == name:
            return node
    return None


def opens_span(fn: ast.FunctionDef) -> bool:
    """True when the method body calls span(...) or super().execute(...)."""
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Name) and f.id == "span":
            return True
        if isinstance(f, ast.Attribute):
            if f.attr == "span":
                return True
            if (
                f.attr == "execute"
                and isinstance(f.value, ast.Call)
                and isinstance(f.value.func, ast.Name)
                and f.value.func.id == "super"
            ):
                return True
    return False


def _canonical(name: str) -> set[str]:
    """A canonical tuple of metrics.py (QUERY_PHASES, STAGE_PARTS), read
    from the AST (no imports)."""
    out: set[str] = set()
    tree = ast.parse((PKG / "metrics.py").read_text())
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign) and node.targets
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == name):
            for c in ast.walk(node.value):
                if isinstance(c, ast.Constant) and isinstance(c.value, str):
                    out.add(c.value)
    return out


def _part_literals(tree: ast.AST):
    """(part-literal, lineno) pairs from one module: ``part=`` kwargs on
    span() calls and ``<x>.add_part("...", ...)`` recorder bumps."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = getattr(f, "attr", None) or getattr(f, "id", None)
        if name == "span":
            for kw in node.keywords:
                if (kw.arg == "part" and isinstance(kw.value, ast.Constant)
                        and isinstance(kw.value.value, str)):
                    yield kw.value.value, node.lineno
        elif name == "add_part" and node.args:
            a = node.args[0]
            if isinstance(a, ast.Constant) and isinstance(a.value, str):
                yield a.value, node.lineno


def _phase_literals(tree: ast.AST):
    """(phase-literal, lineno) pairs from one module: ``phase=`` kwargs on
    span() calls, ``<x>.phase("...")`` context-manager calls, and
    ``rec.add("...", ...)`` recorder bumps."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = getattr(f, "attr", None) or getattr(f, "id", None)
        if name == "span":
            for kw in node.keywords:
                if (kw.arg == "phase" and isinstance(kw.value, ast.Constant)
                        and isinstance(kw.value.value, str)):
                    yield kw.value.value, node.lineno
        elif name == "phase" and isinstance(f, ast.Attribute) and node.args:
            a = node.args[0]
            if isinstance(a, ast.Constant) and isinstance(a.value, str):
                yield a.value, node.lineno
        elif (name == "add" and isinstance(f, ast.Attribute)
              and isinstance(f.value, ast.Name) and f.value.id == "rec"
              and node.args):
            a = node.args[0]
            if isinstance(a, ast.Constant) and isinstance(a.value, str):
                yield a.value, node.lineno


def _count_in(fn: ast.AST, want: str, kinds=("phase", "add", "span")) -> int:
    n = 0
    for lit, _ in _phase_literals(fn):
        if lit == want:
            n += 1
    return n


def phase_violations(classes: dict[str, ast.ClassDef]) -> list[str]:
    out: list[str] = []
    canon = _canonical("QUERY_PHASES")
    if not canon:
        return ["phase lint: QUERY_PHASES not found in filodb_tpu/metrics.py"]
    parts = _canonical("STAGE_PARTS")
    if not parts:
        return ["phase lint: STAGE_PARTS not found in filodb_tpu/metrics.py"]
    # (a) canonical-set rejection over the whole package, phases and the
    # parts of the stage phase alike
    seen_parts: set[str] = set()
    for path in sorted(PKG.rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        tree = ast.parse(path.read_text(), filename=str(path))
        for lit, lineno in _phase_literals(tree):
            if lit not in canon:
                out.append(
                    f"{path}:{lineno}: unknown query phase {lit!r} — not in "
                    f"metrics.QUERY_PHASES {sorted(canon)}"
                )
        for lit, lineno in _part_literals(tree):
            seen_parts.add(lit)
            if lit not in parts:
                out.append(
                    f"{path}:{lineno}: unknown stage part {lit!r} — not in "
                    f"metrics.STAGE_PARTS {sorted(parts)}"
                )
    for missing in sorted(parts - seen_parts):
        out.append(
            f"stage part {missing!r} is in metrics.STAGE_PARTS but no "
            "span(..., part=...) books it — its series would read 0 for ever"
        )
    # (b) engine entry coverage: parse_plan + admission exactly once each
    planner = ast.parse((PKG / "coordinator" / "planner.py").read_text())
    entries = {"_query_range_uncoalesced", "query_instant", "execute_plan"}
    seen_entries = set()
    for node in ast.walk(planner):
        if isinstance(node, ast.FunctionDef) and node.name in entries:
            seen_entries.add(node.name)
            for want in ("parse_plan", "admission"):
                n = _count_in(node, want)
                if n != 1:
                    out.append(
                        f"QueryEngine.{node.name} captures phase {want!r} "
                        f"{n} times (must be exactly once)"
                    )
    for missing in sorted(entries - seen_entries):
        out.append(f"QueryEngine.{missing} not found for phase lint")
    # (c) fused path: one stage capture; every fused:dispatch span routes
    # through _dispatch_fused; _dispatch_fused splits admission + dispatch
    fused = classes.get("FusedAggregateExec")
    if fused is None:
        out.append("FusedAggregateExec not found for phase lint")
        return out
    do_exec = method(fused, "do_execute")
    disp = method(fused, "_dispatch_fused")
    if do_exec is None or disp is None:
        out.append("FusedAggregateExec.do_execute/_dispatch_fused missing")
        return out
    n_stage = _count_in(do_exec, "stage")
    if n_stage != 1:
        out.append(
            f"FusedAggregateExec.do_execute captures phase 'stage' "
            f"{n_stage} times (must be exactly once)"
        )
    n_spans = n_routed = 0
    for node in ast.walk(do_exec):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = getattr(f, "attr", None) or getattr(f, "id", None)
        if name == "span" and node.args:
            a = node.args[0]
            text = None
            if isinstance(a, ast.Constant) and isinstance(a.value, str):
                text = a.value
            elif isinstance(a, ast.JoinedStr) and a.values and isinstance(
                    a.values[0], ast.Constant):
                text = str(a.values[0].value)
            if text and text.startswith("fused:dispatch"):
                n_spans += 1
        elif name == "_dispatch_fused":
            n_routed += 1
    if n_spans != n_routed or n_routed == 0:
        out.append(
            f"FusedAggregateExec.do_execute has {n_spans} fused:dispatch "
            f"spans but {n_routed} _dispatch_fused calls — every dispatch "
            "path must route through the phase-decomposing helper"
        )
    for want in ("admission", "dispatch"):
        if _count_in(disp, want) == 0:
            out.append(
                f"FusedAggregateExec._dispatch_fused never records phase "
                f"{want!r} — the queue-wait/launch decomposition is gone"
            )
    return out


def main() -> int:
    classes: dict[str, ast.ClassDef] = {}
    files: dict[str, Path] = {}
    for path in sorted(PKG.rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        try:
            tree = ast.parse(path.read_text(), filename=str(path))
        except SyntaxError as e:
            print(f"SYNTAX ERROR {path}: {e}")
            return 1
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef):
                classes[node.name] = node
                files[node.name] = path

    # transitive closure over class names (same-name collisions across
    # modules are acceptable at this granularity — plan classes are unique)
    plan_classes: set[str] = {"ExecPlan"}
    changed = True
    while changed:
        changed = False
        for name, cls in classes.items():
            if name not in plan_classes and plan_classes & set(base_names(cls)):
                plan_classes.add(name)
                changed = True
    plan_classes.discard("ExecPlan")

    violations: list[str] = []
    base = classes.get("ExecPlan")
    if base is None:
        violations.append("ExecPlan base class not found")
    else:
        base_exec = method(base, "execute")
        if base_exec is None or not opens_span(base_exec):
            violations.append(
                f"{files['ExecPlan']}: ExecPlan.execute does not open a span"
            )

    for name in sorted(plan_classes):
        fn = method(classes[name], "execute")
        if fn is not None and not opens_span(fn):
            violations.append(
                f"{files[name]}:{fn.lineno}: {name}.execute overrides the "
                "instrumented template without opening a span"
            )

    violations.extend(phase_violations(classes))

    if violations:
        print(f"span-coverage lint: {len(violations)} violation(s)")
        for v in violations:
            print(f"  {v}")
        return 1
    print(
        f"span-coverage lint: OK — {len(plan_classes)} ExecPlan subclasses "
        "all execute under a span; query-phase coverage canonical"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
