#!/usr/bin/env python
"""Lint: every ``filodb_*`` metric family emitted in code is documented in
doc/observability.md, and every family the doc names exists in code.

Companion to tools/check_spans.py (make test-observability): the doc's
metrics reference is the operator contract — an undocumented metric is
invisible to dashboards and runbooks, and a documented-but-deleted one is a
broken alert waiting to fire never.

Method: walk the package AST (no imports — runs without jax) collecting
every string constant matching ``filodb_[a-z0-9_]+`` (registration calls,
collector tuples, docstring references — all legitimate family mentions),
then compare against the same regex over doc/observability.md. Both sides
normalize to the family STEM — trailing ``_total``/``_bucket``/``_sum``/
``_count`` exposition suffixes stripped — so counters registered as
``filodb_queries`` match the documented ``filodb_queries_total`` and
histogram families match any of their derived series names.

Exit code 0 = clean, 1 = violations (printed one per line).
"""

from __future__ import annotations

import ast
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
PKG = ROOT / "filodb_tpu"
DOC = ROOT / "doc" / "observability.md"

# a family mention must not be preceded by a name character (excludes the
# `_filodb_chunkmeta_all` magic selector) nor followed by `*` (glob-style
# prose references like "filodb_tpu_*" aren't family names)
NAME_RE = re.compile(r"(?<![A-Za-z0-9_])filodb_[a-z0-9_]+")
FULL_RE = re.compile(r"^filodb_[a-z0-9]+(_[a-z0-9]+)*$")


def find_names(text: str):
    for m in NAME_RE.finditer(text):
        end = m.end()
        if end < len(text) and text[end] == "*":
            continue  # glob-style prose reference, not a family name
        yield m.group(0)
SUFFIXES = ("_total", "_bucket", "_sum", "_count")

# strings that match the metric-name shape but aren't metric families
ALLOW = {
    "filodb_tpu",  # the package itself (and the filodb_tpu_* glob's stem)
}


def stem(name: str) -> str:
    for suf in SUFFIXES:
        if name.endswith(suf) and len(name) > len(suf) + len("filodb_"):
            return name[: -len(suf)]
    return name


def code_stems() -> tuple[set[str], dict[str, list[str]]]:
    stems: set[str] = set()
    where: dict[str, list[str]] = {}
    for path in sorted(PKG.rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        try:
            tree = ast.parse(path.read_text(), filename=str(path))
        except SyntaxError as e:
            print(f"SYNTAX ERROR {path}: {e}")
            sys.exit(1)
        for node in ast.walk(tree):
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                for m in find_names(node.value):
                    m = m.rstrip("_")
                    if not FULL_RE.match(m) or m in ALLOW:
                        continue
                    s = stem(m)
                    stems.add(s)
                    where.setdefault(s, []).append(
                        f"{path.relative_to(ROOT)}:{node.lineno}"
                    )
    return stems, where


def doc_stems() -> set[str]:
    text = DOC.read_text()
    out = set()
    for m in find_names(text):
        m = m.rstrip("_")
        if FULL_RE.match(m) and m not in ALLOW:
            out.add(stem(m))
    return out


PERF_DOC = ROOT / "doc" / "perf.md"


def fused_reason_violations() -> list[str]:
    """Label-taxonomy lint for ``filodb_fused_fallback_total{reason}``:
    the canonical set (metrics.FUSED_FALLBACK_REASONS) must match BOTH the
    doc/perf.md fallback table's rows and every literal reason the code
    records — a reason recorded but undocumented is an undashboarded
    series, a documented-but-unrecorded one is a dead runbook row, and a
    canonical entry with NO recording call site is a dead taxonomy entry
    (a burned-down fallback whose reason must leave the frozenset and the
    doc table together)."""
    out: list[str] = []
    # canonical set, read from the AST (no imports — runs without jax)
    canon: set[str] = set()
    tree = ast.parse((PKG / "metrics.py").read_text())
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign) and node.targets
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "FUSED_FALLBACK_REASONS"):
            for c in ast.walk(node.value):
                if isinstance(c, ast.Constant) and isinstance(c.value, str):
                    canon.add(c.value)
    if not canon:
        return ["fused-fallback lint: FUSED_FALLBACK_REASONS not found in "
                "filodb_tpu/metrics.py"]
    # literal reasons the code records. Direct call sites —
    # record_fused_fallback("x") and the FusedAggregateExec fallback helper
    # self._fall(ctx, "x") — feed the recorded-but-not-canonical check;
    # most reasons flow through a variable (returned from a classifier,
    # threaded through _grid_variant), so the dead-entry direction counts
    # any EXACT-match string constant in package code outside the
    # frozenset itself (docstrings never equal a bare reason name).
    recorded: set[str] = set()
    mentioned: set[str] = set()
    for path in sorted(PKG.rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        tree = ast.parse(path.read_text())
        if path.name == "metrics.py":
            # skip the canonical frozenset's own literals
            for node in ast.walk(tree):
                if (isinstance(node, ast.Assign) and node.targets
                        and isinstance(node.targets[0], ast.Name)
                        and node.targets[0].id == "FUSED_FALLBACK_REASONS"):
                    skip = {id(c) for c in ast.walk(node.value)}
                    break
            else:
                skip = set()
        else:
            skip = set()
        for node in ast.walk(tree):
            if (isinstance(node, ast.Constant)
                    and isinstance(node.value, str)
                    and node.value in canon and id(node) not in skip):
                mentioned.add(node.value)
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            name = getattr(fn, "attr", None) or getattr(fn, "id", None)
            if name == "record_fused_fallback" and node.args:
                a = node.args[0]
                if isinstance(a, ast.Constant) and isinstance(a.value, str):
                    recorded.add(a.value)
            elif name == "_fall" and len(node.args) >= 2:
                a = node.args[1]
                if isinstance(a, ast.Constant) and isinstance(a.value, str):
                    recorded.add(a.value)
    # documented rows: the doc/perf.md fallback table's `reason` column
    # (the table under "Reason taxonomy:", up to the next heading — other
    # two-column tables in the doc are not reason taxonomies)
    text = PERF_DOC.read_text()
    m = re.search(r"Reason taxonomy:(.*?)^#", text, re.S | re.M)
    table = m.group(1) if m else ""
    documented = set(re.findall(r"^\| `([a-z_]+)` \|", table, re.M))
    for r in sorted(recorded - canon):
        out.append(
            f"fused-fallback reason {r!r} recorded in code but missing from "
            f"metrics.FUSED_FALLBACK_REASONS (it would be minted as "
            f"reason=\"unknown\")"
        )
    for r in sorted(canon - (recorded | mentioned)):
        out.append(
            f"fused-fallback reason {r!r} is canonical but no code records "
            f"it — dead taxonomy entry; remove it from "
            f"metrics.FUSED_FALLBACK_REASONS and doc/perf.md's fallback "
            f"table together"
        )
    for r in sorted(canon - documented):
        out.append(
            f"fused-fallback reason {r!r} is canonical but undocumented — "
            f"add a row to doc/perf.md's fallback table"
        )
    for r in sorted(documented - canon):
        out.append(
            f"doc/perf.md documents fused-fallback reason {r!r} that no "
            f"code can record"
        )
    return out


def standing_violations() -> list[str]:
    """Standing-engine taxonomy lint: (a) every ``filodb_standing_*``
    family emitted in code carries a HELP text (metrics.HELP_TEXTS — the
    families are new; shipping one without operator-facing help would be a
    silent gap the doc lint alone can't see, since docstrings mentioning a
    family satisfy it), and (b) the registry's canonical demotion-reason
    set (standing/registry.DEMOTE_REASONS) includes the fused-fallback
    member ``standing_nondecomposable`` — the two taxonomies must share
    that entry or demotions and fallback counts drift apart."""
    out: list[str] = []
    helped: set[str] = set()
    tree = ast.parse((PKG / "metrics.py").read_text())
    for node in ast.walk(tree):
        target = None
        if isinstance(node, ast.Assign) and node.targets:
            target = node.targets[0]
        elif isinstance(node, ast.AnnAssign):  # HELP_TEXTS: dict[...] = {...}
            target = node.target
        if (target is not None and isinstance(target, ast.Name)
                and target.id == "HELP_TEXTS" and node.value is not None
                and isinstance(node.value, ast.Dict)):
            for k in node.value.keys:
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    helped.add(k.value)
    code, where = code_stems()
    for s in sorted(code):
        if s.startswith("filodb_standing") and s not in helped:
            locs = ", ".join(where.get(s, [])[:2])
            out.append(
                f"standing family {s}* emitted ({locs}) without a HELP "
                f"text in metrics.HELP_TEXTS"
            )
    reg = PKG / "standing" / "registry.py"
    demote: set[str] = set()
    if reg.exists():
        for node in ast.walk(ast.parse(reg.read_text())):
            if (isinstance(node, ast.Assign) and node.targets
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id == "DEMOTE_REASONS"):
                for c in ast.walk(node.value):
                    if isinstance(c, ast.Constant) and isinstance(c.value, str):
                        demote.add(c.value)
        if "standing_nondecomposable" not in demote:
            out.append(
                "standing/registry.DEMOTE_REASONS must include "
                "'standing_nondecomposable' (the shared fused-fallback "
                "taxonomy entry)"
            )
    return out


def rollup_violations() -> list[str]:
    """Rollup-tier taxonomy lint (downsample/rollup.py): (a) every
    ``filodb_rollup_*`` family emitted in code carries a HELP text in
    metrics.HELP_TEXTS, and (b) the canonical maintenance-event set
    (metrics.ROLLUP_EVENTS — the ``filodb_rollup_maintenance{event}``
    label taxonomy) matches every literal event the code records via
    ``record_rollup_event("...")`` — an unrecognized literal would be
    minted as event="unknown", a canonical-but-unrecorded one is a dead
    dashboard row. The ``rollup_ineligible`` fused-fallback reason is
    covered by the shared three-way fused_reason lint above."""
    out: list[str] = []
    helped: set[str] = set()
    canon: set[str] = set()
    tree = ast.parse((PKG / "metrics.py").read_text())
    for node in ast.walk(tree):
        target = None
        if isinstance(node, ast.Assign) and node.targets:
            target = node.targets[0]
        elif isinstance(node, ast.AnnAssign):
            target = node.target
        if target is None or not isinstance(target, ast.Name):
            continue
        if (target.id == "HELP_TEXTS" and node.value is not None
                and isinstance(node.value, ast.Dict)):
            for k in node.value.keys:
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    helped.add(k.value)
        elif target.id == "ROLLUP_EVENTS" and node.value is not None:
            for c in ast.walk(node.value):
                if isinstance(c, ast.Constant) and isinstance(c.value, str):
                    canon.add(c.value)
    if not canon:
        return ["rollup lint: ROLLUP_EVENTS not found in "
                "filodb_tpu/metrics.py"]
    code, where = code_stems()
    for s in sorted(code):
        if s.startswith("filodb_rollup") and s not in helped:
            locs = ", ".join(where.get(s, [])[:2])
            out.append(
                f"rollup family {s}* emitted ({locs}) without a HELP "
                f"text in metrics.HELP_TEXTS"
            )
    recorded: set[str] = set()
    for path in sorted(PKG.rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        for node in ast.walk(ast.parse(path.read_text())):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            name = getattr(fn, "attr", None) or getattr(fn, "id", None)
            if name == "record_rollup_event" and node.args:
                a = node.args[0]
                if isinstance(a, ast.Constant) and isinstance(a.value, str):
                    recorded.add(a.value)
    for r in sorted(recorded - canon):
        out.append(
            f"rollup maintenance event {r!r} recorded in code but missing "
            f"from metrics.ROLLUP_EVENTS (it would be minted as "
            f"event=\"unknown\")"
        )
    for r in sorted(canon - recorded):
        out.append(
            f"rollup maintenance event {r!r} is canonical but no code "
            f"records it — dead dashboard row"
        )
    return out


def alerting_violations() -> list[str]:
    """Alert-taxonomy lint (obs/alerting.py + obs/notify.py): (a) every
    ``filodb_alert*`` family emitted in code carries a HELP text in
    metrics.HELP_TEXTS; (b) the canonical state set (alerting.ALERT_STATES
    — the ``alertstate`` label taxonomy on ``filodb_alerts`` and the
    ``ALERTS`` write-back series) matches the doc's "canonical
    ``alertstate`` values" line in doc/observability.md, and every literal
    ``alertstate`` value in the package is a member — an off-taxonomy
    literal would mint a state no dashboard row matches."""
    out: list[str] = []
    helped: set[str] = set()
    tree = ast.parse((PKG / "metrics.py").read_text())
    for node in ast.walk(tree):
        target = None
        if isinstance(node, ast.Assign) and node.targets:
            target = node.targets[0]
        elif isinstance(node, ast.AnnAssign):
            target = node.target
        if (target is not None and isinstance(target, ast.Name)
                and target.id == "HELP_TEXTS" and node.value is not None
                and isinstance(node.value, ast.Dict)):
            for k in node.value.keys:
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    helped.add(k.value)
    code, where = code_stems()
    for s in sorted(code):
        if s.startswith("filodb_alert") and s not in helped:
            locs = ", ".join(where.get(s, [])[:2])
            out.append(
                f"alerting family {s}* emitted ({locs}) without a HELP "
                f"text in metrics.HELP_TEXTS"
            )
    # canonical state set, read from the AST (no imports — runs without jax)
    canon: set[str] = set()
    alerting = PKG / "obs" / "alerting.py"
    for node in ast.walk(ast.parse(alerting.read_text())):
        if (isinstance(node, ast.Assign) and node.targets
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "ALERT_STATES"):
            for c in ast.walk(node.value):
                if isinstance(c, ast.Constant) and isinstance(c.value, str):
                    canon.add(c.value)
    if not canon:
        return out + ["alerting lint: ALERT_STATES not found in "
                      "filodb_tpu/obs/alerting.py"]
    # the doc's canonical-states line must agree (the operator contract)
    m = re.search(r"canonical `alertstate` values:([^\n]*)", DOC.read_text())
    documented = set(re.findall(r"`([a-z_]+)`", m.group(1))) if m else set()
    if not m:
        out.append(
            "doc/observability.md is missing the 'canonical `alertstate` "
            "values:' line the alerting lint checks"
        )
    else:
        for s in sorted(canon - documented):
            out.append(
                f"alertstate {s!r} is canonical but missing from "
                f"doc/observability.md's canonical-values line"
            )
        for s in sorted(documented - canon):
            out.append(
                f"doc/observability.md documents alertstate {s!r} that is "
                f"not in alerting.ALERT_STATES"
            )
    # every literal alertstate value in the package is canonical
    for path in sorted(PKG.rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        for node in ast.walk(ast.parse(path.read_text())):
            vals: list[tuple[str, int]] = []
            if isinstance(node, ast.Call):
                for kw in node.keywords:
                    if (kw.arg == "alertstate"
                            and isinstance(kw.value, ast.Constant)
                            and isinstance(kw.value.value, str)):
                        vals.append((kw.value.value, node.lineno))
            elif isinstance(node, ast.Dict):
                for k, v in zip(node.keys, node.values):
                    if (isinstance(k, ast.Constant)
                            and k.value == "alertstate"
                            and isinstance(v, ast.Constant)
                            and isinstance(v.value, str)):
                        vals.append((v.value, node.lineno))
            for v, lineno in vals:
                if v not in canon:
                    out.append(
                        f"literal alertstate {v!r} "
                        f"({path.relative_to(ROOT)}:{lineno}) is not in "
                        f"alerting.ALERT_STATES"
                    )
    return out


OPS = PKG / "ops"


def _is_jit_decorator(d: ast.expr) -> bool:
    """True for ``@jax.jit``, ``@jax.jit(...)``, ``@pjit(...)`` and
    ``@functools.partial(jax.jit, ...)`` decorator shapes."""
    if isinstance(d, ast.Attribute) and d.attr in ("jit", "pjit"):
        return True
    if isinstance(d, ast.Name) and d.id == "pjit":
        return True
    if isinstance(d, ast.Call):
        if _is_jit_decorator(d.func):
            return True
        return any(_is_jit_decorator(a) for a in d.args)
    return False


def jit_registration_violations() -> list[str]:
    """Executable-registry coverage lint (obs/kernels.py): every jit
    wrapper defined in ``ops/`` — decorated defs AND ``x = jax.jit(...)``
    assignments — must be registered with the kernel observatory via a
    ``KERNELS.register_jits(...)`` call in the same module (kwarg name ==
    wrapper name). A kernel added without registration would dispatch
    outside the observatory: its compiles and device costs would be
    invisible to /debug/kernels and the recompile-storm detector."""
    out: list[str] = []
    for path in sorted(OPS.glob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        jits: dict[str, int] = {}
        registered: set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.FunctionDef) and any(
                _is_jit_decorator(d) for d in node.decorator_list
            ):
                jits[node.name] = node.lineno
            elif isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Call
            ) and _is_jit_decorator(node.value.func):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        jits[t.id] = node.lineno
            elif (isinstance(node, ast.Call)
                  and getattr(node.func, "attr", None) == "register_jits"):
                for kw in node.keywords:
                    if kw.arg:
                        registered.add(kw.arg)
                for a in node.args:
                    if isinstance(a, ast.Constant) and isinstance(a.value, str):
                        registered.add(a.value)
        for name, lineno in sorted(jits.items()):
            if name not in registered:
                out.append(
                    f"jit wrapper {name!r} "
                    f"({path.relative_to(ROOT)}:{lineno}) is not registered "
                    f"with the executable registry — add it to the module's "
                    f"KERNELS.register_jits(...) call (obs/kernels.py)"
                )
    return out


def main() -> int:
    code, where = code_stems()
    doc = doc_stems()
    violations: list[str] = list(fused_reason_violations())
    violations.extend(standing_violations())
    violations.extend(rollup_violations())
    violations.extend(alerting_violations())
    violations.extend(jit_registration_violations())
    for s in sorted(code - doc):
        locs = ", ".join(where.get(s, [])[:2])
        violations.append(
            f"emitted but undocumented: {s}* ({locs}) — add it to "
            f"doc/observability.md's metrics reference"
        )
    for s in sorted(doc - code):
        violations.append(
            f"documented but not emitted: {s}* — doc/observability.md names "
            f"a family no code registers"
        )
    if violations:
        print(f"metrics-doc lint: {len(violations)} violation(s)")
        for v in violations:
            print(f"  {v}")
        return 1
    print(f"metrics-doc lint: OK — {len(code)} metric families, code and "
          f"doc agree")
    return 0


if __name__ == "__main__":
    sys.exit(main())
