"""RTL132: plane-event name cross-check (``ray_tpu check --events``).

A benchmark or test that asserts on flight-recorder rows references
event names by string (``e["name"] == "bcast.chunk.claim"``); the
registry is whatever ``events.emit("<name>", ...)`` /
``events.count("<name>", ...)`` literals exist in the code. Nothing
validates the two at runtime — ``list_plane_events()`` just returns no
matching rows — so a typo'd name **silently never matches** and the
test green-lights telemetry that was never recorded (the exact failure
mode RTL131 closes for chaos sites). This pass:

1. builds the registered-name set from the scanned package: first
   positional string literal of every ``<base>.emit(...)`` /
   ``<base>.count(...)`` / ``<base>.span(...)`` /
   ``<base>.span_done(...)`` call where ``<base>`` is one of the recorder
   bindings (``events``, ``plane_events``, ``_events``, ``ev`` — the
   spellings the lazy-import shims use);
2. validates each registered literal against the name grammar
   (``plane.noun.verb``: exactly three dot-separated segments, first
   segment in ``events.PLANES``) — a malformed name at the emit site
   would poison every downstream lane grouping;
3. scans the reference paths (``--schedules``, default
   ``benchmarks,tests``) for string literals that MATCH the grammar
   and reports any that resolve to no registered name (error severity:
   the assertion can never see a row).

Synthetic names in recorder unit tests stay invisible by using a first
segment outside the ``PLANES`` alphabet (e.g. ``test.ring.overflow``)
— the grammar filter skips them, no basename exclusion needed.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Sequence, Set, Tuple

from .engine import Finding, Rule, register_rule
from .project import ProjectIndex

# First-segment alphabet comes from the recorder itself so a new plane
# is one edit; falls back to the current set if the import ever cycles.
try:
    from ray_tpu.util.events import PLANES as _PLANES
except Exception:  # pragma: no cover - analysis must stay importable
    _PLANES = ("task", "proto", "gcs", "lease", "wait", "bcast", "coll",
               "serve", "rl", "pipe", "slo", "enforce")

_NAME_RE = re.compile(
    r"^(" + "|".join(_PLANES) + r")\.[a-z_][a-z0-9_]*\.[a-z_][a-z0-9_]*$")

# The spellings emit sites bind the recorder module to (direct import,
# package-qualified, and the lazy shims in protocol.py).
_EMITTER_BASES = {"events", "plane_events", "_events", "ev"}
# The recorder's row-writing calls: a name's first argument registers it.
_EMITTERS = ("emit", "count", "span", "span_done")


@register_rule
class UnknownPlaneEvent(Rule):
    id = "RTL132"
    severity = "error"
    name = "unknown-plane-event"
    hint = ("the string matches the plane-event name grammar but no "
            "events.emit()/count() call registers it — the assertion "
            "can never match a recorded row; fix the name (see "
            "`grep -rn 'plane_events.emit' ray_tpu/`)")


def _emit_name_literals(index: ProjectIndex) -> Dict[str, List[tuple]]:
    """{literal: [(path, line, col), ...]} over every recorder
    emit()/count() call whose first positional arg is a string."""
    out: Dict[str, List[tuple]] = {}
    for mod in index.modules.values():
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if not (isinstance(fn, ast.Attribute)
                    and fn.attr in _EMITTERS
                    and isinstance(fn.value, ast.Name)
                    and fn.value.id in _EMITTER_BASES):
                continue
            if not (node.args and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            out.setdefault(node.args[0].value, []).append(
                (mod.path, node.lineno, node.col_offset))
    return out


def check_events(registry_index: ProjectIndex,
                 reference_index: ProjectIndex) -> List[Finding]:
    registered = _emit_name_literals(registry_index)
    findings: List[Finding] = []
    # An EMPTY scope must fail loudly — exiting 0 because the paths
    # resolved to nothing is the "green run proving nothing" mode.
    if not reference_index.modules:
        return [Finding(
            rule="RTL132", severity="error", path="<references>", line=0,
            col=0,
            message="no reference files found — --schedules paths "
                    "resolve to no Python files, so NO plane-event "
                    "name was validated",
            hint=UnknownPlaneEvent.hint)]
    if not registered:
        return [Finding(
            rule="RTL132", severity="error", path="<registry>", line=0,
            col=0,
            message="no events.emit()/count() sites found in the "
                    "scanned paths — point the positional paths at the "
                    "package that registers the emit sites",
            hint=UnknownPlaneEvent.hint)]
    # Registry-side grammar gate: a malformed literal AT the emit site.
    for name, sites in sorted(registered.items()):
        if _NAME_RE.match(name):
            continue
        for path, line, col in sites:
            findings.append(Finding(
                rule="RTL132", severity="error", path=path, line=line,
                col=col,
                message=f"emit site registers {name!r} which violates "
                        f"the plane-event name grammar "
                        f"(<plane>.<noun>.<verb>, plane in "
                        f"{'/'.join(_PLANES)})",
                hint=UnknownPlaneEvent.hint))
    names: Set[str] = set(registered)
    for mod in reference_index.modules.values():
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Constant)
                    and isinstance(node.value, str)
                    and _NAME_RE.match(node.value)):
                continue
            if node.value in names:
                continue
            findings.append(Finding(
                rule="RTL132", severity="error", path=mod.path,
                line=node.lineno, col=node.col_offset,
                message=f"references plane event {node.value!r} which "
                        f"no events.emit()/count() call registers — "
                        f"it can never match a recorded row",
                hint=UnknownPlaneEvent.hint))
    # inline allowlist via the standard suppression comment (both the
    # registry grammar gate and the reference check honor it)
    out = []
    for f in findings:
        mod = (reference_index.by_path.get(f.path)
               or registry_index.by_path.get(f.path))
        if mod is not None and mod.suppressed(f.rule, f.line):
            continue
        out.append(f)
    out.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return out


def check_event_paths(registry_paths: Sequence[str],
                      reference_paths: Sequence[str],
                      on_error=None) -> List[Finding]:
    reg = ProjectIndex.build(registry_paths, on_error=on_error)
    ref = ProjectIndex.build(reference_paths, on_error=on_error)
    return check_events(reg, ref)
