"""metric-name lint: every metric literal names a canonical metric.

Source of truth: the ``METRIC_NAMES`` literal in
``multiverso_tpu/util/dashboard.py`` (parsed, never imported). Checked
per scanned file:

* ``monitor("X")`` / ``laps("X")`` / ``samples("X")`` / ``count("X")``
  / ``count_event("X")`` — called as a PLAIN NAME with a literal string
  first argument — must name a registry entry. A trailing-``*`` family
  entry (``DISPATCH_MS[d*]``) covers its per-destination/per-table
  instances (``DISPATCH_MS[d3]``). A typo'd metric name otherwise
  splits a signal into two registries nobody correlates — the metric
  twin of the flag-lint's silently-ignored flag.
* Attribute calls (``str.count("x")``, ``report.count(...)``) are NOT
  matched — ``count`` is a common method name; the dashboard counters
  are only ever imported as plain names. Non-literal names (f-string
  families, module constants) are skipped, same contract as flag-lint's
  dynamic names.
* The metric table in ``docs/OBSERVABILITY.md`` is cross-checked
  against the registry in BOTH directions (| `NAME` | rows), so the
  doc cannot drift from the code.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, Iterator, Optional

from .framework import LintPass, ModuleInfo, Violation

METRIC_FNS = {"monitor", "laps", "samples", "count", "count_event"}

#: A metric-table row is `NAME` followed by its KIND (monitor /
#: samples / counter) — the kind column is what distinguishes the
#: metric registry table from the doc's other backticked tables (span
#: schema, endpoints), which must not be cross-checked as metrics.
DOC_ROW_RE = re.compile(
    r"^\|\s*`([A-Za-z0-9_.\[\]*]+)`\s*\|\s*(monitor|samples|counter)\b")


def load_metric_names(dashboard_path: Path) -> Dict[str, str]:
    """The METRIC_NAMES literal, by AST parse of util/dashboard.py."""
    tree = ast.parse(dashboard_path.read_text(encoding="utf-8"))
    for node in ast.walk(tree):
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
        for target in targets:
            if isinstance(target, ast.Name) \
                    and target.id == "METRIC_NAMES":
                value = ast.literal_eval(node.value)
                if isinstance(value, dict):
                    return value
    raise RuntimeError(
        f"no METRIC_NAMES dict literal in {dashboard_path}")


def parse_doc_metrics(doc_path: Path) -> Dict[str, int]:
    """``| `NAME` | ...`` rows from the doc's metric table (name ->
    first line seen)."""
    names: Dict[str, int] = {}
    if not doc_path.exists():
        return names
    for lineno, line in enumerate(
            doc_path.read_text(encoding="utf-8").splitlines(), 1):
        m = DOC_ROW_RE.match(line.strip())
        if m:
            names.setdefault(m.group(1), lineno)
    return names


def family_match(name: str, registry: Dict[str, str]) -> bool:
    """Exact entry, or covered by a trailing-``*`` family entry."""
    if name in registry:
        return True
    for pattern in registry:
        star = pattern.find("*")
        if star < 0:
            continue
        prefix, suffix = pattern[:star], pattern[star + 1:]
        if name.startswith(prefix) and name.endswith(suffix) \
                and len(name) >= len(prefix) + len(suffix):
            return True
    return False


def _literal_str(node: Optional[ast.AST]) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


class MetricNameLint(LintPass):
    name = "metric-name"

    def __init__(self, registry: Dict[str, str], doc_path: Path,
                 doc_rel: str = "docs/OBSERVABILITY.md"):
        self.registry = registry
        self.doc_path = doc_path
        self.doc_rel = doc_rel
        self._doc_checked = False

    def check(self, module: ModuleInfo) -> Iterator[Violation]:
        if not self._doc_checked:
            self._doc_checked = True
            yield from self._check_doc()
        if module.path.name == "dashboard.py" \
                and "util" in module.path.parts:
            return  # the registry / accessor layer itself
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            fn = node.func
            # Plain-name calls only: `x.count("y")` is str/list.count,
            # not the dashboard counter (PR-5 `.get(key)` precedent).
            if not isinstance(fn, ast.Name) or fn.id not in METRIC_FNS:
                continue
            name = _literal_str(node.args[0])
            if name is None:
                continue  # dynamic name (f-string family): out of scope
            if family_match(name, self.registry):
                continue
            import difflib
            close = difflib.get_close_matches(
                name, sorted(self.registry), n=1)
            hint = f" — did you mean {close[0]!r}?" if close else ""
            yield Violation(
                module.rel, node.lineno, node.col_offset, self.name,
                f"{fn.id}({name!r}): not in the canonical metric "
                f"registry (util/dashboard.py METRIC_NAMES){hint}")

    def _check_doc(self) -> Iterator[Violation]:
        if not self.doc_path.exists():
            yield Violation(
                self.doc_rel, 1, 0, self.name,
                "observability doc missing: the metric registry must "
                "be documented (| `NAME` | table)")
            return
        doc = parse_doc_metrics(self.doc_path)
        for name in sorted(self.registry):
            if name not in doc:
                yield Violation(
                    self.doc_rel, 1, 0, self.name,
                    f"registered metric {name} missing from the doc's "
                    f"metric table (| `{name}` | row)")
        for name, lineno in sorted(doc.items()):
            if name not in self.registry:
                yield Violation(
                    self.doc_rel, lineno, 0, self.name,
                    f"doc documents metric {name} which is not in "
                    f"util/dashboard.py METRIC_NAMES — stale doc entry")
