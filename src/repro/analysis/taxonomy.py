"""Taxonomy pass (rules TAX001-TAX006).

Keeps the observability vocabulary closed and documented:

* **TAX001** unknown trace kind: every ``bus.emit("<kind>", ...)`` /
  ``self._emit("<kind>", ...)`` / ``bus.span("<kind>", ...)`` (the
  first string literal among the positional arguments) must be a member
  of ``trace.EVENT_KINDS`` (the runtime asserts this too, but only on
  the paths a test happens to drive).
* **TAX002** malformed metric name: every emitted ``gravfm_*`` name
  must match ``^gravfm_[a-z0-9_]+$``.
* **TAX003** suffix/type mismatch: counters end ``_total``;
  gauges/histograms must not.
* **TAX004** kind conflict: one name used as more than one metric type
  (the registry raises at runtime; this catches it at review time).
* **TAX005** undocumented metric family: every emitted name (or
  f-string family) must match a row of the README "Metric-name
  taxonomy" table (``{a,b}`` alternations and ``<k>`` wildcards
  expand).
* **TAX006** undocumented trace kind: every ``EVENT_KINDS`` member
  must appear in the README event-taxonomy table.

Dynamic (f-string) names resolve exactly when their substitutions
iterate literal string tuples in the same function; otherwise the
static prefix/suffix become a wildcard family checked against the
documented wildcard rows.
"""
from __future__ import annotations

import ast
import fnmatch
import itertools
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .findings import Finding, SourceFile, attr_chain

__all__ = ["TaxonomyPass", "parse_readme_metrics", "parse_readme_kinds"]

_NAME_RE = re.compile(r"^gravfm_[a-z0-9_]+$")
_TICK_RE = re.compile(r"`([^`]+)`")

_EMIT_METHODS = {"emit", "_emit", "span", "_span"}
_METRIC_METHODS = {"inc": "counter", "set_counter": "counter",
                   "set_gauge": "gauge", "observe": "histogram"}


def _expand_braces(tok: str) -> List[str]:
    """``a_{x,y}_b`` -> [a_x_b, a_y_b]; multiple groups take the
    product."""
    parts = re.split(r"\{([^{}]*)\}", tok)
    fixed = parts[0::2]
    groups = [p.split(",") for p in parts[1::2]]
    out = []
    for combo in itertools.product(*groups) if groups else [()]:
        s = fixed[0]
        for g, f in zip(combo, fixed[1:]):
            s += g.strip() + f
        out.append(s)
    return out


def parse_readme_metrics(text: str) -> List[str]:
    """fnmatch patterns from the README metric-taxonomy table
    (``<k>`` -> ``*``)."""
    pats: List[str] = []
    in_section = False
    for line in text.splitlines():
        if "Metric-name taxonomy" in line:
            in_section = True
            continue
        if in_section and line.startswith("## "):
            break
        if not in_section or not line.lstrip().startswith("|"):
            continue
        first_cell = line.split("|")[1] if "|" in line else ""
        for tok in _TICK_RE.findall(first_cell):
            if not tok.startswith("gravfm_"):
                continue
            tok = re.sub(r"<[^<>]+>", "*", tok)
            pats.extend(_expand_braces(tok))
    return pats


def parse_readme_kinds(text: str) -> Set[str]:
    kinds: Set[str] = set()
    in_section = False
    for line in text.splitlines():
        if "Event taxonomy" in line:
            in_section = True
            continue
        if in_section and (line.startswith("## ")
                           or line.startswith("**")):
            break
        if not in_section or not line.lstrip().startswith("|"):
            continue
        first_cell = line.split("|")[1] if "|" in line else ""
        kinds.update(_TICK_RE.findall(first_cell))
    kinds.discard("kind")
    return kinds


def _literal_str(node) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


class TaxonomyPass:
    name = "taxonomy"

    def __init__(self, event_kinds: Optional[Set[str]] = None,
                 readme_text: Optional[str] = None):
        """``event_kinds``/``readme_text`` override discovery (tests);
        by default EVENT_KINDS is parsed out of ``service/trace.py``
        among the scanned files and the README is read by the CLI."""
        self.event_kinds = event_kinds
        self.readme_text = readme_text

    # ---------------- EVENT_KINDS discovery --------------------------
    @staticmethod
    def _find_event_kinds(files: Sequence[SourceFile]) -> Optional[Set[str]]:
        for sf in files:
            if sf.rel.rsplit("/", 1)[-1] != "trace.py":
                continue
            for node in ast.walk(sf.tree):
                if isinstance(node, ast.Assign) and any(
                        isinstance(t, ast.Name)
                        and t.id == "EVENT_KINDS"
                        for t in node.targets):
                    try:
                        v = node.value
                        if isinstance(v, ast.Call):   # frozenset({...})
                            v = v.args[0]
                        return set(ast.literal_eval(v))
                    except Exception:
                        return None
        return None

    # ---------------- f-string family resolution ---------------------
    @staticmethod
    def _loop_literals(fn) -> Dict[str, List[str]]:
        """for-targets iterating literal string tuples -> values."""
        out: Dict[str, List[str]] = {}
        if fn is None:
            return out
        for node in ast.walk(fn):
            if not isinstance(node, ast.For):
                continue
            if not isinstance(node.target, ast.Name):
                continue
            if isinstance(node.iter, (ast.Tuple, ast.List)):
                vals = [_literal_str(e) for e in node.iter.elts]
                if all(v is not None for v in vals):
                    out[node.target.id] = vals  # type: ignore[assignment]
        return out

    def _name_variants(self, node, fn) -> Optional[List[str]]:
        """Concrete names, or wildcard families, for a metric-name
        argument. None when it cannot start with gravfm_."""
        s = _literal_str(node)
        if s is not None:
            return [s] if s.startswith("gravfm_") else None
        if not isinstance(node, ast.JoinedStr):
            return None
        loops = self._loop_literals(fn)
        parts: List[List[str]] = []
        for v in node.values:
            if isinstance(v, ast.Constant):
                parts.append([str(v.value)])
            elif isinstance(v, ast.FormattedValue) and \
                    isinstance(v.value, ast.Name) and \
                    v.value.id in loops:
                parts.append(loops[v.value.id])
            else:
                parts.append(["*"])
        names = ["".join(c) for c in itertools.product(*parts)]
        names = [re.sub(r"\*+", "*", n) for n in names]
        return [n for n in names if n.startswith("gravfm_")] or None

    # ---------------- main ------------------------------------------
    def run(self, files: Sequence[SourceFile]) -> List[Finding]:
        findings: List[Finding] = []
        kinds = self.event_kinds
        if kinds is None:
            kinds = self._find_event_kinds(files)

        doc_patterns = (parse_readme_metrics(self.readme_text)
                        if self.readme_text else None)
        doc_kinds = (parse_readme_kinds(self.readme_text)
                     if self.readme_text else None)

        # name -> (kind, first site) for TAX004
        seen_kind: Dict[str, Tuple[str, str, int]] = {}

        def check_name(sf, scope, node, name, mkind, line):
            if "*" not in name:
                if not _NAME_RE.match(name):
                    if not sf.allows(line, "TAX002"):
                        findings.append(sf.make(
                            "TAX002", line, scope,
                            f"malformed metric name {name!r} (want "
                            f"^gravfm_[a-z0-9_]+$)"))
                    return
                ends_total = name.endswith("_total")
                if mkind == "counter" and not ends_total and \
                        not sf.allows(line, "TAX003"):
                    findings.append(sf.make(
                        "TAX003", line, scope,
                        f"counter {name!r} must end with '_total'"))
                if mkind in ("gauge", "histogram") and ends_total and \
                        not sf.allows(line, "TAX003"):
                    findings.append(sf.make(
                        "TAX003", line, scope,
                        f"{mkind} {name!r} must not end with '_total'"))
                prev = seen_kind.get(name)
                if prev and prev[0] != mkind:
                    if not sf.allows(line, "TAX004"):
                        findings.append(sf.make(
                            "TAX004", line, scope,
                            f"{name!r} used as {mkind} here but as "
                            f"{prev[0]} at {prev[1]}:{prev[2]}"))
                else:
                    seen_kind.setdefault(name, (mkind, sf.rel, line))
            if doc_patterns is not None:
                sample = name.replace("*", "samplekey")
                if not any(fnmatch.fnmatchcase(sample, p)
                           for p in doc_patterns) and \
                        not sf.allows(line, "TAX005"):
                    findings.append(sf.make(
                        "TAX005", line, scope,
                        f"metric family {name!r} is not documented in "
                        f"the README metric-name taxonomy table"))

        for sf in files:
            # enclosing-function map for loop-literal resolution
            encl: Dict[int, ast.AST] = {}

            def map_encl(node, fn):
                for child in ast.iter_child_nodes(node):
                    nfn = fn
                    if isinstance(child, (ast.FunctionDef,
                                          ast.AsyncFunctionDef)):
                        nfn = child
                    encl[id(child)] = nfn
                    map_encl(child, nfn)

            map_encl(sf.tree, None)

            for node in ast.walk(sf.tree):
                # _SNAP_COUNTERS / _SNAP_GAUGES literal dict values
                if isinstance(node, ast.Assign) and isinstance(
                        node.value, ast.Dict):
                    tname = "".join(t.id for t in node.targets
                                    if isinstance(t, ast.Name))
                    mkind = {"_SNAP_COUNTERS": "counter",
                             "_SNAP_GAUGES": "gauge"}.get(tname)
                    if mkind:
                        for v in node.value.values:
                            s = _literal_str(v)
                            if s:
                                check_name(sf, tname, v, s, mkind,
                                           v.lineno)
                    continue
                if not isinstance(node, ast.Call):
                    continue
                chain = attr_chain(node.func)
                if not chain:
                    continue
                method = chain[-1]
                fn = encl.get(id(node))
                scope = getattr(fn, "name", "<module>")
                # ---- trace kinds --------------------------------
                if method in _EMIT_METHODS and kinds is not None:
                    arg = next((a for a in node.args
                                if _literal_str(a) is not None), None)
                    for kw in node.keywords:
                        if kw.arg == "kind":
                            arg = kw.value
                    k = _literal_str(arg) if arg is not None else None
                    if k is not None and k not in kinds and \
                            not sf.allows(node.lineno, "TAX001"):
                        findings.append(sf.make(
                            "TAX001", node.lineno, scope,
                            f"trace kind {k!r} is not in "
                            f"trace.EVENT_KINDS"))
                # ---- metric names -------------------------------
                mkind = _METRIC_METHODS.get(method)
                if mkind and node.args:
                    variants = self._name_variants(node.args[0], fn)
                    for name in variants or ():
                        check_name(sf, scope, node, name, mkind,
                                   node.lineno)

        # ---- README completeness of EVENT_KINDS ---------------------
        if kinds is not None and doc_kinds is not None:
            trace_sf = next(
                (sf for sf in files
                 if sf.rel.rsplit("/", 1)[-1] == "trace.py"), None)
            for k in sorted(kinds - doc_kinds):
                if trace_sf is not None:
                    findings.append(trace_sf.make(
                        "TAX006", 1, "EVENT_KINDS",
                        f"trace kind {k!r} is in EVENT_KINDS but "
                        f"missing from the README event-taxonomy "
                        f"table"))
        return findings
