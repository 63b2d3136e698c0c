"""Pass 4: Prometheus metrics / span naming discipline.

Statically scans every ``Counter(...)`` / ``Gauge(...)`` /
``Histogram(...)`` construction in the tree and the dashboard renderer:

- family names are valid Prometheus identifiers (lowercase snake) and
  do not pre-bake the ``ray_tpu_`` prefix (the renderer applies it
  idempotently; double-prefixed source names mask collisions);
- every family carries a non-empty description — that string IS the
  ``# HELP`` line the dashboard emits;
- one family is registered at exactly one construction site (two sites
  with one name either double-count or fight over kind/help);
- every family the renderer hardcodes (``fam("…")``) carries the
  ``ray_tpu_`` prefix, and the renderer both emits ``# HELP``/``# TYPE``
  and applies the prefix to pushed families;
- SLO rules (any string literal in the tree parsing under
  ``_private/slo.py``'s grammar — DEFAULT_RULES, test rules, smoke
  rules) reference only families that exist: ctor-registered,
  dict-literal-synthesized (``{"name": ..., "kind": ...}``, the
  slo_burn_rate/slo_healthy path), or the TSDB's runtime ``node_*``
  namespace — a rule over a typo'd family silently never fires;
- the reverse direction: a ctor-registered family whose name appears in
  no OTHER source/doc (no rule, dashboard, CLI, test, or README mention)
  is flagged as unconsumed — it burns scrape bytes nobody judges;
- every family listed in ``util/metrics.py``'s ``EXEMPLAR_FAMILIES``
  (the exemplar-capable serving-latency set) is constructed as a
  ``Histogram`` — exemplars hang off buckets, so a Counter/Gauge (or an
  unregistered name) in that tuple could never carry one.
"""

from __future__ import annotations

import ast
import re

from ray_tpu._private.staticcheck.common import (
    Violation,
    nodes,
    parse,
    read_source,
    walk_sources,
)

_METRIC_CTORS = {"Counter", "Gauge", "Histogram"}
_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")


def _ctor_kind(func: ast.expr) -> str | None:
    if isinstance(func, ast.Name) and func.id in _METRIC_CTORS:
        return func.id
    if isinstance(func, ast.Attribute) and func.attr in _METRIC_CTORS:
        return func.attr
    return None


def _literal_str(node: ast.expr | None) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _fstring_prefix(node: ast.expr) -> str | None:
    """First literal chunk of an f-string, or the whole literal."""
    lit = _literal_str(node)
    if lit is not None:
        return lit
    if isinstance(node, ast.JoinedStr) and node.values:
        return _literal_str(node.values[0])
    return None


def _scan_registrations(root: str, violations: list[Violation]):
    sites: dict[str, list[tuple[str, int, str]]] = {}
    for rel, src in walk_sources(root, (".py",)):
        if rel.endswith("util/metrics.py") or "/staticcheck/" in rel:
            continue  # the class definitions / this checker itself
        try:
            tree = parse(src)
        except SyntaxError:
            continue
        for node in nodes(tree):
            if not isinstance(node, ast.Call):
                continue
            kind = _ctor_kind(node.func)
            if kind is None:
                continue
            name_node = node.args[0] if node.args else None
            name = _literal_str(name_node)
            if name is None:
                continue  # dynamic name: out of static reach
            desc = _literal_str(
                node.args[1] if len(node.args) > 1 else
                next((k.value for k in node.keywords
                      if k.arg == "description"), None))
            if not _NAME_RE.match(name):
                violations.append(Violation(
                    "metrics/invalid-name", rel, node.lineno,
                    f"{kind} family {name!r} is not a lowercase snake_case "
                    "Prometheus name"))
            if name.startswith("ray_tpu_"):
                violations.append(Violation(
                    "metrics/prebaked-prefix", rel, node.lineno,
                    f"{kind} family {name!r} hardcodes the ray_tpu_ prefix; "
                    "register the bare name — the dashboard renderer "
                    "prefixes every pushed family"))
            if not (desc or "").strip():
                violations.append(Violation(
                    "metrics/missing-help", rel, node.lineno,
                    f"{kind} family {name!r} has no description (its # HELP "
                    "line would be empty)"))
            sites.setdefault(name, []).append((rel, node.lineno, kind))
    for name, where in sorted(sites.items()):
        if len(where) > 1:
            locs = ", ".join(f"{r}:{ln}" for r, ln, _ in where)
            rel, line, _ = where[0]
            violations.append(Violation(
                "metrics/duplicate-family", rel, line,
                f"family {name!r} is constructed at {len(where)} sites "
                f"({locs}); register it once and share the instance"))
    return sites


def _scan_synthesized(root: str) -> set[str]:
    """Families synthesized as push-shaped dict literals ({"name": N,
    "kind": K, ...} — slo.py's status_metrics) rather than constructed:
    real on the wire, so rules may reference them."""
    names: set[str] = set()
    for rel, src in walk_sources(root, (".py",)):
        if "/staticcheck/" in rel:
            continue
        try:
            tree = parse(src)
        except SyntaxError:
            continue
        for node in nodes(tree):
            if not isinstance(node, ast.Dict):
                continue
            keys = {k.value for k in node.keys
                    if isinstance(k, ast.Constant)}
            if "name" not in keys or "kind" not in keys:
                continue
            for k, v in zip(node.keys, node.values):
                if (isinstance(k, ast.Constant) and k.value == "name"
                        and isinstance(v, ast.Constant)
                        and isinstance(v.value, str)):
                    names.add(v.value)
    return names


def _scan_slo_rules(root: str, registered: set[str],
                    violations: list[Violation]):
    """Both directions of rule/registry agreement.

    Forward: every family referenced by an SLO rule — any string literal
    that parses under the rule grammar — must exist.  The TSDB's runtime
    namespace (node_* gauges from metrics_snapshot, resource gauges) is
    implicitly registered; everything else must be a ctor or synthesized
    family.  Returns the set of rule-consumed families for the reverse
    pass."""
    from ray_tpu._private import slo as slo_mod

    consumed: set[str] = set()
    for rel, src in walk_sources(root, (".py",)):
        if "/staticcheck/" in rel:
            continue
        try:
            tree = parse(src)
        except SyntaxError:
            continue
        for node in nodes(tree):
            if not (isinstance(node, ast.Constant)
                    and isinstance(node.value, str)):
                continue
            for part in re.split(r"[;\n]", node.value):
                m = slo_mod._RULE_RE.match(part.strip())
                if not m:
                    continue
                try:
                    rule = slo_mod.Rule(part)
                except slo_mod.RuleError:
                    continue
                for fam in rule.families():
                    consumed.add(fam)
                    if fam in registered or fam.startswith("node_") \
                            or fam.startswith("resource_"):
                        continue
                    violations.append(Violation(
                        "metrics/slo-unknown-family", rel, node.lineno,
                        f"SLO rule {rule.name!r} references family "
                        f"{fam!r}, which no Counter/Gauge/Histogram "
                        "registers and no push path synthesizes — the "
                        "rule can never fire"))
    return consumed


def _scan_unconsumed(root: str, sites: dict, violations: list[Violation]):
    """A ctor-registered family nobody mentions anywhere else (not a
    rule, dashboard, CLI, test, or doc) is write-only telemetry."""
    mentions: dict[str, set[str]] = {name: set() for name in sites}
    for rel, src in walk_sources(root, (".py", ".md"), subdir=""):
        if "/staticcheck/" in rel:
            continue  # this checker + its allowlist don't count as use
        for name in mentions:
            if name in src:
                mentions[name].add(rel)
    for name, where in sorted(sites.items()):
        rel, line, _ = where[0]
        others = mentions[name] - {rel}
        if not others:
            violations.append(Violation(
                "metrics/family-unconsumed", rel, line,
                f"family {name!r} is registered here but consumed "
                "nowhere — no SLO rule, dashboard, CLI, test, or doc "
                "mentions it"))


def _scan_exemplars(root: str, sites: dict, violations: list[Violation]):
    """Every family in util/metrics.py's EXEMPLAR_FAMILIES tuple must be
    constructed as a Histogram somewhere in the tree: exemplar trace ids
    are banked per bucket, so a non-histogram (or never-registered)
    family in that list silently drops the "which request was the p99"
    linkage."""
    for rel, src in walk_sources(root, (".py",)):
        if not rel.endswith("util/metrics.py"):
            continue
        try:
            tree = parse(src)
        except SyntaxError:
            continue
        for node in nodes(tree):
            if not isinstance(node, ast.Assign):
                continue
            targets = [t.id for t in node.targets
                       if isinstance(t, ast.Name)]
            if "EXEMPLAR_FAMILIES" not in targets:
                continue
            if not isinstance(node.value, (ast.Tuple, ast.List)):
                continue
            for elt in node.value.elts:
                fam = _literal_str(elt)
                if fam is None:
                    continue
                where = sites.get(fam)
                if not where:
                    violations.append(Violation(
                        "metrics/exemplar-not-histogram", rel, elt.lineno,
                        f"EXEMPLAR_FAMILIES lists {fam!r}, but no "
                        "Counter/Gauge/Histogram registers it — an "
                        "exemplar-capable family must be a registered "
                        "Histogram"))
                    continue
                bad = [(r, ln, k) for r, ln, k in where
                       if k != "Histogram"]
                if bad:
                    locs = ", ".join(f"{r}:{ln} ({k})"
                                     for r, ln, k in bad)
                    violations.append(Violation(
                        "metrics/exemplar-not-histogram", rel, elt.lineno,
                        f"EXEMPLAR_FAMILIES lists {fam!r}, but it is "
                        f"constructed as a non-histogram at {locs} — "
                        "exemplars hang off histogram buckets"))


def _scan_renderer(root: str, violations: list[Violation]):
    rendered_any = False
    for rel, src in walk_sources(root, (".py",), subdir="ray_tpu/dashboard"):
        try:
            tree = parse(src)
        except SyntaxError:
            continue
        has_renderer = "_render_prometheus" in src
        if has_renderer:
            rendered_any = True
            if "# HELP" not in src or "# TYPE" not in src:
                violations.append(Violation(
                    "metrics/renderer-missing-help-type", rel, 1,
                    "_render_prometheus does not emit # HELP/# TYPE "
                    "headers"))
            if 'startswith("ray_tpu_")' not in src:
                violations.append(Violation(
                    "metrics/renderer-prefix-missing", rel, 1,
                    "_render_prometheus does not apply the ray_tpu_ prefix "
                    "to pushed families"))
        for node in nodes(tree):
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                    and node.func.id == "fam" and node.args:
                prefix = _fstring_prefix(node.args[0])
                if prefix is not None and not prefix.startswith("ray_tpu_"):
                    violations.append(Violation(
                        "metrics/unprefixed-family", rel, node.lineno,
                        f"renderer emits family starting {prefix!r} without "
                        "the ray_tpu_ prefix"))
    return rendered_any


def check(root: str) -> list[Violation]:
    violations: list[Violation] = []
    sites = _scan_registrations(root, violations)
    _scan_renderer(root, violations)
    registered = set(sites) | _scan_synthesized(root)
    _scan_slo_rules(root, registered, violations)
    _scan_unconsumed(root, sites, violations)
    _scan_exemplars(root, sites, violations)
    return violations
