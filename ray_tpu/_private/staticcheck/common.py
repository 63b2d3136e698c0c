"""Shared infrastructure for the static-analysis passes.

Everything here is stdlib-only and jax-free: ``rtpu check`` must run in
well under ten seconds with no cluster and no accelerator runtime.  A
pass is a function ``check(root) -> list[Violation]`` where ``root`` is
a repo root (a directory containing a ``ray_tpu/`` tree) — passing a
fixture tree instead of the real repo is how the checker tests itself.
"""

from __future__ import annotations

import ast
import bisect
import fnmatch
import functools
import os
from dataclasses import dataclass, field


def repo_root() -> str:
    """The repo root this package was imported from (…/ray_tpu/../)."""
    here = os.path.dirname(os.path.abspath(__file__))  # …/ray_tpu/_private/staticcheck
    return os.path.dirname(os.path.dirname(os.path.dirname(here)))


@dataclass(frozen=True)
class Violation:
    """One finding: a rule broken at a specific source location.

    ``rule`` is ``<pass>/<kind>`` (e.g. ``drift/opcode``); allowlist
    entries match on it plus the path and a message substring.
    """

    rule: str
    path: str  # relative to root, forward slashes
    line: int
    message: str

    def format(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


@dataclass(frozen=True)
class Allow:
    """One allowlist entry.  ``reason`` is mandatory and must say *why*
    the finding is acceptable — a bare suppression is itself a check
    failure (see ``validate_allowlist``)."""

    rule: str  # exact rule, or a fnmatch pattern like "locks/*"
    path: str  # fnmatch pattern on the relative path
    match: str  # substring that must occur in the violation message ("" = any)
    reason: str

    def covers(self, v: Violation) -> bool:
        return (fnmatch.fnmatchcase(v.rule, self.rule)
                and fnmatch.fnmatchcase(v.path, self.path)
                and (not self.match or self.match in v.message))


@dataclass
class Report:
    violations: list[Violation] = field(default_factory=list)
    suppressed: list[tuple[Violation, Allow]] = field(default_factory=list)
    unused_allows: list[Allow] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.violations


def apply_allowlist(violations: list[Violation],
                    allows: list[Allow]) -> Report:
    report = Report()
    used: set[int] = set()
    for v in violations:
        hit = next((a for a in allows if a.covers(v)), None)
        if hit is None:
            report.violations.append(v)
        else:
            report.suppressed.append((v, hit))
            used.add(id(hit))
    report.unused_allows = [a for a in allows if id(a) not in used]
    return report


def validate_allowlist(allows: list[Allow]) -> list[str]:
    """Every entry must carry a real reason string (the acceptance bar
    for shipping a suppression instead of a fix)."""
    errors = []
    for a in allows:
        if not (a.reason or "").strip():
            errors.append(f"allowlist entry {a.rule!r} on {a.path!r} has no reason")
    return errors


def walk_sources(root: str, exts: tuple[str, ...],
                 subdir: str = "ray_tpu"):
    """Yield ``(relpath, text)`` for matching sources under root/subdir."""
    base = os.path.join(root, subdir)
    for dirpath, dirnames, files in os.walk(base):
        dirnames[:] = [d for d in dirnames
                       if d not in ("__pycache__", "_build", ".git")]
        for f in sorted(files):
            if f.endswith(exts):
                path = os.path.join(dirpath, f)
                rel = os.path.relpath(path, root).replace(os.sep, "/")
                with open(path, errors="replace") as fh:
                    yield rel, fh.read()


@functools.lru_cache(maxsize=None)
def parse(src: str) -> ast.Module:
    """``ast.parse``, once a source text however many scans read it (a
    scan only reads its tree; a SyntaxError is raised to each)."""
    return ast.parse(src)


@functools.lru_cache(maxsize=None)
def nodes(tree: ast.Module) -> tuple[ast.AST, ...]:
    """``ast.walk`` of a whole module from ``parse``, walked once."""
    return tuple(ast.walk(tree))


def read_source(root: str, rel: str) -> str | None:
    """Read one file by repo-relative path; None if absent (fixture
    trees carry only the files their pass needs)."""
    path = os.path.join(root, *rel.split("/"))
    if not os.path.exists(path):
        return None
    with open(path, errors="replace") as fh:
        return fh.read()


class LineIndex:
    """Offset -> 1-based line number for regex matches over whole files."""

    def __init__(self, text: str):
        self._starts = [0]
        for i, ch in enumerate(text):
            if ch == "\n":
                self._starts.append(i + 1)

    def line(self, offset: int) -> int:
        return bisect.bisect_right(self._starts, offset)


def strip_cc_noise(text: str) -> str:
    """Blank out C++ comments and string/char literals, preserving
    offsets and newlines, so regexes over the remainder can't match
    inside prose or log strings."""
    out = list(text)
    i, n = 0, len(text)
    while i < n:
        ch = text[i]
        nxt = text[i + 1] if i + 1 < n else ""
        if ch == "/" and nxt == "/":
            while i < n and text[i] != "\n":
                out[i] = " "
                i += 1
        elif ch == "/" and nxt == "*":
            out[i] = out[i + 1] = " "
            i += 2
            while i < n and not (text[i] == "*" and i + 1 < n
                                 and text[i + 1] == "/"):
                if text[i] != "\n":
                    out[i] = " "
                i += 1
            if i < n:
                out[i] = " "
                if i + 1 < n:
                    out[i + 1] = " "
                i += 2
        elif ch in ("\"", "'"):
            quote = ch
            out[i] = " "
            i += 1
            while i < n and text[i] != quote:
                if text[i] == "\\":
                    out[i] = " "
                    i += 1
                    if i < n and text[i] != "\n":
                        out[i] = " "
                    i += 1
                    continue
                if text[i] != "\n":
                    out[i] = " "
                i += 1
            if i < n:
                out[i] = " "
                i += 1
        else:
            i += 1
    return "".join(out)
