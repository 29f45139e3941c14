"""Shared infrastructure for the repo-native static-analysis passes.

Every pass (analysis/locks.py, hotpath.py, drift.py, smoke.py) consumes the
same parsed `Source` objects and emits the same `Finding` records; the
runner (analysis/runner.py, CLI perf/dlint.py) applies the one suppression
convention to all of them:

    # dlint: ignore[rule] -- reason
    # dlint: ignore[rule-a,rule-b] -- reason covering both

A suppression silences findings of the named rule(s) on ITS line only — a
file- or block-wide mute does not exist by design: each finding is triaged
individually, and the written reason (mandatory; a reasonless suppression is
itself a `bad-suppression` finding) survives next to the code it excuses.
`ignore[*]` matches any rule; use it only for lines tripping several rules
for one underlying cause. Suppressions are counted and reported (JSON +
text) so a silently-growing pile of excuses is visible in review.

Annotation conventions parsed here (consumed by locks.py / hotpath.py):

    self._lock = threading.Lock()  # guards: _pending, _thread
        declares which attributes of the owning class the lock protects
    def _deliver(...):  # holds: self._lock
        declares a method that is only ever called with the lock held
    def _emit(...):  # hot-path
        marks a host-side hot function: no implicit device->host syncs
    def step(...):  # hot-path: traced
        marks a jit-traced body: additionally no trace-impure calls

All comment parsing is line-anchored on the physical source line of the
relevant AST node, so the conventions work without any tokenizer pass.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# first-party scan roots, mirroring the original perf/smoke_lint.py scope
SCAN_DIRS = ("distributed_llama_tpu", "tests", "perf", "examples")
TOP_FILES = ("bench.py", "chip_smoke.py", "launch.py", "__graft_entry__.py")

_SUPPRESS_RE = re.compile(
    r"#\s*dlint:\s*ignore\[([^\]]*)\](\s*--\s*(.*\S))?")


@dataclass
class Finding:
    """One triaged-or-triagable defect report."""

    rule: str
    path: str       # repo-relative
    line: int       # 1-based; 0 = file-level
    message: str
    suppressed: bool = False
    reason: str = ""  # the suppression's written reason, when suppressed

    def format(self) -> str:
        loc = f"{self.path}:{self.line}" if self.line else self.path
        tag = f" (suppressed: {self.reason})" if self.suppressed else ""
        return f"{loc}: [{self.rule}] {self.message}{tag}"

    def as_dict(self) -> dict:
        d = {"rule": self.rule, "path": self.path, "line": self.line,
             "message": self.message, "suppressed": self.suppressed}
        if self.suppressed:
            d["reason"] = self.reason
        return d


@dataclass
class Suppression:
    path: str
    line: int
    rules: tuple[str, ...]
    reason: str
    used: int = 0


@dataclass
class Source:
    """One parsed first-party file. `tree` is None on a syntax error (the
    compile pass reports that; AST passes skip the file)."""

    path: str          # absolute
    relpath: str
    text: str
    lines: list[str] = field(default_factory=list)
    tree: ast.AST | None = None
    suppressions: dict[int, Suppression] = field(default_factory=dict)

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ""


def repo_py_files(repo: str = REPO) -> list[str]:
    """Every first-party .py (same roots the original smoke lint scanned)."""
    out = []
    for d in SCAN_DIRS:
        for root, dirs, files in os.walk(os.path.join(repo, d)):
            dirs[:] = [x for x in dirs
                       if not x.startswith((".", "__pycache__"))]
            out.extend(os.path.join(root, f) for f in files
                       if f.endswith(".py"))
    out.extend(os.path.join(repo, f) for f in TOP_FILES
               if os.path.exists(os.path.join(repo, f)))
    return sorted(out)


def package_py_files(repo: str = REPO) -> list[str]:
    """The `distributed_llama_tpu` package only — the scope of the
    annotation-driven passes (tests/perf deliberately violate rules in
    fixtures and bench scratch code)."""
    pkg = "distributed_llama_tpu" + os.sep
    return [f for f in repo_py_files(repo)
            if os.path.relpath(f, repo).startswith(pkg)]


def _real_comments(text: str) -> list[tuple[int, str]] | None:
    """[(line, comment)] via the tokenizer, so a docstring QUOTING the
    suppression syntax is never mistaken for one; None when the file does
    not tokenize (the compile pass reports it, callers fall back to the
    line scan)."""
    import io
    import tokenize

    out = []
    try:
        for tok in tokenize.generate_tokens(io.StringIO(text).readline):
            if tok.type == tokenize.COMMENT:
                out.append((tok.start[0], tok.string))
    except (tokenize.TokenError, SyntaxError, IndentationError):
        return None
    return out


def parse_suppressions(path: str, relpath: str, lines: list[str],
                       text: str | None = None
                       ) -> tuple[dict[int, Suppression], list[Finding]]:
    """Collect `# dlint: ignore[...] -- reason` markers (real comments only).
    A marker without a written reason is a finding, not a suppression — the
    whole point of the convention is that every excuse is recorded."""
    sups: dict[int, Suppression] = {}
    findings: list[Finding] = []
    comments = _real_comments(text if text is not None
                              else "\n".join(lines))
    if comments is None:  # untokenizable: conservative line scan
        comments = list(enumerate(lines, start=1))
    for i, line in comments:
        m = _SUPPRESS_RE.search(line)
        if not m:
            continue
        rules = tuple(r.strip() for r in m.group(1).split(",") if r.strip())
        reason = (m.group(3) or "").strip()
        if not rules or not reason:
            findings.append(Finding(
                "bad-suppression", relpath, i,
                "suppression needs `# dlint: ignore[rule] -- reason` with a "
                "non-empty rule list AND a written reason"))
            continue
        sups[i] = Suppression(relpath, i, rules, reason)
    return sups, findings


def load_source(path: str, repo: str = REPO) -> Source:
    with open(path, encoding="utf-8") as fh:
        text = fh.read()
    relpath = os.path.relpath(path, repo)
    lines = text.splitlines()
    try:
        tree = ast.parse(text)
    except SyntaxError:
        tree = None  # the compile pass reports this file
    sups, bad = parse_suppressions(path, relpath, lines, text)
    src = Source(path, relpath, text, lines, tree, sups)
    # bad-suppression findings ride on the source so the runner collects
    # them exactly once per file
    src.bad_suppressions = bad  # type: ignore[attr-defined]
    return src


def load_sources(files: list[str] | None = None,
                 repo: str = REPO) -> list[Source]:
    return [load_source(f, repo) for f in (files if files is not None
                                           else repo_py_files(repo))]


def apply_suppressions(sources: list[Source],
                       findings: list[Finding]) -> list[Finding]:
    """Mark findings whose line carries a matching suppression. Returns the
    same list (mutated) for chaining; Suppression.used counts consumers."""
    by_rel = {s.relpath: s for s in sources}
    for f in findings:
        src = by_rel.get(f.path)
        if src is None:
            continue
        sup = src.suppressions.get(f.line)
        if sup is None:
            continue
        if "*" in sup.rules or f.rule in sup.rules:
            f.suppressed = True
            f.reason = sup.reason
            sup.used += 1
    return findings


def comment_on(source: Source, lineno: int) -> str:
    """The comment tail of a physical line ('' when none)."""
    line = source.line_text(lineno)
    i = line.find("#")
    return line[i:] if i != -1 else ""


def marker_on(source: Source, node: ast.AST, pattern: re.Pattern,
              look_above: int = 2) -> re.Match | None:
    """Search `pattern` in the comment of the node's def/decl line, or in up
    to `look_above` immediately preceding COMMENT-ONLY lines (the decorator /
    leading-comment zone) — a trailing comment on unrelated preceding code
    never marks the node below it."""
    start = getattr(node, "lineno", 0)
    m = pattern.search(comment_on(source, start))
    if m:
        return m
    for ln in range(start - 1, max(start - look_above - 1, 0), -1):
        text = source.line_text(ln).strip()
        if not text.startswith("#"):
            break
        m = pattern.search(text)
        if m:
            return m
    return None
