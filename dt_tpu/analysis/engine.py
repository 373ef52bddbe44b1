"""dtlint rule engine: findings, suppressions, baseline, and the runner.

The reference enforced its project invariants with ``make cpplint`` /
``make pylint`` (reference ``Makefile:140-160``, ``tests/ci_build/``);
dt_tpu's hardest-won invariants are TPU/jax gotchas and concurrency
discipline that no stock linter knows about, so this engine hosts
project-specific rules (:mod:`dt_tpu.analysis.rules_tpu`,
:mod:`dt_tpu.analysis.rules_project`) instead.  Pure stdlib ``ast`` — the
linter must run (and be imported) without jax or a backend.

Concepts
--------

- :class:`Finding`: one report — rule id, file:line, message, fix hint,
  and the stripped source line (``snippet``) it anchors to.
- Suppression: a trailing ``# dtlint: ignore[DT001]`` (comma-separated
  ids, or bare ``ignore`` for all rules) silences findings reported on
  that physical line.
- Baseline: a checked-in file of grandfathered findings keyed by
  ``(rule, path, snippet)`` — line-number drift never invalidates an
  entry, and fixing the flagged line retires it.  ``check_baseline``
  reports entries that no longer match anything (stale grandfathers must
  be deleted, keeping the file honest).
"""

from __future__ import annotations

import ast
import dataclasses
import io
import os
import re
import tokenize
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

_SUPPRESS_RE = re.compile(
    r"#\s*dtlint:\s*ignore(?:\[(?P<rules>[A-Z0-9,\s]+)\])?")


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str          # repo-relative, '/'-separated
    line: int          # 1-indexed
    message: str
    hint: str = ""
    snippet: str = ""  # stripped source line (baseline key)

    @property
    def key(self) -> Tuple[str, str, str]:
        return (self.rule, self.path, self.snippet)

    def render(self) -> str:
        s = f"{self.path}:{self.line}: {self.rule} {self.message}"
        if self.hint:
            s += f"  [hint: {self.hint}]"
        return s


class FileContext:
    """One parsed source file handed to every rule's ``check_file``."""

    def __init__(self, root: str, relpath: str, source: str):
        self.root = root
        self.path = relpath.replace(os.sep, "/")
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=relpath)
        self._suppressions = _collect_suppressions(source)

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def suppressed(self, lineno: int, rule: str) -> bool:
        rules = self._suppressions.get(lineno)
        return rules is not None and ("*" in rules or rule in rules)

    def finding(self, rule: "Rule", node_or_line, message: str,
                hint: Optional[str] = None) -> Finding:
        line = getattr(node_or_line, "lineno", node_or_line)
        return Finding(rule=rule.id, path=self.path, line=line,
                       message=message,
                       hint=rule.hint if hint is None else hint,
                       snippet=self.line_text(line))


class ProjectContext:
    """Cross-file state: rules stash per-file observations here during
    ``check_file`` and emit aggregate findings from ``finalize`` (e.g.
    DT005's dead-registry-entry check needs every file's env reads)."""

    def __init__(self, root: str, paths: Sequence[str]):
        self.root = root
        self.paths = list(paths)
        self.data: Dict[str, object] = {}


class Rule:
    """Base class; subclasses set ``id``/``name``/``hint`` and override
    ``check_file`` (per file) and/or ``finalize`` (once, after all
    files)."""

    id: str = ""
    name: str = ""
    hint: str = ""

    def applies_to(self, relpath: str) -> bool:
        return True

    def check_file(self, ctx: FileContext,
                   project: ProjectContext) -> Iterable[Finding]:
        return ()

    def finalize(self, project: ProjectContext) -> Iterable[Finding]:
        return ()


def _collect_suppressions(source: str) -> Dict[int, Set[str]]:
    """{lineno: {"DT001", ...} or {"*"}} from ``# dtlint: ignore[...]``
    comments, via the tokenizer (string literals containing the marker
    don't count)."""
    out: Dict[int, Set[str]] = {}
    try:
        tokens = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = _SUPPRESS_RE.search(tok.string)
            if not m:
                continue
            rules = m.group("rules")
            ids = {r.strip() for r in rules.split(",")} if rules else {"*"}
            out.setdefault(tok.start[0], set()).update(ids)
    except tokenize.TokenError:
        pass
    return out


# ---------------------------------------------------------------------------
# file walking
# ---------------------------------------------------------------------------

#: default lint scope, relative to the repo root.  tests/ is excluded on
#: purpose: fixtures under tests/dtlint_fixtures/ violate rules by design,
#: and test code freely pokes private state the rules guard.
DEFAULT_PATHS = ("dt_tpu", "tools", "examples", "__graft_entry__.py")

_SKIP_DIRS = {"__pycache__", ".git", ".dtlint_cache", "node_modules"}


def iter_python_files(root: str, paths: Sequence[str]) -> List[str]:
    """Repo-relative paths of every .py file under ``paths`` (files or
    directories), sorted for deterministic output."""
    found: Set[str] = set()
    for p in paths:
        full = os.path.join(root, p)
        if os.path.isfile(full) and p.endswith(".py"):
            found.add(os.path.relpath(full, root))
        elif os.path.isdir(full):
            for dirpath, dirnames, filenames in os.walk(full):
                dirnames[:] = sorted(d for d in dirnames
                                     if d not in _SKIP_DIRS)
                for fn in filenames:
                    if fn.endswith(".py"):
                        found.add(os.path.relpath(
                            os.path.join(dirpath, fn), root))
    return sorted(f.replace(os.sep, "/") for f in found)


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------


class Baseline:
    """Grandfathered findings.  File format, one entry per line::

        # reason: why this finding is acceptable (required, checked)
        DT004\ttools/foo.py\tjax.block_until_ready(loss)

    Tab-separated ``rule<TAB>path<TAB>snippet``; each entry MUST be
    preceded by a ``# reason:`` comment — an undocumented grandfather is
    a parse error, which is the point."""

    def __init__(self, entries: Optional[Dict[Tuple[str, str, str], str]]
                 = None):
        self.entries = dict(entries or {})

    @classmethod
    def load(cls, path: str) -> "Baseline":
        entries: Dict[Tuple[str, str, str], str] = {}
        if not os.path.exists(path):
            return cls(entries)
        reason = None
        with open(path) as f:
            for i, raw in enumerate(f, 1):
                line = raw.rstrip("\n")
                if not line.strip():
                    reason = None
                    continue
                if line.lstrip().startswith("#"):
                    m = re.match(r"\s*#\s*reason:\s*(.+)", line)
                    if m:
                        reason = m.group(1).strip()
                    continue
                parts = line.split("\t")
                if len(parts) != 3:
                    raise ValueError(
                        f"{path}:{i}: baseline entries are "
                        f"rule<TAB>path<TAB>snippet, got {line!r}")
                if not reason:
                    raise ValueError(
                        f"{path}:{i}: baseline entry has no preceding "
                        f"'# reason:' comment — document why "
                        f"{parts[0]} in {parts[1]} is grandfathered")
                entries[tuple(parts)] = reason
                reason = None
        return cls(entries)

    def save(self, path: str, findings: Iterable[Finding],
             reasons: Optional[Dict[Tuple[str, str, str], str]] = None
             ) -> None:
        reasons = reasons or {}
        lines = ["# dtlint baseline — grandfathered findings.",
                 "# Every entry needs a '# reason:' line; delete entries "
                 "as the findings are fixed.", ""]
        for f in sorted(set(fi.key for fi in findings)):
            reason = reasons.get(f) or self.entries.get(f) \
                or "TODO: document why this is grandfathered"
            lines.append(f"# reason: {reason}")
            lines.append("\t".join(f))
            lines.append("")
        with open(path, "w") as fh:
            fh.write("\n".join(lines))

    def covers(self, finding: Finding) -> bool:
        return finding.key in self.entries

    def stale(self, findings: Iterable[Finding]) -> List[Tuple[str, ...]]:
        live = {f.key for f in findings}
        return sorted(k for k in self.entries if k not in live)


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------


def run(root: str, paths: Optional[Sequence[str]] = None,
        rules: Optional[Sequence[Rule]] = None,
        select: Optional[Set[str]] = None,
        timings: Optional[Dict[str, float]] = None) -> List[Finding]:
    """Lint ``paths`` under ``root`` with ``rules``; returns ALL findings
    (pre-baseline), sorted (path, line, rule) — deterministic across
    runs.  Suppressed lines are dropped here; baseline filtering is the
    caller's (so `--write-baseline` sees the full set).  ``timings``,
    when given, is filled with cumulative per-rule wall milliseconds
    (``check_file`` + ``finalize`` — the ``--json`` CLI reports it)."""
    import time as _time
    from dt_tpu.analysis import all_rules
    paths = list(paths if paths is not None else DEFAULT_PATHS)
    active = [r for r in (rules if rules is not None else all_rules())
              if not select or r.id in select]
    project = ProjectContext(root, paths)
    findings: List[Finding] = []
    contexts: Dict[str, FileContext] = {}

    def timed(rule: Rule, it: Iterable[Finding]) -> List[Finding]:
        if timings is None:
            return list(it)
        t0 = _time.perf_counter()
        out = list(it)
        timings[rule.id] = timings.get(rule.id, 0.0) + \
            (_time.perf_counter() - t0) * 1e3
        return out

    for rel in iter_python_files(root, paths):
        try:
            with open(os.path.join(root, rel), encoding="utf-8") as f:
                source = f.read()
            ctx = FileContext(root, rel, source)
        except (SyntaxError, UnicodeDecodeError, OSError) as e:
            findings.append(Finding(
                rule="DT000", path=rel.replace(os.sep, "/"), line=1,
                message=f"unparseable: {e}", snippet=""))
            continue
        contexts[ctx.path] = ctx
        for rule in active:
            if not rule.applies_to(ctx.path):
                continue
            for f in timed(rule, rule.check_file(ctx, project)):
                if not ctx.suppressed(f.line, f.rule):
                    findings.append(f)
    for rule in active:
        for f in timed(rule, rule.finalize(project)):
            # finalize findings honor suppressions too, when they anchor
            # to a file this run parsed (e.g. a registry line in
            # config.py); non-Python anchors like PARITY.md have no
            # comment syntax to suppress with
            ctx = contexts.get(f.path)
            if ctx is not None and ctx.suppressed(f.line, f.rule):
                continue
            findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    return findings
