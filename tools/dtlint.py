#!/usr/bin/env python
"""dtlint — project-invariant static analysis for dt_tpu.

The reference's ``make cpplint``/``make pylint`` gate (reference
``Makefile:140-160``) for this tree: walks the repo, runs the DT001-DT007
rules (``dt_tpu/analysis/``), and reports findings as
``path:line: RULEID message [hint: ...]``.

Usage::

    python tools/dtlint.py                  # default scope, baseline applied
    python tools/dtlint.py dt_tpu/elastic   # explicit paths
    python tools/dtlint.py --select DT006   # one rule
    python tools/dtlint.py --changed        # only git-changed files
    python tools/dtlint.py --no-baseline    # full finding set
    python tools/dtlint.py --write-baseline # grandfather current findings
    python tools/dtlint.py --fix-annotations  # insert DT008's guarded-by
    python tools/dtlint.py --sarif out.sarif  # CI diff-annotation output
    python tools/dtlint.py --list-rules
    python tools/dtlint.py --explain DT016  # catalog entry + fixture pair

Exit codes: 0 clean (after baseline), 1 findings (or stale baseline
entries), 2 usage/internal error.  Per-line suppression:
``# dtlint: ignore[DT001]``.  Baseline: ``dtlint_baseline.txt`` at the
repo root — every entry needs a ``# reason:`` line.

The whole-tree result cache (``.dtlint_cache.json``) keys scanned files
by (size, mtime) and the rule engine's own sources by CONTENT digest —
editing a rule in ``dt_tpu/analysis/`` invalidates the cache even when
size and mtime are preserved (r12).  ``--json`` appends one
``{"rule_timings_ms": ..., "from_cache": ...}`` summary object after the
findings (``from_cache``: the verdict was read back, no rule ran).
"""

import argparse
import hashlib
import json
import os
import re
import subprocess
import sys
import types

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _import_analysis():
    """Import dt_tpu.analysis WITHOUT executing dt_tpu/__init__.py (which
    pulls the ops surface and therefore jax): register a path-only shim
    for the parent package first.  Under pytest dt_tpu is already real
    and the shim is skipped."""
    if "dt_tpu" not in sys.modules:
        if _ROOT not in sys.path:
            sys.path.insert(0, _ROOT)
        shim = types.ModuleType("dt_tpu")
        shim.__path__ = [os.path.join(_ROOT, "dt_tpu")]
        sys.modules["dt_tpu"] = shim
    import dt_tpu.analysis as analysis
    return analysis


_CACHE_NAME = ".dtlint_cache.json"


def _tree_signature(root, relpaths):
    return {p: list(os.stat(os.path.join(root, p))[6:9:2])  # size, mtime
            for p in relpaths}


def _analysis_digest():
    """Content digest of the rule engine's own EXECUTING sources — the
    ``dt_tpu/analysis/*.py`` under ``_ROOT`` that ``_import_analysis``
    actually loads (NOT the linted ``--root``'s copies, which may not
    even exist), plus this CLI.  (size, mtime) is not enough for these:
    an edited rule with preserved stat metadata (same length, restored
    mtime — editors and checkouts both do this) would serve stale
    verdicts for the whole tree."""
    import glob
    h = hashlib.sha256()
    srcs = sorted(glob.glob(os.path.join(_ROOT, "dt_tpu", "analysis",
                                         "*.py")))
    srcs.append(os.path.join(_ROOT, "tools", "dtlint.py"))
    for p in srcs:
        try:
            with open(p, "rb") as f:
                h.update(os.path.relpath(p, _ROOT).encode() + b"\0")
                h.update(f.read())
                h.update(b"\0")
        except OSError:
            h.update(b"missing\0")
    return h.hexdigest()


def _cached_findings(analysis, root, paths, select):
    """Whole-tree result cache: reused only when every linted file AND
    every cross-file input (PARITY.md, the DT005 registry in
    dt_tpu/config.py) is byte-identical by (size, mtime) AND the rule
    engine's own sources hash to the same content digest — cross-file
    rules make per-file caching unsound, and stat metadata alone is
    unsound for the code that computes the verdicts."""
    from dt_tpu.analysis.engine import iter_python_files
    relpaths = iter_python_files(root, paths)
    sig = {"paths": list(paths), "select": sorted(select or []),
           "files": _tree_signature(root, relpaths),
           "engine_digest": _analysis_digest()}
    # non-linted cross-file inputs: PARITY.md (DT007), the env registry
    # (DT005), and the r17 generated wire-command catalog (DT012) —
    # editing any of them must invalidate the whole-tree verdict
    for extra in ("PARITY.md", "dt_tpu/config.py",
                  "docs/protocol_commands.md"):
        if os.path.exists(os.path.join(root, extra)):
            sig["files"][extra] = _tree_signature(root, [extra])[extra]
    cache_path = os.path.join(root, _CACHE_NAME)
    try:
        with open(cache_path) as f:
            cached = json.load(f)
        if cached.get("sig") == sig:
            return ([analysis.Finding(**fi) for fi in cached["findings"]],
                    sig, cached.get("timings") or {})
    except (OSError, ValueError, TypeError, KeyError):
        pass
    return None, sig, {}


def _store_cache(root, sig, findings, timings):
    try:
        with open(os.path.join(root, _CACHE_NAME), "w") as f:
            json.dump({"sig": sig, "timings": timings,
                       "findings": [vars(fi) for fi in findings]}, f)
    except OSError:
        pass


def _changed_paths(root):
    """Repo-relative .py files touched vs HEAD (worktree diff + staged +
    untracked) — the ``--changed`` fast-local-loop scope.  Intersected
    with the DEFAULT lint scope: a changed file under ``tests/`` (e.g.
    a rule fixture that violates rules on purpose) stays excluded,
    exactly as in a full run."""
    from dt_tpu.analysis.engine import DEFAULT_PATHS

    def git(*args):
        try:
            proc = subprocess.run(["git", *args], cwd=root,
                                  capture_output=True, text=True,
                                  timeout=30)
        except (OSError, subprocess.TimeoutExpired):
            return None
        return proc.stdout if proc.returncode == 0 else None

    # `git diff` reports paths relative to the repo TOPLEVEL; when
    # --root is a subdirectory of a larger checkout, re-relativize
    # through the show-prefix instead of silently matching nothing.
    # `git ls-files` is already CWD-relative (= root-relative) — only
    # the diff output carries the prefix.
    prefix = git("rev-parse", "--show-prefix")
    if prefix is None:
        return None
    prefix = prefix.strip()
    out = set()
    for args, strip in ((("diff", "--name-only", "HEAD"), prefix),
                        (("ls-files", "--others",
                          "--exclude-standard"), "")):
        listed = git(*args)
        if listed is None:
            return None
        for ln in listed.splitlines():
            ln = ln.strip()
            if ln.startswith(strip):
                out.add(ln[len(strip):])
    in_scope = tuple(p if p.endswith(".py") else p.rstrip("/") + "/"
                     for p in DEFAULT_PATHS)
    return sorted(
        p for p in out
        if p.endswith(".py") and os.path.exists(os.path.join(root, p))
        and (p in in_scope or p.startswith(in_scope)))


def _fix_annotations(root, paths, baseline_keys=frozenset()):
    """Insert the ``# guarded-by: <lock>`` comments DT008 suggests, at
    each racy attribute's ``__init__`` assignment line.  Idempotent
    (re-running adds nothing), preserves existing trailing comments
    (the annotation appends after them — DT006's regex accepts that
    form), and never annotates a race the user suppressed inline or
    grandfathered.  Returns the number of lines edited."""
    from dt_tpu.analysis import rules_flow
    edits = 0
    by_file = {}
    for s in rules_flow.collect_suggestions(root, paths,
                                            baseline_keys=baseline_keys):
        by_file.setdefault(s["path"], []).append(s)
    for rel, suggestions in sorted(by_file.items()):
        full = os.path.join(root, rel)
        with open(full, encoding="utf-8") as f:
            lines = f.read().splitlines(keepends=True)
        changed = False
        for s in suggestions:
            i = s["line"] - 1
            if not (0 <= i < len(lines)):
                continue
            line = lines[i]
            if "guarded-by:" in line:
                continue  # already annotated (idempotence)
            body = line.rstrip("\n")
            nl = line[len(body):]
            # DT006's regex binds the annotation to the FIRST
            # `self.<attr>` on the line — refuse anchors where that is
            # not the racy attribute (multi-target assigns), and lines
            # a trailing comment would break (backslash continuations)
            first = re.search(r"self\.(\w+)", body)
            if first is None or first.group(1) != s["attr"] or \
                    body.rstrip().endswith("\\"):
                print(f"{rel}:{s['line']}: cannot auto-annotate "
                      f"'{s['cls']}.{s['attr']}' here — add "
                      f"'# guarded-by: {s['lock']}' by hand")
                continue
            lines[i] = f"{body}  # guarded-by: {s['lock']}{nl}"
            print(f"{rel}:{s['line']}: annotated "
                  f"'{s['cls']}.{s['attr']}' guarded-by: {s['lock']}")
            edits += 1
            changed = True
        if changed:
            with open(full, "w", encoding="utf-8") as f:
                f.write("".join(lines))
    return edits


def _write_sarif(path, analysis, reported):
    """SARIF 2.1.0 log of the post-baseline findings (r17) — the
    interchange format CI uses to annotate diffs (GitHub code scanning,
    ``sarif-tools``).  One run, one rule table (id + short description
    from each rule's docstring), one result per finding with a
    ``physicalLocation`` region; byte-deterministic (sort_keys) like
    every other serialized surface in this repo."""
    rules = analysis.all_rules()
    doc = {
        "$schema": ("https://raw.githubusercontent.com/oasis-tcs/"
                    "sarif-spec/master/Schemata/sarif-schema-2.1.0.json"),
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "dtlint",
                "rules": [{
                    "id": r.id,
                    "name": r.name,
                    "shortDescription": {
                        "text": (r.__doc__ or r.name)
                        .strip().splitlines()[0]},
                    # repo-relative, anchor-free: heading anchors vary
                    # by renderer, a dead link helps nobody
                    "helpUri": "docs/dtlint_rules.md",
                } for r in rules],
            }},
            "results": [{
                "ruleId": f.rule,
                "level": "error",
                "message": {"text": f.message
                            + (f"  [hint: {f.hint}]" if f.hint else "")},
                "locations": [{
                    "physicalLocation": {
                        "artifactLocation": {"uri": f.path,
                                             "uriBaseId": "SRCROOT"},
                        "region": {"startLine": max(f.line, 1),
                                   "snippet": {"text": f.snippet}},
                    }}],
            } for f in reported],
        }],
    }
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True)


def _explain(root, analysis, ids):
    """Print each rule's ``docs/dtlint_rules.md`` catalog entry followed
    by its checked-in bad/good fixture pair — the offline "why is this
    flagged, what does the fix look like" card.  Unknown ids exit 2;
    missing docs/fixtures degrade to a note (a pruned tree — e.g. a
    tests/-less deployment — still explains from the rule docstring)."""
    import glob
    rules = {r.id: r for r in analysis.all_rules()}
    unknown = [i for i in ids if i not in rules]
    if unknown:
        print(f"dtlint: unknown rule id(s): {', '.join(sorted(unknown))} "
              f"(see --list-rules)", file=sys.stderr)
        return 2
    sections = {}
    doc_path = os.path.join(root, "docs", "dtlint_rules.md")
    try:
        with open(doc_path, encoding="utf-8") as f:
            text = f.read()
        for m in re.finditer(r"(?ms)^## (DT\d+)[^\n]*\n.*?(?=^## |\Z)",
                             text):
            sections[m.group(1)] = m.group(0).rstrip()
    except OSError:
        pass
    for rid in sorted(ids):
        r = rules[rid]
        print(f"{r.id} {r.name}: "
              f"{(r.__doc__ or '').strip().splitlines()[0]}\n")
        print(sections.get(rid,
                           f"(no catalog entry for {rid} in {doc_path})"))
        for kind in ("bad", "good"):
            pat = os.path.join(root, "tests", "dtlint_fixtures", "**",
                               f"{rid.lower()}_{kind}.py")
            hits = sorted(glob.glob(pat, recursive=True))
            if not hits:
                print(f"\n--- {kind} example: (no fixture "
                      f"{rid.lower()}_{kind}.py in this tree) ---")
                continue
            for p in hits:
                print(f"\n--- {kind} example: {os.path.relpath(p, root)} "
                      f"---")
                with open(p, encoding="utf-8") as f:
                    print(f.read().rstrip())
        print()
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="dtlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: dt_tpu tools "
                         "examples __graft_entry__.py)")
    ap.add_argument("--root", default=_ROOT)
    ap.add_argument("--baseline", default=None,
                    help="baseline file (default: <root>/dtlint_baseline"
                         ".txt)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding, ignoring the baseline")
    ap.add_argument("--write-baseline", action="store_true",
                    help="grandfather the current findings into the "
                         "baseline file and exit 0")
    ap.add_argument("--select", action="append", default=None,
                    metavar="RULE", help="run only these rule ids")
    ap.add_argument("--changed", action="store_true",
                    help="lint only files changed vs git HEAD "
                         "(+ staged/untracked) — the fast local loop")
    ap.add_argument("--fix-annotations", action="store_true",
                    help="insert the '# guarded-by:' comments DT008 "
                         "suggests (idempotent), then exit")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--explain", action="append", default=None,
                    metavar="RULE",
                    help="print the rule's docs-catalog entry + its "
                         "bad/good fixture pair, then exit (repeatable; "
                         "unions with --select; exit 2 on unknown ids)")
    ap.add_argument("--sarif", default=None, metavar="PATH",
                    help="also write the post-baseline findings as a "
                         "SARIF 2.1.0 log (CI diff annotation); exit "
                         "code is unchanged")
    ap.add_argument("--json", action="store_true",
                    help="one JSON object per finding, then one "
                         "rule_timings_ms summary object")
    ap.add_argument("--no-cache", action="store_true")
    args = ap.parse_args(argv)

    analysis = _import_analysis()
    if args.list_rules:
        for r in analysis.all_rules():
            print(f"{r.id} {r.name}: {(r.__doc__ or '').strip().splitlines()[0]}")
        return 0

    root = os.path.abspath(args.root)
    if args.explain:
        ids = list(dict.fromkeys(args.explain + (args.select or [])))
        return _explain(root, analysis, ids)
    paths = args.paths or None
    if args.changed and args.paths:
        print("dtlint: --changed and explicit paths are mutually "
              "exclusive (pick one scope)", file=sys.stderr)
        return 2
    if args.changed:
        changed = _changed_paths(root)
        if changed is None:
            print("dtlint: --changed needs a git checkout",
                  file=sys.stderr)
            return 2
        if not changed:
            print("dtlint: no changed python files", file=sys.stderr)
            return 0
        paths = changed
    select = set(args.select) if args.select else None
    from dt_tpu.analysis.engine import DEFAULT_PATHS
    eff_paths = list(paths if paths is not None else DEFAULT_PATHS)

    if args.fix_annotations:
        bl = args.baseline or os.path.join(root, "dtlint_baseline.txt")
        keys = frozenset(analysis.Baseline.load(bl).entries)
        n = _fix_annotations(root, eff_paths, baseline_keys=keys)
        print(f"dtlint: {n} annotation(s) inserted", file=sys.stderr)
        return 0

    findings = None
    sig = None
    timings = {}
    # the result cache is single-slot: reserve it for the canonical
    # full-default run (the pre-commit gate) so a fast --changed /
    # --select loop doesn't keep evicting the expensive entry
    cacheable = not args.no_cache and not args.changed and \
        paths is None and select is None
    if cacheable:
        findings, sig, timings = _cached_findings(analysis, root,
                                                  eff_paths, select)
    from_cache = findings is not None
    if not from_cache:
        timings = {}
        findings = analysis.run(root, paths=eff_paths, select=select,
                                timings=timings)
        if sig is not None:
            _store_cache(root, sig, findings, timings)

    baseline_path = args.baseline or os.path.join(root,
                                                  "dtlint_baseline.txt")
    if args.write_baseline:
        if args.changed or args.paths or select:
            # a scoped run only produced the scoped findings — saving
            # them would silently drop every out-of-scope grandfather
            # (and its reason line) from the baseline
            print("dtlint: --write-baseline needs the full default "
                  "run (no --changed / paths / --select)",
                  file=sys.stderr)
            return 2
        analysis.Baseline.load(baseline_path).save(baseline_path, findings)
        print(f"wrote {len(set(f.key for f in findings))} baseline "
              f"entries to {baseline_path}")
        return 0

    baseline = analysis.Baseline() if args.no_baseline else \
        analysis.Baseline.load(baseline_path)
    reported = [f for f in findings if not baseline.covers(f)]
    # stale-entry detection is only sound over the FULL run (default
    # path scope, every rule): a scoped run — --changed, explicit
    # paths, --select — never produces the findings that keep
    # out-of-scope grandfathers alive, and flagging them stale would
    # fail every scoped run under a non-empty baseline
    full_scope = select is None and \
        set(DEFAULT_PATHS) <= {p.rstrip("/") for p in eff_paths}
    stale = [] if (args.no_baseline or not full_scope) else \
        baseline.stale(findings)

    if args.sarif:
        _write_sarif(args.sarif, analysis, reported)
    for f in reported:
        print(json.dumps(vars(f)) if args.json else f.render())
    if args.json:
        print(json.dumps({"rule_timings_ms":
                          {k: round(v, 2)
                           for k, v in sorted(timings.items())},
                          "from_cache": from_cache}))
    for key in stale:
        print(f"{baseline_path}: stale baseline entry (fixed or moved — "
              f"delete it): {' | '.join(key)}")
    n_base = sum(1 for f in findings if baseline.covers(f))
    if reported or stale:
        print(f"dtlint: {len(reported)} finding(s), {n_base} baselined, "
              f"{len(stale)} stale baseline entr(y/ies)", file=sys.stderr)
        return 1
    print(f"dtlint: clean ({n_base} baselined finding(s), "
          f"{len(findings) - n_base} live)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
