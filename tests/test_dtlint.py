"""dtlint — the repo's own invariants, enforced in tier-1.

Covers: the repo-wide zero-finding gate (with the checked-in baseline),
per-rule fixture pairs (bad fires / good silent), determinism, the
suppression and baseline round-trips, and the acceptance scenario of
un-guarding a field in a fixture copy of the real scheduler.
"""

import os
import subprocess
import sys

import pytest

from dt_tpu.analysis import Baseline, all_rules, run
from dt_tpu.analysis.engine import DEFAULT_PATHS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(ROOT, "tests", "dtlint_fixtures")


def _lint(paths, select=None, root=FIXTURES):
    return run(root, paths=paths, select={select} if select else None)


# ---------------------------------------------------------------------------
# the tier-1 gate: the repo itself is clean
# ---------------------------------------------------------------------------


def test_repo_is_clean_after_baseline():
    findings = run(ROOT, paths=DEFAULT_PATHS)
    baseline = Baseline.load(os.path.join(ROOT, "dtlint_baseline.txt"))
    live = [f for f in findings if not baseline.covers(f)]
    assert not live, "non-baselined dtlint findings:\n" + \
        "\n".join(f.render() for f in live)
    stale = baseline.stale(findings)
    assert not stale, f"stale baseline entries (delete them): {stale}"


def test_two_runs_identical_ordering():
    a = run(ROOT, paths=DEFAULT_PATHS)
    b = run(ROOT, paths=DEFAULT_PATHS)
    assert [f.render() for f in a] == [f.render() for f in b]


def test_cli_exits_zero(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "dtlint.py"),
         "--no-cache"],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr


# ---------------------------------------------------------------------------
# per-rule fixture pairs
# ---------------------------------------------------------------------------

_PAIRS = [
    ("DT001", "dt_tpu/dt001_bad.py", "dt_tpu/dt001_good.py"),
    ("DT002", "dt_tpu/ops/dt002_bad.py", "dt_tpu/ops/dt002_good.py"),
    ("DT003", "dt_tpu/dt003_bad.py", "dt_tpu/dt003_good.py"),
    ("DT004", "tools/dt004_bad.py", "tools/dt004_good.py"),
    ("DT005", "dt_tpu/dt005_bad.py", "dt_tpu/dt005_good.py"),
    ("DT006", "dt_tpu/dt006_bad.py", "dt_tpu/dt006_good.py"),
    ("DT007", "dt_tpu/dt007_bad.py", "dt_tpu/dt007_good.py"),
    ("DT008", "dt_tpu/dt008_bad.py", "dt_tpu/dt008_good.py"),
    ("DT009", "dt_tpu/dt009_bad.py", "dt_tpu/dt009_good.py"),
    ("DT010", "dt_tpu/dt010_bad.py", "dt_tpu/dt010_good.py"),
    ("DT011", "dt_tpu/dt011_bad.py", "dt_tpu/dt011_good.py"),
    ("DT013", "dt_tpu/dt013_bad.py", "dt_tpu/dt013_good.py"),
    ("DT014", "dt_tpu/dt014_bad.py", "dt_tpu/dt014_good.py"),
    ("DT015", "dt_tpu/dt015_bad.py", "dt_tpu/dt015_good.py"),
    ("DT016", "dt_tpu/training/dt016_bad.py",
     "dt_tpu/training/dt016_good.py"),
    ("DT017", "dt_tpu/dt017_bad.py", "dt_tpu/dt017_good.py"),
]


@pytest.mark.parametrize("rule,bad,good", _PAIRS)
def test_rule_fires_on_bad_and_not_on_good(rule, bad, good):
    bad_findings = _lint([bad], select=rule)
    assert any(f.rule == rule for f in bad_findings), \
        f"{rule} did not fire on {bad}"
    good_findings = _lint([good], select=rule)
    assert not good_findings, \
        f"{rule} false positives on {good}:\n" + \
        "\n".join(f.render() for f in good_findings)


def test_dt001_flags_both_tiling_and_unsigned_reduction():
    msgs = [f.message for f in _lint(["dt_tpu/dt001_bad.py"],
                                     select="DT001")]
    assert any("BlockSpec" in m for m in msgs), msgs
    assert any("unsigned" in m for m in msgs), msgs


def test_dt005_dead_entry_arm(tmp_path):
    """Dead-entry findings only fire on a full-default-scope run: build a
    tree whose registry declares DT_DECLARED but where nothing reads it."""
    root = tmp_path / "dead"
    (root / "dt_tpu").mkdir(parents=True)
    for name in ("config.py", "dt005_dead.py"):
        (root / "dt_tpu" / name).write_text(
            open(os.path.join(FIXTURES, "dt_tpu", name)).read())
    findings = run(str(root), paths=DEFAULT_PATHS, select={"DT005"})
    assert any("dead registry entry" in f.message and
               "DT_DECLARED" in f.message for f in findings), findings


def test_dt005_dead_entry_arm_skipped_on_path_subset():
    """Linting a subset must NOT report knobs whose readers are merely
    outside the subset (the `dtlint dt_tpu/elastic`-style invocation)."""
    findings = _lint(["dt_tpu/dt005_dead.py"], select="DT005")
    assert not findings, [f.render() for f in findings]


def test_dt006_closure_does_not_inherit_lock():
    findings = _lint(["dt_tpu/dt006_bad.py"], select="DT006")
    # both the plain unguarded read and the under-lock-defined closure
    assert len(findings) >= 2, [f.render() for f in findings]


# ---------------------------------------------------------------------------
# DT006 acceptance: un-guard a field in a fixture copy of the REAL scheduler
# ---------------------------------------------------------------------------


def test_dt006_scheduler_copy_detects_unguarded_access(tmp_path):
    src = open(os.path.join(ROOT, "dt_tpu", "elastic",
                            "scheduler.py")).read()
    fixture_root = tmp_path / "fr"
    pkg = fixture_root / "dt_tpu" / "elastic"
    pkg.mkdir(parents=True)
    (pkg / "scheduler.py").write_text(src)
    clean = run(str(fixture_root), paths=["dt_tpu"], select={"DT006"})
    assert not clean, None if not clean else \
        "\n".join(f.render() for f in clean)

    # move an access outside the lock: a new method reads the guarded
    # journaled control state with no 'with self._lock' — the
    # quick-restart-race class of bug this rule exists to catch
    racy = src.replace(
        "    def _audit_locked(self, action: str, host: str):",
        "    def _racy_membership(self):\n"
        "        return list(self._state.workers)\n\n"
        "    def _audit_locked(self, action: str, host: str):")
    assert "_racy_membership" in racy
    (pkg / "scheduler.py").write_text(racy)
    findings = run(str(fixture_root), paths=["dt_tpu"], select={"DT006"})
    assert any("_state" in f.message for f in findings), \
        [f.render() for f in findings]

    # equivalently: deleting the guarded-by annotation must not crash and
    # silences the rule for that attribute (annotation IS the contract)
    unannotated = racy.replace(
        "self._state = journal.ControlState()  # guarded-by: _lock",
        "self._state = journal.ControlState()")
    (pkg / "scheduler.py").write_text(unannotated)
    findings = run(str(fixture_root), paths=["dt_tpu"], select={"DT006"})
    assert not any("'_state'" in f.message for f in findings)


# ---------------------------------------------------------------------------
# DT008-DT010 acceptance: break fixture copies of the REAL scheduler/client
# (detection power: the pristine copies are clean; one deleted guard, one
# reversed acquisition, one WAL bypass each yield the expected finding)
# ---------------------------------------------------------------------------


def _copy_into(tmp_path, relsrc, content=None):
    src = content if content is not None else \
        open(os.path.join(ROOT, *relsrc.split("/"))).read()
    fixture_root = tmp_path / "fr"
    dst = fixture_root / relsrc
    dst.parent.mkdir(parents=True, exist_ok=True)
    dst.write_text(src)
    return fixture_root, src


def test_dt008_scheduler_copy_detects_deleted_guard(tmp_path):
    rel = "dt_tpu/elastic/scheduler.py"
    root, src = _copy_into(tmp_path, rel)
    clean = run(str(root), paths=["dt_tpu"],
                select={"DT008", "DT009", "DT010"})
    assert not clean, "\n".join(f.render() for f in clean)

    # delete one guard: un-annotate _heartbeats AND add an unlocked
    # public write — the quick-restart-race bug shape DT008 infers
    # WITHOUT any annotation left to check syntactically
    racy = src.replace(
        "self._heartbeats = {h: now for h in self._state.workers}"
        "  # guarded-by: _lock",
        "self._heartbeats = {h: now for h in self._state.workers}")
    assert racy != src
    racy = racy.replace(
        "    def _audit_locked(self, action: str, host: str):",
        "    def poke_heartbeat(self, host):\n"
        "        self._heartbeats[host] = 0.0\n\n"
        "    def _audit_locked(self, action: str, host: str):")
    assert "poke_heartbeat" in racy
    root, _ = _copy_into(tmp_path, rel, racy)
    findings = run(str(root), paths=["dt_tpu"], select={"DT008"})
    hits = [f for f in findings if "_heartbeats" in f.message]
    assert hits, [f.render() for f in findings]
    assert "guarded-by: _lock" in hits[0].message


def test_dt008_client_copy_detects_unlocked_fence(tmp_path):
    rel = "dt_tpu/elastic/client.py"
    root, src = _copy_into(tmp_path, rel)
    clean = run(str(root), paths=["dt_tpu"],
                select={"DT008", "DT009", "DT010"})
    assert not clean, "\n".join(f.render() for f in clean)

    # un-lock the failover fence refresh (and drop the annotation so
    # the syntactic DT006 cannot see it either) — DT008 must re-infer
    # the heartbeat-vs-caller race from the lock sets alone
    racy = src.replace("self.fence = 0  # guarded-by: _addr_lock",
                       "self.fence = 0")
    racy = racy.replace(
        "        with self._addr_lock:\n"
        "            changed = fence != self.fence\n"
        "            self.fence = fence",
        "        changed = fence != self.fence\n"
        "        self.fence = fence")
    assert racy != src
    root, _ = _copy_into(tmp_path, rel, racy)
    findings = run(str(root), paths=["dt_tpu"], select={"DT008"})
    hits = [f for f in findings if "fence" in f.message]
    assert hits, [f.render() for f in findings]


def test_dt009_scheduler_copy_detects_reversed_locks(tmp_path):
    rel = "dt_tpu/elastic/scheduler.py"
    root, src = _copy_into(tmp_path, rel)
    # _register -> _server_list already orders _lock -> _servers_lock;
    # inject the reverse acquisition
    racy = src.replace(
        "    def _audit_locked(self, action: str, host: str):",
        "    def backwards_probe(self):\n"
        "        with self._servers_lock:\n"
        "            with self._lock:\n"
        "                return len(self._state.workers)\n\n"
        "    def _audit_locked(self, action: str, host: str):")
    assert racy != src
    root, _ = _copy_into(tmp_path, rel, racy)
    findings = run(str(root), paths=["dt_tpu"], select={"DT009"})
    cycles = [f for f in findings if "cycle" in f.message]
    assert cycles, [f.render() for f in findings]
    assert any("_servers_lock" in f.message for f in cycles)


def test_dt009_blocking_under_lock_on_scheduler_copy(tmp_path):
    rel = "dt_tpu/elastic/scheduler.py"
    root, src = _copy_into(tmp_path, rel)
    racy = src.replace(
        "    def _audit_locked(self, action: str, host: str):",
        "    def relay_blocking(self, host, port):\n"
        "        with self._lock:\n"
        "            return protocol.request(host, port,\n"
        "                                    {\"cmd\": \"status\"})\n\n"
        "    def _audit_locked(self, action: str, host: str):")
    assert racy != src
    root, _ = _copy_into(tmp_path, rel, racy)
    findings = run(str(root), paths=["dt_tpu"], select={"DT009"})
    assert any("blocking while locked" in f.message for f in findings), \
        [f.render() for f in findings]


def test_dt010_scheduler_copy_detects_wal_bypass(tmp_path):
    rel = "dt_tpu/elastic/scheduler.py"
    root, src = _copy_into(tmp_path, rel)
    racy = src.replace(
        "    def _audit_locked(self, action: str, host: str):",
        "    def force_membership(self, host):\n"
        "        with self._cv:\n"
        "            self._state.workers.append(host)\n\n"
        "    def _audit_locked(self, action: str, host: str):")
    assert racy != src
    root, _ = _copy_into(tmp_path, rel, racy)
    findings = run(str(root), paths=["dt_tpu"], select={"DT010"})
    assert any("workers" in f.message for f in findings), \
        [f.render() for f in findings]
    # the journaled path stays silent: _apply / replay are the WAL gate
    assert not any(f.line <= 310 for f in findings), \
        [f.render() for f in findings]


# ---------------------------------------------------------------------------
# DT012-DT014 (dtproto, r17): fixture trees + acceptance on copies of the
# REAL protocol files (pristine clean; each one-sided edit yields exactly
# the expected finding class)
# ---------------------------------------------------------------------------

#: the closure of files whose send sites / handler arms / registry /
#: catalog make the REAL wire vocabulary self-consistent — what the
#: acceptance tests copy into a scratch root
_PROTO_CLOSURE = (
    "dt_tpu/elastic/client.py",
    "dt_tpu/elastic/scheduler.py",
    "dt_tpu/elastic/scheduler_main.py",
    "dt_tpu/elastic/range_server.py",
    "dt_tpu/elastic/dataplane.py",
    "dt_tpu/elastic/journal.py",
    "dt_tpu/elastic/commands.py",
    "dt_tpu/serve/gateway.py",
    "dt_tpu/serve/client.py",
    "dt_tpu/serve/replica.py",
    "dt_tpu/serve/refresh.py",
    "dt_tpu/obs/names.py",
    "tools/chaos_run.py",
    "tools/dtop.py",
    "docs/protocol_commands.md",
)


def _proto_root(tmp_path, edits=None):
    """A scratch root holding the protocol closure, with optional
    ``{relpath: (old, new)}`` source edits applied (each must match)."""
    edits = edits or {}
    root = tmp_path / "proto"
    for rel in _PROTO_CLOSURE:
        src = open(os.path.join(ROOT, *rel.split("/"))).read()
        if rel in edits:
            old, new = edits[rel]
            assert old in src, f"edit anchor missing in {rel}: {old!r}"
            src = src.replace(old, new)
        dst = root / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        dst.write_text(src)
    return root


def _proto_run(root, select):
    return run(str(root), paths=list(DEFAULT_PATHS), select=set(select))


def test_dt012_fixture_trees():
    bad = run(os.path.join(FIXTURES, "proto", "dt012_bad"),
              paths=list(DEFAULT_PATHS), select={"DT012"})
    msgs = [f.message for f in bad]
    assert any("'frobnicate'" in m and "no dispatcher" in m
               for m in msgs), msgs
    assert any("dead handler arm" in m and "'push'" in m
               for m in msgs), msgs
    assert any("'extra'" in m and "ever reads it" in m
               for m in msgs), msgs
    assert any("requires field 'key'" in m for m in msgs), msgs
    assert any("response key 'missing'" in m for m in msgs), msgs
    good = run(os.path.join(FIXTURES, "proto", "dt012_good"),
               paths=list(DEFAULT_PATHS), select={"DT012"})
    assert not good, [f.render() for f in good]


def test_proto_pristine_copies_clean(tmp_path):
    root = _proto_root(tmp_path)
    findings = _proto_run(root, {"DT012", "DT013", "DT014"})
    assert not findings, "\n".join(f.render() for f in findings)


def test_dt012_unhandled_send_on_client_copy(tmp_path):
    """Inject a send of a command no dispatcher handles (the ROADMAP-1
    resharding shape: sender written first) — DT012 flags both the
    orphan send and the missing registry row."""
    root = _proto_root(tmp_path, edits={
        "dt_tpu/elastic/client.py": (
            "def auto_client(",
            "def reshard_probe(host, port):\n"
            "    return protocol.request(host, port,\n"
            "                            {\"cmd\": \"reshard\"})\n\n\n"
            "def auto_client(")})
    findings = _proto_run(root, {"DT012"})
    msgs = [f.message for f in findings]
    assert any("'reshard'" in m and "no dispatcher" in m
               for m in msgs), msgs
    assert any("'reshard'" in m and "PROTOCOL_REGISTRY" in m
               for m in msgs), msgs


def test_dt012_deleted_handler_arm_on_scheduler_copy(tmp_path):
    """Deleting one handler arm flips DT012: the client's send goes
    unhandled and the registry row goes dead."""
    root = _proto_root(tmp_path, edits={
        "dt_tpu/elastic/scheduler.py": (
            '        if cmd == "num_dead":\n'
            '            return {"count": '
            'self._num_dead(float(msg.get("timeout_s", 60)))}\n',
            "")})
    findings = _proto_run(root, {"DT012"})
    msgs = [f.message for f in findings]
    assert any("'num_dead'" in m and "no dispatcher" in m
               for m in msgs), msgs
    assert any("dead registry row" in m and "'num_dead'" in m
               for m in msgs), msgs


def test_dt012_deleted_registry_row_flips(tmp_path):
    root = _proto_root(tmp_path, edits={
        "dt_tpu/elastic/commands.py": (
            '    "num_dead": (\n'
            '        "scheduler", "read_only", "exempt",\n'
            '        "count workers silent past timeout_s '
            '(postoffice.cc:410-429)"),\n',
            "")})
    findings = _proto_run(root, {"DT012"})
    msgs = [f.message for f in findings]
    assert any("'num_dead'" in m and "no PROTOCOL_REGISTRY row" in m
               for m in msgs), msgs
    # the committed catalog still lists it: stale-table finding too
    assert any("catalog is stale" in m and "'num_dead'" in m
               for m in msgs), msgs


def test_dt013_register_moved_into_token_exempt(tmp_path):
    """The acceptance scenario from the PR-6 bug class: make the
    derived exemption view a literal that includes the mutating
    no-dedup 'register' — DT013 flags the journaled mutation under an
    exempt command AND the registry drift."""
    literal = ('_TOKEN_EXEMPT = frozenset({"register", "fetch_snapshot",'
               ' "allreduce",\n'
               '                           "async_init", "async_push",\n'
               '                           "async_pull_rows", '
               '"async_stats",\n'
               '                           "heartbeat", "num_dead", '
               '"membership",\n'
               '                           "servers", "obs_push", '
               '"obs_dump",\n'
               '                           "ha_round", "status", '
               '"health",\n'
               '                           "blackbox_index"})')
    root = _proto_root(tmp_path, edits={
        "dt_tpu/elastic/scheduler.py": (
            '_TOKEN_EXEMPT = commands.token_exempt("scheduler")',
            literal)})
    findings = _proto_run(root, {"DT013"})
    msgs = [f.message for f in findings]
    assert any("'register'" in m and "_apply" in m for m in msgs), msgs
    assert any("'register'" in m and "'once'" in m for m in msgs), msgs
    assert any("drifted" in m and "'register'" in m for m in msgs), msgs


def test_dt014_clock_inside_apply_op_on_journal_copy(tmp_path):
    """time.time() inside a ControlState op: replay would re-stamp a
    different value than live — the exact divergence the HA
    journal-replay contract forbids."""
    root = _proto_root(tmp_path, edits={
        "dt_tpu/elastic/journal.py": (
            "    def _op_evict(self, host: str, seq: int) -> None:\n",
            "    def _op_evict(self, host: str, seq: int) -> None:\n"
            "        self.stamp = time.time()\n")})
    findings = _proto_run(root, {"DT014"})
    hits = [f for f in findings if "_op_evict" in f.message
            and "wall-clock" in f.message]
    assert hits, [f.render() for f in findings]


def test_dt014_sort_keys_and_marker_on_export_copy(tmp_path):
    """Deleting sort_keys in a byte-deterministic surface — or the
    marker that declares it — flips DT014 on a pristine-clean copy of
    the real export module."""
    rel = "dt_tpu/obs/export.py"
    src = open(os.path.join(ROOT, *rel.split("/"))).read()
    root = tmp_path / "fr"
    dst = root / rel
    dst.parent.mkdir(parents=True)
    dst.write_text(src)
    clean = run(str(root), paths=["dt_tpu"], select={"DT014"})
    assert not clean, "\n".join(f.render() for f in clean)

    broken = src.replace("json.dump(chrome, f, sort_keys=True)",
                         "json.dump(chrome, f)")
    assert broken != src
    dst.write_text(broken)
    findings = run(str(root), paths=["dt_tpu"], select={"DT014"})
    assert any("sort_keys" in f.message for f in findings), \
        [f.render() for f in findings]

    unmarked = src.replace(
        "# deterministic: bytes — two writes of one dump are "
        "byte-identical\n", "")
    assert unmarked != src
    dst.write_text(unmarked)
    findings = run(str(root), paths=["dt_tpu"], select={"DT014"})
    assert any("promised deterministic surface" in f.message
               for f in findings), [f.render() for f in findings]

    # renaming the promised function must not let the promise rot
    renamed = src.replace("def write(", "def write_renamed(")
    assert renamed != src
    dst.write_text(renamed)
    findings = run(str(root), paths=["dt_tpu"], select={"DT014"})
    assert any("is gone from this module" in f.message
               for f in findings), [f.render() for f in findings]


def test_protocol_catalog_in_sync():
    """docs/protocol_commands.md is generated — the committed bytes
    must equal render_catalog() exactly (DT012 checks the cmd set; this
    pins the whole table)."""
    from dt_tpu.elastic import commands
    committed = open(os.path.join(ROOT, "docs",
                                  "protocol_commands.md")).read()
    assert committed == commands.render_catalog(), \
        "regenerate: python -m dt_tpu.elastic.commands > " \
        "docs/protocol_commands.md"


def test_derived_views_are_consistent():
    """The servers' exemption/passive sets ARE the registry views (no
    literal to drift), and the registry's own invariants hold."""
    from dt_tpu.elastic import commands, range_server, scheduler
    assert scheduler._TOKEN_EXEMPT == commands.token_exempt("scheduler")
    assert scheduler._PASSIVE_CMDS == commands.passive_cmds()
    assert range_server._TOKEN_EXEMPT == \
        commands.token_exempt("range_server")
    for cmd, (roles, idem, flags, doc) in \
            commands.PROTOCOL_REGISTRY.items():
        if idem == "once":
            assert "exempt" not in flags.split("|"), cmd


def test_sarif_round_trip(tmp_path):
    """--sarif writes a valid SARIF 2.1.0 log whose results mirror the
    reported findings (here: a tree with known findings, no baseline)."""
    import json as _json
    root = tmp_path / "s"
    (root / "dt_tpu").mkdir(parents=True)
    bad = open(os.path.join(FIXTURES, "dt_tpu", "dt003_bad.py")).read()
    (root / "dt_tpu" / "mod.py").write_text(bad)
    sarif_path = str(tmp_path / "out.sarif")
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "dtlint.py"),
         "--root", str(root), "--no-cache", "--no-baseline",
         "--select", "DT003", "--sarif", sarif_path],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 1, out.stdout + out.stderr
    doc = _json.load(open(sarif_path))
    assert doc["version"] == "2.1.0"
    rundoc = doc["runs"][0]
    rule_ids = [r["id"] for r in rundoc["tool"]["driver"]["rules"]]
    assert rule_ids == sorted(r.id for r in all_rules())
    results = rundoc["results"]
    findings = run(str(root), paths=["dt_tpu"], select={"DT003"})
    assert len(results) == len(findings) > 0
    for res, f in zip(results, findings):
        assert res["ruleId"] == f.rule == "DT003"
        assert res["level"] == "error"
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == f.path
        assert loc["region"]["startLine"] == f.line
        assert f.message in res["message"]["text"]
    # clean tree -> zero results, exit 0, still a valid log
    (root / "dt_tpu" / "mod.py").write_text("import os\n")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "dtlint.py"),
         "--root", str(root), "--no-cache", "--no-baseline",
         "--select", "DT003", "--sarif", sarif_path],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    assert _json.load(open(sarif_path))["runs"][0]["results"] == []


def test_cold_and_cached_runs_meet_the_perf_gates(tmp_path):
    """The canonical full run's speed is the result cache: its second
    run is served whole from ``.dtlint_cache.json`` (same ``sig``, no
    rule executed) and ``--no-cache`` never is.  The ProtocolModel rides
    project.data like the DT008/DT009 ClassModel cache, and the result
    cache covers the whole verdict."""
    import json as _json
    import shutil
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT
    # run against a pristine copy of the default scope so this test
    # never races the developer's working tree or the repo's own cache
    root = tmp_path / "repo"
    for rel in DEFAULT_PATHS + ("docs", "PARITY.md",
                                "dtlint_baseline.txt"):
        src = os.path.join(ROOT, rel)
        dst = root / rel
        if os.path.isdir(src):
            shutil.copytree(src, dst)
        elif os.path.exists(src):
            dst.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(src, dst)
    cli = os.path.join(ROOT, "tools", "dtlint.py")
    cache = root / ".dtlint_cache.json"

    def run(*extra):
        out = subprocess.run(
            [sys.executable, cli, "--root", str(root), "--json", *extra],
            capture_output=True, text=True, env=env, timeout=120)
        assert out.returncode == 0, out.stdout + out.stderr
        return _json.loads(out.stdout.strip().splitlines()[-1])

    cold = run("--no-cache")
    assert cold["from_cache"] is False and cold["rule_timings_ms"]
    assert not cache.exists()  # --no-cache neither reads nor writes it
    warm = run()
    assert warm["from_cache"] is False
    sig = _json.load(open(cache))["sig"]
    cached = run()
    assert cached["from_cache"] is True
    assert _json.load(open(cache))["sig"] == sig
    # the served verdict carries the stored run's per-rule timings
    assert cached["rule_timings_ms"] == warm["rule_timings_ms"]
    assert run("--no-cache")["from_cache"] is False


# ---------------------------------------------------------------------------
# r12 CLI satellites: cache digest, --fix-annotations, --changed, timings
# ---------------------------------------------------------------------------


def _load_cli():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_dtlint_cli", os.path.join(ROOT, "tools", "dtlint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cache_misses_on_rule_edit_with_preserved_stat(tmp_path):
    """Editing an analysis source must invalidate the whole-tree cache
    even when the file's (size, mtime) are byte-identical — the r12
    content-digest key (the old stat-only key served stale verdicts)."""
    cli = _load_cli()
    analysis = cli._import_analysis()
    root = tmp_path / "r"
    (root / "dt_tpu" / "analysis").mkdir(parents=True)
    rule_src = root / "dt_tpu" / "analysis" / "rules_x.py"
    rule_src.write_text("X = 1  # a rule constant\n")
    (root / "dt_tpu" / "mod.py").write_text("import os\n")
    # the digest covers the EXECUTING engine's sources (module _ROOT);
    # point this CLI instance's _ROOT at the scratch tree so the test
    # can edit a "rule" without touching the real checkout
    cli._ROOT = str(root)

    missed, sig, _ = cli._cached_findings(analysis, str(root),
                                          ["dt_tpu"], None)
    assert missed is None
    cli._store_cache(str(root), sig, [], {"DT008": 1.0})
    hit, _, timings = cli._cached_findings(analysis, str(root),
                                           ["dt_tpu"], None)
    assert hit == [] and timings == {"DT008": 1.0}

    st = rule_src.stat()
    rule_src.write_text("X = 2  # a rule constant\n")  # same size
    os.utime(rule_src, (st.st_atime, st.st_mtime))     # same mtime
    assert rule_src.stat().st_size == st.st_size
    stale, _, _ = cli._cached_findings(analysis, str(root),
                                       ["dt_tpu"], None)
    assert stale is None, "stat-identical rule edit served a stale cache"


def test_fix_annotations_inserts_and_is_idempotent(tmp_path):
    root = tmp_path / "fa"
    (root / "dt_tpu").mkdir(parents=True)
    bad = open(os.path.join(FIXTURES, "dt_tpu", "dt008_bad.py")).read()
    bad = bad.replace("self._pending = []",
                      "self._pending = []  # staged items")
    (root / "dt_tpu" / "mod.py").write_text(bad)
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT
    cmd = [sys.executable, os.path.join(ROOT, "tools", "dtlint.py"),
           "--root", str(root), "--fix-annotations", "dt_tpu"]
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    text = (root / "dt_tpu" / "mod.py").read_text()
    # inserted at the __init__ assignment, after the existing comment
    assert "self._pending = []  # staged items  # guarded-by: _lock" \
        in text
    # idempotent: a second run changes nothing
    again = subprocess.run(cmd, capture_output=True, text=True, env=env,
                           timeout=120)
    assert again.returncode == 0
    assert (root / "dt_tpu" / "mod.py").read_text() == text
    # the annotation silences DT008 for the annotatable attr and hands
    # the contract to DT006, which now pins the unlocked caller-side
    # write; Relay (no lock in the class) is NOT auto-annotated — the
    # fixer must never fabricate a lock name — so its finding persists
    left = run(str(root), paths=["dt_tpu"], select={"DT008"})
    assert not any("_pending" in f.message for f in left), \
        [f.render() for f in left]
    assert any("_errors" in f.message and "owns no lock" in f.message
               for f in left), [f.render() for f in left]
    dt006 = run(str(root), paths=["dt_tpu"], select={"DT006"})
    assert any("_pending" in f.message for f in dt006), \
        [f.render() for f in dt006]


def test_changed_scope_lints_only_git_diff(tmp_path):
    import shutil
    if shutil.which("git") is None:
        pytest.skip("git unavailable")
    root = tmp_path / "cg"
    (root / "dt_tpu").mkdir(parents=True)
    (root / "dt_tpu" / "clean.py").write_text("import os\n")
    bad = open(os.path.join(FIXTURES, "dt_tpu", "dt003_bad.py")).read()
    (root / "dt_tpu" / "was_there.py").write_text(bad)

    def git(*args):
        proc = subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t",
             *args], cwd=root, capture_output=True, text=True,
            timeout=60)
        assert proc.returncode == 0, proc.stderr
        return proc

    git("init", "-q")
    git("add", "-A")
    git("commit", "-qm", "seed")
    # a NEW bad file is in scope; the committed bad file is not, and a
    # changed file under tests/ (fixtures violate rules on purpose)
    # stays excluded exactly as in a full run
    (root / "dt_tpu" / "fresh.py").write_text(bad)
    (root / "tests").mkdir()
    (root / "tests" / "fixture_bad.py").write_text(bad)
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "dtlint.py"),
         "--root", str(root), "--changed", "--no-cache",
         "--no-baseline", "--select", "DT003"],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 1, out.stdout + out.stderr
    assert "fresh.py" in out.stdout
    assert "was_there.py" not in out.stdout
    assert "fixture_bad.py" not in out.stdout


def test_changed_scope_with_root_below_git_toplevel(tmp_path):
    """--root pointing at a SUBDIRECTORY of the checkout: `git diff`
    paths carry the toplevel prefix, `git ls-files --others` paths do
    not — both a tracked edit and a new untracked file must be linted."""
    import shutil
    if shutil.which("git") is None:
        pytest.skip("git unavailable")
    top = tmp_path / "mono"
    sub = top / "proj"
    (sub / "dt_tpu").mkdir(parents=True)
    (sub / "dt_tpu" / "tracked.py").write_text("import os\n")

    def git(*args):
        proc = subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t",
             *args], cwd=top, capture_output=True, text=True,
            timeout=60)
        assert proc.returncode == 0, proc.stderr
        return proc

    git("init", "-q")
    git("add", "-A")
    git("commit", "-qm", "seed")
    bad = open(os.path.join(FIXTURES, "dt_tpu", "dt003_bad.py")).read()
    (sub / "dt_tpu" / "tracked.py").write_text(bad)      # modified
    (sub / "dt_tpu" / "untracked.py").write_text(bad)    # brand new
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "dtlint.py"),
         "--root", str(sub), "--changed", "--no-cache",
         "--no-baseline", "--select", "DT003"],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 1, out.stdout + out.stderr
    assert "tracked.py" in out.stdout
    assert "untracked.py" in out.stdout


def test_fix_annotations_respects_suppressions(tmp_path):
    """A race the user silenced with '# dtlint: ignore[DT008]' must not
    be annotated — the fixer would otherwise activate DT006 at the very
    site the user suppressed and flip a passing gate to exit 1."""
    root = tmp_path / "fs"
    (root / "dt_tpu").mkdir(parents=True)
    bad = open(os.path.join(FIXTURES, "dt_tpu", "dt008_bad.py")).read()
    bad = bad.replace("self._pending.append(item)",
                      "self._pending.append(item)"
                      "  # dtlint: ignore[DT008]")
    (root / "dt_tpu" / "mod.py").write_text(bad)
    assert not any("_pending" in f.message for f in
                   run(str(root), paths=["dt_tpu"], select={"DT008"}))
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "dtlint.py"),
         "--root", str(root), "--fix-annotations", "dt_tpu"],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    text = (root / "dt_tpu" / "mod.py").read_text()
    assert "self._pending = []  # guarded-by" not in text


def test_scoped_run_skips_out_of_scope_stale_check(tmp_path):
    """A path-scoped run (--changed / explicit paths) never produces
    the findings that keep out-of-scope grandfathers alive — it must
    not flag them stale (and exit 1) for that reason alone; the full
    default-scope run still does."""
    root = tmp_path / "sc"
    (root / "dt_tpu").mkdir(parents=True)
    bad = open(os.path.join(FIXTURES, "dt_tpu", "dt003_bad.py")).read()
    (root / "dt_tpu" / "a.py").write_text(bad)
    (root / "dt_tpu" / "b.py").write_text(bad)
    grand = run(str(root), paths=["dt_tpu/a.py"], select={"DT003"})
    assert grand
    bl = str(root / "baseline.txt")
    Baseline().save(bl, grand, reasons={f.key: "test grandfather"
                                        for f in grand})
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT
    base_cmd = [sys.executable, os.path.join(ROOT, "tools", "dtlint.py"),
                "--root", str(root), "--baseline", bl, "--no-cache",
                "--select", "DT003"]
    scoped = subprocess.run(base_cmd + ["dt_tpu/b.py"],
                            capture_output=True, text=True, env=env,
                            timeout=120)
    assert scoped.returncode == 1, scoped.stdout + scoped.stderr
    assert "b.py" in scoped.stdout
    assert "stale baseline" not in scoped.stdout, scoped.stdout
    # rule-scoped over the full paths: --select of a DIFFERENT rule
    # never produces the grandfathered findings either — no stale, rc 0
    selected = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "dtlint.py"),
         "--root", str(root), "--baseline", bl, "--no-cache",
         "--select", "DT006"],
        capture_output=True, text=True, env=env, timeout=120)
    assert selected.returncode == 0, selected.stdout + selected.stderr
    assert "stale baseline" not in selected.stdout
    # fix the grandfathered file: the FULL run (all paths, all rules —
    # --select also counts as scoped now) reports the entry stale
    (root / "dt_tpu" / "a.py").write_text("import os\n")
    (root / "dt_tpu" / "b.py").write_text("import os\n")
    full = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "dtlint.py"),
         "--root", str(root), "--baseline", bl, "--no-cache"],
        capture_output=True, text=True, env=env, timeout=120)
    assert full.returncode == 1, full.stdout + full.stderr
    assert "stale baseline" in full.stdout


def test_scoped_flags_refuse_unsound_combinations(tmp_path):
    """--write-baseline on any scoped run would silently drop every
    out-of-scope grandfather; --changed plus explicit paths is two
    contradictory scopes — both are usage errors (rc 2)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT
    cli = os.path.join(ROOT, "tools", "dtlint.py")
    for extra in (["--select", "DT003", "--write-baseline",
                   "--baseline", str(tmp_path / "bl.txt")],
                  ["dt_tpu", "--changed"]):
        out = subprocess.run([sys.executable, cli, "--no-cache"] + extra,
                             capture_output=True, text=True, env=env,
                             timeout=120)
        assert out.returncode == 2, (extra, out.stdout, out.stderr)


def test_json_reports_per_rule_timings():
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "dtlint.py"),
         "--json", "--no-cache", "--select", "DT008", "--select",
         "DT010", os.path.join("dt_tpu", "elastic")],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    import json as _json
    summary = _json.loads(out.stdout.strip().splitlines()[-1])
    timings = summary["rule_timings_ms"]
    assert set(timings) == {"DT008", "DT010"}
    assert all(v >= 0 for v in timings.values())


def test_repo_baseline_entries_are_reasoned_and_known():
    """Every grandfather must carry a real reason and cite a live rule
    id — Baseline.load already hard-fails on a missing '# reason:'."""
    baseline = Baseline.load(os.path.join(ROOT, "dtlint_baseline.txt"))
    ids = {r.id for r in all_rules()}
    for (rule, path, _snippet), reason in baseline.entries.items():
        assert rule in ids, f"baseline cites unknown rule {rule}"
        assert reason.strip() and "TODO" not in reason, \
            f"undocumented baseline entry for {rule} in {path}"


# ---------------------------------------------------------------------------
# DT015-DT017 (dtxla, r20): arm coverage on the fixture pairs +
# acceptance on copies of the REAL hot-path files (pristine clean; each
# injected defect flips exactly its rule)
# ---------------------------------------------------------------------------


def test_dt015_flags_every_arm():
    msgs = [f.message for f in _lint(["dt_tpu/dt015_bad.py"],
                                     select="DT015")]
    for marker in ("immediately used", "inside a loop",
                   "in-body jit construction", "unhashable argument",
                   "bare lower().compile()"):
        assert any(marker in m for m in msgs), (marker, msgs)


def test_dt016_flags_every_sink_kind():
    msgs = [f.message for f in _lint(
        ["dt_tpu/training/dt016_bad.py"], select="DT016")]
    for marker in ("float(...)", "truthiness", ".item()",
                   "np.asarray(...)"):
        assert any(marker in m for m in msgs), (marker, msgs)


def test_dt017_flags_every_arm():
    msgs = [f.message for f in _lint(["dt_tpu/dt017_bad.py"],
                                     select="DT017")]
    assert any("use after donate" in m for m in msgs), msgs
    assert any("copy_to_host_async pending" in m for m in msgs), msgs
    assert any("default_backend() guard" in m for m in msgs), msgs


_XLA = {"DT015", "DT016", "DT017"}


def test_xla_pristine_module_copy_clean(tmp_path):
    root, _ = _copy_into(tmp_path, "dt_tpu/training/module.py")
    findings = run(str(root), paths=["dt_tpu"], select=_XLA)
    assert not findings, "\n".join(f.render() for f in findings)


def test_dt015_module_copy_detects_in_body_jit(tmp_path):
    rel = "dt_tpu/training/module.py"
    anchor = "host_step += 1"  # in the step loop, after the dispatch
    _, src = _copy_into(tmp_path, rel)
    assert anchor in src
    broken = src.replace(
        anchor,
        "extra = jax.jit(lambda s: s)(self.state)\n"
        "                    " + anchor)
    root, _ = _copy_into(tmp_path, rel, broken)
    findings = run(str(root), paths=["dt_tpu"], select=_XLA)
    assert findings and all(f.rule == "DT015" for f in findings), \
        [f.render() for f in findings]
    assert any("immediately used" in f.message for f in findings)


def test_dt016_module_copy_detects_step_loop_sync(tmp_path):
    rel = "dt_tpu/training/module.py"
    anchor = "host_step += 1"  # in the step loop, after the dispatch
    _, src = _copy_into(tmp_path, rel)
    broken = src.replace(
        anchor,
        anchor + "\n                    lv_probe = float(loss)")
    assert broken != src
    root, _ = _copy_into(tmp_path, rel, broken)
    findings = run(str(root), paths=["dt_tpu"], select=_XLA)
    assert findings and all(f.rule == "DT016" for f in findings), \
        [f.render() for f in findings]
    assert any("float(...)" in f.message for f in findings)


def test_dt017_module_copy_detects_read_after_donate(tmp_path):
    rel = "dt_tpu/training/module.py"
    _, src = _copy_into(tmp_path, rel)
    broken = src.replace(
        "    def fit(",
        "    def _poke_donated(self, data, labels, rng):\n"
        "        st = self.state\n"
        "        out = self._train_step(st, data, labels, rng)\n"
        "        return st\n\n"
        "    def fit(")
    assert broken != src
    root, _ = _copy_into(tmp_path, rel, broken)
    findings = run(str(root), paths=["dt_tpu"], select=_XLA)
    assert findings and all(f.rule == "DT017" for f in findings), \
        [f.render() for f in findings]
    assert any("use after donate" in f.message and "'st'" in f.message
               for f in findings)


def test_dt016_overlap_copy_detects_bucket_sync(tmp_path):
    rel = "dt_tpu/training/overlap.py"
    _, src = _copy_into(tmp_path, rel)
    clean_root, _ = _copy_into(tmp_path, rel)
    clean = run(str(clean_root), paths=["dt_tpu"], select=_XLA)
    assert not clean, "\n".join(f.render() for f in clean)
    broken = src.replace(
        "        avg_dev = out_dev[0] if nb == 1 else "
        "jnp.concatenate(out_dev)\n"
        "        return avg_dev, stats_avg",
        "        avg_dev = out_dev[0] if nb == 1 else "
        "jnp.concatenate(out_dev)\n"
        "        chk = float(avg_dev[0])\n"
        "        return avg_dev, stats_avg")
    assert broken != src
    root, _ = _copy_into(tmp_path, rel, broken)
    findings = run(str(root), paths=["dt_tpu"], select=_XLA)
    assert findings and all(f.rule == "DT016" for f in findings), \
        [f.render() for f in findings]


def test_xla_pristine_client_copy_clean(tmp_path):
    root, _ = _copy_into(tmp_path, "dt_tpu/elastic/client.py")
    findings = run(str(root), paths=["dt_tpu"], select=_XLA)
    assert not findings, "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# --explain (r20 CLI satellite)
# ---------------------------------------------------------------------------


def _run_cli(*args, timeout=120):
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "dtlint.py"),
         *args], capture_output=True, text=True, env=env,
        timeout=timeout)


def test_explain_prints_catalog_entry_and_fixture_pair():
    out = _run_cli("--explain", "DT016")
    assert out.returncode == 0, out.stdout + out.stderr
    assert "## DT016" in out.stdout
    assert "dt016_bad.py" in out.stdout
    assert "dt016_good.py" in out.stdout
    # the fixture SOURCE is inlined, not just the path
    assert "implicit synchronous D2H" in out.stdout.lower() or \
        "device" in out.stdout


def test_explain_unknown_rule_exits_2():
    out = _run_cli("--explain", "DT999")
    assert out.returncode == 2, out.stdout + out.stderr
    assert "DT999" in out.stderr


def test_explain_unions_with_select():
    out = _run_cli("--explain", "DT015", "--select", "DT017")
    assert out.returncode == 0, out.stdout + out.stderr
    assert "## DT015" in out.stdout
    assert "## DT017" in out.stdout


# ---------------------------------------------------------------------------
# suppression + baseline round-trip
# ---------------------------------------------------------------------------


def test_suppression_comment_silences_finding(tmp_path):
    root = tmp_path / "s"
    (root / "dt_tpu").mkdir(parents=True)
    bad = open(os.path.join(FIXTURES, "dt_tpu", "dt003_bad.py")).read()
    bad = bad.replace("donate_argnums=(0,))",
                      "donate_argnums=(0,))  # dtlint: ignore[DT003]")
    (root / "dt_tpu" / "mod.py").write_text(bad)
    assert not run(str(root), paths=["dt_tpu"], select={"DT003"})
    # an ignore listing a DIFFERENT rule does not silence it
    other = bad.replace("ignore[DT003]", "ignore[DT001]")
    (root / "dt_tpu" / "mod.py").write_text(other)
    assert run(str(root), paths=["dt_tpu"], select={"DT003"})


def test_baseline_round_trip(tmp_path):
    findings = _lint(["dt_tpu/dt003_bad.py"], select="DT003")
    assert findings
    path = str(tmp_path / "baseline.txt")
    Baseline().save(path, findings,
                    reasons={f.key: "fixture grandfather"
                             for f in findings})
    loaded = Baseline.load(path)
    assert all(loaded.covers(f) for f in findings)
    assert not loaded.stale(findings)
    # an entry whose line was fixed shows up as stale
    assert loaded.stale([]) == sorted({f.key for f in findings})


def test_baseline_requires_reason(tmp_path):
    path = tmp_path / "b.txt"
    path.write_text("DT003\tdt_tpu/mod.py\tjax.jit(f, donate_argnums=(0,))\n")
    with pytest.raises(ValueError, match="reason"):
        Baseline.load(str(path))


# ---------------------------------------------------------------------------
# tooling invariants that ride along with the linter
# ---------------------------------------------------------------------------


def test_rule_ids_unique_and_documented():
    rules = all_rules()
    ids = [r.id for r in rules]
    assert len(set(ids)) == len(ids) == 17
    catalog = open(os.path.join(ROOT, "docs", "dtlint_rules.md")).read()
    for r in rules:
        assert r.id in catalog, f"{r.id} missing from docs/dtlint_rules.md"


def test_repo_baseline_ships_empty():
    """House style: true positives get FIXED, not baselined — the
    checked-in baseline must stay empty (r8 discipline, re-pinned when
    the r17 dtproto rules landed with their sweep's fixes applied)."""
    baseline = Baseline.load(os.path.join(ROOT, "dtlint_baseline.txt"))
    assert baseline.entries == {}, sorted(baseline.entries)


def test_chaos_run_imports_without_side_effects():
    """tools/chaos_run.py must be importable (the linter and tooling
    load it); importing must not spawn work."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_dtlint_import_chaos_run",
        os.path.join(ROOT, "tools", "chaos_run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(getattr(mod, "main"))
