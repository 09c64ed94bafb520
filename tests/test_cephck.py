"""cephck engine + rule tests.

Every rule must demonstrate its bug: at least one red fixture it
flags and one green fixture it stays silent on
(tests/fixtures/cephck/).  On top of the corpus, the whole tree must
scan clean under the committed baseline — the same gate
scripts/check_green.sh --static ships on.
"""
import json
import pathlib
import subprocess
import sys

import pytest

from ceph_tpu.analysis import ALL_RULES
from ceph_tpu.analysis.engine import (BaselineError, Engine,
                                      load_baseline, repo_root,
                                      sarif_report)

ROOT = repo_root(pathlib.Path(__file__).resolve())
FIXTURES = ROOT / "tests" / "fixtures" / "cephck"

#: rule id -> fixture stem (red = must flag, green = must not)
RULE_FIXTURES = {
    "raw-lock": "raw_lock",
    "wire-drift": "wire_drift",
    "unregistered-message": "unregistered_message",
    "txn-atomicity": "osd/txn_atomicity",
    "silent-thread": "silent_thread",
    "jax-timing": "jax_timing",
    "jit-static": "jit_static",
    "bare-except": "bare_except",
    # device-contract family (cephck v2) — the host-sync and
    # implicit-transfer rules are scoped to the EC/CRUSH hot path, so
    # their fixtures live under ec/ (same trick as osd/txn_atomicity)
    "host-sync-hot-path": "ec/host_sync",
    "jit-retrace-churn": "jit_retrace",
    "tracer-leak": "tracer_leak",
    "implicit-transfer": "ec/implicit_transfer",
    # concurrency family (racecheck's static half)
    "guarded-by": "guarded_by",
    "blocking-in-dispatch": "blocking_dispatch",
    # error-contract family (errcheck's static half)
    "swallowed-error": "swallowed_error",
    "errno-conflation": "errno_conflation",
    "reply-on-all-paths": "reply_on_all_paths",
    "bare-retry": "bare_retry",
}


def scan(path: pathlib.Path, baseline=None) -> list:
    eng = Engine([cls() for cls in ALL_RULES], ROOT,
                 suppressions=baseline or [])
    return list(eng.check_file(path)), eng


def rules_hit(path: pathlib.Path) -> set:
    findings, _ = scan(path)
    return {f.rule for f in findings}


def test_every_rule_has_fixtures():
    assert {r.id for r in (cls() for cls in ALL_RULES)} == \
        set(RULE_FIXTURES)


@pytest.mark.parametrize("rule", sorted(RULE_FIXTURES))
def test_red_fixture_flags(rule):
    red = FIXTURES / f"{RULE_FIXTURES[rule]}_red.py"
    assert rule in rules_hit(red), f"{red.name} must trip {rule}"


@pytest.mark.parametrize("rule", sorted(RULE_FIXTURES))
def test_green_fixture_passes(rule):
    green = FIXTURES / f"{RULE_FIXTURES[rule]}_green.py"
    assert rule not in rules_hit(green), \
        f"{green.name} must NOT trip {rule}"


def test_red_fixtures_are_otherwise_clean():
    """A red fixture demonstrates ITS bug, not a pile of them — any
    other rule firing on it means the fixture (or a rule) drifted."""
    for rule, stem in RULE_FIXTURES.items():
        extra = rules_hit(FIXTURES / f"{stem}_red.py") - {rule}
        assert not extra, f"{stem}_red.py also trips {extra}"


def test_green_fixtures_are_fully_clean():
    for stem in RULE_FIXTURES.values():
        hit = rules_hit(FIXTURES / f"{stem}_green.py")
        assert not hit, f"{stem}_green.py trips {hit}"


# ------------------------------------------------------- rule details

def test_wire_drift_catches_removal_retype_and_compat():
    findings, _ = scan(FIXTURES / "wire_drift_red.py")
    msgs = {f.symbol: f.message for f in findings
            if f.rule == "wire-drift"}
    # dropping a mid-list field shifts every later one: reported as a
    # positional mismatch at the first diverging slot
    assert "breaks positional decode" in msgs["SnapTrim"]
    assert "retyped" in msgs["SnapTrimReply"]
    assert "compat" in msgs["SnapTrimPurged"]


def test_wire_drift_append_needs_version_bump(tmp_path):
    """Appending a field is the LEGAL evolution — but only with a
    version bump; same-version append is drift."""
    src = (FIXTURES / "wire_drift_green.py").read_text()
    appended = src.replace("    from_osd: int = -1\n",
                           "    from_osd: int = -1\n"
                           "    extra: int = 0\n", 1)
    bad = tmp_path / "append_same_version.py"
    bad.write_text(appended)
    findings, _ = scan(bad)
    assert any(f.rule == "wire-drift" and "version bump" in f.message
               for f in findings)
    good = tmp_path / "append_bumped.py"
    good.write_text(appended + '\n_VERSIONS = {"SnapTrim": (2, 1)}\n')
    findings, _ = scan(good)
    assert not [f for f in findings if f.rule == "wire-drift"]


def test_inline_ignore_waives_a_finding(tmp_path):
    p = tmp_path / "ign.py"
    p.write_text("try:\n    pass\n"
                 "except:  # cephck: ignore[bare-except]\n    pass\n")
    findings, _ = scan(p)
    assert not findings


# ------------------------------------------- cross-module pass (v2)

def test_host_sync_flags_callee_through_call_graph():
    """The cross-module half: the loop itself is sync-free, but it
    calls a helper that .item()s — flagged at the CALLSITE."""
    findings, _ = scan(FIXTURES / "ec" / "host_sync_red.py")
    msgs = [f.message for f in findings
            if f.rule == "host-sync-hot-path"]
    assert any("callee host-syncs" in m for m in msgs), msgs


def test_host_sync_scoped_to_hot_path(tmp_path):
    """The same source OUTSIDE ec//crush//osd-EC paths is silent —
    the rule polices the hot path, not the whole tree."""
    src = (FIXTURES / "ec" / "host_sync_red.py").read_text()
    p = tmp_path / "not_hot.py"
    p.write_text(src)
    assert "host-sync-hot-path" not in rules_hit(p)


def test_project_context_resolves_imported_jit(tmp_path):
    """implicit-transfer recognizes a jit wrapper IMPORTED from
    another scanned module — the cross-module jit registry."""
    from ceph_tpu.analysis.engine import collect_files  # noqa: F401
    pkg = tmp_path / "ec"
    pkg.mkdir()
    (pkg / "kern.py").write_text(
        "import jax\n\n\n"
        "@jax.jit\n"
        "def gf_mul(a, b):\n"
        "    return a @ b\n")
    (pkg / "plug.py").write_text(
        "import jax\n"
        "import numpy as np\n\n"
        "from ec.kern import gf_mul\n\n\n"
        "def encode(data):\n"
        "    table = np.zeros((8, 8), dtype=np.int8)\n"
        "    return gf_mul(table, data)\n")
    eng = Engine([cls() for cls in ALL_RULES], tmp_path)
    eng.run([str(pkg)])
    hits = [f for f in eng.findings if f.rule == "implicit-transfer"]
    assert len(hits) == 1 and hits[0].path.endswith("plug.py"), \
        [f.render() for f in eng.findings]


def test_guarded_by_flags_minority_access_and_covers_helpers():
    findings, _ = scan(FIXTURES / "guarded_by_red.py")
    hits = [f for f in findings if f.rule == "guarded-by"]
    # exactly the drain() accesses — the locked majority and the
    # covered-helper pattern stay silent (green fixture proves the
    # latter end to end)
    assert hits and all(f.symbol == "PGMetaTable.drain" for f in hits)
    assert all("self._lock" in f.message for f in hits)


def test_blocking_in_dispatch_local_and_cross_function():
    findings, _ = scan(FIXTURES / "blocking_dispatch_red.py")
    msgs = [f.message for f in findings
            if f.rule == "blocking-in-dispatch"]
    assert any("time.sleep" in m for m in msgs), msgs
    assert any("reaches" in m and "wait" in m for m in msgs), msgs


def test_format_github_emits_workflow_annotations():
    red = FIXTURES / "bare_except_red.py"
    proc = subprocess.run(
        [sys.executable, "-m", "ceph_tpu.analysis",
         "--format", "github", str(red)],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("::error "))
    assert "file=tests/fixtures/cephck/bare_except_red.py" in line
    assert "title=cephck bare-except" in line


def test_format_json_matches_legacy_json_flag():
    red = FIXTURES / "bare_except_red.py"
    out = {}
    for flag in (["--json"], ["--format", "json"]):
        proc = subprocess.run(
            [sys.executable, "-m", "ceph_tpu.analysis",
             *flag, str(red)],
            cwd=ROOT, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 1
        out[tuple(flag)] = json.loads(proc.stdout)
    assert out[("--json",)] == out[("--format", "json")]
    assert out[("--json",)]["findings"][0]["rule"] == "bare-except"


def test_jit_retrace_flags_per_call_static():
    findings, _ = scan(FIXTURES / "jit_retrace_red.py")
    msgs = [f.message for f in findings
            if f.rule == "jit-retrace-churn"]
    assert any("per-call value" in m for m in msgs), msgs
    assert any("compile-per-call" in m for m in msgs), msgs


def test_tracer_leak_flags_self_and_module_state():
    findings, _ = scan(FIXTURES / "tracer_leak_red.py")
    msgs = [f.message for f in findings if f.rule == "tracer-leak"]
    assert any("self.last" in m for m in msgs), msgs
    assert any("_DEBUG_TAPS" in m for m in msgs), msgs


# --------------------------------------- error-contract family details

def test_swallowed_error_flags_pass_and_continue():
    findings, _ = scan(FIXTURES / "swallowed_error_red.py")
    hits = [f for f in findings if f.rule == "swallowed-error"]
    assert len(hits) == 2, [f.render() for f in hits]


def test_errno_conflation_flags_all_three_shapes():
    findings, _ = scan(FIXTURES / "errno_conflation_red.py")
    msgs = [f.message for f in findings if f.rule == "errno-conflation"]
    assert any("return []" in m for m in msgs), msgs
    assert any("size = 0" in m for m in msgs), msgs
    assert any("ENOENT-shaped" in m for m in msgs), msgs


def test_errno_conflation_scoped_out_of_tests(tmp_path):
    """The same source under tests/ (outside the fixture corpus) is
    silent — the error-contract rules police daemon code."""
    src = (FIXTURES / "errno_conflation_red.py").read_text()
    # scoping is by repo-relative path: simulate a tests/ location
    sub = tmp_path / "tests"
    sub.mkdir()
    q = sub / "x.py"
    q.write_text(src)
    eng = Engine([cls() for cls in ALL_RULES], tmp_path)
    hits = [f for f in eng.check_file(q)
            if f.rule in ("errno-conflation", "swallowed-error",
                          "bare-retry", "reply-on-all-paths")]
    assert not hits, [f.render() for f in hits]


def test_reply_on_all_paths_flags_missing_branch_and_bare_return():
    findings, _ = scan(FIXTURES / "reply_on_all_paths_red.py")
    msgs = [f.message for f in findings
            if f.rule == "reply-on-all-paths"]
    assert any("without sending a reply" in m for m in msgs), msgs
    assert any("bare `return`" in m for m in msgs), msgs
    assert any("fall off the end" in m for m in msgs), msgs


def test_bare_retry_points_at_backoff():
    findings, _ = scan(FIXTURES / "bare_retry_red.py")
    msgs = [f.message for f in findings if f.rule == "bare-retry"]
    assert len(msgs) == 2, msgs
    assert all("Backoff" in m for m in msgs), msgs


# --------------------------------------------------- baseline contract

def test_baseline_requires_reasons(tmp_path):
    b = tmp_path / "b.json"
    b.write_text(json.dumps({"suppressions": [
        {"rule": "raw-lock", "path": "x.py"}]}))
    with pytest.raises(BaselineError):
        load_baseline(b)
    b.write_text(json.dumps({"suppressions": [
        {"rule": "raw-lock", "path": "x.py", "reason": "why\nnot"}]}))
    with pytest.raises(BaselineError):
        load_baseline(b)


def test_committed_baseline_is_valid():
    entries = load_baseline(ROOT / ".cephck-baseline.json")
    assert all(e.reason for e in entries)


def test_baseline_suppresses(tmp_path):
    red = FIXTURES / "bare_except_red.py"
    baseline = load_baseline_from({"suppressions": [
        {"rule": "bare-except",
         "path": "tests/fixtures/cephck/bare_except_red.py",
         "reason": "fixture exercise"}]}, tmp_path)
    findings, eng = scan(red, baseline)
    assert not findings and len(eng.suppressed) == 1


def load_baseline_from(data, tmp_path):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps(data))
    return load_baseline(p)


# ------------------------------------------------------ the ship gate

def test_tree_scans_clean():
    """The acceptance gate itself: the full-tree scan is clean under
    the committed baseline (unsuppressed findings fail the build via
    scripts/check_green.sh --static).  In-process — the CLI wrapper
    is covered separately by test_cli_exit_codes."""
    eng = Engine([cls() for cls in ALL_RULES], ROOT,
                 suppressions=load_baseline(
                     ROOT / ".cephck-baseline.json"))
    rc = eng.run(["ceph_tpu", "tests", "scripts", "chip_smoke.py"])
    assert rc == 0, "\n".join(f.render() for f in eng.findings)
    assert not eng.errors, eng.errors
    assert not eng.stale_suppressions(), [
        (s.rule, s.path) for s in eng.stale_suppressions()]


def test_stale_suppression_fails_and_prune_rewrites(tmp_path):
    """Baseline hygiene: a suppression nothing matches FAILS the run
    (exit 1); --prune-baseline rewrites the file dropping exactly the
    stale entries, so the blindfold can only shrink."""
    green = FIXTURES / "bare_except_green.py"
    b = tmp_path / "baseline.json"
    live = {"rule": "bare-except",
            "path": "tests/fixtures/cephck/bare_except_red.py",
            "reason": "fixture exercise"}
    stale = {"rule": "raw-lock",
             "path": "tests/fixtures/cephck/bare_except_green.py",
             "reason": "no longer true"}
    b.write_text(json.dumps({"suppressions": [live, stale]}))
    proc = subprocess.run(
        [sys.executable, "-m", "ceph_tpu.analysis",
         "--baseline", str(b), str(green)],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "stale suppression" in proc.stderr
    proc = subprocess.run(
        [sys.executable, "-m", "ceph_tpu.analysis",
         "--baseline", str(b), "--prune-baseline", str(green)],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    kept = json.loads(b.read_text())["suppressions"]
    # the stale entry went; the (unscanned, hence not-stale) live
    # entry survives the rewrite untouched
    assert kept == [live], kept


def test_cli_exit_codes():
    """CLI contract: 1 on findings, 0 on a clean file."""
    red = FIXTURES / "bare_except_red.py"
    green = FIXTURES / "bare_except_green.py"
    proc = subprocess.run(
        [sys.executable, "-m", "ceph_tpu.analysis", str(red)],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "bare-except" in proc.stdout
    proc = subprocess.run(
        [sys.executable, "-m", "ceph_tpu.analysis", str(green)],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_sarif_output_schema_and_escaping():
    """--format sarif: a valid SARIF 2.1.0 log whose results point at
    the right file/line, with rule metadata for every fired rule and
    json-level escaping of hostile message content."""
    red = FIXTURES / "bare_except_red.py"
    proc = subprocess.run(
        [sys.executable, "-m", "ceph_tpu.analysis", "--format", "sarif",
         str(red)],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    log = json.loads(proc.stdout)          # must parse as-is
    assert log["version"] == "2.1.0"
    assert log["$schema"].endswith("sarif-2.1.0.json")
    run = log["runs"][0]
    assert run["tool"]["driver"]["name"] == "cephck"
    results = run["results"]
    assert results, "red fixture must produce results"
    rule_ids = [r["id"] for r in run["tool"]["driver"]["rules"]]
    for res in results:
        assert res["level"] == "error"
        # ruleIndex must agree with the driver rules table
        assert rule_ids[res["ruleIndex"]] == res["ruleId"]
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith(
            "bare_except_red.py")
        assert loc["region"]["startLine"] >= 1
    assert any(r["ruleId"] == "bare-except" for r in results)
    assert run["invocations"][0]["executionSuccessful"] is True


def test_sarif_report_escapes_hostile_messages():
    """Messages carrying quotes, newlines, %-sequences and non-ascii
    must survive the emit -> parse round trip byte-exact (json.dumps
    owns the escaping; this pins that no manual mangling creeps in)."""
    import dataclasses as _dc
    from ceph_tpu.analysis.engine import Finding
    nasty = 'quote " backslash \\ newline \n percent %0A tab \t \u00e9'
    f = Finding(rule="bare-except", path='a "b"/c.py', line=3,
                symbol="f", message=nasty)
    rules = [cls() for cls in ALL_RULES]
    log = sarif_report(rules, [f], errors=["boom \n %25"],
                       stale=[])
    text = json.dumps(log)
    back = json.loads(text)
    res = back["runs"][0]["results"][0]
    assert res["message"]["text"] == nasty
    assert res["locations"][0]["physicalLocation"][
        "artifactLocation"]["uri"] == 'a "b"/c.py'
    notes = back["runs"][0]["invocations"][0][
        "toolExecutionNotifications"]
    assert notes[0]["message"]["text"] == "boom \n %25"
    assert back["runs"][0]["invocations"][0][
        "executionSuccessful"] is False
    # only fired rules appear in the driver table, with descriptions
    table = back["runs"][0]["tool"]["driver"]["rules"]
    assert [r["id"] for r in table] == ["bare-except"]
    assert table[0]["shortDescription"]["text"]


def test_no_raw_locks_outside_lockdep():
    """Belt + suspenders for the acceptance criterion: zero raw
    threading.Lock/RLock/Condition constructions outside
    common/lockdep.py (grep-level, independent of the rule code)."""
    import re
    pat = re.compile(r"threading\.(R?Lock|Condition)\(")
    offenders = []
    for d in ("ceph_tpu", "tests", "scripts"):
        for f in (ROOT / d).rglob("*.py"):
            if "fixtures" in f.parts or "__pycache__" in f.parts:
                continue
            if f.name == "lockdep.py":
                continue
            for i, line in enumerate(f.read_text().splitlines(), 1):
                if pat.search(line):
                    offenders.append(f"{f}:{i}")
    assert not offenders, offenders
