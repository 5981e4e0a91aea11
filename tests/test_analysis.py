"""The PR 9 static-analysis layer (repro/analysis/, DESIGN.md §14):
the stdlib-ast contract linter that turns this repo's past bug classes
into machine-checked invariants.  Covers every checker against its
fire/clean fixture pair, the suppression + baseline escape hatches,
the CLI exit-code contract, the JSON artifact round-trip through
``repro.obs.validate --analysis``, and the self-scan gate — ``src/``
must stay clean modulo the committed baseline."""
import json
import os
import subprocess
import sys

import pytest

from repro.analysis import (Baseline, BaselineError, CHECKER_IDS,
                            default_checkers, run)
from repro.analysis.findings import Finding, SuppressionSet
from repro.obs import validate as obs_validate

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "analysis")


def scan(*names, select=None, baseline=None):
    """Run the engine over fixture files; returns the RunResult."""
    paths = [os.path.join(FIXTURES, n) for n in names]
    return run(paths, default_checkers(), baseline=baseline,
               select=select)


def lines_of(result, checker):
    return sorted(f.line for f in result.findings
                  if f.checker == checker)


# ---------------------------------------------------------------------------
# per-checker fixture pairs: each positive fires at the expected lines,
# each clean twin stays silent

FIXTURE_EXPECTATIONS = [
    # (checker id, fire fixture, clean fixture, severity, expected lines)
    ("host-sync", "host_sync_fire.py", "host_sync_clean.py",
     "warn", [10, 11, 20, 27, 28]),
    ("host-aliasing", "host_aliasing_fire.py", "host_aliasing_clean.py",
     "error", [8, 20, 21]),
    ("prng-reuse", "prng_reuse_fire.py", "prng_reuse_clean.py",
     "error", [7, 16, 22]),
    ("pallas-contract", "pallas_contract_fire.py",
     "pallas_contract_clean.py", "error", [23, 34, 41, 51, 65, 77, 92]),
    ("recompile-hazard", "recompile_fire.py", "recompile_clean.py",
     None, [14, 21, 28, 37]),
    ("bit-accounting", "bits_fire.py", "bits_clean.py",
     "warn", [3, 6, 11, 16, 20]),
]


@pytest.mark.parametrize(
    "checker,fire,clean,severity,expected",
    FIXTURE_EXPECTATIONS, ids=[e[0] for e in FIXTURE_EXPECTATIONS])
def test_checker_fires_on_positive_fixture(checker, fire, clean,
                                           severity, expected):
    result = scan(fire, select=[checker])
    assert lines_of(result, checker) == expected, \
        [f.render() for f in result.findings]
    if severity is not None:
        assert all(f.severity == severity for f in result.findings)
    assert all(f.checker == checker for f in result.findings)


@pytest.mark.parametrize(
    "checker,fire,clean,severity,expected",
    FIXTURE_EXPECTATIONS, ids=[e[0] for e in FIXTURE_EXPECTATIONS])
def test_checker_silent_on_clean_fixture(checker, fire, clean,
                                         severity, expected):
    result = scan(clean, select=[checker])
    assert result.findings == [], [f.render() for f in result.findings]
    assert result.suppressed == []


def test_recompile_severities():
    """jit-in-loop and mutable static defaults are errors; jit built
    per step (without the factory-return idiom) is a warning."""
    result = scan("recompile_fire.py", select=["recompile-hazard"])
    by_line = {f.line: f.severity for f in result.findings}
    assert by_line == {14: "error", 21: "error",
                       28: "warn", 37: "warn"}


# ---------------------------------------------------------------------------
# suppression machinery

def test_suppressions_fixture():
    """Justified suppressions silence findings (including across a
    multi-line statement); reason-less or unknown-id suppressions are
    themselves findings and silence nothing they shouldn't."""
    result = scan("suppressions.py")
    sup_lines = sorted(f.line for f in result.suppressed)
    # the multiline finding anchors to the physical line holding the
    # reused key (32), inside the span the standalone comment covers
    assert sup_lines == [10, 32]
    open_prng = lines_of(result, "prng-reuse")
    assert open_prng == [16, 23]          # missing_reason / unknown_id
    sup_findings = sorted(f.line for f in result.findings
                          if f.checker == "suppression")
    assert sup_findings == [16, 22]       # malformed + unknown id


def test_suppression_covers_whole_logical_statement():
    src = ("import jax\n"
           "def f(key, model):\n"
           "    a = jax.random.normal(key, ())\n"
           "    # repro: ignore[prng-reuse] -- callee re-derives\n"
           "    out = model.apply(a,\n"
           "                      key)\n"
           "    return out\n")
    sups = SuppressionSet(src)
    assert len(sups.suppressions) == 1
    sup = sups.suppressions[0]
    assert (sup.line, sup.end_line) == (5, 6)
    hit = Finding("prng-reuse", "x.py", 6, 22, "error", "reused")
    miss = Finding("prng-reuse", "x.py", 3, 8, "error", "reused")
    assert sups.matches(hit)
    assert not sups.matches(miss)


def test_inline_suppression_covers_only_its_line():
    src = ("x = 1  # repro: ignore[host-sync] -- known sync point\n"
           "y = 2\n")
    sups = SuppressionSet(src)
    assert len(sups.suppressions) == 1
    assert sups.matches(Finding("host-sync", "x.py", 1, 0, "warn", "m"))
    assert not sups.matches(Finding("host-sync", "x.py", 2, 0,
                                    "warn", "m"))


def test_suppression_without_reason_is_malformed():
    sups = SuppressionSet("x = 1  # repro: ignore[host-sync]\n")
    assert sups.suppressions == []
    assert len(sups.malformed) == 1


def test_suppression_finding_cannot_self_suppress():
    """A suppression-hygiene finding must not be silenced by the very
    comment it complains about."""
    result = scan("suppressions.py", select=["prng-reuse"])
    assert any(f.checker == "suppression" for f in result.findings)


# ---------------------------------------------------------------------------
# baseline

def test_baseline_is_line_agnostic():
    f = Finding("bit-accounting", "src/x.py", 42, 0, "warn",
                "width literal 32 in bits context")
    b = Baseline([{"checker": f.checker, "path": f.path,
                   "message": f.message,
                   "justification": "legacy wire model, tracked"}])
    assert b.contains(f)
    moved = Finding(f.checker, f.path, 7, 0, f.severity, f.message)
    assert b.contains(moved)
    other = Finding(f.checker, f.path, 42, 0, f.severity, "different")
    assert not b.contains(other)


def test_baseline_rejects_empty_justification():
    with pytest.raises(BaselineError, match="justification"):
        Baseline([{"checker": "host-sync", "path": "a.py",
                   "message": "m", "justification": "  "}])


def test_baseline_load_missing_file_is_empty(tmp_path):
    b = Baseline.load(str(tmp_path / "nope.json"))
    assert not b.contains(Finding("host-sync", "a.py", 1, 0,
                                  "warn", "m"))


def test_baseline_moves_findings_out_of_open():
    result = scan("bits_fire.py", select=["bit-accounting"])
    assert result.findings
    entries = [{"checker": f.checker, "path": f.path,
                "message": f.message,
                "justification": "fixture debt for the test"}
               for f in result.findings]
    again = scan("bits_fire.py", select=["bit-accounting"],
                 baseline=Baseline(entries))
    assert again.findings == []
    assert len(again.baselined) == len(result.findings)


# ---------------------------------------------------------------------------
# CLI contract

def run_cli(*args, cwd=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, cwd=cwd or REPO, env=env)


def test_cli_exit_codes(tmp_path):
    clean = run_cli(os.path.join(FIXTURES, "host_sync_clean.py"),
                    "--baseline", str(tmp_path / "none.json"))
    assert clean.returncode == 0, clean.stderr
    dirty = run_cli(os.path.join(FIXTURES, "prng_reuse_fire.py"),
                    "--baseline", str(tmp_path / "none.json"))
    assert dirty.returncode == 1
    assert "prng-reuse" in dirty.stdout
    missing = run_cli(str(tmp_path / "no_such_dir"))
    assert missing.returncode == 2


def test_cli_list_names_every_checker():
    proc = run_cli("--list")
    assert proc.returncode == 0
    for cid in CHECKER_IDS:
        assert cid in proc.stdout


def test_cli_rejects_unknown_select():
    proc = run_cli("--select", "no-such-checker", FIXTURES)
    assert proc.returncode == 2


def test_cli_update_baseline_skeleton_needs_justifications(tmp_path):
    base = str(tmp_path / "base.json")
    proc = run_cli(os.path.join(FIXTURES, "bits_fire.py"),
                   "--baseline", base, "--update-baseline")
    assert proc.returncode == 0, proc.stderr
    with open(base) as f:
        entries = json.load(f)
    assert entries and all(e["justification"] == "" for e in entries)
    # the skeleton is deliberately unusable until reasons are written
    rerun = run_cli(os.path.join(FIXTURES, "bits_fire.py"),
                    "--baseline", base)
    assert rerun.returncode == 2
    for e in entries:
        e["justification"] = "accepted fixture debt"
    with open(base, "w") as f:
        json.dump(entries, f)
    final = run_cli(os.path.join(FIXTURES, "bits_fire.py"),
                    "--baseline", base)
    assert final.returncode == 0, final.stdout + final.stderr


# ---------------------------------------------------------------------------
# JSON artifact + obs.validate round-trip

def test_artifact_validates_and_counts_statuses(tmp_path):
    out = str(tmp_path / "findings.json")
    proc = run_cli(os.path.join(FIXTURES, "suppressions.py"),
                   "--baseline", str(tmp_path / "none.json"),
                   "--json", out)
    assert proc.returncode == 1
    with open(out) as f:
        doc = json.load(f)
    assert obs_validate.validate_analysis(doc) == []
    kind, errors = obs_validate.validate_file(out)       # auto-detect
    assert (kind, errors) == ("analysis", [])
    assert obs_validate.main(["--analysis", out]) == 0
    statuses = {f["status"] for f in doc["findings"]}
    assert statuses == {"open", "suppressed"}
    assert doc["summary"]["open"] == 4
    assert doc["summary"]["suppressed"] == 2


def test_validate_analysis_rejects_bad_docs():
    assert obs_validate.validate_analysis([]) != []
    base = {"ts": 1.0, "tool": "repro.analysis", "version": 1,
            "paths": ["src"], "findings": [], "summary": {
                "files": 0, "open": 0, "errors": 0, "warnings": 0,
                "suppressed": 0, "baselined": 0}}
    assert obs_validate.validate_analysis(base) == []
    bad_tool = dict(base, tool="other")
    assert any("tool" in e for e in
               obs_validate.validate_analysis(bad_tool))
    bad_finding = dict(base, findings=[{
        "checker": "host-sync", "path": "a.py", "line": 0, "col": 0,
        "severity": "fatal", "message": "m", "status": "open"}])
    errs = obs_validate.validate_analysis(bad_finding)
    assert any("line" in e for e in errs)
    assert any("severity" in e for e in errs)
    drift = dict(base, findings=[{
        "checker": "host-sync", "path": "a.py", "line": 3, "col": 0,
        "severity": "warn", "message": "m", "status": "open"}])
    assert any("summary.open" in e for e in
               obs_validate.validate_analysis(drift))


# ---------------------------------------------------------------------------
# self-scan: the gate CI enforces

def test_self_scan_src_is_clean_modulo_baseline():
    """``python -m repro.analysis src/`` must exit 0 — every remaining
    finding in the repo's own source is either fixed, inline-justified,
    or carries a written justification in the committed baseline."""
    proc = run_cli("src")
    assert proc.returncode == 0, (
        "open findings in src/ — fix them or justify them:\n"
        + proc.stdout + proc.stderr)


def test_registry_ids_are_unique_and_sorted():
    ids = [c.id for c in default_checkers()]
    assert ids == sorted(ids)
    assert len(ids) == len(set(ids))
    assert set(ids) == set(CHECKER_IDS)
