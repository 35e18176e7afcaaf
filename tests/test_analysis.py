"""Self-tests for ``repro.analysis``: the fixture suite.

Every rule ID has a known-bad snippet under ``tests/analysis_fixtures/``
(including a reconstruction of the PR-5 carry-shadowing bug) and a clean
twin.  Each pass must fire exactly on its bad fixture — right rule,
right count, right file — and stay silent on the twin.  The CLI tests
pin the exit-code contract the CI gate relies on (0 clean, 1 new
findings, 2 usage error) and the baseline's grandfathering semantics.
"""
from __future__ import annotations

import json
from collections import Counter
from pathlib import Path

import pytest

from repro.analysis import Baseline, Finding, registered_passes, run_analysis
from repro.analysis.__main__ import main as analysis_main

REPO = Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "analysis_fixtures"

# fixture -> exact rule-id multiset it must produce (and nothing else)
CASES = [
    ("units_mix_bad.py", {"UNITS001": 1}),
    ("units_mix_clean.py", {}),
    ("units_literal_bad.py", {"UNITS002": 1}),
    ("units_literal_clean.py", {}),
    ("scan_shadow_bad.py", {"SCAN001": 2}),   # shadow + dead overwrite
    ("scan_shadow_clean.py", {}),
    ("scan_impure_bad.py", {"SCAN002": 1}),
    ("scan_mutate_bad.py", {"SCAN003": 1}),
    ("scan_tracer_bad.py", {"SCAN004": 2}),   # if + float()
    ("scan_clean.py", {}),
    ("lock_cycle_bad.py", {"LOCK001": 1}),
    ("lock_block_bad.py", {"LOCK002": 1}),
    ("lock_stats_bad.py", {"LOCK003": 1}),
    ("lock_clean.py", {}),
    ("parity_bad", {"PARITY001": 1, "PARITY002": 2}),
    ("parity_clean", {}),
    ("race_write_bad.py", {"RACE001": 1}),
    ("race_write_clean.py", {}),
    ("race_rmw_bad.py", {"RACE002": 3}),      # 2 class RMWs + closure RMW
    ("race_rmw_clean.py", {}),
    ("race_cta_bad.py", {"RACE002": 2}),      # check-then-act, both roles
    ("race_cta_clean.py", {}),
    ("race_escape_bad.py", {"RACE003": 1}),
    ("race_escape_clean.py", {}),
]


def _analyze(name: str):
    return run_analysis([FIXTURES / name], root=REPO)


@pytest.mark.parametrize("name,expected", CASES,
                         ids=[c[0] for c in CASES])
def test_fixture_fires_exactly(name, expected):
    res = _analyze(name)
    assert Counter(f.rule for f in res.findings) == Counter(expected)
    for f in res.findings:
        assert f.path.startswith("tests/analysis_fixtures/")
        assert f.severity == "error"
        assert f.line > 0


def test_every_rule_has_a_bad_fixture():
    covered = {rid for _, exp in CASES for rid in exp}
    declared = {rid for ps in registered_passes() for rid in ps.rules}
    assert covered == declared


def test_pr5_reconstruction_both_hazards():
    # the PR-5 bug was two hazards at once: the carry element shadowed
    # the enclosing accumulator AND was overwritten before any read
    msgs = [f.message for f in _analyze("scan_shadow_bad.py").findings]
    assert any("shadows" in m for m in msgs)
    assert any("overwritten before" in m for m in msgs)
    assert all("'win'" in m for m in msgs)


def test_pr6_reconstruction_stats_buffering():
    # the PR-6 bug: sweep threads flushed stats counters with no guard;
    # the fixture reconstructs it and the RACE pass must name both
    # counters plus the function-scope twin of the same bug class
    msgs = [f.message for f in _analyze("race_rmw_bad.py").findings]
    assert any("'self.wakeups'" in m for m in msgs)
    assert any("'self.items'" in m for m in msgs)
    assert any("closed-over 'total'" in m for m in msgs)


def test_race_messages_name_roles_and_methods():
    write = _analyze("race_write_bad.py").findings[0]
    assert "_poll" in write.message          # the thread role
    assert "Telemetry" in write.message      # the class
    escape = _analyze("race_escape_bad.py").findings[0]
    assert "__init__" in escape.message      # where the late write lives


def test_clean_twins_are_parseable_python():
    # fixtures must stay real code: a syntax error would be silently
    # skipped by collect_files and turn every assertion above vacuous
    res = run_analysis([FIXTURES], root=REPO)
    assert len(res.files) == len(list(FIXTURES.rglob("*.py")))


# -- fingerprints and baseline semantics ------------------------------------


def _finding(line=1, message="m"):
    return Finding(rule="UNITS001", severity="error",
                   path="src/x.py", line=line, col=0, message=message)


def test_fingerprint_ignores_line_numbers():
    assert _finding(line=1).fingerprint == _finding(line=99).fingerprint
    assert (_finding(message="a").fingerprint
            != _finding(message="b").fingerprint)


def test_baseline_is_a_multiset():
    f = _finding()
    baseline = Baseline.from_findings([f])
    new, old = baseline.split([f, f])
    assert len(old) == 1 and len(new) == 1


def test_baseline_roundtrip(tmp_path):
    f = _finding()
    p = tmp_path / "b.json"
    Baseline.from_findings([f]).save(p)
    new, old = Baseline.load(p).split([f])
    assert not new and len(old) == 1


# -- CLI exit-code contract (what the CI gate runs) -------------------------


def test_cli_repo_is_clean():
    # the committed baseline is empty for runtime/core by construction
    # (ISSUE satellite: real findings were fixed, not grandfathered)
    assert analysis_main(["--paths", str(REPO / "src" / "repro")]) == 0


def test_cli_bad_fixture_exits_one(tmp_path, capsys):
    rc = analysis_main(["--paths", str(FIXTURES / "units_mix_bad.py"),
                        "--baseline", str(tmp_path / "b.json")])
    out = capsys.readouterr().out
    assert rc == 1
    assert "UNITS001" in out


def test_cli_json_format(tmp_path, capsys):
    rc = analysis_main(["--paths", str(FIXTURES / "lock_stats_bad.py"),
                        "--baseline", str(tmp_path / "b.json"),
                        "--format", "json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert payload["schema"] == "repro-analysis/1"
    assert not payload["ok"]
    assert [f["rule"] for f in payload["new"]] == ["LOCK003"]


def test_cli_update_baseline_grandfathers(tmp_path, capsys):
    bad = str(FIXTURES / "scan_impure_bad.py")
    baseline = str(tmp_path / "b.json")
    assert analysis_main(["--paths", bad, "--baseline", baseline,
                          "--update-baseline"]) == 0
    # grandfathered: same findings no longer gate...
    assert analysis_main(["--paths", bad, "--baseline", baseline]) == 0
    # ...but a finding outside the baseline still does
    rc = analysis_main(["--paths", bad,
                        str(FIXTURES / "scan_mutate_bad.py"),
                        "--baseline", baseline])
    capsys.readouterr()
    assert rc == 1


def test_cli_json_rule_counts(tmp_path, capsys):
    rc = analysis_main(["--paths", str(FIXTURES / "race_rmw_bad.py"),
                        "--baseline", str(tmp_path / "b.json"),
                        "--format", "json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert payload["rule_counts"] == {"RACE002": 3}
    assert "RACE001" in payload["rules_known"]


def test_cli_since_scopes_and_intersects(monkeypatch, tmp_path, capsys):
    # the git diff says two files changed; only the one under --paths
    # may be scanned (a changed src file must not leak into a
    # fixtures-scoped run)
    import repro.analysis.__main__ as cli
    changed = [FIXTURES / "units_mix_bad.py",
               REPO / "src" / "repro" / "analysis" / "core.py"]
    monkeypatch.setattr(cli, "_changed_files", lambda root, since: changed)
    rc = cli.main(["--since", "some-rev", "--paths", str(FIXTURES),
                   "--baseline", str(tmp_path / "b.json")])
    out = capsys.readouterr().out
    assert rc == 1
    assert "UNITS001" in out
    assert "1 file(s) scanned" in out


def test_cli_bad_revision_exits_two(capsys):
    rc = analysis_main(["--since", "definitely-not-a-revision"])
    err = capsys.readouterr().err
    assert rc == 2
    assert "git diff" in err


def test_cli_since_conflicts_with_changed_only():
    with pytest.raises(SystemExit) as ei:
        analysis_main(["--since", "HEAD", "--changed-only"])
    assert ei.value.code == 2


def test_cli_missing_path_exits_two(capsys):
    rc = analysis_main(["--paths", str(FIXTURES / "no_such_file.py")])
    capsys.readouterr()
    assert rc == 2


def test_cli_corrupt_baseline_exits_two(tmp_path, capsys):
    bad = tmp_path / "b.json"
    bad.write_text("not json{")
    rc = analysis_main(["--paths", str(FIXTURES / "scan_clean.py"),
                        "--baseline", str(bad)])
    err = capsys.readouterr().err
    assert rc == 2
    assert "unreadable baseline" in err
