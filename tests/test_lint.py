"""House-rule linter tests: the fixture corpus (every bad snippet one
finding with file:line, every good twin clean), the suppression idiom,
the schema-kind registry plumbing, the JSON report shape — and the gate
itself: the repo at HEAD must lint clean (`make lint` inside tier-1)."""

import json
from pathlib import Path

import pytest

from shallowspeed_tpu.analysis import lint as lint_cli
from shallowspeed_tpu.analysis.rules import (
    Scope,
    lint_file,
    lint_source,
    load_schema_kinds,
    scope_for,
)

FIXTURES = Path(__file__).parent / "lint_fixtures"

# (fixture stem, expected rule, scope override forcing the path-scoped
# rules on — fixture files live under tests/, outside the real scopes)
CORPUS = (
    ("broad_except", "BLE001", Scope()),
    ("metrics_nan", "SSP002", Scope(metrics_path=True)),
    ("raw_write", "SSP003", Scope(atomic_module=True)),
    ("donation", "SSP004", Scope()),
    ("kind_registry", "SSP005", Scope()),
    ("lock_discipline", "SSP006", Scope()),
)


def _marker_line(path):
    for i, line in enumerate(path.read_text().splitlines(), start=1):
        if "# MARK" in line:
            return i
    raise AssertionError(f"{path}: no # MARK line")


@pytest.mark.parametrize("stem,rule,scope", CORPUS, ids=[c[0] for c in CORPUS])
def test_bad_fixture_produces_exactly_one_finding(stem, rule, scope):
    """Each known-bad snippet yields EXACTLY one finding, of the expected
    rule, anchored at the marked file:line — the refusal is actionable."""
    path = FIXTURES / "bad" / f"{stem}.py"
    findings = lint_file(path, scope=scope)
    assert len(findings) == 1, findings
    f = findings[0]
    assert f.rule == rule
    assert f.path == str(path)
    assert f.line == _marker_line(path)
    assert f"{path}:{f.line}" in f.format()


@pytest.mark.parametrize("stem,rule,scope", CORPUS, ids=[c[0] for c in CORPUS])
def test_good_twin_is_clean(stem, rule, scope):
    findings = lint_file(FIXTURES / "good" / f"{stem}.py", scope=scope)
    assert findings == [], [f.format() for f in findings]


def test_repo_is_lint_clean():
    """The gate: `make lint` must exit 0 on HEAD — every rule the linter
    enforces holds (or is justified) across the whole lintable tree.
    Running it here puts the lint gate inside tier-1."""
    findings, n_files = lint_cli.lint_paths()
    assert n_files > 40  # the real tree, not an accidental empty walk
    assert findings == [], "\n".join(f.format() for f in findings)


def test_justified_noqa_suppresses_and_bare_noqa_does_not():
    bad = "try:\n    pass\nexcept Exception:  {}\n    pass\n"
    justified = bad.format("# noqa: BLE001 — probe only, absence is fine")
    assert lint_source(justified, path="x.py") == []
    bare = bad.format("# noqa: BLE001")
    assert [f.rule for f in lint_source(bare, path="x.py")] == ["BLE001"]
    wrong_rule = bad.format("# noqa: SSP002 — not the rule that fired")
    assert [f.rule for f in lint_source(wrong_rule, path="x.py")] == ["BLE001"]


def test_broad_except_that_reraises_is_lawful():
    src = (
        "try:\n    pass\n"
        "except BaseException:\n    cleanup = 1\n    raise\n"
    )
    assert lint_source(src, path="x.py") == []


def test_non_literal_kind_is_refused():
    src = (
        "class R:\n"
        "    def _emit(self, r):\n        pass\n"
        "    def go(self, kind):\n"
        "        self._emit({'kind': kind, 'name': 'x'})\n"
    )
    findings = lint_source(src, path="x.py")
    assert [f.rule for f in findings] == ["SSP005"]
    assert "string literal" in findings[0].message


def test_schema_kinds_registry_matches_metrics():
    """The AST-parsed registry equals the imported one — the linter's
    ground truth can never drift from what the recorders actually emit."""
    from shallowspeed_tpu.observability.metrics import (
        SCHEMA_KINDS,
        SCHEMA_VERSION,
    )

    parsed = load_schema_kinds()
    assert parsed == SCHEMA_KINDS
    assert parsed["static_analysis"] == 9
    assert max(parsed.values()) == SCHEMA_VERSION


def test_scope_for_real_paths():
    assert scope_for("shallowspeed_tpu/observability/metrics.py").metrics_path
    assert scope_for("shallowspeed_tpu/serving/engine.py").metrics_path
    assert scope_for("shallowspeed_tpu/checkpoint.py").atomic_module
    assert scope_for("shallowspeed_tpu/trainer.py").donation_ok
    assert scope_for("shallowspeed_tpu/parallel/executor.py").donation_ok
    neutral = scope_for("shallowspeed_tpu/api.py")
    assert not (
        neutral.metrics_path or neutral.atomic_module or neutral.donation_ok
    )


def test_cli_exit_codes_and_json_report(capsys):
    """Exit 2 + file:line text on findings, exit 0 clean, and the stable
    --format json shape (lint_report_version, findings, counts)."""
    bad = str(FIXTURES / "bad" / "broad_except.py")
    good = str(FIXTURES / "good" / "broad_except.py")
    assert lint_cli.main([good]) == 0
    out = capsys.readouterr().out
    assert "clean: 0 findings" in out
    assert lint_cli.main([bad]) == 2
    out = capsys.readouterr().out
    assert f"{bad}:{_marker_line(Path(bad))}" in out and "BLE001" in out
    assert lint_cli.main([bad, "--format", "json"]) == 2
    rep = json.loads(capsys.readouterr().out)
    assert rep["lint_report_version"] == lint_cli.LINT_REPORT_VERSION
    assert rep["files_scanned"] == 1
    assert rep["counts"] == {"BLE001": 1}
    assert rep["findings"][0]["rule"] == "BLE001"
    assert rep["findings"][0]["path"] == bad
    assert rep["findings"][0]["line"] == _marker_line(Path(bad))
    assert lint_cli.main(["/nonexistent/nope.py"]) == 1


def test_cli_metrics_out_records_lint_verdict(tmp_path, capsys):
    """--metrics-out appends the static_analysis record named 'lint'
    with the rule ids and per-rule finding counts (stamped with the
    CURRENT schema version — the pin itself lives with the newest
    schema's test, per the bump convention)."""
    from shallowspeed_tpu.observability import SCHEMA_VERSION, read_jsonl

    bad = str(FIXTURES / "bad" / "broad_except.py")
    out = tmp_path / "lint.jsonl"
    assert lint_cli.main([bad, "--metrics-out", str(out)]) == 2
    capsys.readouterr()
    recs = [r for r in read_jsonl(out) if r["kind"] == "static_analysis"]
    assert len(recs) == 1
    r = recs[0]
    assert r["name"] == "lint" and r["v"] == SCHEMA_VERSION
    assert r["findings"] == 1 and r["by_rule"] == {"BLE001": 1}
    assert r["passes"] == sorted(
        ("BLE001", "SSP002", "SSP003", "SSP004", "SSP005", "SSP006")
    )
    assert any("broad_except.py" in line for line in r["finding_lines"])


def test_syntax_error_is_a_finding_not_a_crash(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n")
    findings = lint_file(p)
    assert [f.rule for f in findings] == ["E999"]


def test_default_targets_exclude_tests():
    """The fixture corpus must never fail the repo gate: tests/ is not in
    the default lint walk."""
    files = lint_cli.iter_target_files()
    assert not any("lint_fixtures" in str(f) for f in files)
    assert not any(f.name == "test_lint.py" for f in files)
    assert any(f.name == "metrics.py" for f in files)
    assert any(f.name == "lowering.py" for f in files)
