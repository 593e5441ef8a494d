"""Tier-1 gate for the static-analysis plane (ccsx_tpu/lint/).

Three contracts:

- the TREE IS CLEAN: the repo-native checkers over ccsx_tpu/ against
  the committed baseline produce zero unsuppressed findings, in a
  subprocess that also proves the no-jax discipline (the linter must
  cost seconds of the 870s tier-1 budget, not a jax import);
- the FIXTURE CORPUS pins each checker both ways: the known-bad twin
  (including BOTH historical int32-wrap expressions, verbatim) MUST
  flag, the minimal-fix sibling MUST NOT — false-negative and
  false-positive guards in one parametrized table;
- the SUPPRESSION machinery is itself tested: inline pragmas, baseline
  matching (by stripped line text, not line number), stale-entry
  detection, and the every-entry-needs-a-reason rule.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from ccsx_tpu.lint import checks_schema, core
from ccsx_tpu.lint.core import Finding

ROOT = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).parent / "lint_fixtures"


def _lint_fixture(relfile: str, check: str):
    findings, _ = core.lint_file(FIXTURES / relfile, relfile)
    return [f for f in findings if f.check == check]


# ---- the tree is clean (and the linter is jax-free) ------------------------


def test_tree_clean_no_jax_subprocess():
    code = (
        "import sys\n"
        "from ccsx_tpu.lint.core import lint_main\n"
        "rc = lint_main([])\n"
        "assert 'jax' not in sys.modules, 'linter imported jax'\n"
        "sys.exit(rc)\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, (
        f"unsuppressed lint findings (or jax import) — fix them or "
        f"baseline with a justification:\n{proc.stdout}{proc.stderr}")


def test_committed_baseline_valid_and_not_stale():
    entries = core.load_baseline(ROOT / core.BASELINE_NAME)
    assert entries, "committed baseline missing or empty"
    res = core.run_lint(ROOT, baseline=entries)
    assert res.clean, [f.format() for f in res.findings]
    assert not res.stale_baseline, (
        f"baseline entries that no longer match anything — delete "
        f"them: {res.stale_baseline}")


def test_real_tree_schema_contract():
    found = list(checks_schema.check_tree(ROOT / "ccsx_tpu",
                                          "ccsx_tpu/"))
    assert found == [], [f.format() for f in found]


# ---- fixture corpus: bad twin flags, fixed sibling doesn't -----------------

CORPUS = [
    ("ops/overflow_bad.py", "int32-overflow", 3),
    ("ops/overflow_ok.py", "int32-overflow", 0),
    ("crashsafe/lease.py", "bare-write", 2),
    ("crashsafe/spool_writer_bad.py", "bare-write", 1),
    ("crashsafe_ok/lease.py", "bare-write", 0),
    ("concurrency/metrics_bad.py", "metrics-lock", 2),
    ("concurrency/metrics_bad.py", "contextvar-restore", 1),
    ("concurrency/metrics_ok.py", "metrics-lock", 0),
    ("concurrency/metrics_ok.py", "contextvar-restore", 0),
]


@pytest.mark.parametrize("relfile,check,expected", CORPUS)
def test_fixture_corpus(relfile, check, expected):
    findings = _lint_fixture(relfile, check)
    assert len(findings) == expected, [f.format() for f in findings]


def test_historical_wrap_expressions_flag_verbatim():
    """Both shipped int32 wraps — the pre-r11 _line_interp product and
    the pre-r14 compute_offsets re-derivation — must flag as written."""
    texts = {f.text for f in _lint_fixture("ops/overflow_bad.py",
                                           "int32-overflow")}
    assert "return ip * span // denom" in texts
    assert ("nom_j = lj0 + (i - li0) * (lj1 - lj0) "
            "// jnp.maximum(li1 - li0, 1)") in texts


def test_schema_fixture_both_directions():
    bad = list(checks_schema.check_tree(FIXTURES / "schema_bad"))
    msgs = " | ".join(f.message for f in bad)
    assert len(bad) == 2, [f.format() for f in bad]
    assert "missing_key" in msgs      # consumed but never emitted
    assert "orphan_key" in msgs       # emitted but never exported
    assert checks_schema.check_tree(FIXTURES / "schema_ok") == []


# ---- suppression machinery -------------------------------------------------


def test_pragma_suppresses_only_named_check(tmp_path):
    src = (
        "import contextvars\n"
        "_v = contextvars.ContextVar('v')\n\n\n"
        "def set_only(x):\n"
        "    _v.set(x)  # lint: ok[contextvar-restore] fixture pragma\n\n\n"
        "def set_wrong_id(x):\n"
        "    _v.set(x)  # lint: ok[bare-write] wrong id\n"
    )
    p = tmp_path / "mod.py"
    p.write_text(src)
    findings, pragma_n = core.lint_file(p, "mod.py")
    assert pragma_n == 1
    assert [f.line for f in findings
            if f.check == "contextvar-restore"] == [10]


def test_baseline_matches_by_line_text_and_reports_stale():
    f1 = Finding("metrics-lock", "a.py", 3, 0, "m", "metrics.x += 1")
    f2 = Finding("metrics-lock", "a.py", 9, 0, "m", "metrics.y += 1")
    entries = [
        {"check": "metrics-lock", "file": "a.py",
         "match": "metrics.x += 1", "reason": "single writer"},
        {"check": "metrics-lock", "file": "gone.py",
         "match": "metrics.z += 1", "reason": "stale"},
    ]
    kept, n, stale = core.apply_baseline([f1, f2], entries)
    assert kept == [f2] and n == 1
    assert [e["file"] for e in stale] == ["gone.py"]


def test_baseline_entry_requires_reason(tmp_path):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps({"entries": [
        {"check": "bare-write", "file": "x.py", "match": "open(p)",
         "reason": " "}]}))
    with pytest.raises(ValueError):
        core.load_baseline(p)


# ---- CLI surfaces ----------------------------------------------------------


def test_cli_lint_json_and_gauge(tmp_path, capsys):
    from ccsx_tpu import cli

    gauge = tmp_path / "lint_gauge.json"
    rc = cli.main(["lint", "--json", "--gauge-file", str(gauge),
                   "--root", str(ROOT)])
    data = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert data["findings"] == []
    assert data["gauge"]["lint_findings"] == 0
    assert data["suppressed"]["baseline"] >= 1  # the committed triage
    assert json.loads(gauge.read_text()) == {"lint_findings": 0}


def test_lint_findings_prometheus_gauge():
    """The dashboard path: a populated lint_findings rides snapshot()
    into the /metrics rendering like any other gauge."""
    from ccsx_tpu.utils import telemetry
    from ccsx_tpu.utils.metrics import Metrics

    m = Metrics()
    assert m.snapshot()["lint_findings"] is None  # clean: no sample
    m.bump(lint_findings=5)
    text = telemetry.render_prometheus(m.snapshot())
    assert "ccsx_lint_findings 5" in text
    assert "# TYPE ccsx_lint_findings gauge" in text
