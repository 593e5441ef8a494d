"""Reference-parity harness (benchmarks/parity.py, VERDICT Missing #1):
the stub-binary test that proves the harness runs MECHANICALLY — two
tools invoked, outputs matched per hole, identity + Q20-yield fields
computed — so the first day a real `ccsx` binary is buildable it can
be pointed at the harness with zero new code.

The stub "reference binary" is a shell script that execs this repo's
own CLI, so every parity number must read perfect agreement."""

import json
import os
import stat
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmarks"))

import parity  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def stub_bin(tmp_path_factory):
    """A fake `ccsx`: same CLI contract, implemented by exec'ing our
    own CLI (backend pinned to CPU, the test-suite idiom)."""
    tmp = tmp_path_factory.mktemp("stub")
    p = tmp / "ccsx"
    code = ("import sys, jax; "
            "jax.config.update('jax_platforms', 'cpu'); "
            "from ccsx_tpu.cli import main; "
            "sys.exit(main(sys.argv[1:]))")
    p.write_text("#!/bin/sh\n"
                 f'export PYTHONPATH="{_REPO}:$PYTHONPATH"\n'
                 f'exec "{sys.executable}" -c "{code}" "$@"\n')
    p.chmod(p.stat().st_mode | stat.S_IXUSR)
    return str(p)


def test_parity_missing_binary_refused(tmp_path):
    with pytest.raises(FileNotFoundError, match="not executable"):
        parity.run_parity(str(tmp_path / "nope"), 2, [1])


@pytest.mark.slow  # ~20s: stub-binary harness mechanics (r11 duration audit)
def test_parity_harness_runs_against_stub(stub_bin, tmp_path):
    summary = parity.run_parity(stub_bin, 2, [1], seed=0)
    assert summary["ccsx_bin"] == stub_bin
    [cfg] = summary["configs"]
    assert "error" not in cfg, cfg
    assert cfg["n_holes"] >= 1
    for h in cfg["holes"]:
        # stub == ourselves: byte-level agreement, so identity 1.0
        assert h["emitted_tpu"] and h["emitted_ref"]
        assert h["identity_cross"] == 1.0
        assert h["identity_tpu"] == h["identity_ref"]
        assert h["q20_pred_tpu"] is not None
    assert cfg["n_identical"] == cfg["n_holes"]
    assert summary["mean_identity_cross"] == 1.0
    # the yield delta of a tool against itself is exactly zero
    assert cfg["q20_yield_delta"] == 0.0
    # and the report is JSON-serializable as the CLI would emit it
    json.dumps(summary)


def test_parity_reports_reference_failure(tmp_path):
    """A reference binary that crashes is reported per config, not
    raised — the harness survives partially-broken builds."""
    p = tmp_path / "ccsx"
    p.write_text("#!/bin/sh\necho boom >&2\nexit 3\n")
    p.chmod(p.stat().st_mode | stat.S_IXUSR)
    r = parity.run_config_parity(1, str(p), 2, seed=0)
    assert "error" in r and "rc=3" in r["error"]


@pytest.mark.slow
def test_parity_cli_smoke(stub_bin, tmp_path):
    """(slow: two more cold CLI processes on top of the in-process
    harness test above.)"""
    out = tmp_path / "parity.json"
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "benchmarks", "parity.py"),
         "--ccsx", stub_bin, "--holes", "2", "--configs", "1",
         "--json", str(out)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=_REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert json.loads(out.read_text())["mean_identity_cross"] == 1.0
