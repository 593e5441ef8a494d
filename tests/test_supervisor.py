"""`ccsx-tpu shepherd` (pipeline/supervisor.py): rank supervision for
sharded runs — launch, heartbeat monitoring, restart-with-backoff,
auto-merge.

THE acceptance case pinned here: a rank SIGKILLed mid-run (rank_death
fault = os._exit at a retirement point) is restarted by the shepherd,
resumes from its shard journal, and the auto-merged output is
byte-identical to the unsharded run — the manual "re-run the dead
rank(s)" instruction in merge_shards, closed into a supervised loop.
"""

import os

import numpy as np
import pytest

from ccsx_tpu import cli, exitcodes
from ccsx_tpu.pipeline import supervisor
from ccsx_tpu.utils import faultinject, synth

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------- units ----------

def test_strip_shepherd_flags():
    argv = ["-A", "--max-rank-restarts", "3", "in.fa",
            "--rank-backoff", "0.5", "--rank-stall-timeout=9", "out.fa",
            "--hosts", "2"]
    assert supervisor.strip_shepherd_flags(argv) == [
        "-A", "in.fa", "out.fa", "--hosts", "2"]


@pytest.mark.parametrize("platforms,chips,n,want", [
    ("cpu", 4, 8, [{}] * 8),      # CPU-pinned children: unaffected
    ("", 0, 3, [{}] * 3),         # no TPU chip on this host
    ("", 4, 2, ["0", "1"]),       # one visible chip per child
    ("", 2, 3, None),             # more children than chips: refuse
])
def test_one_chip_envs(monkeypatch, platforms, chips, n, want):
    monkeypatch.setattr(supervisor, "local_tpu_chips", lambda: chips)
    got = supervisor.one_chip_envs(n, {"JAX_PLATFORMS": platforms})
    if want and isinstance(want[0], str):
        assert [e["TPU_VISIBLE_CHIPS"] for e in got] == want
        assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
                   for e in got)
        assert len({e["TPU_PROCESS_PORT"] for e in got}) == n
    else:
        assert got == want


@pytest.mark.parametrize("launcher", ["shepherd", "serve_fleet"])
def test_launcher_refuses_more_chip_children_than_chips(
        monkeypatch, tmp_path, capsys, launcher):
    """Two processes cannot share a chip: a local launcher asked for
    more chip-using children than the host has chips exits rc 1 before
    spawning anything."""
    monkeypatch.setattr(supervisor, "local_tpu_chips", lambda: 1)
    monkeypatch.setattr(supervisor.subprocess, "Popen", None)  # no spawn
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    if launcher == "shepherd":
        rc = supervisor.shepherd_run(
            "in.fa", str(tmp_path / "o.fa"), 2, ["in.fa", "o.fa"],
            env=env)
    else:
        rc = supervisor.serve_fleet_run(str(tmp_path / "spool"), 2, [],
                                        env=env)
    assert rc == exitcodes.RC_FATAL
    assert "TPU chips" in capsys.readouterr().err


def test_latest_mtime(tmp_path):
    assert supervisor._latest_mtime([str(tmp_path / "nope")]) is None
    a = tmp_path / "a"
    a.write_text("x")
    m = supervisor._latest_mtime([str(a), str(tmp_path / "nope")])
    assert m == a.stat().st_mtime


def test_shepherd_main_validation(tmp_path, capsys):
    out = str(tmp_path / "o.fa")
    # --hosts is required
    assert supervisor.shepherd_main(["in.fa", out]) == exitcodes.RC_FATAL
    assert "--hosts" in capsys.readouterr().err
    # --host-id belongs to the shepherd
    assert supervisor.shepherd_main(
        ["--hosts", "2", "--host-id", "0", "in.fa", out]) == 1
    assert "--host-id" in capsys.readouterr().err
    # stdin/stdout make no sense for a sharded supervised run
    assert supervisor.shepherd_main(["--hosts", "2"]) == 1
    assert "INPUT/OUTPUT" in capsys.readouterr().err
    # rank config errors are refused up front, not N times over
    assert supervisor.shepherd_main(
        ["--hosts", "2", "--batch", "off", "in.fa", out]) == 1
    assert "--batch off" in capsys.readouterr().err
    # the shepherd subcommand is reachable through the main CLI
    assert cli.main(["shepherd", "in.fa", out]) == exitcodes.RC_FATAL


# ---------- THE acceptance case: SIGKILLed rank, restart, merge ----------

@pytest.fixture(scope="module")
def corpus4(tmp_path_factory):
    """4 holes (so rank 1 of 2 owns two holes and rank_death@1 fires
    mid-shard), same 700 bp / 5-pass geometry as the other fault
    suites (shared in-process jit cache for the unsharded reference)."""
    tmp = tmp_path_factory.mktemp("shep")
    rng = np.random.default_rng(0)
    zs = [synth.make_zmw(rng, template_len=700, n_passes=5, movie="mv",
                         hole=str(100 + h)) for h in range(4)]
    fa = tmp / "in.fa"
    fa.write_text(synth.make_fasta(zs))
    ref = tmp / "ref.fa"
    assert cli.main(["-A", "-m", "1000", "--batch", "on",
                     str(fa), str(ref)]) == 0
    return fa, ref


@pytest.mark.slow  # ~29s: static-shepherd restart + shard-journal
# resume e2e (r20 budget audit); the restart loop stays tier-1 via
# test_shepherd_exhausted_restarts_fails_cleanly, the supervisor
# reap-then-byte-identical pin via test_fleet.py::
# test_fleet_run_sigkilled_worker_rebalances, and the slow chaos soak
# keeps this exact shepherd_rank_death arm
def test_shepherd_restarts_sigkilled_rank_and_merges(corpus4, tmp_path,
                                                     capsys):
    fa, ref = corpus4
    out = tmp_path / "shep.fa"
    fwd = ["-A", "-m", "1000", "--hosts", "2", str(fa), str(out)]
    rc = supervisor.shepherd_run(
        str(fa), str(out), 2, fwd,
        max_restarts=2, backoff_s=0.1, poll_s=0.1,
        env=dict(os.environ, CCSX_JOURNAL_FSYNC_S="0"),
        # attempt 0 of rank 1 dies (os._exit 57) after its first
        # retired hole; the restart runs CLEAN (CCSX_FAULTS stripped)
        # and resumes from the shard journal
        first_launch_env={1: {"CCSX_FAULTS": "rank_death@1"}})
    err = capsys.readouterr().err
    assert rc == 0, err
    assert out.read_bytes() == ref.read_bytes()
    assert f"died (rc {faultinject.EXIT_CODE})" in err
    assert "restarting in" in err
    assert "merged 4 records" in err
    # the rank logs survive for postmortems; rank 1 has two attempts
    log1 = (out.parent / "shep.fa.shard1.log").read_text()
    assert "attempt 0" in log1 and "attempt 1" in log1
    # the injected fault actually fired in attempt 0
    assert "rank_death" in log1


@pytest.mark.slow  # ~25s: full-shepherd budget-accounting A/B (r16
# budget audit; r20 moved the sigkilled-restart e2e slow too — the
# tier-1 keepers are named on its mark)
def test_shepherd_drained_rank_is_not_charged_a_restart(corpus4,
                                                        tmp_path,
                                                        capsys):
    """Satellite fix: a rank that exits rc 75 (SIGTERM graceful drain,
    journal durable) is a VOLUNTARY preemption — the shepherd must
    relaunch it immediately without spending the restart budget or
    backoff.  Before the fix a drained rank burned --max-rank-restarts
    like a crash, so a maintenance drain could fail the whole run."""
    fa, ref = corpus4
    out = tmp_path / "drain.fa"
    fwd = ["-A", "-m", "1000", "--hosts", "2", str(fa), str(out)]
    rc = supervisor.shepherd_run(
        str(fa), str(out), 2, fwd,
        # zero restart budget: the old (buggy) accounting would fail
        # the rank on its first drain; voluntary preemption must not
        # touch this budget at all
        max_restarts=0, backoff_s=0.1, poll_s=0.1,
        env=dict(os.environ, CCSX_JOURNAL_FSYNC_S="0"),
        first_launch_env={1: {"CCSX_FAULTS": "sigterm@1"}})
    err = capsys.readouterr().err
    assert rc == 0, err
    assert out.read_bytes() == ref.read_bytes()
    assert "voluntary preemption" in err
    assert "drained (rc 75)" in err
    # no restart budget/backoff was spent on the drain
    assert "restarting in" not in err
    # the relaunch is still attempt 0 (preemption, not a restart) and
    # runs clean: the sigterm fault must not re-fire on the relaunch
    log1 = (out.parent / "drain.fa.shard1.log").read_text()
    assert log1.count("attempt 0") == 2 and "attempt 1" not in log1


def test_shepherd_budget_abort_is_not_restarted(corpus4, tmp_path,
                                                capsys):
    """rc 2 (--max-failed-holes exceeded) is deterministic — the
    journal carries the failure count across resumes, so a restart
    would re-abort: the shepherd must fail the rank immediately
    instead of burning its restart budget."""
    fa, _ = corpus4
    out = tmp_path / "o.fa"
    fwd = ["-A", "-m", "1000", "--hosts", "1",
           "--max-failed-holes", "0", str(fa), str(out)]
    rc = supervisor.shepherd_run(
        str(fa), str(out), 1, fwd,
        max_restarts=3, backoff_s=0.05, poll_s=0.05,
        first_launch_env={0: {"CCSX_FAULTS": "compute@1+"}})
    # the taxonomy survives supervision: a budget abort is rc 2 from
    # the shepherd too, not a generic rc 1
    assert rc == exitcodes.RC_FAILED_HOLES
    err = capsys.readouterr().err
    assert "not restartable" in err
    # exactly one launch: no restart attempts were burned
    log0 = (tmp_path / "o.fa.shard0.log").read_text()
    assert "attempt 0" in log0 and "attempt 1" not in log0


def test_shepherd_exhausted_restarts_fails_cleanly(corpus4, tmp_path,
                                                   capsys):
    """A rank that dies on EVERY launch (fault armed via base env, so
    restarts inherit it... except the shepherd strips CCSX_FAULTS on
    restarts — so here we make the rank die structurally instead: its
    output directory is unwritable) exhausts max_restarts and the
    shepherd fails with rc 1, naming the rank."""
    fa, _ = corpus4
    dead_dir = tmp_path / "ro"
    dead_dir.mkdir()
    out = dead_dir / "sub" / "o.fa"   # parent dir missing: rank rc 1
    fwd = ["-A", "-m", "1000", "--hosts", "1", str(fa), str(out)]
    rc = supervisor.shepherd_run(
        str(fa), str(out), 1, fwd,
        max_restarts=1, backoff_s=0.05, poll_s=0.05)
    assert rc == exitcodes.RC_FATAL
    err = capsys.readouterr().err
    assert "exhausted" in err and "rank 0" in err
