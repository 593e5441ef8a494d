"""Fault-tolerance layer (ARCHITECTURE.md "Failure domains"): failure
taxonomy + adaptive OOM resplit in the batched executor, crash-safe
journal v2 (torn-tail truncation, fingerprint compatibility), the
deterministic fault-injection harness, and per-shard completion markers.

The load-bearing guarantees pinned here: an injected device OOM degrades
to a resplit (or, persistent, to the host path) with BYTE-IDENTICAL
output; a kill between a flushed write and the journal update resumes to
byte-identical output with no duplicated or dropped holes; a dead shard
is named by merge_shards instead of silently shortening the merge.

All CLI tests share ONE synthetic corpus and ONE no-fault reference run
(module-scoped fixture): every recovery path must reproduce those exact
bytes, and sharing the compiled shapes keeps the file cheap in tier-1.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from ccsx_tpu import cli
from ccsx_tpu.io import fastx
from ccsx_tpu.parallel import distributed as dist
from ccsx_tpu.pipeline.batch import classify_failure
from ccsx_tpu.utils import faultinject, synth
from ccsx_tpu.utils.journal import Journal

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    faultinject.disarm()
    yield
    faultinject.disarm()


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """(input fasta, no-fault reference output) — 3 holes, one shape
    bucket, batched pipeline.  Every fault test must reproduce the
    reference bytes exactly."""
    tmp = tmp_path_factory.mktemp("faults")
    rng = np.random.default_rng(0)
    zs = [synth.make_zmw(rng, template_len=700, n_passes=5, movie="mv",
                         hole=str(100 + h)) for h in range(3)]
    fa = tmp / "in.fa"
    fa.write_text(synth.make_fasta(zs))
    ref = tmp / "ref.fa"
    assert cli.main(["-A", "-m", "1000", "--batch", "on",
                     str(fa), str(ref)]) == 0
    return fa, ref


def _names(path):
    return [r.name for r in fastx.read_fastx(str(path))]


def _records(path):
    """FASTA text split into whole records (header + one seq line)."""
    lines = path.read_text().splitlines(keepends=True)
    return ["".join(lines[i:i + 2]) for i in range(0, len(lines), 2)]


# ---------- taxonomy + harness units ----------

def test_classify_failure():
    assert classify_failure(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "1073741824 bytes")) == "oom"
    assert classify_failure(RuntimeError("Failed to allocate device "
                                         "buffer")) == "oom"
    assert classify_failure(RuntimeError(
        "Mosaic failed to compile TPU kernel")) == "compile"
    assert classify_failure(NotImplementedError(
        "pallas lowering rule for foo not found")) == "compile"
    assert classify_failure(ValueError("draft longer than tmax")) == "data"
    assert classify_failure(IndexError("oops")) == "data"
    # broad compiler-ish words in ordinary errors must NOT pin the
    # process-wide scan fallback (the markers are deliberately narrow)
    assert classify_failure(TypeError(
        "unsupported operand type(s) for -: 'str' and 'int'")) == "data"
    assert classify_failure(RuntimeError(
        "compilation of x failed")) == "data"
    # our own kernel-config ValueErrors name the kernel but are
    # per-group data conditions, never toolchain failures
    assert classify_failure(ValueError(
        "qmax=2048 exceeds PALLAS_MAX_QMAX; use the scan aligner"
    )) == "data"


def test_faultinject_spec_and_schedule():
    assert faultinject.parse_spec("device_oom@2,write") == {
        "device_oom": [2, False], "write": [1, False]}
    assert faultinject.parse_spec("compute@3+") == {"compute": [3, True]}
    with pytest.raises(ValueError, match="unknown fault point"):
        faultinject.parse_spec("frobnicate@1")
    with pytest.raises(ValueError, match=">= 1"):
        faultinject.parse_spec("write@0")
    with pytest.raises(ValueError, match="bad fault schedule"):
        faultinject.parse_spec("write@x")
    # once-schedule fires exactly on the Nth call
    faultinject.arm("compute@2")
    faultinject.fire("compute")  # call 1: no-op
    with pytest.raises(RuntimeError, match="injected compute fault"):
        faultinject.fire("compute")
    faultinject.fire("compute")  # call 3: past the schedule, no-op
    # repeat-schedule keeps firing
    faultinject.arm("device_oom@1+")
    for _ in range(3):
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            faultinject.fire("device_oom")


def test_bad_env_spec_fails_attributed(monkeypatch):
    """A typo'd CCSX_FAULTS must fail naming the env var (SystemExit),
    not leak a ValueError into the first pipeline stage that fires —
    the drivers would misreport that as an input-stream error."""
    monkeypatch.setenv("CCSX_FAULTS", "wrte@2")
    faultinject._plan = faultinject._UNSET  # force re-init from env
    with pytest.raises(SystemExit, match="CCSX_FAULTS"):
        faultinject.fire("ingest")
    faultinject.fire("ingest")  # after the report: disarmed, no-op


def test_cli_rejects_bad_fault_spec(tmp_path, capsys):
    rc = cli.main(["--inject-faults", "bogus@1", "x.fa",
                   str(tmp_path / "y.fa")])
    assert rc == 1
    assert "--inject-faults" in capsys.readouterr().err


def test_journal_v1_still_accepted(tmp_path):
    """Legacy journals (no version/offsets) keep their cursor and skip
    the v2 verifications."""
    jp = tmp_path / "j.json"
    jp.write_text(json.dumps({"input_id": "in.fa", "holes_done": 5}))
    j = Journal.load_or_create(str(jp), input_id="in.fa",
                               fingerprint="abc-def")
    assert j.holes_done == 5 and j.out_bytes is None
    out = tmp_path / "o.fa"
    out.write_text("anything\n")
    j.verify_output(str(out))  # no offsets recorded: must be a no-op
    assert j.holes_done == 5
    assert out.read_text() == "anything\n"


# ---------- quarantine ----------

def test_compute_fault_quarantines_one_hole(corpus, tmp_path, capsys):
    """One injected per-hole failure costs that hole, never the run —
    in both drivers."""
    fa, _ = corpus
    for batch in ("on", "off"):
        out = tmp_path / f"o_{batch}.fa"
        faultinject.arm("compute@2")
        rc = cli.main(["-A", "-m", "1000", "--batch", batch,
                       str(fa), str(out)])
        assert rc == 0
        assert _names(out) == ["mv/100/ccs", "mv/102/ccs"]
        assert "failed" in capsys.readouterr().err


def test_ingest_fault_clean_rc1(corpus, tmp_path, capsys):
    fa, _ = corpus
    out = tmp_path / "o.fa"
    rc = cli.main(["-A", "-m", "1000", "--batch", "on",
                   "--inject-faults", "ingest@1", str(fa), str(out)])
    assert rc == 1
    assert "invalid input stream" in capsys.readouterr().err


# ---------- OOM resplit / host-fallback ladder ----------

def test_injected_oom_resplit_output_identical(corpus, tmp_path, capsys):
    """A device OOM on a multi-request shape group bisects and retries
    at smaller Z; the output must be byte-identical to the no-fault run
    (per-request results are Z-invariant: padding is masked).

    Inline prep + a pinned admission window: with the background prep
    pool, the first sweep dispatches however many holes prep delivered
    in time — sometimes ONE, whose group cannot resplit (it goes
    straight to host replay) — so the multi-request-group premise was
    a coin flip.  Inline admission fills the window before the first
    sweep, deterministically."""
    fa, ref = corpus
    out = tmp_path / "o.fa"
    faultinject.arm("device_oom@1")
    assert cli.main(["-A", "-m", "1000", "--batch", "on",
                     "--inflight", "8", "--prep-threads", "0",
                     str(fa), str(out)]) == 0
    assert out.read_bytes() == ref.read_bytes()
    assert "resplitting" in capsys.readouterr().err


def test_persistent_oom_falls_back_to_host(corpus, tmp_path, capsys):
    """Every device dispatch OOMing rides the whole ladder down to the
    per-request host replay — and still produces byte-identical output
    (the host path is the spec the fused step mirrors)."""
    fa, ref = corpus
    out = tmp_path / "o.fa"
    m = tmp_path / "m.jsonl"
    faultinject.arm("device_oom@1+")
    assert cli.main(["-A", "-m", "1000", "--batch", "on",
                     "--metrics", str(m), str(fa), str(out)]) == 0
    faultinject.disarm()
    assert out.read_bytes() == ref.read_bytes()
    err = capsys.readouterr().err
    assert "replaying on the host path" in err
    final = [json.loads(line) for line in m.read_text().splitlines()][-1]
    assert final["host_fallbacks"] >= 1
    assert final["oom_resplits"] >= 1
    assert final["holes_out"] == 3 and final["holes_failed"] == 0


def test_compile_failure_fails_the_run(corpus, tmp_path, capsys,
                                       monkeypatch):
    """A Pallas/Mosaic-looking compile failure is a program error: the
    run ends non-zero — no quiet switch to the scan, no host replay —
    and compile_fallbacks is never booked."""
    from ccsx_tpu.pipeline import batch as batch_mod

    fa, _ = corpus

    def fake_fire(point):
        if point == "device_oom":
            raise RuntimeError("Mosaic lowering failed (injected)")

    monkeypatch.setattr(batch_mod.faultinject, "fire", fake_fire)
    out, m = tmp_path / "o.fa", tmp_path / "m.jsonl"
    with pytest.raises(RuntimeError, match="Mosaic"):  # a process: rc 1
        cli.main(["-A", "-m", "1000", "--batch", "on", "--metrics",
                  str(m), str(fa), str(out)])
    assert "replaying on the host path" not in capsys.readouterr().err
    events = [json.loads(line) for line in m.read_text().splitlines()]
    assert events and events[-1]["event"] == "final"
    assert all(e.get("compile_fallbacks", 0) == 0 for e in events)
    assert all(e.get("host_fallbacks", 0) == 0 for e in events)


def test_resolve_device_tpu_raises_on_cpu():
    from ccsx_tpu.utils import device

    with pytest.raises(RuntimeError, match="tpu"):
        device.resolve_device("tpu")


def test_resolve_device_auto_starts_no_subprocess(monkeypatch):
    """auto is whatever backend JAX initialises, in this process: no
    out-of-process probe."""
    from ccsx_tpu.utils import device

    def no_spawn(*a, **k):
        raise AssertionError("resolve_device started a subprocess")

    monkeypatch.setattr(subprocess, "run", no_spawn)
    monkeypatch.setattr(subprocess, "Popen", no_spawn)
    assert device.resolve_device("auto") == "cpu"


LOCATIONS = ("jax_traceback_in_locations_limit", 1)


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR is honoured (and left to JAX: nothing
    is set in code); unset, the cache is <checkout>/.jax_cache.  Either
    way, locations keep one frame (stable keys for kernels)."""
    import jax

    from ccsx_tpu.utils import device

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert device.enable_compile_cache() == str(tmp_path)
        assert updates == [LOCATIONS]
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(_REPO, ".jax_cache")
        assert device.enable_compile_cache() == want
        assert updates == [LOCATIONS, ("jax_compilation_cache_dir", want)]


# ---------- journal v2: crash-safe resume ----------

def _run_cli_subprocess(args, env_extra):
    """Run the CLI in its own OS process (the write/journal faults
    os._exit; in-process would kill pytest).  Same CPU-pinning idiom as
    tests/test_distributed.py."""
    runner = ("import sys, jax; jax.config.update('jax_platforms', 'cpu'); "
              "from ccsx_tpu.cli import main; sys.exit(main(sys.argv[1:]))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="", **env_extra)
    return subprocess.run([sys.executable, "-c", runner, *args], env=env,
                          cwd=_REPO, capture_output=True, text=True,
                          timeout=300)


def test_kill_between_write_and_journal_then_resume(corpus, tmp_path):
    """THE acceptance case: a hard kill after a record is flushed but
    before the journal advances leaves the output AHEAD of the journal;
    a --journal resume truncates the torn tail, recomputes the
    interrupted hole, and finishes byte-identical to an uninterrupted
    run — no duplicated, no dropped holes."""
    fa, ref = corpus
    out = tmp_path / "o.fa"
    jp = tmp_path / "j.json"
    args = ["-A", "-m", "1000", "--batch", "on", "--journal", str(jp),
            str(fa), str(out)]
    # CCSX_JOURNAL_FSYNC_S=0: every advance hits disk, so the crashed
    # journal's cursor is deterministic (the rate limit would otherwise
    # make it timing-dependent)
    r = _run_cli_subprocess(args, {"CCSX_FAULTS": "write@2",
                                   "CCSX_JOURNAL_FSYNC_S": "0"})
    assert r.returncode == faultinject.EXIT_CODE, (r.stdout, r.stderr)
    j = json.loads(jp.read_text())
    assert j["version"] == 2 and j["holes_done"] == 1
    # the torn state: record 2 hit the disk, the journal never saw it
    assert os.path.getsize(out) > j["out_bytes"]
    assert len(_names(out)) == 2

    assert cli.main(args) == 0  # resume, no faults
    assert out.read_text() == ref.read_text()
    assert json.loads(jp.read_text())["holes_done"] == 3


@pytest.mark.slow
def test_kill_inside_journal_replace_then_resume(corpus, tmp_path):
    """A kill between the fsynced tmp journal and the atomic replace
    leaves the OLD journal intact (never a torn one); resume repairs
    the output tail exactly as in the write-kill case.  (slow: a second
    cold CLI subprocess.)"""
    fa, ref = corpus
    out = tmp_path / "o.fa"
    jp = tmp_path / "j.json"
    args = ["-A", "-m", "1000", "--batch", "on", "--journal", str(jp),
            str(fa), str(out)]
    # fsync rate limit off: the journal fault point fires per-advance
    # (disk updates), so @2 lands deterministically on hole 2's update
    r = _run_cli_subprocess(args, {"CCSX_FAULTS": "journal@2",
                                   "CCSX_JOURNAL_FSYNC_S": "0"})
    assert r.returncode == faultinject.EXIT_CODE, (r.stdout, r.stderr)
    j = json.loads(jp.read_text())   # the OLD journal, still valid JSON
    assert j["holes_done"] == 1
    assert cli.main(args) == 0
    assert out.read_text() == ref.read_text()
    assert json.loads(jp.read_text())["holes_done"] == 3


def test_torn_partial_record_tail_truncated(corpus, tmp_path, capsys):
    """A tail torn MID-RECORD (half a FASTA line) is truncated back to
    the journaled offset and the hole recomputed."""
    fa, ref = corpus
    recs = _records(ref)
    out = tmp_path / "o.fa"
    out.write_text(recs[0] + recs[1][: len(recs[1]) // 2])  # torn rec 2
    jp = tmp_path / "j.json"
    jp.write_text(json.dumps({"version": 2, "input_id": str(fa),
                              "holes_done": 1,
                              "out_bytes": len(recs[0])}))
    assert cli.main(["-A", "-m", "1000", "--batch", "on", "--journal",
                     str(jp), str(fa), str(out)]) == 0
    assert "truncating torn tail" in capsys.readouterr().err
    assert out.read_text() == ref.read_text()


def test_output_behind_journal_refuses_resume(corpus, tmp_path, capsys):
    """A file SHORTER than the journal means journaled output was lost
    (nothing durable to trust): the resume is refused and the run
    recomputes from scratch — still byte-identical at the end."""
    fa, ref = corpus
    recs = _records(ref)
    out = tmp_path / "o.fa"
    out.write_text(recs[0])
    jp = tmp_path / "j.json"
    jp.write_text(json.dumps({"version": 2, "input_id": str(fa),
                              "holes_done": 2,
                              "out_bytes": len(recs[0]) + len(recs[1])}))
    assert cli.main(["-A", "-m", "1000", "--batch", "on", "--journal",
                     str(jp), str(fa), str(out)]) == 0
    assert "refusing to resume" in capsys.readouterr().err
    assert out.read_text() == ref.read_text()


def test_fingerprint_mismatch_refuses_resume(corpus, tmp_path, capsys):
    """A journal cut by different code/config must not be resumed into
    this run's artifact."""
    fa, ref = corpus
    recs = _records(ref)
    out = tmp_path / "o.fa"
    out.write_text(recs[0])
    jp = tmp_path / "j.json"
    jp.write_text(json.dumps({"version": 2, "input_id": str(fa),
                              "holes_done": 1, "out_bytes": len(recs[0]),
                              "fingerprint": "stale-code-stale-cfg"}))
    assert cli.main(["-A", "-m", "1000", "--batch", "on", "--journal",
                     str(jp), str(fa), str(out)]) == 0
    assert "fingerprint mismatch" in capsys.readouterr().err
    assert out.read_text() == ref.read_text()
    # the rewritten journal carries THIS run's fingerprint
    assert json.loads(jp.read_text())["fingerprint"] != "stale-code-stale-cfg"


# ---------- shard failure visibility ----------

def test_merge_refuses_dead_shard_and_names_it(corpus, tmp_path):
    fa, ref = corpus
    out = tmp_path / "dist.fa"
    assert cli.main(["-A", "-m", "1000", "--hosts", "2", "--host-id", "0",
                     str(fa), str(out)]) == 0
    # rank 0 completed and says so
    marker = json.loads((tmp_path / "dist.fa.shard0.done").read_text())
    assert marker["rank"] == 0 and marker["records"] == len(_names(
        tmp_path / "dist.fa.shard0"))
    # rank 1 never ran: the merge must refuse and name it, not emit a
    # silently short output
    with pytest.raises(ValueError, match="shard1"):
        dist.merge_shards(str(out), 2)
    assert not out.exists()
    # after the dead rank reruns, the merge equals the single-host run
    assert cli.main(["-A", "-m", "1000", "--hosts", "2", "--host-id", "1",
                     str(fa), str(out)]) == 0
    assert dist.merge_shards(str(out), 2) == 3
    assert out.read_text() == ref.read_text()
    assert not (tmp_path / "dist.fa.shard0.done").exists()  # cleaned up


def test_all_unmarked_set_refused_unless_allowed(tmp_path):
    """ALL ranks unmarked is indistinguishable from a node-wide kill, so
    it refuses too (hinting at allow_unmarked for true legacy sets)."""
    out = str(tmp_path / "o.fa")
    for r in range(2):
        w = dist.ShardWriter(out, r, 2, append=False)
        w.put_at(0, f"mv/{r}/ccs", b"ACGT")
        w.close()
    with pytest.raises(ValueError, match="allow_unmarked"):
        dist.merge_shards(out, 2)
    assert dist.merge_shards(out, 2, allow_unmarked=True) == 2
    names = [r.name for r in fastx.read_fastx(out)]
    assert names == ["mv/0/ccs", "mv/1/ccs"]


def test_merge_wrong_host_count_refused(tmp_path):
    """Markers record the run's host count; merging a 4-host set with
    --merge-shards 2 would silently drop shards 2-3 — refused."""
    out = str(tmp_path / "o.fa")
    for r in range(2):
        w = dist.ShardWriter(out, r, 4, append=False)
        w.put_at(0, f"mv/{r}/ccs", b"ACGT")
        w.close()
        dist._write_done_marker(out, r, 4, 1)
    with pytest.raises(ValueError, match="4 hosts"):
        dist.merge_shards(out, 2)


def test_dead_shard_with_partial_output_reports_progress(corpus, tmp_path):
    """A shard that died mid-run (partial shard + idx, no marker) is
    reported with how far it got."""
    fa, _ = corpus
    out = tmp_path / "dist.fa"
    assert cli.main(["-A", "-m", "1000", "--hosts", "2", "--host-id", "0",
                     str(fa), str(out)]) == 0
    # simulate rank 1 dying mid-run: partial files, no .done marker
    (tmp_path / "dist.fa.shard1").write_text(">mv/101/ccs\nACGT\n")
    (tmp_path / "dist.fa.shard1.idx").write_text("#mode=rr\n1\n")
    with pytest.raises(ValueError, match=r"shard1 \(died after 1 durable"):
        dist.merge_shards(str(out), 2)
