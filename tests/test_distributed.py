"""Multi-host sharding (parallel/distributed.py): round-robin ownership,
shard writing, k-way merge, CLI wiring.  Most ranks are simulated as
sequential processes in one test process — the sharding logic is a pure
function of (rank, n), so this exercises exactly what real hosts run
(collectives are exercised separately by __graft_entry__.dryrun_multichip).
test_two_process_coordinator_run additionally executes the REAL control
plane: two concurrent OS processes rendezvous through
jax.distributed.initialize on a localhost coordinator."""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from ccsx_tpu import cli
from ccsx_tpu.io import fastx
from ccsx_tpu.parallel import distributed as dist
from ccsx_tpu.utils import synth


def _make_inputs(tmp_path, rng, n_holes, tlen=700):
    zs = [synth.make_zmw(rng, template_len=tlen, n_passes=5 + (h % 2),
                         movie="mv", hole=str(100 + h))
          for h in range(n_holes)]
    fa = tmp_path / "in.fa"
    fa.write_text(synth.make_fasta(zs))
    return zs, fa


def test_shard_stream_partition():
    items = list(range(10))
    shards = [list(dist.shard_stream(iter(items), r, 3)) for r in range(3)]
    assert shards[0] == [0, 3, 6, 9]
    assert shards[1] == [1, 4, 7]
    assert shards[2] == [2, 5, 8]


@pytest.mark.slow  # ~18s 4-shard A/B (r15 budget audit); tier-1 keeps
# the mesh-sharded merge==single-host pin below and the real
# two-process coordinator run
def test_sharded_run_merge_equals_single_host(tmp_path, rng):
    """N sequential 'hosts' + merge == the single-process batched output."""
    zs, fa = _make_inputs(tmp_path, rng, n_holes=7)
    ref = tmp_path / "ref.fa"
    assert cli.main(["-A", "-m", "1000", "--batch", "on",
                     str(fa), str(ref)]) == 0

    out = tmp_path / "dist.fa"
    for r in range(3):
        assert cli.main(["-A", "-m", "1000", "--hosts", "3",
                         "--host-id", str(r), str(fa), str(out)]) == 0
    assert cli.main(["--merge-shards", "3", "ignored.in", str(out)]) == 0
    assert out.read_text() == ref.read_text()


@pytest.mark.slow  # ~12s: FASTQ twin of the BAM merge test above (r11 audit)
def test_sharded_fastq_merge_equals_single_host(tmp_path, rng):
    """--fastq shards (4-line records) must merge byte-identically to
    the single-process FASTQ output."""
    zs, fa = _make_inputs(tmp_path, rng, n_holes=5)
    ref = tmp_path / "ref.fq"
    assert cli.main(["-A", "-m", "1000", "--fastq", "--batch", "on",
                     str(fa), str(ref)]) == 0
    out = tmp_path / "dist.fq"
    for r in range(2):
        assert cli.main(["-A", "-m", "1000", "--fastq", "--hosts", "2",
                         "--host-id", str(r), str(fa), str(out)]) == 0
    assert cli.main(["--merge-shards", "2", "ignored.in", str(out)]) == 0
    assert out.read_text() == ref.read_text()
    for r in fastx.read_fastx(str(out)):
        assert r.qual is not None and len(r.qual) == len(r.seq)


def test_two_process_coordinator_run(tmp_path, rng):
    """The real jax.distributed control plane (SURVEY.md §5.8): two
    concurrent OS processes initialize through a localhost coordinator
    (cli --coordinator -> init_distributed, distributed.py:38-54), each
    runs its shard of the pipeline, and the merge must be byte-identical
    to the single-host batched output.  This is the seam no sequential
    simulation covers — jax.process_index()/process_count() come from
    the coordination service, not from CLI flags."""
    zs, fa = _make_inputs(tmp_path, rng, n_holes=4, tlen=500)
    ref = tmp_path / "ref.fa"
    assert cli.main(["-A", "-m", "1000", "--batch", "on",
                     str(fa), str(ref)]) == 0

    out = tmp_path / "dist.fa"
    # the runner pins platforms=cpu before any backend init
    runner = (
        "import sys, jax; jax.config.update('jax_platforms', 'cpu'); "
        "from ccsx_tpu.cli import main; sys.exit(main(sys.argv[1:]))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="")
    # bind-then-close port picking is TOCTOU (another process can grab
    # the port before rank 0's coordinator binds it) — retry the whole
    # rendezvous on a fresh port if that race hits, and always reap both
    # subprocesses even when communicate() times out
    for attempt in range(3):
        with socket.socket() as s:  # pick a free localhost port
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", runner, "-A", "-m", "1000",
                 "--hosts", "2", "--host-id", str(r),
                 "--coordinator", f"127.0.0.1:{port}", str(fa), str(out)],
                env=env, cwd=os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
            for r in range(2)]
        try:
            outs = [p.communicate(timeout=300) for p in procs]
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.wait()
        if (attempt < 2 and any(p.returncode != 0 for p in procs)
                and any("bind" in se.lower() or "in use" in se.lower()
                        for _, se in outs)):
            continue  # coordinator lost the port race; fresh port
        break
    for p, (so, se) in zip(procs, outs):
        assert p.returncode == 0, f"rank failed:\n{so}\n{se}"
    # both ranks went through the coordination service
    assert (tmp_path / "dist.fa.shard0").exists()
    assert (tmp_path / "dist.fa.shard1").exists()
    assert dist.merge_shards(str(out), 2) == ref.read_text().count(">")
    assert out.read_text() == ref.read_text()


def test_sharded_journal_resume(tmp_path, rng):
    """A crashed rank resumes from its shard journal without re-emitting."""
    zs, fa = _make_inputs(tmp_path, rng, n_holes=6)
    out = tmp_path / "o.fa"
    jp = str(tmp_path / "j.json")
    # run rank 0 fully, then "resume" it: second run must append nothing
    assert cli.main(["-A", "-m", "1000", "--hosts", "2", "--host-id", "0",
                     "--journal", jp, str(fa), str(out)]) == 0
    first = (tmp_path / "o.fa.shard0").read_text()
    assert cli.main(["-A", "-m", "1000", "--hosts", "2", "--host-id", "0",
                     "--journal", jp, str(fa), str(out)]) == 0
    assert (tmp_path / "o.fa.shard0").read_text() == first


def test_hosts_requires_host_id(tmp_path, capsys):
    rc = cli.main(["--hosts", "2", "x.fa", str(tmp_path / "y.fa")])
    assert rc == 1
    assert "--host-id" in capsys.readouterr().err


def test_metrics_jsonl(tmp_path, rng):
    import json

    zs, fa = _make_inputs(tmp_path, rng, n_holes=2)
    m = tmp_path / "m.jsonl"
    assert cli.main(["-A", "-m", "1000", "--batch", "on",
                     "--metrics", str(m), str(fa), str(out := tmp_path / "o.fa")]) == 0
    events = [json.loads(line) for line in m.read_text().splitlines()]
    assert events and events[-1]["event"] == "final"
    assert events[-1]["holes_out"] == out.read_text().count(">")


def test_sharded_run_with_mesh_matches_single_host(tmp_path, rng):
    """--hosts with --mesh 4,2: sharded + pass-parallel rounds must still
    merge to the exact single-host output."""
    zs, fa = _make_inputs(tmp_path, rng, n_holes=5)
    ref = tmp_path / "ref.fa"
    assert cli.main(["-A", "-m", "1000", "--batch", "on",
                     str(fa), str(ref)]) == 0
    out = tmp_path / "dist.fa"
    for r in range(2):
        assert cli.main(["-A", "-m", "1000", "--hosts", "2",
                         "--host-id", str(r), "--mesh", "4,2",
                         str(fa), str(out)]) == 0
    assert cli.main(["--merge-shards", "2", "ignored.in", str(out)]) == 0
    assert out.read_text() == ref.read_text()


def test_sharded_run_invalid_mesh_clean_error(tmp_path, rng, capsys):
    """An infeasible --mesh in a sharded run fails rc 1 without
    truncating an existing shard file."""
    zs, fa = _make_inputs(tmp_path, rng, n_holes=2)
    out = tmp_path / "o.fa"
    shard = tmp_path / "o.fa.shard0"
    shard.write_text("precious\n")
    rc = cli.main(["-A", "-m", "1000", "--hosts", "2", "--host-id", "0",
                   "--mesh", "16,2", str(fa), str(out)])
    assert rc == 1
    assert "invalid --mesh" in capsys.readouterr().err
    assert shard.read_text() == "precious\n"
