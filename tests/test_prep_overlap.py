"""Overlapped prep plane (pipeline/prep_pool.py) + adaptive admission
window + batched seeding (ISSUE 8).

Load-bearing guarantees pinned here:

* Output bytes are IDENTICAL with the prep pool on or off, and across
  every --prep-threads setting (prep is per-hole deterministic and the
  pair/refine executors are batch-composition-invariant).
* The adaptive admission window (reference chunk growth, main.c:686-691
  scaled to --inflight as cap) changes scheduling only — bytes match an
  explicitly pinned window.
* A prep-thread exception quarantines exactly that hole (ordered output
  intact), and a kill-and-resume with --journal works identically with
  prep threads on.
* Batched seeding (ops/seed.batch_sorted_indexes + the per-template
  token cache) reproduces per-pair seed_diagonal exactly.

One module-scoped corpus + one reference run keep the file cheap in
tier-1: every variant must reproduce those exact bytes.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from ccsx_tpu import cli
from ccsx_tpu.config import AlignParams, CcsConfig
from ccsx_tpu.consensus import prepare as prep_mod
from ccsx_tpu.io import fastx
from ccsx_tpu.ops import seed
from ccsx_tpu.pipeline.batch import PairExecutor, _grow_window
from ccsx_tpu.pipeline.prep_pool import resolve_prep_threads
from ccsx_tpu.utils import faultinject, synth

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    faultinject.disarm()
    yield
    faultinject.disarm()


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """(input fasta, reference output): 6 holes, one length bucket,
    with adapter read-throughs so the orientation walk actually yields
    pair alignments (the prep plane's whole reason to exist)."""
    tmp = tmp_path_factory.mktemp("prep")
    rng = np.random.default_rng(7)
    zs = []
    for h in range(6):
        z = synth.make_zmw(rng, 600, 5 + (h % 3), movie="mv",
                           hole=str(100 + h), partial_ends=True)
        if h % 3 == 0:
            # longer-than-group pass: the walk must strand_match it
            z.passes.insert(len(z.passes) // 2,
                            synth.read_through(rng, z.template))
            z.strands.insert(len(z.strands) // 2, 0)
        zs.append(z)
    fa = tmp / "in.fa"
    fa.write_text(synth.make_fasta(zs))
    ref = tmp / "ref.fa"
    # reference run: defaults — adaptive window + auto prep threads
    assert cli.main(["-A", "-m", "1000", "--batch", "on",
                     str(fa), str(ref)]) == 0
    assert len(_records(ref)) == 6
    return fa, ref


def _records(path):
    lines = path.read_text().splitlines(keepends=True)
    return ["".join(lines[i:i + 2]) for i in range(0, len(lines), 2)]


def _run(fa, out, extra, metrics_path=None):
    args = ["-A", "-m", "1000", "--batch", "on", *extra]
    if metrics_path:
        args += ["--metrics", str(metrics_path)]
    assert cli.main([*args, str(fa), str(out)]) == 0
    if metrics_path:
        return [json.loads(line) for line in open(metrics_path)][-1]
    return None


# ---------- byte identity: pool on/off, thread counts, window modes --------

@pytest.mark.slow  # ~35s: 3-arm width A/B; kill-and-resume with a live
# pool keeps the prep plane's tier-1 byte pin (r13 budget audit)
def test_pool_on_off_byte_identical(corpus, tmp_path):
    """THE acceptance invariant: inline prep (--prep-threads 0) and any
    pool width produce the reference bytes, and the inline run's
    prep-plane counters read unoverlapped (blocked == worked)."""
    fa, ref = corpus
    out = tmp_path / "o.fa"
    m = _run(fa, out, ["--prep-threads", "0"], tmp_path / "m0.jsonl")
    assert out.read_bytes() == ref.read_bytes()
    assert m["prep_threads"] == 0
    # inline prep is all critical path (the two nested timers differ by
    # ~context-manager overhead, so "no overlap" reads as ~0, not 0.0)
    assert m["prep_overlap_share"] <= 0.005
    assert m["prep_blocked_s"] == pytest.approx(m["prep_s"], rel=1e-2)

    m = _run(fa, out, ["--prep-threads", "3"], tmp_path / "m3.jsonl")
    assert out.read_bytes() == ref.read_bytes()
    assert m["prep_threads"] == 3
    # the pool never blocks the driver for more than it worked
    assert m["prep_blocked_s"] <= m["prep_s"] + 1e-6

    _run(fa, out, ["--prep-threads", "1"])
    assert out.read_bytes() == ref.read_bytes()


@pytest.mark.slow  # ~20s: admission-window A/B; pool identity stays tier-1 (r11 audit)
def test_adaptive_vs_pinned_window_identical(corpus, tmp_path):
    """An explicit --inflight pins the old fixed window; bytes match
    the adaptive default exactly (scheduling-only change)."""
    fa, ref = corpus
    out = tmp_path / "o.fa"
    _run(fa, out, ["--inflight", "64", "--prep-threads", "0"])
    assert out.read_bytes() == ref.read_bytes()
    _run(fa, out, ["--inflight", "2"])
    assert out.read_bytes() == ref.read_bytes()
    # --inflight 0 keeps its historical "use the default" meaning
    # (adaptive), never a pinned 1-hole window
    _run(fa, out, ["--inflight", "0"])
    assert out.read_bytes() == ref.read_bytes()


def test_window_growth_schedule():
    """The reference's chunk policy scaled to the cap: 1024 -> x4 ->
    16384 becomes cap/16 -> x4 -> cap (main.c:686-691 semantics)."""
    w, cap, seen = max(1, 64 // 16), 64, []
    while True:
        seen.append(w)
        if w >= cap:
            break
        w = _grow_window(w, cap, 4)
    assert seen == [4, 16, 64]
    # reference numbers, for the avoidance of doubt
    assert _grow_window(1024, 16384, 4) == 4096
    assert _grow_window(4096, 16384, 4) == 16384
    assert _grow_window(16384, 16384, 4) == 16384


def test_ramp_sweeps_hold_the_same_holes_whatever_prep_pace(monkeypatch):
    """While the adaptive window grows, each sweep waits for its window
    and the pool hands holes over in input order: the ramp's sweeps are
    holes [0], [1..4], [5..20] for a cap of 16, however unevenly prep
    finishes them (a hole's prep here sleeps 0-90 ms by its index)."""
    import time
    import types

    from ccsx_tpu.consensus import hole as hole_mod
    from ccsx_tpu.pipeline import batch
    from ccsx_tpu.utils.journal import Journal
    from ccsx_tpu.utils.metrics import Metrics

    def gen(z, cfg):
        time.sleep(0.03 * (3 - int(z.hole) % 4))
        yield ("window", int(z.hole))
        return np.zeros(8, np.uint8)

    sweeps = []

    def run(self, requests):
        sweeps.append(sorted(r[1] for r in requests))
        return [None] * len(requests)

    monkeypatch.setattr(hole_mod, "full_gen_for_zmw", gen)
    monkeypatch.setattr(batch.BatchExecutor, "run", run)

    class Writer:
        names = []

        def put(self, name, seq, qual=None):
            self.names.append(name)

        def flush(self):
            pass

        def close(self):
            pass

    cfg = CcsConfig(is_bam=False, zmw_microbatch=16, prep_threads=4)
    zs = [types.SimpleNamespace(movie="mv", hole=str(i)) for i in range(24)]
    w = Writer()
    assert batch.drive_batched(iter(zs), w, cfg,
                               Journal.for_run(None, "in", cfg),
                               Metrics(verbose=0, stream=None)) == 0
    assert sweeps[:3] == [[0], [1, 2, 3, 4], list(range(5, 21))]
    assert sorted(h for s in sweeps for h in s) == list(range(24))
    assert w.names == [f"mv/{i}/ccs" for i in range(24)]


def test_resolve_prep_threads():
    assert resolve_prep_threads(CcsConfig(prep_threads=0)) == 0
    assert resolve_prep_threads(CcsConfig(prep_threads=7)) == 7
    auto = resolve_prep_threads(CcsConfig())
    assert 1 <= auto <= 4


# ---------- fault tolerance through the pool -------------------------------

@pytest.mark.slow  # ~15s: pool-thread fault A/B; the pool blast-radius
# twin below (test_pair_gate_host_replay_failure_quarantines) and the
# inline-path quarantine pins in test_faults.py stay tier-1 (r20
# budget audit)
def test_prep_fault_quarantines_one_hole(corpus, tmp_path):
    """An injected prep-point failure on a pool thread quarantines
    exactly that hole; the remaining output is the reference minus one
    record, still in input order.  (Which hole eats call #2 of the
    compute point depends on thread scheduling — the inline path pins
    that, the pool pins the blast radius.)"""
    fa, ref = corpus
    out = tmp_path / "o.fa"
    faultinject.arm("compute@2")
    m = _run(fa, out, ["--prep-threads", "2"], tmp_path / "m.jsonl")
    assert m["holes_failed"] == 1
    got, want = _records(out), _records(ref)
    assert len(got) == len(want) - 1
    # ordered subsequence: one record dropped, nothing reordered
    it = iter(want)
    assert all(any(r == w for w in it) for r in got)


def test_pair_gate_host_replay_failure_quarantines(corpus, tmp_path,
                                                   monkeypatch):
    """A pair result that is an Exception (the executor's last-resort
    host replay failed) quarantines the calling hole, not the run —
    the pool's twin of the inline _feed_hole contract."""
    fa, ref = corpus
    calls = {"n": 0}
    orig = PairExecutor.run

    def flaky(self, pairs):
        calls["n"] += 1
        if calls["n"] == 1:
            return [RuntimeError("injected pair replay failure")
                    for _ in pairs]
        return orig(self, pairs)

    monkeypatch.setattr(PairExecutor, "run", flaky)
    out = tmp_path / "o.fa"
    m = _run(fa, out, ["--prep-threads", "2"], tmp_path / "m.jsonl")
    assert m["holes_failed"] >= 1
    assert len(_records(out)) == len(_records(ref)) - m["holes_failed"]


def _run_cli_subprocess(args, env_extra):
    runner = ("import sys, jax; jax.config.update('jax_platforms', 'cpu'); "
              "from ccsx_tpu.cli import main; sys.exit(main(sys.argv[1:]))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="", **env_extra)
    return subprocess.run([sys.executable, "-c", runner, *args], env=env,
                          cwd=_REPO, capture_output=True, text=True,
                          timeout=300)


@pytest.mark.slow  # ~20s subprocess kill+resume A/B (r15 budget
# audit); tier-1 keeps the kill/resume pins in test_faults.py and the
# serve drain/restart resume in test_serve.py
def test_kill_and_resume_with_prep_threads(corpus, tmp_path):
    """Kill-and-resume with the pool ON: the write-fault hard kill
    leaves a torn tail, and a --journal resume (prep threads still on)
    finishes byte-identical to the uninterrupted reference — the
    flush-before-cursor invariant lives in the driver/writer path the
    pool never touches."""
    fa, ref = corpus
    out = tmp_path / "o.fa"
    jp = tmp_path / "j.json"
    args = ["-A", "-m", "1000", "--batch", "on", "--prep-threads", "2",
            "--journal", str(jp), str(fa), str(out)]
    r = _run_cli_subprocess(args, {"CCSX_FAULTS": "write@2",
                                   "CCSX_JOURNAL_FSYNC_S": "0"})
    assert r.returncode == faultinject.EXIT_CODE, (r.stdout, r.stderr)
    j = json.loads(jp.read_text())
    assert j["holes_done"] == 1
    assert os.path.getsize(out) > j["out_bytes"]  # the torn tail

    assert cli.main(args) == 0  # resume, pool on, no faults
    assert out.read_bytes() == ref.read_bytes()
    assert json.loads(jp.read_text())["holes_done"] == 6


@pytest.mark.slow  # ~17s: resume-budget livelock guard (r11 duration audit)
def test_resumed_stretch_does_not_stall_pool(corpus, tmp_path):
    """A resume whose already-done stretch exceeds the 4x-inflight
    ingest budget must keep retiring resumed holes while the driver
    waits for real work — the budget is released at EMISSION, and a
    done-hole stretch longer than the bound once live-locked the
    accumulate loop (workers starved of budget, driver polling an
    empty queue forever)."""
    fa, ref = corpus
    out = tmp_path / "o.fa"
    jp = tmp_path / "j.json"
    args = ["-A", "-m", "1000", "--batch", "on", "--inflight", "1",
            "--prep-threads", "2", "--journal", str(jp),
            str(fa), str(out)]
    assert cli.main(args) == 0
    assert out.read_bytes() == ref.read_bytes()
    # journal-complete resume: all 6 holes arrive resumed-done through
    # a budget of only 4 — must terminate and leave the bytes alone
    assert cli.main(args) == 0
    assert out.read_bytes() == ref.read_bytes()
    assert json.loads(jp.read_text())["holes_done"] == 6


# ---------- overlap evidence (trace) ---------------------------------------

def test_prep_spans_ride_pool_threads(corpus, tmp_path):
    """The flight recorder shows prep where it now runs: prep_hole
    spans on the pool's worker threads, pair sweeps on the pair-gate
    pump — off the MainThread, which is what lets them overlap the
    driver's device sweeps."""
    fa, ref = corpus
    out = tmp_path / "o.fa"
    tr = tmp_path / "t.jsonl"
    _run(fa, out, ["--prep-threads", "2", "--trace", str(tr)])
    assert out.read_bytes() == ref.read_bytes()
    spans = [json.loads(line) for line in open(tr)
             if '"ev": "span"' in line]
    prep_tids = {s["tid"] for s in spans if s["name"] == "prep_hole"}
    assert prep_tids and all(t.startswith("ccsx-prep") for t in prep_tids)
    pair_tids = {s["tid"] for s in spans if s["name"] == "pair_sweep"}
    assert pair_tids == {"ccsx-prep-pairs"}
    # device dispatches stay on the driver thread
    dev = [s for s in spans if s["cat"] == "device"
           and s["name"] in ("refine_packed", "refine", "round")]
    assert dev and all(s["tid"] == "MainThread" for s in dev)


# ---------- batched seeding ------------------------------------------------

def test_seed_batch_matches_per_pair(rng):
    """batch_sorted_indexes + t_index-fed seed_diagonal reproduce the
    plain per-pair seeding exactly, incl. N-containing sequences and
    seedless pairs."""
    pairs = []
    for i in range(40):
        t = rng.integers(0, 5, int(rng.integers(30, 500))).astype(np.uint8)
        if i % 3:
            s = int(rng.integers(0, max(len(t) - 20, 1)))
            q = t[s:s + int(rng.integers(15, len(t) - s + 1))].copy()
            mut = rng.random(len(q)) < 0.04
            q[mut] = rng.integers(0, 4, mut.sum())
        else:
            q = rng.integers(0, 5, int(rng.integers(20, 300))).astype(
                np.uint8)
        pairs.append((q, t))
    indexes = seed.batch_sorted_indexes([t for _, t in pairs])
    for (q, t), ti in zip(pairs, indexes):
        a = seed.seed_diagonal(q, t)
        b = seed.seed_diagonal(q, t, t_index=ti)
        assert (a is None) == (b is None)
        if a is not None:
            assert a.diag == b.diag and a.votes == b.votes
            assert (a.line == b.line).all()


def test_seed_token_cache_reuse(rng):
    """PairExecutor's token-keyed sort cache: the second batch carrying
    the same template token reuses the cached index (no re-sort) and
    returns identical results to an uncached executor."""
    t = rng.integers(0, 4, 800).astype(np.uint8)
    tok = object()
    reqs = []
    for _ in range(4):
        s = int(rng.integers(0, 300))
        q = t[s:s + 400].copy()
        mut = rng.random(len(q)) < 0.03
        q[mut] = rng.integers(0, 4, mut.sum())
        reqs.append(prep_mod.PairRequest(q, t, 75, t_token=tok))
    pe = PairExecutor(AlignParams())
    r1 = pe.run(reqs[:2])
    assert tok in pe._seed_cache
    cached = pe._seed_cache[tok]
    r2 = pe.run(reqs[2:])
    assert pe._seed_cache[tok] is cached  # reused, not re-sorted
    fresh = PairExecutor(AlignParams()).run(reqs[2:])
    for (ok_a, a), (ok_b, b) in zip(r2, fresh):
        assert ok_a == ok_b and a.qb == b.qb and a.qe == b.qe \
            and a.score == b.score


def test_seed_cache_bounded(rng):
    pe = PairExecutor(AlignParams())
    pe.seed_cache_max = 8
    for i in range(20):
        t = rng.integers(0, 4, 100).astype(np.uint8)
        q = t[:60].copy()
        pe.run([prep_mod.PairRequest(q, t, 75, t_token=object())])
    assert len(pe._seed_cache) <= 8
