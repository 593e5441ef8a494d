"""The windowed loop at the default widths: the breakpoint scan and the
cursor bump against a loop-by-loop transcription of the reference,
chained windows through the batched driver against the per-hole path,
the loop's counts (windows, growths, forced flushes) on both drivers,
and the warm-up of its final windows' shapes.

``loop_breakpoint`` and ``loop_advance`` transcribe main.c:580-612 and
622-638 one column, one row at a time: scan down from column
``tlen - bp_window`` to 1 for the first column that opens ``bp_window``
consecutive columns in which every consensus base is matched by at
least ``colrate``% of the passes (60% under 10 passes), with at least
``minwin`` consensus bases, and every pass matching at least
``rowrate``% of those bases; then each pass's cursor moves past every
cell it has before the breakpoint (its bases, its insertions at those
columns, and its bases before column 0).  They read nothing of the
program but a RoundResult's fields.
"""

import json
import os
import sys

import jax
import numpy as np
import pytest

from ccsx_tpu.config import CcsConfig
from ccsx_tpu.consensus import windowed as win_mod
from ccsx_tpu.consensus.align_host import HostAligner
from ccsx_tpu.consensus.hole import ccs_hole
from ccsx_tpu.consensus.star import RoundResult
from ccsx_tpu.io import fastx
from ccsx_tpu.ops import breakpoint as bp_mod
from ccsx_tpu.pipeline.batch import run_pipeline_batched
from ccsx_tpu.pipeline.run import open_zmw_stream
from ccsx_tpu.utils import synth
from ccsx_tpu.utils.metrics import Metrics

COLS = 2048          # the default window_init, in MSA columns
BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "ccsbench")


def loop_breakpoint(rr, nseq, window, minwin, rowrate, colrate,
                    colrate_lowpass):
    """The highest breakpoint column in [1, tlen - window], or None."""
    rate = colrate if nseq >= 10 else colrate_lowpass
    for i in range(rr.tlen - window, 0, -1):
        if rr.cons[i] >= 4:
            continue
        bases = 0
        row_hits = [0] * nseq
        agreed = True
        for j in range(i, i + window):
            if rr.cons[j] >= 4:
                continue
            bases += 1
            hits = 0
            for k in range(nseq):
                if rr.match[k][j]:
                    hits += 1
                    row_hits[k] += 1
            if hits * 100 < rate * nseq:
                agreed = False
                break
        if not agreed or bases < minwin:
            continue
        if all(row_hits[k] * 100 >= rowrate * bases for k in range(nseq)):
            return i
    return None


def loop_advance(rr, nseq, bp):
    """Bases each pass consumed before column ``bp``."""
    out = []
    for k in range(nseq):
        n = int(rr.lead_ins[k])
        for j in range(bp):
            if rr.aligned[k][j] < 4:
                n += 1
            n += int(rr.ins_cnt[k][j])
        out.append(n)
    return out


def _msa(rng, nseq, tlen, err, bad_tail):
    """A seeded (nseq, COLS) MSA of tlen columns: consensus bases with
    one gap column in ten, each pass matching with probability 1 - err,
    and the last ``bad_tail`` columns of pass 0 matching half the time
    (rowrate pushes the breakpoint before them)."""
    cons = rng.integers(0, 4, COLS).astype(np.uint8)
    cons[rng.random(COLS) < 0.1] = 4
    cons[tlen:] = 4
    match = rng.random((nseq, COLS)) >= err
    if bad_tail:
        match[0, tlen - bad_tail:tlen] &= rng.random(bad_tail) < 0.5
    match &= (cons < 4)[None, :]
    aligned = np.where(rng.random((nseq, COLS)) < 0.1, 4,
                       rng.integers(0, 4, (nseq, COLS))).astype(np.uint8)
    aligned[:, tlen:] = 4
    ins_cnt = (rng.random((nseq, COLS)) < 0.05).astype(np.int32)
    ins_cnt[:, tlen:] = 0
    lead = rng.integers(0, 3, nseq).astype(np.int32)
    z = np.zeros(COLS, np.int32)
    return RoundResult(cons=cons, ins_base=None, ins_votes=None, ncov=z,
                       tlen=tlen, match=match, aligned=aligned,
                       ins_cnt=ins_cnt, lead_ins=lead)


def _cases():
    rng = np.random.default_rng(2**31 + 25)
    out = []
    for nseq, tlen, err, tail in [
            (3, 2048, 0.05, 0), (6, 2048, 0.10, 300), (10, 2048, 0.15, 0),
            (4, 1800, 0.30, 0), (9, 2040, 0.05, 900), (5, 11, 0.0, 0),
            (7, 10, 0.0, 0), (8, 1024, 0.45, 0), (10, 2000, 0.25, 600),
            (3, 2048, 0.60, 0)]:
        out.append((nseq, _msa(rng, nseq, tlen, err, tail)))
    return out


CASES = _cases()


def test_cases_cover_found_and_missing_breakpoints():
    cfg = CcsConfig(is_bam=False)
    found = [loop_breakpoint(rr, n, cfg.bp_window, cfg.bp_minwin,
                             cfg.bp_rowrate, cfg.bp_colrate,
                             cfg.bp_colrate_lowpass) for n, rr in CASES]
    assert sum(b is None for b in found) >= 3
    # found deep in the window too, not only at its last column
    assert any(b is not None and b < rr.tlen - 200
               for b, (_, rr) in zip(found, CASES))


@pytest.mark.parametrize("case", range(len(CASES)))
def test_host_scan_and_advance_match_the_loops(case):
    cfg = CcsConfig(is_bam=False)
    nseq, rr = CASES[case]
    want = loop_breakpoint(rr, nseq, cfg.bp_window, cfg.bp_minwin,
                           cfg.bp_rowrate, cfg.bp_colrate,
                           cfg.bp_colrate_lowpass)
    assert win_mod.find_breakpoint(rr, nseq, cfg) == want
    bp_eff = want if want is not None else max(rr.tlen - cfg.bp_window, 1)
    assert win_mod._advance(rr, bp_eff)[:nseq].tolist() == \
        loop_advance(rr, nseq, bp_eff)


def test_packed_device_scan_matches_the_loops():
    """All cases as the holes of one packed slab (ops/breakpoint.py's
    segment scan, as the batched driver runs it)."""
    cfg = CcsConfig(is_bam=False)
    H = len(CASES)
    R = sum(n for n, _ in CASES)
    seg = np.repeat(np.arange(H, dtype=np.int32), [n for n, _ in CASES])

    def cat(field):
        return np.concatenate([getattr(rr, field)[:n] for n, rr in CASES])

    f = jax.jit(bp_mod.make_bp_advance_packed(
        COLS, H, cfg.bp_window, cfg.bp_minwin, cfg.bp_rowrate,
        cfg.bp_colrate, cfg.bp_colrate_lowpass))
    bp, adv = f(cat("match"), np.stack([rr.cons for _, rr in CASES]),
                cat("aligned"), cat("ins_cnt"), cat("lead_ins"),
                np.ones(R, bool), seg,
                np.array([rr.tlen for _, rr in CASES], np.int32))
    bp, adv = np.asarray(bp), np.asarray(adv)
    for h, (nseq, rr) in enumerate(CASES):
        want = loop_breakpoint(rr, nseq, cfg.bp_window, cfg.bp_minwin,
                               cfg.bp_rowrate, cfg.bp_colrate,
                               cfg.bp_colrate_lowpass)
        assert (int(bp[h]) if bp[h] >= 1 else None) == want, h
        bp_eff = want if want is not None else max(
            rr.tlen - cfg.bp_window, 1)
        assert adv[seg == h].tolist() == loop_advance(rr, nseq, bp_eff)


# ---- the loop's counts on both drivers -----------------------------------

COUNTS = ("windows", "window_growths", "window_forced_flushes")


@pytest.mark.parametrize("name,tlen,kw,growths,flushes", [
    # an 8-base window is shorter than the scan (bp_window 10): every
    # window attempt grows once, to 520 bases, and breaks there
    ("growth", 1500, dict(window_init=8, window_add=512,
                          window_minlen=256), True, False),
    # no pass can reach rowrate 101%: every window grows to max_window
    # and is flushed there
    ("forced_flush", 2600, dict(window_init=512, window_add=512,
                                window_minlen=256, max_window=1024,
                                bp_rowrate=101), True, True),
])
def test_window_counts_agree_between_drivers(tmp_path, name, tlen, kw,
                                             growths, flushes):
    rng = np.random.default_rng(2**31 + 7)
    z = synth.make_zmw(rng, template_len=tlen, n_passes=6, movie="mv",
                       hole="3")
    fa = tmp_path / "in.fa"
    fa.write_text(synth.make_fasta([z]))
    # 8-row slabs: the slab height moves no byte of the result, and a
    # 6-row hole in the default 64-row slab costs the CPU 8x the fill
    cfg = CcsConfig(is_bam=False, min_subread_len=1000, slab_rows=8, **kw)

    stats: dict = {}
    al = HostAligner(cfg.align)
    holes = list(open_zmw_stream(str(fa), cfg))
    assert len(holes) == 1
    rec = ccs_hole(holes[0], al, cfg, stats)

    out = tmp_path / "o.fa"
    metrics = Metrics(verbose=0, stream=None)
    assert run_pipeline_batched(str(fa), str(out), cfg,
                                metrics=metrics) == 0
    got = {r.name: r.seq for r in fastx.read_fastx(str(out))}
    assert got["mv/3/ccs"] == rec[0]

    snap = metrics.snapshot()
    assert {k: snap[k] for k in COUNTS} == \
        {k: stats.get(k, 0) for k in COUNTS}
    assert (snap["window_growths"] > 0) == growths
    assert (snap["window_forced_flushes"] > 0) == flushes
    if flushes:
        # every non-final window: one growth, then the flush
        assert snap["window_growths"] == snap["window_forced_flushes"]
        assert snap["windows"] == 2 * snap["window_forced_flushes"] + 1


# ---- chained windows through the batched driver ----------------------------


@pytest.fixture
def one_device(monkeypatch):
    """One device, as on one chip (the tests' CPU has 8)."""
    from ccsx_tpu.pipeline import batch

    base = batch.BatchExecutor

    class OneDevice(base):
        def __init__(self, cfg, **kw):
            kw["devices"] = jax.local_devices()[:1]
            super().__init__(cfg, **kw)

    monkeypatch.setattr(batch, "BatchExecutor", OneDevice)


def test_chained_windows_match_the_per_hole_path(tmp_path, one_device):
    """Two ~7 kb HiFi-like holes (10 passes, 12% i.i.d. errors) at the
    default widths chain 3 and 4 windows through the batched driver,
    each placed by the device breakpoint scan and advance: the records
    equal the per-hole path's (the host scan, the spec) byte for byte,
    with the same window attempts, and each lies within the
    ``hifi_wgs.stream`` cell's ``err_rate`` limit of its template."""
    rng = np.random.default_rng(2**31 + 25)
    zs = [synth.make_zmw(rng, template_len=n, n_passes=10, movie="m",
                         hole=str(h), sub_rate=0.02, ins_rate=0.05,
                         del_rate=0.05)
          for h, n in enumerate((6800, 8000))]
    fa, out = tmp_path / "in.fa", tmp_path / "o.fa"
    fa.write_text(synth.make_fasta(zs))
    # 32-row slabs: the slab height moves no byte of the result, and
    # two holes' 20 rows in a 128-row slab cost the CPU 4x the fill
    cfg = CcsConfig(is_bam=False, slab_rows=32)

    metrics = Metrics(verbose=0, stream=None)
    assert run_pipeline_batched(str(fa), str(out), cfg,
                                metrics=metrics) == 0
    got = {r.name: r.seq for r in fastx.read_fastx(str(out))}

    aligner = HostAligner(cfg.align)
    windows = []
    for z in open_zmw_stream(str(fa), cfg):
        stats: dict = {}
        seq, _ = ccs_hole(z, aligner, cfg, stats)
        assert got[f"m/{z.hole}/ccs"] == seq
        windows.append(stats["windows"])
    assert windows == [3, 4]
    assert metrics.windows == sum(windows)
    assert metrics.window_growths == metrics.window_forced_flushes == 0

    sys.path.insert(0, BENCH)
    import reference

    with open(os.path.join(BENCH, "limits", "hifi_wgs.stream.json")) as f:
        limit = json.load(f)["err_rate"]["limit"]
    for z in zs:
        errors = reference.hole_errors(got[f"m/{z.hole}/ccs"], z.template)
        assert errors < limit * len(z.template)


# ---- warming the final windows' groups ------------------------------------


class _Recorder:
    """A warmup compiler that records the keys it is given."""

    def __init__(self):
        self.keys = []

    def submit(self, key, builder, urgent=False):
        self.keys.append(key)
        return True

    def claim(self, key):
        return None


def _request(cfg, lens, rng):
    from ccsx_tpu.consensus.star import RefineRequest, StarMsa

    sm = StarMsa(cfg.align, cfg.max_ins_per_col, cfg.len_bucket_quant)
    ws = [rng.integers(0, 4, n).astype(np.uint8) for n in lens]
    qs, qlens, row_mask = sm.pack(ws, cfg.pass_buckets, cfg.max_passes)
    return RefineRequest(qs, qlens, row_mask, ws[0], cfg.refine_iters)


@pytest.mark.parametrize("lens,split,want", [
    # a window at the first width: a final window follows it
    ([2048] * 5, True, (1025, 3073)),
    # a pass cut short: this is the final window already
    ([2048, 2048, 1900], True, None),
    # -P: one whole-read window, no windowed loop
    ([2048] * 5, False, None),
])
def test_final_window_lengths_follow_the_fits_rule(lens, split, want):
    cfg = CcsConfig(is_bam=False, split_subread=split)
    assert win_mod.final_window_lengths(cfg, np.array(lens)) == want


def test_a_full_first_window_warms_the_final_windows_groups():
    """Final windows (tails of window_minlen to window_init +
    window_minlen bases) get their (qmax, tmax) groups warmed at every
    canonical height from the first full-width window on, once; a
    whole-read window (pass lengths of their own) warms none."""
    from ccsx_tpu.pipeline.batch import BatchExecutor

    cfg = CcsConfig(is_bam=False)
    rng = np.random.default_rng(2**31 + 3)
    whole = _request(cfg, [1500, 1480, 1530, 1510], rng)
    full = _request(cfg, [cfg.window_init] * 6, rng)

    rec = _Recorder()
    ex = BatchExecutor(cfg, warmup=rec, devices=jax.local_devices()[:1])
    ex.warm_refine(whole, hole_id=0)
    assert {k[1:3] for k in rec.keys} == {(1536, 2048)}
    ex.warm_refine(full, hole_id=1)
    n = len(rec.keys)
    ex.warm_refine(full, hole_id=2)
    assert len(rec.keys) == n
    buckets = (1536, 2048, 2560, 3072, 3584)
    nxt = dict(zip(buckets, buckets[1:] + (4096,)))
    for height in (64, 128):
        tails = {k[1:3] for k in rec.keys if k[4] == height}
        assert tails >= {(q, t) for q in buckets for t in (q, nxt[q])}
    assert max(k[1] for k in rec.keys) == 3584
