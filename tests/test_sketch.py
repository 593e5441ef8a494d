"""Pre-alignment plane (ISSUE 11): the batched device sketch screen
(ops/sketch.py) and device k-mer seeding (ops/seed_device.py).

The two contracts pinned here:

* bit-exactness — the device screen reproduces screen_host exactly, and
  the device seeder reproduces seed_diagonal's SeedHit exactly (stable
  sort order, capped first-hits, argmax/median tie-breaks), across
  random AND adversarial (repeat-heavy, N-laden, unrelated) corpora;
* conservativeness — the filter-oracle sweep: every pair the prefilter
  rejects must FAIL strand_match acceptance when force-aligned (0 false
  rejects), so output bytes cannot depend on the filter firing (the
  walk discards a failed pair's payload).
"""

import hashlib

import numpy as np
import pytest

from ccsx_tpu import cli
from ccsx_tpu.config import AlignParams, CcsConfig
from ccsx_tpu.consensus import prepare as prep_mod
from ccsx_tpu.consensus.align_host import HostAligner
from ccsx_tpu.consensus.star import bucket_len, pad_to
from ccsx_tpu.ops import banded
from ccsx_tpu.ops import encode as enc
from ccsx_tpu.ops import seed as seed_mod
from ccsx_tpu.ops import seed_device, sketch
from ccsx_tpu.pipeline.batch import PairExecutor
from ccsx_tpu.utils import faultinject, synth
from ccsx_tpu.utils.metrics import Metrics

ERR = dict(sub_rate=0.02, ins_rate=0.05, del_rate=0.05)


def _adversarial_pair(rng, kind: int, lo=2000, hi=9000):
    """One (q, t) pair from the fuzz corpus: 0 related, 1 repeat-heavy,
    2 N-laden, 3 unrelated, 4 wrong-strand related."""
    L = int(rng.integers(lo, hi))
    t = rng.integers(0, 4, L).astype(np.uint8)
    if kind == 1:
        unit = rng.integers(0, 4, int(rng.integers(7, 61))).astype(np.uint8)
        t = np.tile(unit, L // len(unit) + 1)[:L].copy()
    if kind == 2:
        t[rng.random(L) < 0.05] = 4
    if kind == 3:
        q = rng.integers(0, 4, int(rng.integers(lo, hi))).astype(np.uint8)
    elif kind == 4:
        q = enc.revcomp_codes(synth.mutate(rng, t, **ERR))
    else:
        q = synth.mutate(rng, t, **ERR)
    if kind == 2:
        q = q.copy()
        q[rng.random(len(q)) < 0.05] = 4
    return q, t


def _device_rows(q, t, quant=512):
    """(screen_row, seed_row) for one pair through the real jitted
    steps, padded exactly as PairExecutor pads."""
    qmax, tmax = bucket_len(len(q), quant), bucket_len(len(t), quant)
    big = np.full((1, qmax + tmax), banded.PAD, np.uint8)
    big[0, :qmax] = pad_to(q, qmax)
    big[0, qmax:] = pad_to(t, tmax)
    small = np.array([[len(q), len(t)]], np.int32)
    srow = np.asarray(sketch.screen_step(qmax, tmax)(big, small))[0]
    drow = np.asarray(seed_device.seed_step(qmax, tmax)(big, small))[0]
    return srow, drow


def test_screen_and_seed_device_match_host(rng):
    """Differential fuzz: device screen == screen_host and device seed
    == seed_diagonal, bit-for-bit, across the adversarial corpus.
    Shapes stay in one (qmax, tmax) family per kind so the jit cache
    amortizes."""
    for trial in range(15):
        q, t = _adversarial_pair(rng, trial % 5, lo=2048, hi=4000)
        srow, drow = _device_rows(q, t)
        assert tuple(int(v) for v in srow) == sketch.screen_host(q, t)
        hit = seed_mod.seed_diagonal(q, t)
        dhit = seed_device.hit_from_row(drow)
        if hit is None:
            assert dhit is None
        else:
            assert dhit is not None
            assert dhit.diag == hit.diag and dhit.votes == hit.votes
            assert (np.asarray(dhit.line)
                    == np.asarray(hit.line)).all()


@pytest.mark.slow  # ~9s boundary A/B; screen_and_seed_device_match_host
# pins the device/host routing parity tier-1 (r16 budget audit)
def test_seed_device_crossover_boundary(rng):
    """PairExecutor routing at the --seed-device-min-t boundary:
    templates one below / at / above the crossover produce identical
    (ok, clip, score) results whichever side seeds them, and the
    seeding-split counters account every pair exactly once."""
    min_t = 2560
    pairs = []
    for tl in (min_t - 1, min_t, min_t + 1):
        t = rng.integers(0, 4, tl).astype(np.uint8)
        pairs.append(prep_mod.PairRequest(synth.mutate(rng, t, **ERR),
                                          t, 75))
    m = Metrics()
    pe = PairExecutor(AlignParams(), metrics=m, prefilter=True,
                      seed_device_min_t=min_t)
    got = pe.run(pairs)
    ha = HostAligner(AlignParams())
    for pr, (ok, rs) in zip(pairs, got):
        ok_w, w = ha.strand_match(pr.q, pr.t, pr.pct)
        assert ok == ok_w
        if ok:
            assert (rs.qb, rs.qe, rs.score) == (w.qb, w.qe, w.score)
    assert m.pairs_seeded_device == 2 and m.pairs_seeded_host == 1
    assert m.pairs_screened == 3  # all above SCREEN_MIN_QT
    snap = m.snapshot()
    assert snap["prefilter_share"] is not None


def test_filter_oracle_no_false_rejects(rng):
    """The conservativeness oracle: every pair the prefilter's
    reject_reason fires on must fail strand_match acceptance when
    force-aligned through the spec aligner — 0 false rejects on the
    corpus.  (A false reject here would change output bytes; the rules'
    provable cases are argued in ops/sketch.py.)"""
    ha = HostAligner(AlignParams())
    band = AlignParams().band
    rejected = accepted_kept = 0
    for trial in range(20):
        q, t = _adversarial_pair(rng, trial % 5, lo=2048, hi=4000)
        total, votes, win_lo = sketch.screen_host(q, t)
        reason = sketch.reject_reason(total, votes, win_lo, len(q),
                                      len(t), 75, band)
        ok, _ = ha.strand_match(q, t, 75)
        if reason:
            rejected += 1
            assert not ok, (
                f"FALSE REJECT ({reason}): trial {trial} kind "
                f"{trial % 5} votes={votes} total={total}")
        elif ok:
            accepted_kept += 1
    # the corpus must actually exercise both sides of the filter
    assert rejected >= 5, f"oracle corpus too soft: {rejected} rejects"
    assert accepted_kept >= 5


def test_reject_reason_rules_unit():
    """Rule boundaries pinned: (a) seed-gate parity at any length, (b)
    the noise gate degenerating to (a) below SCREEN_MIN_QT, (c) the
    band-overlap bound firing only past band//4."""
    band = AlignParams().band
    # rule (a): votes < MIN_VOTES rejects even for tiny pairs
    assert sketch.reject_reason(10, 2, 0, 500, 500, 75, band) \
        == "seed_gate"
    assert sketch.reject_reason(0, 0, 0, 500, 500, 75, band) \
        == "seed_gate"
    # below the screen floor rule (b) cannot fire: votes=3 passes
    assert sketch.reject_reason(10, 3, 0, 1000, 1000, 75, band) == ""
    # above it, 3 votes on a 100k pair is noise
    assert sketch.reject_reason(10, 3, 0, 100000, 100000, 75, band) \
        == "noise_gate"
    # an acceptance-grade vote count sails through
    q = 100000
    assert sketch.reject_reason(q // 50, q // 50, 0, q, q, 75, band) == ""
    # rule (c): a far off-diagonal window with no reachable overlap
    assert sketch.reject_reason(200, 200, 90000, 100000, 100000, 75,
                                band) == "band_overlap"
    # same diag near the corner line threshold: kept
    assert sketch.reject_reason(200, 200, 0, 100000, 100000, 75,
                                band) == ""


def test_pair_batch_lazy_vs_speculative(rng):
    """The PairBatch first-accept contract from both evaluators: the
    lazy driver (drive_pairs semantics) stops at the first accept; the
    speculative executor evaluates every arm; the walk-visible
    precedence is identical."""
    tpl = rng.integers(0, 4, 4096).astype(np.uint8)
    fwd = synth.mutate(rng, tpl, **ERR)
    ha = HostAligner(AlignParams())

    # lazy: fwd accepts -> RC arm must be skipped (None)
    calls = []

    class CountingAligner:
        def strand_match(self, q, t, pct):
            calls.append(len(q))
            return ha.strand_match(q, t, pct)

    def gen():
        res = yield prep_mod.PairBatch(
            [prep_mod.PairRequest(fwd, tpl, 75),
             prep_mod.PairRequest(enc.revcomp_codes(fwd), tpl, 75)])
        assert res[0][0] is True
        assert res[1] is None  # first-accept: never evaluated
        return "done"

    assert prep_mod.drive_pairs(gen(), CountingAligner()) == "done"
    assert len(calls) == 1

    # speculative: both arms real, same precedence
    pe = PairExecutor(AlignParams(), prefilter=True,
                      seed_device_min_t=0)
    [res] = pe.run([prep_mod.PairBatch(
        [prep_mod.PairRequest(fwd, tpl, 75),
         prep_mod.PairRequest(enc.revcomp_codes(fwd), tpl, 75)])])
    assert res[0][0] is True and res[1][0] is False


def _spec_zmws(rng, n=2, tlen=2200):
    """Holes whose walk actually speculates: template >= SCREEN_MIN_QT
    and a read-through pass forcing alignment-verified strand for the
    following passes (the e2e_scale recipe)."""
    zs = []
    for h in range(n):
        z = synth.make_zmw(rng, template_len=tlen, n_passes=5,
                           movie="mv", hole=str(h), partial_ends=True,
                           **ERR)
        z.passes.insert(len(z.passes) // 2,
                        synth.read_through(rng, z.template, **ERR))
        z.strands.insert(len(z.strands) // 2, 0)
        zs.append(z)
    return zs


@pytest.mark.slow  # ~85s: 6-arm CLI A/B; the filter-oracle fuzz and
# counter checks stay tier-1, and the scale-config byte pin rides the
# committed fleet_r13 artifact (r13 budget audit)
def test_cli_byte_identity_prefilter_arms(tmp_path, rng):
    """Output bytes are invariant to the whole pre-alignment plane:
    prefilter on/off, device seeding off/at-crossover, the per-hole
    (--batch off) spec path, and inline (--prep-threads 0) vs the
    background prep pool all emit identical FASTA bytes on a config
    whose walk speculates and screens — and the on-arms' metrics carry
    the new screen/seeding counters."""
    import json

    fa = tmp_path / "in.fa"
    fa.write_text(synth.make_fasta(_spec_zmws(rng)))
    sums = {}
    for name, extra in [
            # the full plane: screen on AND device seeding at the
            # crossover the config actually hits (pool prep = default)
            ("on", ["--prefilter", "on", "--seed-device-min-t", "2048"]),
            ("off", ["--prefilter", "off", "--seed-device-min-t", "0"]),
            ("inline", ["--prefilter", "on", "--seed-device-min-t",
                        "2048", "--prep-threads", "0"]),
            ("perhole", ["--prefilter", "on", "--batch", "off"])]:
        out = tmp_path / f"o_{name}.fa"
        mpath = tmp_path / f"m_{name}.jsonl"
        assert cli.main(["-A", "-m", "1000", "--batch", "on",
                         "--metrics", str(mpath), *extra,
                         str(fa), str(out)]) == 0, name
        sums[name] = hashlib.md5(out.read_bytes()).hexdigest()
        final = [json.loads(ln) for ln in open(mpath)][-1]
        if name in ("on", "inline"):
            # the plane actually fired: screens ran (pool or inline)
            # and the crossover routed long templates to the device
            assert final["pairs_screened"] > 0, name
            assert final["pairs_seeded_device"] > 0, name
        if name == "off":
            assert final["pairs_screened"] == 0
            assert final["pairs_seeded_device"] == 0
            assert final["pairs_seeded_host"] > 0
    assert len(set(sums.values())) == 1, sums


def test_injected_oom_on_sketch_wave_recovers(rng):
    """An injected device OOM whose first strike lands on a sketch
    screen wave must ride the recovery ladder (resplit down to the
    host screen rung) and still produce results identical to a clean
    run — the screen stays advisory under failure."""
    tpl = rng.integers(0, 4, 3000).astype(np.uint8)
    pairs = []
    for _ in range(4):
        pairs.append(prep_mod.PairRequest(synth.mutate(rng, tpl, **ERR),
                                          tpl, 75))
    pairs.append(prep_mod.PairRequest(
        enc.revcomp_codes(synth.mutate(rng, tpl, **ERR)), tpl, 75))
    clean = PairExecutor(AlignParams(), prefilter=True,
                         seed_device_min_t=0).run(list(pairs))
    m = Metrics()
    pe = PairExecutor(AlignParams(), metrics=m, prefilter=True,
                      seed_device_min_t=0)
    # drive the device-screen dispatch site at test shapes (the default
    # floor is SPECULATE_MIN_QT; the routing knob is what tests use to
    # land the FIRST device_oom strike on a sketch wave)
    pe.screen_min_device = 2048
    faultinject.arm("device_oom@1")
    try:
        got = pe.run(list(pairs))
    finally:
        faultinject.disarm()
    for (ok_a, a), (ok_b, b) in zip(clean, got):
        assert ok_a == ok_b
        assert (a.qb, a.qe, a.score, a.mat) == (b.qb, b.qe, b.score,
                                                b.mat)
    # the ladder actually ran: the OOM bisected the screen wave (or
    # bottomed out onto the host screen rung)
    assert m.oom_resplits + m.host_fallbacks >= 1
    assert m.pairs_prefiltered >= 1  # the wrong-strand pair still died


@pytest.mark.slow  # ~11s warm-routing A/B; serve's zero-recompile pin and
# screen_and_seed_device_match_host stay tier-1 (r16 budget audit)
def test_warm_covers_prefilter_shapes(rng):
    """PairExecutor.warm precompiles the pre-alignment executables
    alongside the pair fills (inline when no compiler is attached),
    predicting the ROUTING exactly: a device-seeded pair warms only
    the seed step (its seed rows carry the screen statistics — one
    dispatch does both jobs, so warming a screen shape for it would
    compile an executable run() never calls), while a screened
    host-seeded pair warms the screen step.  A warmed run returns
    identical results."""
    tpl = rng.integers(0, 4, 4096).astype(np.uint8)
    pairs = [prep_mod.PairRequest(synth.mutate(rng, tpl, **ERR), tpl, 75)
             for _ in range(3)]
    pe = PairExecutor(AlignParams(), prefilter=True,
                      seed_device_min_t=2048)
    pe.screen_min_device = 2048   # device screen floor at test shapes
    pe.warm(pairs)
    kinds = {k[0] for k in pe._warmed}
    # all pairs device-seed -> the unified path: no screen executable
    assert {"pair_fill", "seed_device"} <= kinds
    assert "sketch_screen" not in kinds
    cold = PairExecutor(AlignParams(), prefilter=True,
                        seed_device_min_t=2048).run(list(pairs))
    warmed = pe.run(list(pairs))
    for (ok_a, a), (ok_b, b) in zip(cold, warmed):
        assert ok_a == ok_b and a.score == b.score and a.qb == b.qb
    # device seeding off -> the same pairs screen instead, and warm
    # predicts that too
    pe2 = PairExecutor(AlignParams(), prefilter=True,
                       seed_device_min_t=0)
    pe2.screen_min_device = 2048
    pe2.warm(pairs)
    kinds2 = {k[0] for k in pe2._warmed}
    assert {"pair_fill", "sketch_screen"} <= kinds2
    assert "seed_device" not in kinds2




@pytest.mark.parametrize("site", ["ccsx_pair_fill", "ccsx_prefilter",
                                  "ccsx_seed"])
def test_prep_steps_name_their_program(site):
    """Lowered, not compiled: each prep dispatch site's program is named
    for it in a profiler trace's "XLA Modules"."""
    import jax
    import jax.numpy as jnp

    from ccsx_tpu.pipeline import batch as bm

    qmax = tmax = 128
    step, width = {
        "ccsx_pair_fill": (lambda: bm._pair_fill_packed(
            AlignParams(), qmax, tmax), 6),
        "ccsx_prefilter": (lambda: sketch.screen_step(qmax, tmax), 2),
        "ccsx_seed": (lambda: seed_device.seed_step(qmax, tmax), 2),
    }[site]
    text = step().lower(jax.ShapeDtypeStruct((4, qmax + tmax), jnp.uint8),
                        jax.ShapeDtypeStruct((4, width), jnp.int32)
                        ).as_text()
    assert f"module @jit_{site} " in text
