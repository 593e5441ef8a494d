"""Differential tests: Pallas banded kernel vs the scan implementation.

The lax.scan aligner (ops/banded.py) is the spec; the Pallas kernel
(ops/banded_pallas.py) must be bit-exact in global+moves mode: same scores,
same stats, same band offsets, and identical move bytes for every live row
(rows beyond qlen carry frozen garbage in both — not compared).

On CPU (the default test mesh) the kernel runs in interpret mode, so
shapes are kept small.  Run with CCSX_TEST_TPU=1 on a TPU host and the
kernel runs Mosaic-compiled (interpret=False) on the chip — last done
2026-07-29 on v5e, all green.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ccsx_tpu.config import AlignParams
from ccsx_tpu.ops import banded, banded_pallas, banded_rotband
from ccsx_tpu.utils import synth

# interpret only off-TPU: Mosaic-compile the kernel when the chip is real
INTERPRET = jax.default_backend() != "tpu"


def _random_case(rng, Qmax, Tmax, tmin=40, tspan=160):
    tl = int(rng.integers(tmin, tmin + tspan))
    tpl = rng.integers(0, 4, tl).astype(np.uint8)
    q = synth.mutate(rng, tpl, 0.03, 0.05, 0.05)[:Qmax]
    qs = np.full(Qmax, banded.PAD, np.uint8)
    qs[: len(q)] = q
    ts = np.full(Tmax, banded.PAD, np.uint8)
    ts[:tl] = tpl
    return qs, np.int32(len(q)), ts, np.int32(tl)


def _compare(qs, qlens, ts, tlens, params, with_stats=True, gblock=None):
    scan_f = banded.make_batched("global", params, with_moves=True,
                                 with_stats=with_stats)
    r1, m1, o1 = scan_f(qs, qlens, ts, tlens)
    r2, m2, o2 = banded_pallas.batched_align_global_moves(
        qs, qlens, ts, tlens, params, interpret=INTERPRET,
        with_stats=with_stats, gblock=gblock)
    np.testing.assert_array_equal(np.asarray(r1.score), np.asarray(r2.score))
    np.testing.assert_array_equal(np.asarray(r1.mat), np.asarray(r2.mat))
    np.testing.assert_array_equal(np.asarray(r1.aln), np.asarray(r2.aln))
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    m1, m2 = np.asarray(m1), np.asarray(m2)
    for i in range(len(qlens)):
        ql = int(qlens[i])
        np.testing.assert_array_equal(
            m1[i, :ql], m2[i, :ql], err_msg=f"moves mismatch, problem {i}")


def _stack(cases):
    return (np.stack([c[0] for c in cases]),
            np.array([c[1] for c in cases], np.int32),
            np.stack([c[2] for c in cases]),
            np.array([c[3] for c in cases], np.int32))


def _case_random(rng):
    return _stack([_random_case(rng, 256, 256) for _ in range(5)])


def _case_maxshift_edge(rng):
    """Bands that advance by maxshift on every row (template 4x the
    query and more), so that a template window is used up to its last
    lane before the kernel rebuilds it; and one that stops at tcap."""
    Qmax, Tmax, ms = 128, 768, 4
    cases = []
    for ql, tl in ((128, 128 * ms + 127), (128, Tmax), (96, 96 * ms + 127),
                   (120, 300)):
        q = rng.integers(0, 4, Qmax).astype(np.uint8)
        q[ql:] = banded.PAD
        t = np.full(Tmax, banded.PAD, np.uint8)
        t[:tl] = rng.integers(0, 4, tl)
        t[:ql] = q[:ql]            # some diagonal signal near the start
        cases.append((q, np.int32(ql), t, np.int32(tl)))
    return _stack(cases)


def _case_tlen_edges(rng):
    """tlen < B (the band is the whole template, offsets stay 0) and
    tlen == Tmax (the last offsets sit at tlen - B + 1)."""
    Qmax, Tmax = 256, 256
    cases = []
    for tl in (1, 50, 127, 128, 129, Tmax, Tmax):
        tpl = rng.integers(0, 4, tl).astype(np.uint8)
        q = synth.mutate(rng, tpl, 0.03, 0.05, 0.05)[:Qmax]
        qs = np.full(Qmax, banded.PAD, np.uint8)
        qs[:len(q)] = q
        ts = np.full(Tmax, banded.PAD, np.uint8)
        ts[:tl] = tpl
        cases.append((qs, np.int32(len(q)), ts, np.int32(tl)))
    return _stack(cases)


def _case_nonbase(rng):
    """N (4) and PAD (5) codes inside both sequences never match, and
    whatever lies in the template past tlen is compared as it is."""
    qs, qlens, ts, tlens = _case_random(rng)
    for a, lens in ((qs, qlens), (ts, tlens)):
        for i, n in enumerate(lens):
            at = rng.integers(0, int(n), max(1, int(n) // 8))
            a[i, at] = rng.choice([4, 5], len(at))
    ts[0, tlens[0]:] = rng.integers(0, 6, ts.shape[1] - tlens[0])
    return qs, qlens, ts, tlens


def _case_pad_problems(rng):
    """Pad problems (qlen 0, tlen 0) among live ones, in a batch that is
    no whole number of problem blocks."""
    qs, qlens, ts, tlens = _stack(
        [_random_case(rng, 128, 128, tmin=40, tspan=60) for _ in range(10)])
    for i in (0, 4, 9):
        qs[i], ts[i] = banded.PAD, banded.PAD
        qlens[i] = tlens[i] = 0
    return qs, qlens, ts, tlens


def _case_qmax4096(rng):
    """The largest qmax the kernel takes, over two problem blocks: the
    window moves through every block of a 4 kb template."""
    Qmax, Tmax, N = 4096, 4096, 9
    cases = []
    for i in range(N):
        tl = int(rng.integers(3000, Tmax + 1)) if i else Tmax
        tpl = rng.integers(0, 4, tl).astype(np.uint8)
        q = synth.mutate(rng, tpl, 0.02, 0.05, 0.05)[:Qmax]
        qs = np.full(Qmax, banded.PAD, np.uint8)
        qs[:len(q)] = q
        ts = np.full(Tmax, banded.PAD, np.uint8)
        ts[:tl] = tpl
        cases.append((qs, np.int32(len(q)), ts, np.int32(tl)))
    return _stack(cases)


@pytest.mark.parametrize("case,seed,with_stats,gblock", [
    (_case_random, 7, True, None),
    (_case_random, 8, False, 16),
    (_case_maxshift_edge, 9, True, 8),
    (_case_maxshift_edge, 10, False, 16),
    (_case_tlen_edges, 11, False, 8),
    (_case_tlen_edges, 12, True, 16),
    (_case_nonbase, 13, True, 8),
    (_case_nonbase, 14, False, 16),
    (_case_pad_problems, 15, False, 8),
    (_case_qmax4096, 16, False, 8),
], ids=lambda v: v.__name__[6:] if callable(v) else None)
def test_bit_exact_random_batch(case, seed, with_stats, gblock):
    """v1 against the scan, byte for byte: the random batch and the
    template-window edges (see each case)."""
    qs, qlens, ts, tlens = case(np.random.default_rng(seed))
    _compare(qs, qlens, ts, tlens, AlignParams(), with_stats=with_stats,
             gblock=gblock)


@pytest.mark.slow  # ~15s edge sweep; bit_exact_random_batch and
# gblock/qmax siblings keep the kernel's tier-1 pin (r13 audit)
def test_empty_and_extreme_rows():
    """Padding rows (qlen=0), very short queries, and full-length queries."""
    rng = np.random.default_rng(11)
    Qmax, Tmax = 128, 128
    tl = 100
    tpl = rng.integers(0, 4, tl).astype(np.uint8)
    ts_row = np.full(Tmax, banded.PAD, np.uint8)
    ts_row[:tl] = tpl
    qs = np.full((3, Qmax), banded.PAD, np.uint8)
    qlens = np.zeros(3, np.int32)
    # row 0: empty (padding row); row 1: tiny query; row 2: qlen == Qmax
    qs[1, :5] = tpl[:5]
    qlens[1] = 5
    full = synth.mutate(rng, tpl, 0.02, 0.3, 0.02)
    full = np.concatenate([full, rng.integers(0, 4, Qmax).astype(np.uint8)])
    qs[2] = full[:Qmax]
    qlens[2] = Qmax
    ts = np.broadcast_to(ts_row, (3, Tmax)).copy()
    tlens = np.full(3, tl, np.int32)
    _compare(qs, qlens, ts, tlens, AlignParams())


@pytest.mark.slow  # ~43s: interpret-mode kernel at an extra batch shape
def test_leading_batch_dims():
    """(Z, P, Qmax) nested batching reshapes correctly."""
    rng = np.random.default_rng(3)
    Qmax, Tmax = 128, 128
    cases = [_random_case(rng, Qmax, Tmax, tmin=40, tspan=60)
             for _ in range(4)]
    qs = np.stack([c[0] for c in cases]).reshape(2, 2, Qmax)
    qlens = np.array([c[1] for c in cases], np.int32).reshape(2, 2)
    ts = np.stack([c[2] for c in cases]).reshape(2, 2, Tmax)
    tlens = np.array([c[3] for c in cases], np.int32).reshape(2, 2)
    r, moves, offs = banded_pallas.batched_align_global_moves(
        qs, qlens, ts, tlens, AlignParams(), interpret=INTERPRET)
    assert r.score.shape == (2, 2)
    assert moves.shape == (2, 2, Qmax, 128)
    assert offs.shape == (2, 2, Qmax)
    flat = banded_pallas.batched_align_global_moves(
        qs.reshape(4, Qmax), qlens.reshape(4), ts.reshape(4, Tmax),
        tlens.reshape(4), AlignParams(), interpret=INTERPRET)
    np.testing.assert_array_equal(
        np.asarray(r.score).ravel(), np.asarray(flat[0].score))


@pytest.mark.slow  # ~7s: with_stats-knob A/B; test_bit_exact_random_batch
# keeps the kernel's bit-exactness tier-1 (r16 budget audit)
def test_with_stats_false_same_moves_and_score():
    """The slim kernel (with_stats=False — the consensus-round config,
    star._aligner) must emit bit-identical moves/offs/score; mat/aln are
    zeros by contract, as in ops/banded.py's with_stats=False."""
    rng = np.random.default_rng(19)
    Qmax, Tmax, N = 256, 256, 5
    cases = [_random_case(rng, Qmax, Tmax) for _ in range(N)]
    qs = np.stack([c[0] for c in cases])
    qlens = np.array([c[1] for c in cases], np.int32)
    ts = np.stack([c[2] for c in cases])
    tlens = np.array([c[3] for c in cases], np.int32)
    # compare the slim kernel against the scan spec's slim mode directly
    # (the full-mode kernel is pinned by the _compare tests above; not
    # re-run here to keep suite runtime down)
    r2, m2, o2 = banded_pallas.batched_align_global_moves(
        qs, qlens, ts, tlens, AlignParams(), interpret=INTERPRET,
        with_stats=False)
    assert not np.asarray(r2.mat).any() and not np.asarray(r2.aln).any()
    scan_f = banded.make_batched("global", AlignParams(), with_moves=True,
                                 with_stats=False)
    r3, m3, o3 = scan_f(qs, qlens, ts, tlens)
    np.testing.assert_array_equal(np.asarray(r3.score), np.asarray(r2.score))
    np.testing.assert_array_equal(np.asarray(o3), np.asarray(o2))
    m2, m3 = np.asarray(m2), np.asarray(m3)
    for i in range(N):
        ql = int(qlens[i])
        np.testing.assert_array_equal(
            m3[i, :ql], m2[i, :ql], err_msg=f"moves mismatch, problem {i}")


@pytest.mark.slow  # ~12s: gblock-knob A/B; test_rotband_slim_and_gblock
# keeps gblock coverage tier-1 (r16 budget audit)
def test_gblock_override_bit_exact():
    """A non-default problem block (gblock=16, the A/B sweep knob) must
    not change any output."""
    rng = np.random.default_rng(23)
    Qmax, Tmax, N = 128, 128, 18   # N % 16 != 0 to exercise padding
    cases = [_random_case(rng, Qmax, Tmax, tmin=40, tspan=60)
             for _ in range(N)]
    qs = np.stack([c[0] for c in cases])
    qlens = np.array([c[1] for c in cases], np.int32)
    ts = np.stack([c[2] for c in cases])
    tlens = np.array([c[3] for c in cases], np.int32)
    r1, m1, o1 = banded_pallas.batched_align_global_moves(
        qs, qlens, ts, tlens, AlignParams(), interpret=INTERPRET,
        with_stats=False)
    r2, m2, o2 = banded_pallas.batched_align_global_moves(
        qs, qlens, ts, tlens, AlignParams(), interpret=INTERPRET,
        with_stats=False, gblock=16)
    np.testing.assert_array_equal(np.asarray(r1.score), np.asarray(r2.score))
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    m1, m2 = np.asarray(m1), np.asarray(m2)
    for i in range(N):
        ql = int(qlens[i])
        np.testing.assert_array_equal(m1[i, :ql], m2[i, :ql])


def test_qmax_cap():
    with pytest.raises(ValueError):
        banded_pallas.batched_align_global_moves(
            np.zeros((1, banded_pallas.PALLAS_MAX_QMAX + 8), np.uint8),
            np.zeros(1, np.int32),
            np.zeros((1, 128), np.uint8),
            np.zeros(1, np.int32),
            AlignParams(), interpret=INTERPRET)


# ---- offset-schedule differentials (r14) -----------------------------------
# compute_offsets is shared by BOTH kernels and fed to the traceback, so a
# silent divergence from the scan's in-body recurrence mis-bands every
# kernel alignment at once.  The r14 bugfix replaced its raw int32
# interpolation product with the shared _line_interp (the raw product
# overflowed for large seeded lines); these tests pin the schedule against
# (1) a pure-Python big-int oracle at coordinates whose product crosses
# 2**31 and (2) the scan's own emitted offsets under seeded lines.


def _offsets_oracle(qlen, tlen, qmax, band, maxshift, line):
    """The scan's offset recurrence in pure Python (unbounded ints) —
    overflow-free by construction, floor division exact on negatives
    (Python // == the mathematical floor _line_interp implements)."""
    li0, lj0, li1, lj1 = line
    tcap = max(tlen - band + 1, 0)
    denom = max(li1 - li0, 1)
    off_prev, out = 0, []
    for i in range(1, qmax + 1):
        nom_j = lj0 + ((i - li0) * (lj1 - lj0)) // denom
        desired = nom_j - band // 2
        lo = max(0, tcap - (qlen - i) * maxshift)
        off = min(max(max(desired, lo), off_prev),
                  min(off_prev + maxshift, tcap))
        off = max(off, off_prev)
        if i > qlen:
            off = off_prev
        out.append(off)
        off_prev = off
    return out


def test_compute_offsets_matches_bigint_oracle_large_coords():
    """Seeded lines (and the default global line) at template coordinates
    where the interpolation product (i-li0)*(lj1-lj0) exceeds int32 —
    the exact regime where the pre-r14 raw product silently wrapped."""
    rng = np.random.default_rng(29)
    qmax, band, maxshift = 256, 128, 4
    for rep in range(6):
        qlen = int(rng.integers(64, qmax + 1))
        tlen = int(rng.integers(2**24, 2**25))
        if rep % 2 == 0:
            line = (0, 0, qlen, tlen)  # the default global line
            arg = None
        else:
            lj0 = int(rng.integers(0, 2**20))
            lj1 = int(rng.integers(lj0 + 2**24, tlen))
            line = (0, lj0, qlen, lj1)
            arg = np.array(line, np.int32)
        assert (qmax - line[0]) * (line[3] - line[1]) > 2**31
        got = np.asarray(banded_pallas.compute_offsets(
            jnp.int32(qlen), jnp.int32(tlen), qmax, band, maxshift,
            line=arg))
        want = _offsets_oracle(qlen, tlen, qmax, band, maxshift, line)
        np.testing.assert_array_equal(
            got, np.array(want, np.int32),
            err_msg=f"rep {rep}: qlen={qlen} tlen={tlen} line={line}")


def test_compute_offsets_matches_scan_schedule_seeded_lines():
    """compute_offsets == the offsets the scan itself emits, under random
    seeded lines — the kernels' schedule and the spec's must be the SAME
    array or the traceback walks a different band than the fill wrote."""
    rng = np.random.default_rng(31)
    Qmax, Tmax, N = 128, 2048, 6
    params = AlignParams()
    qs = np.full((N, Qmax), banded.PAD, np.uint8)
    ts = np.full((N, Tmax), banded.PAD, np.uint8)
    qlens = np.zeros(N, np.int32)
    tlens = np.zeros(N, np.int32)
    lines = np.zeros((N, 4), np.int32)
    for i in range(N):
        tl = int(rng.integers(600, Tmax))
        ql = int(rng.integers(40, Qmax + 1))
        tb = int(rng.integers(0, tl - 300))
        te = int(rng.integers(tb + 200, tl + 1))
        ts[i, :tl] = rng.integers(0, 4, tl)
        qs[i, :ql] = rng.integers(0, 4, ql)
        qlens[i], tlens[i] = ql, tl
        lines[i] = (0, tb, ql, te)
    scan_f = banded.make_batched("global", params, with_moves=True,
                                 with_line=True)
    _, _, offs_scan = scan_f(qs, qlens, ts, tlens, lines)
    offs_cmp = jax.vmap(
        lambda ql, tl, ln: banded_pallas.compute_offsets(
            ql, tl, Qmax, params.band, 4, line=ln)
    )(jnp.asarray(qlens), jnp.asarray(tlens), jnp.asarray(lines))
    np.testing.assert_array_equal(np.asarray(offs_scan),
                                  np.asarray(offs_cmp))


# ---- rotband v2 differentials (r14) ----------------------------------------


def _compare3(qs, qlens, ts, tlens, params, with_stats=True):
    """All three impls on the same batch: the scan is the oracle, both
    kernels must match it bit-for-bit (scores, stats, offsets, and every
    live move row)."""
    scan_f = banded.make_batched("global", params, with_moves=True,
                                 with_stats=with_stats)
    r0, m0, o0 = scan_f(qs, qlens, ts, tlens)
    m0 = np.asarray(m0)
    for name, mod in (("pallas", banded_pallas), ("rotband", banded_rotband)):
        r, m, o = mod.batched_align_global_moves(
            qs, qlens, ts, tlens, params, interpret=INTERPRET,
            with_stats=with_stats)
        np.testing.assert_array_equal(
            np.asarray(r0.score), np.asarray(r.score),
            err_msg=f"{name}: score")
        if with_stats:
            np.testing.assert_array_equal(
                np.asarray(r0.mat), np.asarray(r.mat),
                err_msg=f"{name}: mat")
            np.testing.assert_array_equal(
                np.asarray(r0.aln), np.asarray(r.aln),
                err_msg=f"{name}: aln")
        np.testing.assert_array_equal(
            np.asarray(o0), np.asarray(o), err_msg=f"{name}: offs")
        m = np.asarray(m)
        for i in range(len(qlens)):
            ql = int(qlens[i])
            np.testing.assert_array_equal(
                m0[i, :ql], m[i, :ql],
                err_msg=f"{name}: moves mismatch, problem {i}")


@pytest.mark.slow  # ~27s: three interpret-mode arms; the tier-1 pins
# are rotband_slim_and_gblock (rotband vs scan) + bit_exact_random_batch
# (v1 vs scan), and the 256-wide edge sweep covers all three in slow
def test_rotband_three_way_bit_exact():
    """The tier-1 slice of the three-way fuzz: scan vs Pallas v1 vs
    rotband v2 on a small random batch, full-stats mode (the slim mode
    rides test_rotband_slim_and_gblock; the heavy shape/edge sweep is
    the slow sibling below)."""
    rng = np.random.default_rng(37)
    Qmax, Tmax, N = 128, 128, 4
    cases = [_random_case(rng, Qmax, Tmax, tmin=40, tspan=60)
             for _ in range(N)]
    qs = np.stack([c[0] for c in cases])
    qlens = np.array([c[1] for c in cases], np.int32)
    ts = np.stack([c[2] for c in cases])
    tlens = np.array([c[3] for c in cases], np.int32)
    _compare3(qs, qlens, ts, tlens, AlignParams())


def test_rotband_slim_and_gblock():
    """rotband in the consensus-round config (with_stats=False — the
    arm star._aligner actually dispatches) must match the scan's slim
    mode, and a non-default gblock must not change a byte of it."""
    rng = np.random.default_rng(41)
    Qmax, Tmax, N = 128, 128, 10   # N % 8 != 0 to exercise G padding
    cases = [_random_case(rng, Qmax, Tmax, tmin=40, tspan=60)
             for _ in range(N)]
    qs = np.stack([c[0] for c in cases])
    qlens = np.array([c[1] for c in cases], np.int32)
    ts = np.stack([c[2] for c in cases])
    tlens = np.array([c[3] for c in cases], np.int32)
    scan_f = banded.make_batched("global", AlignParams(), with_moves=True,
                                 with_stats=False)
    r0, m0, o0 = scan_f(qs, qlens, ts, tlens)
    r1, m1, o1 = banded_rotband.batched_align_global_moves(
        qs, qlens, ts, tlens, AlignParams(), interpret=INTERPRET,
        with_stats=False)
    assert not np.asarray(r1.mat).any() and not np.asarray(r1.aln).any()
    np.testing.assert_array_equal(np.asarray(r0.score), np.asarray(r1.score))
    np.testing.assert_array_equal(np.asarray(o0), np.asarray(o1))
    r2, m2, o2 = banded_rotband.batched_align_global_moves(
        qs, qlens, ts, tlens, AlignParams(), interpret=INTERPRET,
        with_stats=False, gblock=16)
    np.testing.assert_array_equal(np.asarray(r1.score), np.asarray(r2.score))
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    m0, m1, m2 = np.asarray(m0), np.asarray(m1), np.asarray(m2)
    for i in range(N):
        ql = int(qlens[i])
        np.testing.assert_array_equal(
            m0[i, :ql], m1[i, :ql], err_msg=f"slim moves, problem {i}")
        np.testing.assert_array_equal(
            m1[i, :ql], m2[i, :ql], err_msg=f"gblock moves, problem {i}")


def test_rotband_guards():
    """rotband's residue arithmetic needs a power-of-two band (the & mask
    IS the layout); the qmax cap matches v1's."""
    with pytest.raises(ValueError):
        banded_rotband.batched_align_global_moves(
            np.zeros((1, 128), np.uint8), np.zeros(1, np.int32),
            np.zeros((1, 128), np.uint8), np.zeros(1, np.int32),
            AlignParams(), band=96, interpret=INTERPRET)
    with pytest.raises(ValueError):
        banded_rotband.batched_align_global_moves(
            np.zeros((1, banded_pallas.PALLAS_MAX_QMAX + 8), np.uint8),
            np.zeros(1, np.int32),
            np.zeros((1, 128), np.uint8), np.zeros(1, np.int32),
            AlignParams(), interpret=INTERPRET)


@pytest.mark.slow  # ~1-2 min: interpret-mode kernels at an extra shape x
# stats sweep; the fast slices above keep the tier-1 pin (r14 audit)
def test_rotband_three_way_edge_sweep():
    """The full three-way adversarial sweep: 256-wide shapes, padding
    rows (qlen=0), tiny queries, qlen == Qmax, both stats modes."""
    rng = np.random.default_rng(43)
    Qmax, Tmax = 256, 256
    tl = 200
    tpl = rng.integers(0, 4, tl).astype(np.uint8)
    ts_row = np.full(Tmax, banded.PAD, np.uint8)
    ts_row[:tl] = tpl
    qs = np.full((4, Qmax), banded.PAD, np.uint8)
    qlens = np.zeros(4, np.int32)
    # row 0: empty (padding row); row 1: tiny; row 2: qlen == Qmax;
    # row 3: ordinary mutated read
    qs[1, :5] = tpl[:5]
    qlens[1] = 5
    full = synth.mutate(rng, tpl, 0.02, 0.3, 0.02)
    full = np.concatenate([full, rng.integers(0, 4, Qmax).astype(np.uint8)])
    qs[2] = full[:Qmax]
    qlens[2] = Qmax
    mid = synth.mutate(rng, tpl, 0.03, 0.05, 0.05)[:Qmax]
    qs[3, :len(mid)] = mid
    qlens[3] = len(mid)
    ts = np.broadcast_to(ts_row, (4, Tmax)).copy()
    tlens = np.full(4, tl, np.int32)
    _compare3(qs, qlens, ts, tlens, AlignParams(), with_stats=True)
    _compare3(qs, qlens, ts, tlens, AlignParams(), with_stats=False)


@pytest.mark.slow  # ~minutes: three full 64-hole scale-config CLI runs
def test_scale64_bytes_invariant_across_impls(tmp_path, monkeypatch):
    """The acceptance pin: the 64-hole scale config produces the SAME
    output bytes (the committed md5) under all three CCSX_BANDED_IMPL
    values — the impl knob is non-semantic (utils/fingerprint.py
    _NON_SEMANTIC) and this is the test that earns it."""
    import hashlib
    import sys as _sys

    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks"))
    import fleet as fleet_bench

    in_bam = fleet_bench.make_scale64_corpus(str(tmp_path))
    for impl in ("scan", "pallas", "rotband"):
        monkeypatch.setenv("CCSX_BANDED_IMPL", impl)
        sub = tmp_path / impl
        sub.mkdir()
        ref = fleet_bench.run_scale64_reference(in_bam, str(sub))
        assert hashlib.md5(ref).hexdigest() == fleet_bench.SCALE64_MD5, (
            f"impl={impl}: scale64 bytes drifted "
            f"({len(ref)} bytes vs pinned {fleet_bench.SCALE64_BYTES})")


@pytest.mark.parametrize("backend,qmax,partitioned,forced,want", [
    ("tpu", 1536, False, "", "pallas"),
    ("tpu", 2048, False, "", "pallas"),
    ("tpu", banded_pallas.PALLAS_MAX_QMAX, False, "", "pallas"),
    ("tpu", 8192, False, "", "scan"),
    ("tpu", 2044, False, "", "scan"),        # not a ROWBLOCK multiple
    ("cpu", 2048, False, "", "scan"),
    ("tpu", 2048, True, "", "scan"),         # a GSPMD --mesh step
    ("tpu", 2048, False, "scan", "scan"),
    ("tpu", 2048, False, "rotband", "rotband"),
    ("cpu", 2048, False, "pallas", "pallas"),
    ("tpu", 8192, False, "pallas", "scan"),
])
def test_fill_selection_rule(monkeypatch, backend, qmax, partitioned,
                             forced, want):
    """Unforced, the v1 kernel on a TPU at a qmax it takes, outside a
    partitioned step; the scan elsewhere.  A forced fill is honoured
    wherever the kernels take the qmax."""
    from ccsx_tpu.consensus import star

    monkeypatch.setattr(star, "_backend", lambda: backend)
    if forced:
        monkeypatch.setenv("CCSX_BANDED_IMPL", forced)
    else:
        monkeypatch.delenv("CCSX_BANDED_IMPL", raising=False)
    assert star.banded_impl_effective(qmax, partitioned) == want


def test_fill_selection_rejects_unknown_impl(monkeypatch):
    from ccsx_tpu.consensus import star

    monkeypatch.setenv("CCSX_BANDED_IMPL", "fast")
    with pytest.raises(ValueError):
        star.banded_impl_effective(2048)


FILL_SHAPES = pytest.mark.parametrize("n,calls,grid", [
    (8, 1, 1),        # one gblock block
    (24, 3, 1),       # a call per block
    (128, 16, 1),     # a 128-row slab
    (260, 11, 3),     # at most MAX_CALLS calls of 3 blocks each
])
FILL_QMAX = 64


def _fill_jaxpr(n):
    S = jax.ShapeDtypeStruct
    return jax.make_jaxpr(
        lambda *a: banded_pallas.batched_align_global_moves(
            *a, AlignParams(), with_stats=False, interpret=True))(
        S((n, FILL_QMAX), jnp.uint8), S((n,), jnp.int32),
        S((n, FILL_QMAX), jnp.uint8), S((n,), jnp.int32))


@FILL_SHAPES
def test_fill_is_issued_per_block(n, calls, grid):
    """The fill runs as at most MAX_CALLS kernel calls over equal runs of
    whole GBLOCK blocks, so that no single device operation spans a
    whole slab."""
    assert banded_pallas.MAX_CALLS == 16
    jaxpr = str(_fill_jaxpr(n))
    assert jaxpr.count("pallas_call[") == calls
    assert jaxpr.count(
        f"grid=({grid}, {FILL_QMAX // banded_pallas.ROWBLOCK})") == calls


def _primitives_outside_kernels(jaxpr, counts):
    """Primitive counts of a jaxpr and the jaxprs nested in it, leaving
    out the bodies of its pallas_calls."""
    from jax.extend import core as jcore

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        counts[name] = counts.get(name, 0) + 1
        if name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    _primitives_outside_kernels(sub, counts)
    return counts


@FILL_SHAPES
def test_fill_builds_no_match_tile(n, calls, grid):
    """The match indicators are built inside the kernel: outside its
    calls the fill holds no gather, no dynamic slice and no loop but
    compute_offsets' one scan."""
    counts = _primitives_outside_kernels(_fill_jaxpr(n).jaxpr, {})
    assert counts["pallas_call"] == calls
    assert counts.get("scan") == 1
    for name in ("gather", "dynamic_slice", "while"):
        assert name not in counts, f"{name}: {counts}"
