"""Batched device pre-alignment screen (the prefilter's scoring op).

The orientation walk's strand_match pairs are the long-template
regime's hidden cost: at >= 50kb, a wrong-strand pairing shares enough
CHANCE 13-mers (plus the micro-repeats that indel mutation leaves in
every pass) that the host seed gate's fixed ``min_votes=3`` passes it
essentially always — measured 28-30/30 at 50-100kb — and every such
pair then pays a full banded DP (~2.6-5.3s on XLA:CPU at 100kb) whose
acceptance is hopeless.  The pre-alignment accelerator lineage
(PAPERS.md: RASSA's sliding-window similarity filter, SeGraM's
minimizer seeding) puts a cheap batched filter in front of the DP; this
module is that filter for PairExecutor's waves.

One dispatch screens a whole (qmax, tmax) bucket of pairs: the device
computes, per pair, EXACTLY the quantities the host seed gate reads —
the capped k-mer hit total and the best 2-bin diagonal-window vote
count of ops/seed.seed_diagonal (bit-equal by construction: same codes,
same stable sort, same searchsorted join, same MAX_HITS_PER_KMER cap
taking the first hits in sorted order, same DIAG_BIN histogram and
adjacent-bin pairing) — and the host applies the rejection rules below.

A note on the design space: a pure per-sequence profile sketch
(k-mer/minimizer count vectors scored by one cosine/intersection
matmul, the RASSA shape) was prototyped first and rejected: with D
hashable buckets the collision floor of the intersection bound is
Q*T/D, which at DNA scale (Q=T=100k, any practical D) is orders of
magnitude above every useful threshold, and an UNbucketed profile needs
4^13 slots.  Position-blind profiles cannot screen long DNA pairs; the
diagonal-windowed hit count — the same statistic the reference's k-mer
seeding trusts (main.c:264) — is the cheapest sketch that can.

Rejection rules (``reject_reason``), applied to the screen triple
(total, votes, best window):

(a) **Seed-gate parity** (provable): ``votes < MIN_VOTES`` or
    ``total == 0``.  seed_diagonal returns None for exactly these
    pairs, and the spec aligner (align_host.HostAligner.strand_match)
    returns ok=False without running the DP.  Rejecting them here is
    behavior-identical to today, just batched and off the host.

(b) **Noise gate** (statistical, margin-analyzed): ``votes <
    min(qlen, tlen) >> NOISE_GATE_SHIFT``.  An acceptance-eligible pair
    must put >= pct% matches inside the DP band, and the band holds the
    path within ~±64 diagonals of the seeded line (the offset tracker
    advances monotonically at <= maxshift/row around a slope-1 line, so
    a path drifting further exits the band — see the conservativeness
    note in ARCHITECTURE.md).  At the 75%-identity acceptance floor
    with independent errors that implies an expected
    (0.75)^13 * pct/200 * min(Q,T) ~ min(Q,T)/60 k-mer hits
    concentrated in a handful of diagonal windows — >= 8x above this
    gate at min(Q,T)/512 — while measured wrong-strand noise votes stay
    <= ~10 even at 100kb (~min/10000).  The gate deliberately
    degenerates to rule (a) below min(Q,T) = 4 * 512 = 2048, so short
    pairs (the pinned 64-hole scale config's regime) see the exact
    legacy gate.  Not information-theoretically provable — a
    worst-case 3-match-1-error pattern hides from every 13-mer
    statistic (q-gram lemma: k <= pct/(100-pct) would be needed) — but
    that adversary is ALREADY false-rejected by today's min_votes=3
    gate, so the gate introduces no new failure class; the filter-
    oracle fuzz sweep (tests/test_sketch.py) force-aligns every
    rejected pair and pins false rejects at 0, and the scale-config
    md5 is pinned prefilter on == off.

(c) **Band-overlap impossibility** (provable): when the seeded line
    would be used (|diag| > band/4), acceptance needs
    mat > min(Q,T)*pct/200 matched bases, every one inside the band
    around that line.  The band reaches at most
    ``overlap(d) = min(tlen, qlen - d)`` columns above a positive
    diagonal (the offset tracker is bounded by the line), plus — for
    negative diagonals — the crawl phase (offset starts at 0 and
    catches the line at maxshift/row, one match per row) and the
    boundary fringes.  If even the most generous bound cannot reach
    the acceptance floor, the DP cannot accept; rejecting costs
    nothing and is exact.

All three rules only ever reject pairs whose (ok, MatchResult) would
come back ok=False, and the walk discards the MatchResult payload of a
failed pair — so output bytes are invariant to the filter firing
(pinned by the 64-hole scale config md5 with --prefilter on/off/both
crossovers, tests/test_sketch.py + benchmarks).

``screen_host`` is the NumPy twin: the recovery ladder's host-replay
rung for a failed screen dispatch, and the differential-fuzz oracle.
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

from ccsx_tpu.ops import seed as seed_mod

K = seed_mod.DEFAULT_K
MIN_VOTES = 3              # seed_diagonal's default gate
MAX_HITS = seed_mod.MAX_HITS_PER_KMER
DIAG_BIN = seed_mod.DIAG_BIN
SENTINEL = np.int32(1) << np.int32(2 * K)   # 4^13 fits int32
# noise gate: votes < min(qlen, tlen) >> NOISE_GATE_SHIFT (rule (b));
# identical to the legacy gate below min(Q,T) = MIN_VOTES << SHIFT
NOISE_GATE_SHIFT = 9
# screening floor: below min(Q, T) = (MIN_VOTES + 1) << NOISE_GATE_SHIFT
# rule (b) degenerates to the legacy seed gate, which host seeding
# applies anyway — screening such a pair spends a device row to learn
# nothing, so PairExecutor only screens (and the walk only speculates
# fwd+RC, prepare.PairBatch) at or above this length
SCREEN_MIN_QT = (MIN_VOTES + 1) << NOISE_GATE_SHIFT   # 2048
# fwd+RC speculation floor (prepare.PairBatch): a speculated WRONG arm
# must die in the screen or speculation pays a whole extra DP.  The
# noise gate's threshold is min(Q,T) >> 9 while measured wrong-strand
# noise stays ~<= 10-30 votes, so the margin is only decisive a few
# octaves above SCREEN_MIN_QT — at 16384 the gate wants >= 32 votes, ~3x
# the noise ceiling.  (Speculation is additionally restricted to
# IN-GROUP passes: an out-of-group read-through contains BOTH strands,
# so both its arms genuinely accept and even a perfect screen cannot
# save the second DP — measured 8kb A/B, benchmarks/long_molecule.py.)
SPECULATE_MIN_QT = 16384
# band-geometry slack for rule (c): covers the DIAG_BIN-resolution
# diagonal estimate vs the median the DP line would use (±64), the
# early/tail boundary fringes (~2 bands), and the offset tracker's
# maxshift catch-up — generous by design, the rule fires on
# order-min(Q,T)/8 margins
BAND_SLACK = 8 * 128
_MAXSHIFT = 4              # banded_align default, pinned by the fill


# ---- rejection rules (host-side ints; shared by the device screen's
# ---- finish path and the host twin) ---------------------------------------


def noise_gate(qlen: int, tlen: int) -> int:
    """The vote threshold of rules (a)+(b) for a (qlen, tlen) pair."""
    return max(MIN_VOTES, min(qlen, tlen) >> NOISE_GATE_SHIFT)


def _mat_upper_bound(diag: int, qlen: int, tlen: int) -> int:
    """Provable upper bound on matched bases the banded local DP can
    produce with its band following a slope-1 line on ``diag`` (rule
    (c)); see the module docstring for the geometry."""
    overlap = max(0, min(qlen - diag, tlen) - max(-diag, 0))
    bound = overlap + BAND_SLACK
    if diag < 0:
        # crawl phase: the band offset starts at 0 and closes on the
        # line at <= maxshift cols/row; one match per crawl row, and
        # the crawl spans at most |diag|/(maxshift-1) rows (the line
        # advances 1/row) and at most tlen/maxshift columns
        bound += min((-diag) // (_MAXSHIFT - 1),
                     min(qlen, tlen) // _MAXSHIFT) + _MAXSHIFT
    return bound


def reject_from_hit(hit, qlen: int, tlen: int, pct: int,
                    band: int) -> str:
    """'' (keep) or the rejection rule that fires for an already-seeded
    pair (a seed.SeedHit) — the ZERO-DISPATCH form of the filter, used
    below the device-screen floor where the seeding computation already
    holds every statistic the rules read.  hit.votes is the same best
    2-bin window count the screen computes, and hit.diag is the MEDIAN
    diagonal — the exact line the DP would run on, so rule (c) here is
    evaluated at the true line rather than the window edge (at least as
    conservative).  ``hit is None`` is rule (a) and handled by the
    caller exactly as today."""
    if hit.votes < noise_gate(qlen, tlen):
        return "noise_gate"         # rule (b): statistical
    if abs(int(hit.diag)) <= band // 4:
        return ""                   # corner-line case: full overlap
    minqt = min(qlen, tlen)
    if _mat_upper_bound(int(hit.diag), qlen, tlen) * 200 <= minqt * pct:
        return "band_overlap"       # rule (c): provable geometry
    return ""


def reject_reason(total: int, votes: int, win_lo: int, qlen: int,
                  tlen: int, pct: int, band: int) -> str:
    """'' (keep) or the rejection rule that fired for a screen triple.

    ``win_lo`` is the lower diagonal edge of the best 2-bin window (the
    window spans [win_lo, win_lo + 2*DIAG_BIN)).
    """
    if total <= 0 or votes < MIN_VOTES:
        return "seed_gate"          # rule (a): host parity, provable
    if votes < noise_gate(qlen, tlen):
        return "noise_gate"         # rule (b): statistical
    # rule (c): only when the DP would run on the hinted line — the
    # near-diagonal corner-line case has full overlap by construction.
    # Evaluate at the window's |d|-minimal edge: the bound is monotone
    # against |d|, so this is the most permissive diagonal the median
    # could land on (plus BAND_SLACK for the resolution gap).
    win_hi = win_lo + 2 * DIAG_BIN - 1
    d_best = min(max(0, win_lo), win_hi) if win_lo <= 0 <= win_hi \
        else (win_lo if win_lo > 0 else win_hi)
    if abs(d_best) <= band // 4:
        return ""
    minqt = min(qlen, tlen)
    # acceptance => aln*2 > minqt and mat*100 >= aln*pct
    #            => mat*200 > minqt*pct
    if _mat_upper_bound(int(d_best), qlen, tlen) * 200 <= minqt * pct:
        return "band_overlap"       # rule (c): provable geometry
    return ""


# ---- host twin -------------------------------------------------------------


def screen_host(q: np.ndarray, t: np.ndarray,
                t_index=None) -> Tuple[int, int, int]:
    """(total, votes, win_lo) for one pair, NumPy — the same counting
    path as seed_diagonal up to (and excluding) the median/line step.
    The recovery ladder's host rung and the device screen's oracle
    (pinned bit-equal by tests/test_sketch.py)."""
    qk = seed_mod.kmer_codes(q)
    if t_index is None:
        t_index = seed_mod.sorted_kmer_index(t)
    tks, order = t_index
    if len(qk) == 0 or len(tks) == 0:
        return (0, 0, 0)
    left = np.searchsorted(tks, qk, side="left")
    right = np.searchsorted(tks, qk, side="right")
    cnt = np.minimum(right - left, MAX_HITS)
    cnt[qk < 0] = 0
    total = int(cnt.sum())
    if total == 0:
        return (0, 0, 0)
    qpos = np.repeat(np.arange(len(qk)), cnt)
    starts = np.repeat(left, cnt)
    run_ids = np.repeat(np.cumsum(cnt) - cnt, cnt)
    offs = np.arange(total) - run_ids
    diags = qpos - order[starts + offs]
    lo = -len(t)
    nbins = (len(q) + len(t)) // DIAG_BIN + 2
    hist = np.bincount((diags - lo) // DIAG_BIN, minlength=nbins)
    paired = hist[:-1] + hist[1:]
    best = int(np.argmax(paired))
    return (total, int(paired[best]), best * DIAG_BIN + lo)


# ---- device screen ---------------------------------------------------------


def _codes_dev(seq, k: int):
    """Device twin of seed.kmer_codes on a PADDED code array: windows
    touching an N (code 4) or the PAD byte (5) come back -1, which
    covers the padded tail for free (PAD >= 4)."""
    import jax
    import jax.numpy as jnp

    n = seq.shape[0] - k + 1
    s = seq.astype(jnp.int32)
    code = jnp.zeros((n,), jnp.int32)
    bad = jnp.zeros((n,), bool)
    for i in range(k):
        w = jax.lax.dynamic_slice(s, (i,), (n,))
        code = (code << 2) | (w & 3)
        bad = bad | (w >= 4)
    return jnp.where(bad, -1, code)


def _t_index_dev(t):
    """Device twin of seed.sorted_kmer_index: bad/pad codes share the
    tail sentinel (their relative order is irrelevant — valid q codes
    never reach them), real codes keep the host's stable position
    order."""
    import jax.numpy as jnp

    tk = _codes_dev(t, K)
    vals = jnp.where(tk < 0, jnp.int32(SENTINEL), tk)
    order = jnp.argsort(vals, stable=True).astype(jnp.int32)
    return vals[order], order


def _hits_dev(q, t, qlen, tlen):
    """The shared capped-hit machinery: returns (cnt (Qn,), left,
    order, qpos, total) exactly as the host computes them.  Positions
    beyond qlen-K are bad by padding; tlen is unused beyond what the
    pad already encodes but kept for clarity."""
    import jax.numpy as jnp

    del tlen
    qk = _codes_dev(q, K)
    tks, order = _t_index_dev(t)
    left = jnp.searchsorted(tks, qk, side="left").astype(jnp.int32)
    right = jnp.searchsorted(tks, qk, side="right").astype(jnp.int32)
    cnt = jnp.minimum(right - left, MAX_HITS)
    cnt = jnp.where(qk < 0, 0, cnt)
    del qlen
    return cnt, left, order, jnp.arange(cnt.shape[0], dtype=jnp.int32)


def _diag_hist_dev(cnt, left, order, qpos, qlen, tlen, nb: int):
    """(hist (nb,), diags (Qn, MAX_HITS), inhit mask): the DIAG_BIN
    histogram over capped hits, host-bit-equal.  ``nb`` is the static
    bin budget >= any runtime (qlen+tlen)//DIAG_BIN + 2; bins beyond
    the runtime range stay zero, so argmax is unaffected."""
    import jax.numpy as jnp

    Tn = order.shape[0]
    lo = -tlen
    hist = jnp.zeros((nb + 1,), jnp.int32)
    diags_all = []
    mask_all = []
    for j in range(MAX_HITS):
        ok = j < cnt
        tpos = order[jnp.clip(left + j, 0, Tn - 1)]
        dj = qpos - tpos
        b = jnp.where(ok, (dj - lo) // DIAG_BIN, nb)
        hist = hist.at[b].add(1)
        diags_all.append(dj)
        mask_all.append(ok)
    del qlen
    return (hist[:nb], jnp.stack(diags_all, 1), jnp.stack(mask_all, 1),
            lo)


@functools.lru_cache(maxsize=32)
def screen_step(qmax: int, tmax: int):
    """Jitted batched screen: (N, qmax+tmax) uint8 codes + (N, 2) int32
    lengths -> (N, 3) int32 (total, votes, win_lo).  One dispatch
    scores a whole bucket of candidate pairings; PairExecutor routes it
    through the shared recovery ladder (host rung = screen_host)."""
    import jax
    import jax.numpy as jnp

    nb = (qmax + tmax) // DIAG_BIN + 2

    def ccsx_prefilter(row, lens):
        q = row[:qmax]
        t = row[qmax:]
        qlen, tlen = lens[0], lens[1]
        cnt, left, order, qpos = _hits_dev(q, t, qlen, tlen)
        total = cnt.sum()
        hist, _, _, lo = _diag_hist_dev(cnt, left, order, qpos,
                                        qlen, tlen, nb)
        paired = hist[:-1] + hist[1:]
        best = jnp.argmax(paired).astype(jnp.int32)
        votes = paired[best]
        win_lo = best * DIAG_BIN + lo
        empty = total == 0
        return jnp.stack([total,
                          jnp.where(empty, 0, votes),
                          jnp.where(empty, 0, win_lo)])

    # jit names the program after the function vmap wraps
    return jax.jit(jax.vmap(ccsx_prefilter))
