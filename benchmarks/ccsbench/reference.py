"""The plain reference that decides ``correct``, and the quality arithmetic.

Nothing here imports the program.  A consensus record is judged against
the template its hole was generated from: the unit-cost edit distance
(substitutions, insertions and deletions each cost 1) in the better of
the two orientations, since a consensus follows the strand of the pass
it starts from.  Which holes must come out, and in what order, follows
the reference's read-step filter (a hole is kept iff it has at least
``-c`` + 2 subreads and ``-m`` <= total bases <= ``-M``) and its ordered
writer: records in input order.
"""

from __future__ import annotations

import math

import numpy as np

QV_CAP = 60.0           # benchmarks/quality.py q_of caps at 60
_BIG = 1 << 29
_ENC = np.full(256, 4, np.uint8)
for _i, _c in enumerate(b"ACGT"):
    _ENC[_c] = _i
    _ENC[_c + 32] = _i


def encode(seq: bytes) -> np.ndarray:
    """ASCII bases -> 2-bit codes (anything else -> 4, never a match)."""
    return _ENC[np.frombuffer(seq, np.uint8)]


def revcomp(codes: np.ndarray) -> np.ndarray:
    out = (3 - codes.astype(np.int16))[::-1]
    out[out < 0] = 4                     # keep non-ACGT as non-matching
    return out.astype(np.uint8)


def _banded(a: np.ndarray, b: np.ndarray, w: int) -> int:
    """Edit distance of a vs b over the cells within ``w`` of the line
    from (0, 0) to (len a, len b); an upper bound of the true distance
    that equals it when the result plus |len a - len b| is at most w."""
    n, m = len(a), len(b)
    d = np.full(m + 1, _BIG, np.int64)
    hi0 = min(m, w)
    d[:hi0 + 1] = np.arange(hi0 + 1)
    lo_prev = 0
    for i in range(1, n + 1):
        c = (i * m) // n
        lo, hi = max(0, c - w), min(m, c + w)
        if lo > hi:
            continue
        cols = np.arange(lo, hi + 1)
        vert = d[lo:hi + 1] + 1
        if lo == 0:
            diag = np.empty(hi + 1, np.int64)
            diag[0] = _BIG
            diag[1:] = d[0:hi] + (b[0:hi] != a[i - 1])
            vert[0] = i
        else:
            diag = d[lo - 1:hi] + (b[lo - 1:hi] != a[i - 1])
        e = np.minimum(vert, diag)
        row = np.minimum.accumulate(e - cols) + cols
        if lo > lo_prev:
            d[lo_prev:lo] = _BIG         # cells that left the band
        d[lo:hi + 1] = row
        lo_prev = lo
    return int(d[m])


def edit_distance(a: np.ndarray, b: np.ndarray) -> int:
    """Exact unit-cost edit distance, banded where the band provably
    holds the optimum, widened until it does."""
    if len(a) == 0 or len(b) == 0:
        return max(len(a), len(b))
    if len(a) < len(b):
        a, b = b, a                      # rows over the longer one
    w = 256 + abs(len(a) - len(b))
    while True:
        dist = _banded(a, b, w)
        if dist + abs(len(a) - len(b)) <= w or w >= max(len(a), len(b)):
            return dist
        w *= 4


def _kmers(codes: np.ndarray, k: int = 12, step: int = 1) -> set:
    ok = codes < 4
    if len(codes) < k:
        return set()
    x = np.zeros(len(codes) - k + 1, np.int64)
    bad = np.zeros(len(x), bool)
    for j in range(k):
        x = x * 4 + codes[j:j + len(x)].astype(np.int64)
        bad |= ~ok[j:j + len(x)]
    return set(x[~bad][::step].tolist())


def hole_errors(cns: bytes, template: np.ndarray) -> int:
    """Edit distance of a consensus to its template, in the orientation
    in which more of its 12-mers (every 7th) occur in the template (a
    wrong pick only overstates the distance, never hides an error)."""
    q = encode(cns)
    rc = revcomp(q)
    t = _kmers(template)
    fwd = len(_kmers(q, step=7) & t) >= len(_kmers(rc, step=7) & t)
    return edit_distance(q if fwd else rc, template)


def qv(errors: int, bases: int) -> float:
    """Phred of the error share, capped as ``q_of`` caps it."""
    if bases <= 0:
        return 0.0
    if errors <= 0:
        return QV_CAP
    return min(QV_CAP, -10.0 * math.log10(errors / bases))


def kept(n_subreads: int, total_bases: int, cli: dict) -> bool:
    """The reference's read-step filter (main.c:659-672)."""
    return (n_subreads >= cli["min_count"] + 2
            and cli["min_len"] <= total_bases <= cli["max_len"])
