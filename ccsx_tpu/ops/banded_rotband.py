"""Pallas TPU kernel v2: rotating-band lane layout (global+moves mode).

Same op as ops/banded_pallas.py — the banded affine-gap DP fill that
replaces bsalign's banded-striped SIMD POA kernel (main.c:492, band=128
at main.c:849) — but with the one structural attack the v1 docstring
documented and never built: lane k holds column j === k mod B instead of
band-local position j - offs[i].  The lax.scan implementation in
ops/banded.py remains the spec and differential oracle; this kernel is
bit-exact against it (tests/test_banded_pallas.py three-way fuzz).

THE LAYOUT.  v1 keys lanes by band-local position: lane k of row i holds
column offs[i] + k, so when the band advances by d = offs[i] - offs[i-1]
every carried value must MOVE d lanes.  d differs per problem inside a
G-block, so the move is a maxshift+2-way chain of static shifts and
selects (~24 tile ops/row) — irreducible in that layout, as the v1
docstring proves.  Here lanes are keyed by column residue: lane k holds
column j with j === k (mod B), the band-parallel layout family gpuPairHMM
uses (PAPERS.md).  The column -> lane map is row-INDEPENDENT, so the
carry never moves at all:

  krel = (k - offs[i]) & (B-1)      lane k's position inside the band
  j    = offs[i] + krel             the column lane k holds at row i

* vertical predecessor (H_up/E_up): column j of row i-1 lives in the
  SAME lane; it existed in the previous band iff krel < B - d
  (otherwise the lane was just recycled for a new column -> NEG fill,
  exactly _pad_prev's semantics).
* diagonal predecessor: column j-1 lives in lane k-1 (cyclic), one
  STATIC jnp.roll(+1) shared by every problem in the G-block; it
  existed iff krel <= B - d and not (krel == 0 and d == 0).
* the Hillis-Steele F prefix scan runs in krel order: each step's
  static roll(+step) lands lane k on the value at krel-step, masked
  NEG where krel < step — the SAME roll+cmp+select per step as v1,
  with krel substituting karr one-for-one in the masks (the v1
  docstring's "+14 ops" estimate for these wrap masks was wrong: the
  legacy scan pays the identical edge masks against karr).

Static per-row tile-op audit ((G, B)-tile ops, slim with_stats=False
carry, maxshift=4 — same counting convention as the v1 docstring's
~24/~21/~15 ~= 60 budget):

  stage                       v1 (band-local)      v2 (rotating)
  predecessor views           ~24  select chain    ~11
    krel = (k-OFF) & (B-1)          --              2
    up:   cmp + 2 selects           --              3   (same lane)
    diag: roll + ~4 mask + sel      --              6   (one static roll)
    d-chain: 3x(roll+mask) x2ch     12              --
    4x select x2ch + derive up      12              --
  F prefix scan (7 steps)     ~21                  ~21  (unchanged)
  recurrence + moves byte     ~15                  ~13  (j from krel)
  TOTAL                       ~60                  ~45

The select chain is eliminated; nothing else grew.  The moves come out
lane-rotated, un-rotated OUTSIDE the kernel by one batched
take_along_axis gather (same cost class as the ismatch gather already
on the host side, amortized over the whole fill, and it keeps
ops/traceback.py and every consumer byte-identical).  The documented
LOSER is the in-kernel post-rotate: d is per-problem, so restoring the
legacy layout inside the kernel is a 7-step barrel shifter (~21 tile
ops/row) — strictly worse than the ~24-op chain it was meant to kill.
A rotated-aware projector (lane = j & (B-1) in traceback.py) remains a
further option if the epilogue gather ever shows up on hardware
profiles; it is not needed for the promotion decision.

PROMOTION STATUS: bit-exactness vs the scan spec is pinned in
interpret mode on CPU (tier-1), compiled for a described v5e in
tests/test_tpu_compile.py, and checked byte-for-byte on the chip by
chip_smoke.py.  All three arms (scan / pallas / rotband) are timed by
benchmarks/pallas_ab.py under the forced-execution marginal method,
and the harness emits a machine-readable decision record (winner,
margin, backend, method) that bench.py vs_prev gates.

G-blocking, the with_stats channels, the offset schedule
(banded_pallas.compute_offsets, shared), the lane-0 scalar bit-pack,
the qmax/gblock gates and the OOM/compile-recovery ladder semantics all
carry over from v1 unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ccsx_tpu.config import AlignParams
from ccsx_tpu.ops.banded import (
    BandedResult, EBIT_EXT, FBIT_EXT, MOVE_DIAG, MOVE_LEFT, MOVE_UP, NEG, PAD,
)
from ccsx_tpu.ops.banded_pallas import (
    GBLOCK, PALLAS_MAX_QMAX, ROWBLOCK, compute_offsets,
)


def compute_ismatch_rot(q, t, offs, band: int, maxshift: int):
    """(Qmax, band) int8 match indicators in ROTATED lane order: row i-1
    lane k compares q[i-1] with the base entering column
    offs[i] + ((k - offs[i]) & (band-1)) (PAD-safe).  Same tpad gather as
    banded_pallas.compute_ismatch, rotated index."""
    tpad = jnp.concatenate([
        jnp.full((1,), PAD, jnp.uint8), t.astype(jnp.uint8),
        jnp.full((band + maxshift,), PAD, jnp.uint8),
    ])
    karr = jnp.arange(band, dtype=jnp.int32)[None, :]
    krel = (karr - offs[:, None]) & (band - 1)
    j = offs[:, None] + krel
    tb = tpad[j]
    qi = q[:, None]
    ismatch = (qi == tb) & (qi < 4) & (tb < 4)
    return ismatch.astype(jnp.int8)


# rows of the G-batched carry: H, E, [mat, aln, Emat, Ealn]; the band
# offset rides a separate (G, 1) scratch column (off_ref) — keeping it
# out of the (G, B) carry saves the per-row OFF tile-add v1 pays
_CHG_ROT = 6      # with_stats carry rows (stats-free carry is 2)


def _kernel_rot(tlen_ref, ismatch_ref, moves_ref, fin_ref,
                ch_ref, off_ref, *, qmax: int, band: int, maxshift: int,
                params: AlignParams, with_stats: bool, gblock: int):
    """G-batched rotating-band DP fill: GBLOCK alignments per grid step.

    Mirrors banded_pallas._kernel_g's structure (G-block sublane
    stacking, lane-0 scalar bit-pack, row-0 init / fin-write pl.when
    epilogues, int32 carries) with the predecessor select chain replaced
    by the residue-lane masks derived in the module docstring.  The
    carry is column-anchored and NEVER physically rotates; the band
    offset is a (G, 1) scratch column (off_ref), not a carry row.

    Inputs (blocks):
      tlen_ref    (G, 1) int32
      ismatch_ref (G, ROWBLOCK, B) int32 — bit 0 match (rotated lane
                  order); lane 0 carries d at bits 1-3 and live at bit 4
    Outputs: moves (G, ROWBLOCK, B) uint8 (ROTATED lane order — the
    host epilogue un-rotates); fin (G, 8, B) int32 rows 0/1/2 = final
    H/mat/aln bands in rotated order (mat/aln zero when stats are off).
    """
    M, X = params.match, params.mismatch
    O, E = params.gap_open, params.gap_extend
    B = band
    G = gblock
    r = pl.program_id(1)
    karr = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1)
    tlen_col = tlen_ref[:, 0:1]                      # (G, 1)

    def roll1(x):
        # out[..., k] = x[..., k-1] (cyclic): the diagonal-predecessor
        # lane map, one STATIC rotate shared by all problems/shifts
        return jnp.roll(x, 1, axis=1)

    # ---- row 0 init (off = 0 -> krel == karr), exactly banded.py carry0
    @pl.when(r == 0)
    def _():
        j0 = jnp.broadcast_to(karr, (G, B))
        H0 = jnp.where(j0 <= tlen_col,
                       jnp.where(j0 == 0, 0, O + E * j0), NEG)
        E0 = jnp.full((G, B), NEG, jnp.int32)
        z = jnp.zeros((G, B), jnp.int32)
        rows0 = ([H0, E0, z, j0, z, j0] if with_stats
                 else [H0, E0])
        ch_ref[:] = jnp.stack(rows0, axis=0)
        off_ref[:] = jnp.zeros((G, 1), jnp.int32)

    # int32 throughout: i8 sublane slices hit Mosaic relayout limits
    packed_tile = ismatch_ref[...].astype(jnp.int32)   # (G, ROWBLOCK, B)
    ismatch_tile = packed_tile & 1
    ch = ch_ref[:]
    off_col = off_ref[:]                             # (G, 1)
    moves_rows = []
    for s in range(ROWBLOCK):
        i = r * ROWBLOCK + s + 1
        lane0 = packed_tile[:, s, 0:1]               # (G, 1) packed scalars
        d_col = (lane0 >> 1) & 7
        live_col = ((lane0 >> 4) & 1) != 0           # (G, 1) bool

        OFF = off_col + d_col                        # (G, 1) row offset
        krel = (karr - OFF) & (B - 1)                # (G, B) band position
        j = OFF + krel                               # (G, B) column

        # predecessor validity (see module docstring; NEG fill matches
        # _pad_prev semantics, stats rows included)
        up_bad = krel >= (B - d_col)                 # recycled lane
        diag_bad = (krel > (B - d_col)) | ((krel == 0) & (d_col == 0))

        H_up = jnp.where(up_bad, NEG, ch[0])
        E_up = jnp.where(up_bad, NEG, ch[1])
        Hd_diag = jnp.where(diag_bad, NEG, roll1(ch[0]))
        if with_stats:
            mat_up = jnp.where(up_bad, NEG, ch[2])
            aln_up = jnp.where(up_bad, NEG, ch[3])
            Emat_up = jnp.where(up_bad, NEG, ch[4])
            Ealn_up = jnp.where(up_bad, NEG, ch[5])
            mat_diag = jnp.where(diag_bad, NEG, roll1(ch[2]))
            aln_diag = jnp.where(diag_bad, NEG, roll1(ch[3]))

        im = ismatch_tile[:, s, :]                   # (G, B) int32 0/1
        sub = X + (M - X) * im

        # E (vertical)
        e_ext = E_up + E
        e_open = H_up + O + E
        e_is_open = e_open >= e_ext
        Enew = jnp.maximum(e_ext, e_open)
        if with_stats:
            Emat = jnp.where(e_is_open, mat_up, Emat_up)
            Ealn = jnp.where(e_is_open, aln_up, Ealn_up) + 1

        # Hd = best of diag / E
        diag_term = Hd_diag + sub
        d_wins = diag_term >= Enew
        Hd = jnp.maximum(diag_term, Enew)
        if with_stats:
            Hmat = jnp.where(d_wins, mat_diag + im, Emat)
            Haln = jnp.where(d_wins, aln_diag, Ealn - 1) + 1

        # boundary lane j == 0 (global mode)
        at0 = j == 0
        b_H = O + E * i
        Hd = jnp.where(at0, b_H, Hd)
        Enew = jnp.where(at0, b_H, Enew)
        if with_stats:
            Hmat = jnp.where(at0, 0, Hmat)
            Haln = jnp.where(at0, i, Haln)
            Emat = jnp.where(at0, 0, Emat)
            Ealn = jnp.where(at0, i, Ealn)

        # invalid lanes beyond the template
        invalid = j > tlen_col
        Hd = jnp.where(invalid, NEG, Hd)
        Enew = jnp.where(invalid, NEG, Enew)

        # F (horizontal) max-plus prefix scan, Hillis-Steele in krel
        # order: static roll(+step) + wrap mask (krel < step -> NEG) —
        # krel substitutes karr one-for-one in v1's edge masks; combine
        # keeps right on ties (ops/banded.py _combine_rightmax)
        v = Hd + O - E * krel
        if with_stats:
            fm = Hmat
            fa = Haln - krel
        step = 1
        while step < B:
            vs = jnp.where(krel < step, NEG, jnp.roll(v, step, axis=1))
            keep = v >= vs
            if with_stats:
                ms = jnp.where(krel < step, NEG,
                               jnp.roll(fm, step, axis=1))
                as_ = jnp.where(krel < step, NEG,
                                jnp.roll(fa, step, axis=1))
                fm = jnp.where(keep, fm, ms)
                fa = jnp.where(keep, fa, as_)
            v = jnp.where(keep, v, vs)
            step *= 2
        # exclusive: shift right by one in krel order (score fill NEG,
        # stats fill 0)
        v = jnp.where(krel < 1, NEG, roll1(v))
        F = v + E * krel
        if with_stats:
            Fmat = jnp.where(krel < 1, 0, roll1(fm))
            Faln = jnp.where(krel < 1, 0, roll1(fa)) + krel

        hd_wins = Hd >= F
        Hnew = jnp.maximum(Hd, F)
        if with_stats:
            mat_new = jnp.where(hd_wins, Hmat, Fmat)
            aln_new = jnp.where(hd_wins, Haln, Faln)

        # moves byte
        choice = jnp.where(
            hd_wins & d_wins, MOVE_DIAG,
            jnp.where(hd_wins, MOVE_UP, MOVE_LEFT)).astype(jnp.uint8)
        ebit = jnp.where(e_is_open, 0, EBIT_EXT).astype(jnp.uint8)
        H_left = jnp.where(krel < 1, NEG, roll1(Hnew))
        f_is_open = F == (H_left + O + E)
        fbit = jnp.where(f_is_open, 0, FBIT_EXT).astype(jnp.uint8)
        moves_rows.append((choice | ebit | fbit)[:, None, :])

        rows_new = ([Hnew, Enew, mat_new, aln_new, Emat, Ealn]
                    if with_stats else [Hnew, Enew])
        ch_new = jnp.stack(rows_new, axis=0)
        ch = jnp.where(live_col[None], ch_new, ch)
        off_col = jnp.where(live_col, OFF, off_col)

    moves_ref[...] = jnp.concatenate(moves_rows, axis=1)
    ch_ref[:] = ch
    off_ref[:] = off_col

    @pl.when(r == pl.num_programs(1) - 1)
    def _():
        fin_ref[:, 0, :] = ch[0]
        if with_stats:
            fin_ref[:, 1, :] = ch[2]
            fin_ref[:, 2, :] = ch[3]
            fin_ref[:, 3:8, :] = jnp.zeros((G, 5, band), jnp.int32)
        else:
            fin_ref[:, 1:8, :] = jnp.zeros((G, 7, band), jnp.int32)


def batched_align_global_moves(
    qs: jnp.ndarray,
    qlens: jnp.ndarray,
    ts: jnp.ndarray,
    tlens: jnp.ndarray,
    params: AlignParams = AlignParams(),
    band: int | None = None,
    maxshift: int = 4,
    interpret: bool = False,
    with_stats: bool = True,
    gblock: int | None = None,
):
    """Batched global banded alignment with move emission (rotband v2).

    Drop-in for banded_pallas.batched_align_global_moves (same argument
    shapes, same (BandedResult, moves, offs) tuple, same gblock /
    CCSX_PALLAS_GBLOCK resolution outside the jit boundary); the moves
    come back un-rotated into the legacy band-local layout, so
    ops/traceback.py and every downstream consumer are byte-identical.
    """
    if gblock is None:
        import os

        raw = os.environ.get("CCSX_PALLAS_GBLOCK", "")
        try:
            gblock = int(raw) if raw else GBLOCK
        except ValueError:
            raise ValueError(
                f"CCSX_PALLAS_GBLOCK={raw!r}: expected an integer >= 1")
    if gblock < 1:
        raise ValueError(
            f"gblock/CCSX_PALLAS_GBLOCK must be >= 1, got {gblock}")
    return _batched_align_impl(
        qs, qlens, ts, tlens, params=params, band=band, maxshift=maxshift,
        interpret=interpret, with_stats=with_stats, gblock=gblock)


@functools.partial(
    jax.jit,
    static_argnames=("params", "band", "maxshift", "interpret",
                     "with_stats", "gblock"))
def _batched_align_impl(
    qs: jnp.ndarray,
    qlens: jnp.ndarray,
    ts: jnp.ndarray,
    tlens: jnp.ndarray,
    params: AlignParams,
    band: int | None,
    maxshift: int,
    interpret: bool,
    with_stats: bool,
    gblock: int,
):
    B = band if band is not None else params.band
    if B & (B - 1):
        # krel arithmetic is a bitwise mod; every real config is 128
        raise ValueError(f"rotband requires a power-of-two band, got {B}")
    if maxshift > 7:
        # d rides lane 0 of the ismatch tile in bits 1-3 (see _kernel_rot)
        raise ValueError(f"maxshift={maxshift} exceeds the 3-bit pack limit")
    lead = qs.shape[:-1]
    qmax = qs.shape[-1]
    if qmax > PALLAS_MAX_QMAX:
        raise ValueError(
            f"qmax={qmax} exceeds PALLAS_MAX_QMAX={PALLAS_MAX_QMAX}; "
            "use the scan aligner")
    n = 1
    for s in lead:
        n *= s
    qs_f = qs.reshape(n, qmax)
    qlens_f = qlens.reshape(n).astype(jnp.int32)
    ts_f = ts.reshape(n, ts.shape[-1])
    tlens_f = tlens.reshape(n).astype(jnp.int32)

    # pad the problem axis to a gblock multiple (pad rows: qlen 0, tlen 0)
    npad = -(-n // gblock) * gblock
    if npad != n:
        pad = npad - n
        qs_f = jnp.concatenate(
            [qs_f, jnp.full((pad, qmax), PAD, qs_f.dtype)])
        qlens_f = jnp.concatenate([qlens_f, jnp.zeros((pad,), jnp.int32)])
        ts_f = jnp.concatenate(
            [ts_f, jnp.full((pad, ts_f.shape[-1]), PAD, ts_f.dtype)])
        tlens_f = jnp.concatenate([tlens_f, jnp.zeros((pad,), jnp.int32)])

    offs = jax.vmap(
        lambda ql, tl: compute_offsets(ql, tl, qmax, B, maxshift)
    )(qlens_f, tlens_f)
    ismatch = jax.vmap(
        lambda q, t, o: compute_ismatch_rot(q, t, o, B, maxshift)
    )(qs_f, ts_f, offs)

    if qmax % ROWBLOCK != 0:
        raise ValueError(f"qmax={qmax} must be a multiple of {ROWBLOCK}")
    dmat = offs - jnp.concatenate(
        [jnp.zeros((npad, 1), jnp.int32), offs[:, :-1]], axis=1)
    rows = jnp.arange(1, qmax + 1, dtype=jnp.int32)
    live = (rows[None, :] <= qlens_f[:, None]).astype(jnp.int32)
    # bit-pack the per-row scalars into lane 0 of the ismatch tile (bit 0
    # match, bits 1-3 d, bit 4 live): bit 0 stays the match indicator on
    # every lane — including the rotated column lane 0 happens to hold
    aux = (((dmat & 7) << 1) | (live << 4)).astype(jnp.int8)
    lane_is0 = (jnp.arange(B, dtype=jnp.int32) == 0)[None, None, :]
    ismatch = jnp.where(lane_is0, ismatch | aux[:, :, None], ismatch)

    kern = functools.partial(
        _kernel_rot, qmax=qmax, band=B, maxshift=maxshift, params=params,
        with_stats=with_stats, gblock=gblock)
    nb = qmax // ROWBLOCK
    moves, fin = pl.pallas_call(
        kern,
        grid=(npad // gblock, nb),
        in_specs=[
            pl.BlockSpec((gblock, 1), lambda i, r: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((gblock, ROWBLOCK, B), lambda i, r: (i, r, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((gblock, ROWBLOCK, B), lambda i, r: (i, r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((gblock, 8, B), lambda i, r: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((npad, qmax, B), jnp.uint8),
            jax.ShapeDtypeStruct((npad, 8, B), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((_CHG_ROT if with_stats else 2, gblock, B),
                       jnp.int32),
            pltpu.VMEM((gblock, 1), jnp.int32),
        ],
        interpret=interpret,
    )(tlens_f[:, None], ismatch)
    moves = moves[:n]
    fin = fin[:n]
    offs = offs[:n]
    qlens_f = qlens_f[:n]
    tlens_f = tlens_f[:n]

    # un-rotate the moves into the legacy band-local layout: legacy lane
    # kk of row i is column offs[i] + kk, which the kernel wrote to lane
    # (offs[i] + kk) & (B-1) — one batched gather, amortized over the
    # fill (the documented winner of the ISSUE's layout choice; the
    # in-kernel alternative is a per-problem barrel shifter, see module
    # docstring)
    idx = ((offs[:, :, None]
            + jnp.arange(B, dtype=jnp.int32)[None, None, :]) & (B - 1))
    moves = jnp.take_along_axis(moves, idx, axis=2)

    # final-row extraction: column tlen lives in lane tlen & (B-1)
    # (residue map), masked by band reachability as in ops/banded.py
    off_fin = offs[:, -1]
    laneT = tlens_f - off_fin
    reachable = (laneT >= 0) & (laneT < B)
    lane = tlens_f & (B - 1)
    take = jax.vmap(lambda f, l: f[:, l])(fin, lane)  # (n, 8)
    zeros = jnp.zeros(lead, jnp.int32)
    res = BandedResult(
        score=jnp.where(reachable, take[:, 0], NEG).reshape(lead),
        qb=jnp.zeros(lead, jnp.int32),
        qe=qlens_f.reshape(lead),
        tb=jnp.zeros(lead, jnp.int32),
        te=tlens_f.reshape(lead),
        aln=jnp.where(reachable, take[:, 2], 0).reshape(lead)
        if with_stats else zeros,
        mat=jnp.where(reachable, take[:, 1], 0).reshape(lead)
        if with_stats else zeros,
    )
    moves = moves.reshape(lead + (qmax, B))
    offs = offs.reshape(lead + (qmax,))
    return res, moves, offs
