"""Pallas TPU kernel for the banded affine-gap DP fill (global+moves mode).

This is the hot op of the framework: every consensus round aligns each pass
window against the draft (star.round), which the reference does inside
bsalign's banded-striped SIMD POA (end_bspoa, main.c:492; band=128 at
main.c:849).  The semantics here are *identical* to the lax.scan
implementation in ops/banded.py (mode='global', with_moves=True) — that
version remains the spec and the differential-test oracle; this one maps the
fill onto a single Pallas kernel so the whole DP runs out of VMEM with no
per-row HLO overhead.

Design notes (why the kernel looks like this):

* The band-offset schedule ``offs`` is data-INdependent — it is a pure
  function of (qlen, tlen, line) — so it is computed outside the kernel
  with a tiny vectorized ``lax.scan`` (compute_offsets) and fed to the
  kernel as each row's shift d.  The traceback needs the same array, so
  nothing is wasted.
* The only per-cell input the recurrence needs from (q, t) is the match
  indicator ``q[i-1] == t[offs[i]+k-1]``, and the kernel builds it
  itself.  Each problem's padded template enters as one VMEM block per
  problem block; a 2B-lane window of it, from the band's offset, is kept
  in scratch and rebuilt every few grid steps (a select over 128-lane
  blocks, then a lane gather at the offset's remainder), and each row's
  template band is one per-problem lane gather of that window.  Outside
  the kernel there is no per-row gather or loop: q[i-1]'s code rides
  with the row's other packed scalars.
* The previous-row band must be shifted by d = offs[i] - offs[i-1] ∈
  [0, maxshift].  d is tiny, so the kernel computes all maxshift+2 static
  lane shifts of the carry block and picks with a select chain — static
  shifts vectorize on the VPU; a dynamic lane shift would not.
* The horizontal (within-row) affine gap F is an associative max-plus
  prefix scan (see ops/banded.py); here it is a log2(B)-step Hillis-Steele
  scan of static lane shifts.
* Outputs: the packed move byte per cell (uint8, written row-by-row into
  the VMEM output block) and the final H/mat/aln bands; score extraction
  happens outside.

The kernel is gated to Qmax <= PALLAS_MAX_QMAX (VMEM/SMEM budget) and
Qmax % ROWBLOCK == 0; consensus/star.banded_impl_effective selects it
(the rule is under HARDWARE STATUS below).

Per-cell cost analysis (r5, after the slim with_stats=False carry):
the per-row tile-op budget of THIS (v1, band-local) layout splits
~24 ops select chain (diag/vert views of the H/E carry at
per-problem shift d), ~21 ops F prefix scan (7 Hillis-Steele steps x
roll+cmp+select), ~15 ops recurrence+moves, ~60 total.  The select
chain is irreducible in the band-local lane layout: d differs per
problem inside a G-block, so a scalar dynamic rotate cannot replace
the per-candidate static shifts, and pre-shifting the carry at row
end just moves the same chain.  Building the match in the kernel adds
~8 ops a row (two lane gathers of the template window, a select, a
compare) and the window's rebuild every few grid steps.

The structural attack — a rotating-band layout where lane k holds
column j ≡ k mod B, so the chain becomes one per-problem mask +
static-rotate pair (~11 ops) — is IMPLEMENTED as of r14 in the
sibling ops/banded_rotband.py (v2).  Two estimates in the r5
paragraph above turned out wrong in v2's favor: the F scan needs NO
extra per-step cost (the wrap mask substitutes ``krel`` for the
column index one-for-one, ~21 ops unchanged), and the lane-rotated
moves are restored by a single host-side take_along_axis gather
outside the kernel, not an in-kernel post-pass.  v2's audited budget
is ~45 ops/row vs ~60 here; the full derivation and the audit table
live in banded_rotband.py's docstring.

The scan in ops/banded.py remains the spec and the differential
oracle for BOTH kernels.

HARDWARE STATUS: both kernels compile for a described v5e
(tests/test_tpu_compile.py) and chip_smoke.py checks their consensus
byte-for-byte against the scan on the chip.  Timed on a v5e, one fill
of N=128 alignments at qmax = tmax = 4096, band 128, with_stats=False,
with the match tile still built outside the kernel: scan 1.8431 s,
this v1 kernel 0.7733 s, rotband 1.6803 s.  So v1 is the fill
wherever nothing is forced (consensus/star.banded_impl_effective): on
a TPU, at qmax <= PALLAS_MAX_QMAX and a multiple of ROWBLOCK, in every
step but a GSPMD-partitioned --mesh one.  The scan keeps qmax >
PALLAS_MAX_QMAX, the CPU and --mesh.
With the match indicators built in the kernel, the same fill on a v5e
(median of 10, moves/offs/score identical to the scan's and to the
out-of-kernel tile's): at qmax = tmax = 2048, 0.0321 s (0.2614 s with
the tile outside), of which the 16 kernel calls are 25.5 ms (1.59 ms
each, as before), compute_offsets' scan 4.8 ms and the packing ~2 ms;
at 4096, 0.0626 s (0.5204 s), kernel calls 51.0 ms, the scan 9.6 ms.
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
    _line_interp,
)

PALLAS_MAX_QMAX = 4096  # beyond this fall back to the scan implementation


def compute_offsets(qlen, tlen, qmax: int, band: int, maxshift: int,
                    line=None):
    """The band-offset schedule for rows 1..qmax (shape (qmax,) int32).

    Bit-exact replica of the offset recurrence in ops/banded.py's scan body
    (global mode), including the freeze beyond qlen.  Vectorize over a batch
    with jax.vmap.
    """
    qlen = qlen.astype(jnp.int32)
    tlen = tlen.astype(jnp.int32)
    tcap = jnp.maximum(tlen - band + 1, 0)
    if line is None:
        li0, lj0, li1, lj1 = (jnp.int32(0), jnp.int32(0), qlen, tlen)
    else:
        line = jnp.asarray(line, jnp.int32)
        li0, lj0, li1, lj1 = line[0], line[1], line[2], line[3]

    def body(off_prev, i):
        # overflow-exact interpolation SHARED with the scan body (the raw
        # int32 product silently diverged from ops/banded.py for large
        # seeded lines — the pre-r14 drift; one definition, imported)
        nom_j = lj0 + _line_interp(i - li0, lj1 - lj0,
                                   jnp.maximum(li1 - li0, 1))
        desired = nom_j - band // 2
        lo = jnp.maximum(0, tcap - (qlen - i) * maxshift)
        off = jnp.clip(
            jnp.maximum(desired, lo), off_prev,
            jnp.minimum(off_prev + maxshift, tcap),
        )
        off = jnp.maximum(off, off_prev)
        off = jnp.where(i <= qlen, off, off_prev)
        return off, off

    _, offs = jax.lax.scan(
        body, jnp.int32(0), jnp.arange(1, qmax + 1, dtype=jnp.int32))
    return offs


ROWBLOCK = 8  # rows per grid step: aligned sublane tiles for loads/stores
GBLOCK = 8    # alignments per grid step, stacked in the sublane axis
MAX_CALLS = 16  # kernel calls a fill is issued as (_batched_align_impl)
NOBASE_Q = 4  # packed query code of a non-base; templates' non-bases are PAD


# rows of the G-batched carry: H, E, [mat, aln, Emat, Ealn,] OFF
_CHG = 7          # with_stats carry rows (stats-free carry is 3)


def _kernel_g(tlen_ref, rows_ref, tpad_ref, moves_ref, fin_ref,
              ch_ref, wnd_ref, *, qmax: int, band: int, maxshift: int,
              params: AlignParams, with_stats: bool, gblock: int):
    """G-batched banded DP fill: GBLOCK alignments per grid step.

    The first kernel revision processed one alignment per grid step, so
    every VPU op ran on a (1, B) sliver — 1/8 sublane utilization, and it
    lost to XLA's vmapped scan ~5.7x.  Here GBLOCK alignments ride the
    sublane axis: the carry is (nch, G, B) VMEM scratch, all recurrence
    math is (G, B) tiles, and per-problem row scalars (band shift d, live
    mask, tlen) enter as (G, 1) columns broadcast across lanes.

    ``with_stats=False`` is the consensus-round configuration (star.
    _aligner): the rounds consume only (moves, offs) — BandedResult is
    discarded — so the mat/aln/Emat/Ealn stat channels are dead weight.
    Dropping them shrinks the carry 7 rows -> 3 and the F prefix scan
    from 3 arrays to 1, cutting most of the kernel's per-cell op count
    (the same trade ops/banded.py makes with its with_stats=False path;
    moves/offs are bit-identical either way).

    The d-shift selection is computed ONCE at shift d-1 over the carry
    block and the d view is derived from it with a single static +1
    shift — shift composition holds lane-for-lane except lane B-1 under
    d == 0, which one masked select patches back to the unshifted carry.
    This halves the select-chain cost vs materializing both views per
    candidate d.

    Per-row scalars — q[i-1]'s code, d (band shift, 0..maxshift) and
    live (i <= qlen) — are BIT-PACKED into one int8 per row (bits 0-2,
    3-5 and 6), broadcast across a (ROWBLOCK, B) tile and read at lane 0:
    Mosaic requires lane-dim blocks of 128 (so a (G, ROWBLOCK) scalar
    block never lowers on real TPU) and dynamic lane slices must be
    128-aligned (so a full-lane scalar array can't be sliced per
    ROWBLOCK chunk either).

    The match indicators are built here, row by row: lane k of row i
    compares q[i-1] with tpad[offs[i] + k], the base entering column
    offs[i] + k.  The kernel holds a 2B-lane window of the problem's
    padded template, from the offset of the row before its first step,
    in VMEM scratch and rebuilds it every ``wsteps`` grid steps: in
    that many steps offsets advance by at most B lanes, so each row's
    band is a per-problem lane gather of the window at offs[i] - base.
    A rebuild picks the three B-lane blocks of the template that hold
    the window (a select over blocks, since the block differs per
    problem) and gathers the window out of them at the base's
    remainder.

    Inputs (blocks):
      tlen_ref (G, 1) int32
      rows_ref (G, ROWBLOCK, B) int8 — per row, on every lane: the
               query code (0-3 a base, NOBASE_Q else) at bits 0-2, d at
               bits 3-5, live at bit 6
      tpad_ref (G, TP) int32 — lane j holds the base of column j
               (t[j-1]; PAD at j = 0, past tmax and for a non-base); the
               same block for every grid step of a problem block
    Outputs: moves (G, ROWBLOCK, B) uint8; fin (G, 8, B) int32 rows
    0/1/2 = final H/mat/aln bands (mat/aln zero when stats are off).
    Scratch: ch_ref, the carry; wnd_ref (3, G, B) int32, the template
    window's two halves and its base offset.
    """
    M, X = params.match, params.mismatch
    O, E = params.gap_open, params.gap_extend
    B = band
    G = gblock
    nch = _CHG if with_stats else 3
    noff = nch - 1                                   # OFF row index
    r = pl.program_id(1)
    karr = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1)
    tlen_col = tlen_ref[:, 0:1]                      # (G, 1)
    lg_b = B.bit_length() - 1                        # B is a power of 2
    nblk = tpad_ref.shape[1] // B
    # grid steps one template window serves: offsets advance by at most
    # ROWBLOCK * maxshift lanes a step, and the window's 2B lanes hold
    # every row's band while they have advanced by at most B
    wsteps = max(1, B // (ROWBLOCK * max(maxshift, 1)))

    def shift_blk(blk, s):
        """Static lane shift of a carry block: out[..., k] = blk[..., k+s],
        NEG fill (matches _pad_prev in ops/banded.py).  Expressed as a
        lane rotate + iota mask: Mosaic lowers tpu.rotate natively, while
        lane-dim concatenates hit "offset mismatch on non-concat
        dimension" and never compile on real TPU."""
        if s == 0:
            return blk
        rolled = jnp.roll(blk, -s, axis=2)
        k3 = karr[None]                              # (1, 1, B)
        if s > 0:
            return jnp.where(k3 >= B - s, NEG, rolled)
        return jnp.where(k3 < -s, NEG, rolled)

    def shift_row(x, s, fill):
        """Static lane shift of one (G, B) tile (rotate + mask)."""
        if s == 0:
            return x
        rolled = jnp.roll(x, -s, axis=1)
        if s > 0:
            return jnp.where(karr >= B - s, fill, rolled)
        return jnp.where(karr < -s, fill, rolled)

    # ---- row 0 init (off = 0), exactly ops/banded.py carry0 ----
    @pl.when(r == 0)
    def _():
        j0 = jnp.broadcast_to(karr, (G, B))
        H0 = jnp.where(j0 <= tlen_col,
                       jnp.where(j0 == 0, 0, O + E * j0), NEG)
        E0 = jnp.full((G, B), NEG, jnp.int32)
        z = jnp.zeros((G, B), jnp.int32)
        rows0 = ([H0, E0, z, j0, z, j0, z] if with_stats
                 else [H0, E0, z])
        ch_ref[:] = jnp.stack(rows0, axis=0)

    def gather(x, idx):
        """out[g, k] = x[g, idx[g, k]]: a per-problem lane gather."""
        return jnp.take_along_axis(x, idx, axis=1)

    # ---- template window: tpad[base + k] for k < 2B, base = this row's
    # offset (the carry's OFF, every lane alike) ----
    @pl.when(r % wsteps == 0)
    def _():
        base = ch_ref[noff]                          # (G, B)
        blk = base >> lg_b
        # the blocks blk, blk+1, blk+2 hold the window
        parts = [tpad_ref[:, m * B:(m + 1) * B] for m in range(3)]
        for c in range(1, nblk - 2):
            hit = blk == c
            parts = [jnp.where(hit, tpad_ref[:, (c + m) * B:(c + m + 1) * B],
                               p) for m, p in enumerate(parts)]
        src = (base & (B - 1)) + karr
        lane = src & (B - 1)
        first = src < B
        wnd_ref[0] = jnp.where(first, gather(parts[0], lane),
                               gather(parts[1], lane))
        wnd_ref[1] = jnp.where(first, gather(parts[1], lane),
                               gather(parts[2], lane))
        wnd_ref[2] = base

    wnd_lo, wnd_hi, wnd_base = wnd_ref[0], wnd_ref[1], wnd_ref[2]
    # int32 throughout: i8 sublane slices hit Mosaic relayout limits
    packed_tile = rows_ref[...].astype(jnp.int32)    # (G, ROWBLOCK, B)
    ch = ch_ref[:]
    moves_rows = []
    for s in range(ROWBLOCK):
        i = r * ROWBLOCK + s + 1
        lane0 = packed_tile[:, s, 0:1]               # (G, 1) packed scalars
        q_col = lane0 & 7
        d_col = (lane0 >> 3) & 7
        live_col = ((lane0 >> 6) & 1) != 0           # (G, 1) bool

        # select the (d-1)-shifted view of the shiftable carry rows (the
        # diagonal predecessors), then derive the d view (the vertical
        # predecessors) from it by one static +1 shift
        chs = ch[:noff]
        sel = shift_blk(chs, -1)                     # d == 0 candidate
        for dd in range(1, maxshift + 1):
            cand = chs if dd == 1 else shift_blk(chs, dd - 1)
            sel = jnp.where((d_col == dd)[None], cand, sel)
        up = shift_blk(sel, 1)
        # composition is exact except lane B-1 under d == 0, where
        # shift(ch, 0) keeps the carry value the +1 shift fills with NEG
        patch = (d_col == 0) & (karr == B - 1)       # (G, B)
        up = jnp.where(patch[None], chs, up)

        Hd_diag = sel[0]
        H_up, E_up = up[0], up[1]
        if with_stats:
            mat_diag, aln_diag = sel[2], sel[3]
            mat_up, aln_up = up[2], up[3]
            Emat_up, Ealn_up = up[4], up[5]
        OFF = ch[noff] + d_col                       # this row's band offset

        # the row's template band out of the window; a non-base never
        # matches (query non-bases are NOBASE_Q, template ones PAD)
        widx = OFF - wnd_base + karr                 # in [0, 2B)
        wlane = widx & (B - 1)
        tb = jnp.where(widx < B, gather(wnd_lo, wlane),
                       gather(wnd_hi, wlane))
        is_match = tb == q_col                       # (G, B) bool
        sub = jnp.where(is_match, M, X)
        if with_stats:
            im = is_match.astype(jnp.int32)
        j = OFF + karr

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

        # F (horizontal) max-plus prefix scan, Hillis-Steele over lanes;
        # combine keeps right on ties (ops/banded.py _combine_rightmax)
        v = Hd + O - E * karr
        if with_stats:
            fm = Hmat
            fa = Haln - karr
        step = 1
        while step < B:
            vs = shift_row(v, -step, NEG)
            keep = v >= vs
            if with_stats:
                ms = shift_row(fm, -step, NEG)
                as_ = shift_row(fa, -step, NEG)
                fm = jnp.where(keep, fm, ms)
                fa = jnp.where(keep, fa, as_)
            v = jnp.where(keep, v, vs)
            step *= 2
        # exclusive: shift right by one (score fill NEG, stats fill 0)
        v = shift_row(v, -1, NEG)
        F = v + E * karr
        if with_stats:
            Fmat = shift_row(fm, -1, 0)
            Faln = shift_row(fa, -1, 0) + karr

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
        H_left = shift_row(Hnew, -1, NEG)
        f_is_open = F == (H_left + O + E)
        fbit = jnp.where(f_is_open, 0, FBIT_EXT).astype(jnp.uint8)
        moves_rows.append((choice | ebit | fbit)[:, None, :])

        rows_new = ([Hnew, Enew, mat_new, aln_new, Emat, Ealn, OFF]
                    if with_stats else [Hnew, Enew, OFF])
        ch_new = jnp.stack(rows_new, axis=0)
        ch = jnp.where(live_col[None], ch_new, ch)

    moves_ref[...] = jnp.concatenate(moves_rows, axis=1)
    ch_ref[:] = ch

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
    """Batched global banded alignment with move emission (Pallas).

    Drop-in for the vmapped scan aligner used by the consensus rounds
    (consensus/star.py): same argument shapes — (..., Qmax) uint8 queries,
    (...,) lengths, (..., Tmax) uint8 templates — and the same
    (BandedResult, moves, offs) result tuple.  ``with_stats=False``
    mirrors ops/banded.py's slim mode: moves/offs/score are identical,
    BandedResult.mat/aln are zeros, and the kernel drops the stat
    channels from its carry (the consensus rounds never read them).
    ``gblock`` overrides the per-grid-step problem block (default
    GBLOCK=8 = one native VPU sublane tile; 16/32 trade VMEM for fewer
    grid steps — CCSX_PALLAS_GBLOCK env for A/B sweeps).  The env var is
    resolved HERE, outside the jit boundary, so flipping it between
    calls retraces with the new value.
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
    if maxshift > 7:
        # d rides the packed row scalars in bits 3-5 (see _kernel_g)
        raise ValueError(f"maxshift={maxshift} exceeds the 3-bit pack limit")
    if B & (B - 1):
        # the template window's lane arithmetic is by shifts and masks
        raise ValueError(f"band={B} must be a power of two")
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

    # pad the problem axis to whole kernel calls of per_call problems,
    # whole gblock blocks each (pad rows: qlen 0, tlen 0)
    per_call = -(-n // (gblock * MAX_CALLS)) * gblock
    npad = -(-n // per_call) * per_call
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

    if qmax % ROWBLOCK != 0:
        raise ValueError(f"qmax={qmax} must be a multiple of {ROWBLOCK}")
    dmat = offs - jnp.concatenate(
        [jnp.zeros((npad, 1), jnp.int32), offs[:, :-1]], axis=1)
    rows = jnp.arange(1, qmax + 1, dtype=jnp.int32)
    live = (rows[None, :] <= qlens_f[:, None]).astype(jnp.int32)
    # bit-pack the per-row scalars (see _kernel_g docstring): bits 0-2
    # q[i-1]'s code, bits 3-5 d, bit 6 live
    qcode = jnp.where(qs_f < 4, qs_f, NOBASE_Q).astype(jnp.int32)
    aux = (qcode | ((dmat & 7) << 3) | (live << 6)).astype(jnp.int8)
    # the padded template, column-indexed, in whole B-lane blocks: the
    # window's three blocks from the largest offset's (tmax - B + 1)
    tmax = ts_f.shape[-1]
    tp = (max(tmax - B + 1, 0) // B + 3) * B
    tcode = jnp.where(ts_f < 4, ts_f, PAD).astype(jnp.int32)
    tpad = jnp.concatenate([
        jnp.full((npad, 1), PAD, jnp.int32), tcode,
        jnp.full((npad, tp - 1 - tmax), PAD, jnp.int32)], axis=1)

    kern = functools.partial(
        _kernel_g, qmax=qmax, band=B, maxshift=maxshift, params=params,
        with_stats=with_stats, gblock=gblock)
    nb = qmax // ROWBLOCK
    # the fill is issued as up to MAX_CALLS kernel calls, one after the
    # other, over equal runs of gblock-problem blocks (the same grid
    # steps in the same order as one call).  A 0.1 s profiler session on
    # a v5e that falls inside one long device operation records no device
    # event at all; here each operation covers one run of blocks.
    # (Kernel calls inside a lax.map loop are never recorded, so the
    # calls are not looped.)
    fill = pl.pallas_call(
        kern,
        grid=(per_call // gblock, nb),
        in_specs=[
            pl.BlockSpec((gblock, 1), lambda i, r: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((gblock, ROWBLOCK, B), lambda i, r: (i, r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((gblock, tp), lambda i, r: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((gblock, ROWBLOCK, B), lambda i, r: (i, r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((gblock, 8, B), lambda i, r: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((per_call, qmax, B), jnp.uint8),
            jax.ShapeDtypeStruct((per_call, 8, B), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((_CHG if with_stats else 3, gblock, B), jnp.int32),
            pltpu.VMEM((3, gblock, B), jnp.int32),
        ],
        interpret=interpret,
    )

    def call(k):
        blk = slice(k, k + per_call)
        # slices, not indexing, so that no gather enters the program
        rows_in = jnp.broadcast_to(aux[blk][:, :, None], (per_call, qmax, B))
        return fill(tlens_f[blk][:, None], rows_in, tpad[blk])

    calls = [call(k) for k in range(0, npad, per_call)]
    moves = jnp.concatenate([m for m, _ in calls])[:n]
    fin = jnp.concatenate([f for _, f in calls])[:n]
    offs = offs[:n]
    qlens_f = qlens_f[:n]
    tlens_f = tlens_f[:n]

    # final-row extraction (mirrors ops/banded.py global-mode epilogue)
    off_fin = offs[:, qmax - 1:].reshape(n)
    laneT = tlens_f - off_fin
    reachable = (laneT >= 0) & (laneT < B)
    lane = jnp.clip(laneT, 0, B - 1)
    # the final H/mat/aln bands at each problem's lane, as a masked sum
    # (one lane is kept)
    at_lane = jnp.arange(B, dtype=jnp.int32) == lane[:, None, None]
    h_fin, mat_fin, aln_fin = (
        v.reshape(n) for v in jnp.split(
            jnp.where(at_lane, fin[:, :3], 0).sum(axis=-1), 3, axis=1))
    zeros = jnp.zeros(lead, jnp.int32)
    res = BandedResult(
        score=jnp.where(reachable, h_fin, NEG).reshape(lead),
        qb=jnp.zeros(lead, jnp.int32),
        qe=qlens_f.reshape(lead),
        tb=jnp.zeros(lead, jnp.int32),
        te=tlens_f.reshape(lead),
        aln=jnp.where(reachable, aln_fin, 0).reshape(lead)
        if with_stats else zeros,
        mat=jnp.where(reachable, mat_fin, 0).reshape(lead)
        if with_stats else zeros,
    )
    moves = moves.reshape(lead + (qmax, B))
    offs = offs.reshape(lead + (qmax,))
    return res, moves, offs
