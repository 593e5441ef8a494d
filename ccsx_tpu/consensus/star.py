"""Shared star-MSA machinery: one alignment+projection+vote round.

Both consensus paths build on this:
  * whole-read (consensus/whole_read.py) loops rounds and materializes;
  * windowed (consensus/windowed.py) additionally consumes the per-column
    stats for breakpoint detection and cursor bookkeeping.

A "round" aligns every pass (globally, banded) to the current draft,
projects each alignment onto draft coordinates, and votes per column.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import List, Sequence

import jax
import numpy as np

from ccsx_tpu.config import AlignParams
from ccsx_tpu.ops import banded, banded_pallas, banded_rotband, msa, traceback


def pass_bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def quantize_len(n: int, q: int) -> int:
    return max(q, -(-n // q) * q)


def bucket_len(n: int, q: int) -> int:
    """Geometric length bucket (~1.25x steps, q-aligned).

    Every distinct padded shape costs an XLA compile (tens of seconds on
    TPU); linear q-quantization makes the shape count linear in sequence
    length, this caps it at ~log.  Padding is masked, so results are
    shape-invariant; both the per-hole round and the batched executor use
    this SAME function, keeping their shapes (and jit caches) aligned.
    """
    b = q
    while b < n:
        b = max(b + q, (int(b * 1.25) // q) * q)
    return b


def pad_to(x: np.ndarray, n: int) -> np.ndarray:
    out = np.full(n, banded.PAD, np.uint8)
    out[: len(x)] = x
    return out


def banded_impl() -> str:
    """The banded DP fill asked for explicitly: 'scan' (the lax.scan
    spec, ops/banded.py), 'pallas' (the v1 band-local G-batched kernel,
    ops/banded_pallas.py) or 'rotband' (the v2 rotating-band kernel,
    ops/banded_rotband.py), from CCSX_BANDED_IMPL (the CLI's
    --banded-impl sets it); '' when none is asked for and
    banded_impl_effective's rule chooses.
    All three are bit-identical in global+moves mode — the scan is the
    spec, both kernels are differential-tested against it
    (tests/test_banded_pallas.py three-way fuzz, interpret mode on CPU;
    chip_smoke.py checks byte-identical FASTA on the chip) — so the knob
    is non-semantic (fingerprint._NON_SEMANTIC) and free to A/B.
    Per-dispatch attribution is visible as the ccsx_banded_impl counter
    in /metrics and the :b<impl> trace-group suffix."""
    impl = os.environ.get("CCSX_BANDED_IMPL", "")
    if impl not in ("", "scan", "pallas", "rotband"):
        raise ValueError(
            f"CCSX_BANDED_IMPL={impl!r}: expected 'scan', 'pallas' or "
            "'rotband'")
    return impl


def _backend() -> str:
    """The backend the fill is built for (a seam for tests)."""
    return jax.default_backend()


def banded_impl_effective(qmax: int, partitioned: bool = False) -> str:
    """The fill _aligner dispatches at this qmax.

    Both kernels need qmax <= PALLAS_MAX_QMAX and a multiple of ROWBLOCK;
    outside that every choice is the scan.  An explicit banded_impl()
    is honoured within it.  Otherwise the rule: the v1 Pallas kernel on
    a TPU, the scan elsewhere — on the CPU (where Pallas only
    interprets), and in a GSPMD-partitioned step (``partitioned``, set
    by the --mesh call sites: XLA cannot partition a Mosaic call).  On a
    v5e, N=128 fills at qmax = tmax = 4096, band 128, took 0.7733 s on
    v1, 1.6803 s on rotband and 1.8431 s on the scan."""
    impl = banded_impl()
    if (qmax > banded_pallas.PALLAS_MAX_QMAX
            or qmax % banded_pallas.ROWBLOCK != 0):
        return "scan"
    if impl:
        return impl
    return "pallas" if _backend() == "tpu" and not partitioned else "scan"


@functools.lru_cache(maxsize=16)
def _aligner(params: AlignParams, partitioned: bool = False):
    # one aligner per scoring config (and partitioning); shape
    # specialization is handled by jit's own trace cache, so distinct
    # (qmax, tmax) buckets reuse this callable instead of rebuilding it.
    # The fill is chosen per call (banded_impl_effective), so
    # CCSX_BANDED_IMPL works after first use.
    # with_stats=False: the consensus rounds use only (moves, offs); the
    # slim carry drops the dead mat/aln channels from the DP scan
    scan_f = banded.make_batched("global", params, with_moves=True,
                                 with_stats=False)

    def f(qs, qlens, ts, tlens):
        impl = banded_impl_effective(qs.shape[-1], partitioned)
        if impl == "scan":
            return scan_f(qs, qlens, ts, tlens)
        # with_stats=False for the kernels too: the rounds read only
        # (moves, offs), and the slim carry (3 rows vs 7 / 2 vs 6, a
        # 1-array F scan vs 3) cuts most of the per-cell op count
        mod = banded_rotband if impl == "rotband" else banded_pallas
        return mod.batched_align_global_moves(
            qs, qlens, ts, tlens, params, with_stats=False,
            interpret=_backend() == "cpu")

    return f


@functools.lru_cache(maxsize=64)
def _projector(tmax: int, max_ins: int):
    projector = traceback.make_projector(tmax, max_ins)
    return jax.jit(jax.vmap(projector, in_axes=(0, 0, 0, 0, None)))


@functools.lru_cache(maxsize=8)
def _voter(max_ins: int):
    return msa.make_voter(max_ins)


@dataclasses.dataclass
class RoundRequest:
    """One star-MSA round of device work, requested by a consensus
    generator (windowed.windowed_gen / StarMsa.consensus_gen).

    The per-hole path satisfies these one at a time (run_rounds); the
    batched pipeline (pipeline/batch.py) stacks requests of equal shape
    from many holes into one (Z, P, W) device dispatch.
    """

    qs: np.ndarray        # (P, qmax) uint8 padded passes
    qlens: np.ndarray     # (P,) int32
    row_mask: np.ndarray  # (P,) bool
    draft: np.ndarray     # (tlen,) uint8 codes — alignment target


@dataclasses.dataclass
class RefineRequest:
    """One WINDOW's entire refinement loop (iters speculative rounds +
    the final strict round), requested as a single unit of device work.

    The per-hole path satisfies it with the host loop (refine_host — the
    spec); the batched pipeline runs it as ONE fused device dispatch
    whose intermediate speculative drafts never leave the chip — the
    dominant dispatch-count reduction of the framework (one launch per
    window instead of iters+1).  By default the executor strips the
    pass-bucket padding back off and packs only the row_mask rows into a
    shared slab with other holes' rows (pipeline/pack.py +
    batch._refine_step_packed); the (P, qmax) request shape with its
    padded rows is still what the host replay, the bucketed
    --pass-buckets control (batch._refine_step), and the --mesh
    shardings consume, and the result's ``advance`` always comes back in
    this request's (P,) pass order whichever executor ran."""

    qs: np.ndarray        # (P, qmax) uint8 padded passes
    qlens: np.ndarray     # (P,) int32
    row_mask: np.ndarray  # (P,) bool
    draft: np.ndarray     # (tlen,) uint8 codes — initial alignment target
    iters: int            # speculative refinement rounds before the final
    # why the windowed loop asks for this window (windowed_gen): "growth"
    # — the hole's previous attempt found no breakpoint and this is it
    # grown by window_add; "forced_flush" — the previous window found
    # none at max_window and was flushed; "" otherwise
    after: str = ""


def window_counts(requests) -> dict:
    """The windowed loop's counts over a batch of RefineRequests: window
    attempts, growths and forced flushes (the Metrics counters)."""
    after = [r.after for r in requests]
    return {"windows": len(after),
            "window_growths": after.count("growth"),
            "window_forced_flushes": after.count("forced_flush")}


@dataclasses.dataclass
class RefineResult:
    """Result of one window's refinement: the final round, plus the
    strict draft materialized LAZILY — non-final windows consume only
    ``rr`` (materialize(upto=bp) + advance), so they never pay for the
    full-draft materialization."""

    rr: "RoundResult"     # the final round (windowed needs bp/advance)
    _draft: "np.ndarray | None" = dataclasses.field(
        default=None, repr=False)

    @property
    def draft(self) -> np.ndarray:
        if self._draft is None:
            self._draft = self.rr.materialize(speculative=False)
        return self._draft


def run_rounds(gen, sm: "StarMsa"):
    """Drive a consensus generator with immediate per-hole device work."""
    try:
        req = next(gen)
        while True:
            if isinstance(req, RefineRequest):
                res = refine_host(sm.round, req.qs, req.qlens,
                                  req.row_mask, req.draft, req.iters)
                req = gen.send(res)
            else:
                rr = sm.round(req.qs, req.qlens, req.row_mask, req.draft)
                req = gen.send(rr)
    except StopIteration as e:
        return e.value


def refine_host(round_fn, qs, qlens, row_mask, draft, iters: int) -> "RefineResult":
    """THE refinement-loop spec: iters speculative rounds + a final one,
    with a fixpoint early-exit.

    When a speculative round leaves the draft unchanged, a re-round on
    it would return the same RoundResult (the round is a pure function
    of its request), so the remaining speculative rounds are no-ops and
    the final strict output is this round's strict materialization —
    the rounds are skipped, bit-identically (tested in
    test_consensus.py).  The strict draft itself is lazy
    (RefineResult.draft), so callers that consume only the final round
    never materialize it.  The fused device step replicates exactly this
    loop (per-hole fixpoint masking included) and is differential-tested
    against it (tests/test_refine_fused.py)."""
    rr = None
    it = 0
    while True:
        rr = round_fn(qs, qlens, row_mask, draft)
        if it == iters:
            break
        new_draft = rr.materialize(speculative=True)
        if np.array_equal(new_draft, draft):
            break
        draft = new_draft
        it += 1
    return RefineResult(rr=rr)


def refine_rounds_gen(qs, qlens, row_mask, draft, iters: int,
                      after: str = ""):
    """Request one window's refinement from the driving executor; returns
    the RefineResult (final round + lazy strict draft), whichever
    executor (per-hole host loop or fused batched device step)
    satisfies it."""
    res = yield RefineRequest(qs, qlens, row_mask, draft, iters, after)
    return res


@dataclasses.dataclass
class RoundResult:
    """Device arrays from one star-MSA round (draft coordinates).

    The per-hole path fills every field (host breakpoint scan needs the
    per-pass tensors).  The batched pipeline computes the breakpoint and
    cursor advance ON DEVICE (ops/breakpoint.py) and transfers only the
    small fields, leaving match/aligned/ins_cnt/lead_ins as None and
    setting bp/advance instead — consumers must branch on bp (the
    windowed generator does)."""

    cons: np.ndarray      # (T,) uint8: 0-3 base, 4 gap
    ins_base: np.ndarray  # (T, R) uint8 majority inserted base per slot/rank
    ins_votes: np.ndarray  # (T, R) int32 supporting passes per slot/rank
    ncov: np.ndarray      # (T,) int32 covering passes
    tlen: int
    nwin: np.ndarray | None = None     # (T,) int32 winning-cell votes
    match: np.ndarray | None = None    # (P, T) bool: pass matches consensus
    aligned: np.ndarray | None = None  # (P, T) uint8 projection
    ins_cnt: np.ndarray | None = None  # (P, T) int32 insertion counts
    lead_ins: np.ndarray | None = None  # (P,) int32 bases before column 0
    bp: int | None = None              # device breakpoint (-1 = none)
    advance: np.ndarray | None = None  # (P,) int32 bases consumed @ bp_eff

    def ins_out(self, speculative: bool = False) -> np.ndarray:
        return msa.emit_insertions(self.ins_base, self.ins_votes,
                                   self.ncov, speculative)

    def materialize(self, upto: int | None = None,
                    speculative: bool = False) -> np.ndarray:
        n = self.tlen if upto is None else upto
        return msa.materialize(self.cons, self.ins_out(speculative), n)

    def materialize_with_qual(self, upto: int | None = None,
                              speculative: bool = False,
                              qv_coeffs: tuple = (8.0, 3.0, 6.0, 5, 1.0,
                                                  7.0, 4),
                              qmax: int = 60):
        """(codes, quals): the materialized consensus plus a per-base
        Phred-scale confidence from the coverage-conditioned vote margin.

        Q = clip(round(base + per_s*min(s, knee)
                       + per_s_tail*max(s - knee, 0) - per_d*d), 1, qmax)
        with qv_coeffs = (base, per_s, per_d, knee, per_s_tail[, per_hp,
        hp_cap]).  The homopolymer coefficients (positions 5-6) are NOT
        applied here: run lengths must be computed on the FINAL
        assembled consensus, and the windowed path materializes one
        chunk at a time (a run spanning a window breakpoint would be
        split and under-penalized) — callers apply
        ``apply_hp_penalty`` after assembly (windowed_gen in windowed.py,
        consensus_gen below).  Here a
        base column's support s is nwin (passes voting the winning cell)
        out of ncov covering passes and d = ncov - s dissent; an
        insertion column's s is its ins_votes rank count.  The shape is
        fitted to the measured per-(s, d) error table on the synthetic
        pass distribution (r4 study): one dissenting pass costs ~8 Q at
        fixed support while each supporter adds only ~3, and the
        unanimous-column error plateaus near Q27-28 at s=6-7 (correlated
        homopolymer/stitch errors extra coverage cannot vote away) —
        hence the knee.  The earlier single net-vote slope (2.5 per net
        vote) conflated "low-coverage unanimous" (much better than
        predicted) with "high-coverage with dissent" (worse), producing
        a non-monotone mid-range (VERDICT r3 weak 7).  This is a
        vote-margin confidence, NOT a calibrated HiFi QV model; the
        reference emits no qualities at all (FASTA only, main.c:714).
        """
        n = self.tlen if upto is None else upto
        ins = self.ins_out(speculative)
        cons = np.asarray(self.cons)[:n]
        m = np.concatenate([cons[:, None], np.asarray(ins)[:n]],
                           axis=1)
        ncov = np.asarray(self.ncov).astype(np.int32)[:n, None]
        support = np.concatenate(
            [np.asarray(self.nwin).astype(np.int32)[:n, None],
             np.asarray(self.ins_votes).astype(np.int32)[:n]], axis=1)
        dissent = ncov - support
        base, per_s, per_d, knee, per_s_tail = qv_coeffs[:5]
        sterm = (per_s * np.minimum(support, knee)
                 + per_s_tail * np.maximum(support - knee, 0))
        q = base + sterm - per_d * dissent
        keep = m.ravel() < 4
        codes = m.ravel()[keep].astype(np.uint8)
        return (codes, np.clip(np.rint(q.ravel()[keep]),
                               1, qmax).astype(np.uint8))


def apply_hp_penalty(codes: np.ndarray, quals: np.ndarray,
                     qv_coeffs: tuple) -> np.ndarray:
    """Homopolymer-run QV penalty on the FINAL assembled consensus.

    Q -= per_hp * min(run - 1, hp_cap) with `run` the homopolymer run
    length containing each emitted base (insertions included), then
    re-clipped to >= 1.  Homopolymer indels are correlated across
    passes, so a unanimous column in a long run can be unanimously
    wrong — the r5 correlated-error study (benchmarks/quality.py)
    measures ~6-9 observed Q lost per run unit at fixed vote margin
    (config.py qv_per_hp discussion).  Applied after chunk assembly —
    NOT inside materialize_with_qual — so runs spanning window
    breakpoints are penalized at their true length; the whole-read and
    windowed paths therefore agree on quals for the same sequence.
    The penalty applies to the already-qv_cap-clipped Q; with the
    default coefficients raw Q maxes at 50 (s=32: 8 + 3*5 + 1*27) below
    qv_cap=60, so pre- vs post-cap order is indistinguishable there.
    A 5-tuple qv_coeffs (r4 behavior) is a no-op."""
    per_hp, hp_cap = qv_coeffs[5:7] if len(qv_coeffs) > 5 else (0.0, 0)
    if not per_hp or not len(codes):
        return quals
    # vectorized run lengths: each run's length broadcast to its members
    change = np.flatnonzero(np.diff(codes)) + 1
    bounds = np.concatenate([[0], change, [len(codes)]])
    runs = np.repeat(np.diff(bounds), np.diff(bounds))
    q = quals.astype(np.int32) - np.rint(
        per_hp * np.minimum(runs - 1, hp_cap)).astype(np.int32)
    return np.maximum(q, 1).astype(np.uint8)


class StarMsa:
    def __init__(self, params: AlignParams, max_ins: int = 4,
                 len_quant: int = 512):
        self.params = params
        self.max_ins = max_ins
        self.len_quant = len_quant

    def round(self, qs: np.ndarray, qlens: np.ndarray, row_mask: np.ndarray,
              draft: np.ndarray) -> RoundResult:
        """qs: (P, qmax) uint8 padded passes; draft: (tlen,) codes."""
        P, qmax = qs.shape
        tlen = len(draft)
        tmax = bucket_len(tlen, self.len_quant)
        aligner = _aligner(self.params)
        projector_b = _projector(tmax, self.max_ins)
        voter = _voter(self.max_ins)
        ts = np.ascontiguousarray(
            np.broadcast_to(pad_to(draft, tmax), (P, tmax)))
        tlens = np.full(P, tlen, np.int32)
        _, moves, offs = aligner(qs, qlens, ts, tlens)
        aligned, ins_cnt, ins_b, lead_ins = projector_b(
            moves, offs, qs, qlens, np.int32(tlen))
        cons, ins_base, ins_votes, ncov, match, nwin = voter(
            aligned, ins_cnt, ins_b, row_mask)
        return RoundResult(
            cons=np.asarray(cons), ins_base=np.asarray(ins_base),
            ins_votes=np.asarray(ins_votes),
            ncov=np.asarray(ncov), nwin=np.asarray(nwin),
            match=np.asarray(match),
            aligned=np.asarray(aligned), ins_cnt=np.asarray(ins_cnt),
            lead_ins=np.asarray(lead_ins), tlen=tlen,
        )

    def pack(self, passes: List[np.ndarray], pass_buckets: Sequence[int],
             max_passes: int, qmax: int | None = None):
        """Pad a pass list to (P, qmax) + lens + row mask."""
        if len(passes) > max_passes:
            passes = passes[:max_passes]
        P = pass_bucket(len(passes), pass_buckets)
        # an undersized bucket list must fail loudly here, not ship a
        # raw-pass-count shape that silently defeats bucketing (one XLA
        # compile per distinct count); the CLI validates buckets vs
        # max_passes up front — this guards library callers
        if P < len(passes):
            raise ValueError(
                f"pass_buckets {tuple(pass_buckets)} do not cover "
                f"{len(passes)} passes (max_passes={max_passes})")
        if qmax is None:
            qmax = bucket_len(max(len(p) for p in passes), self.len_quant)
        qs = np.stack(
            [pad_to(p, qmax) for p in passes]
            + [np.full(qmax, banded.PAD, np.uint8)] * (P - len(passes)))
        qlens = np.array(
            [len(p) for p in passes] + [0] * (P - len(passes)), np.int32)
        return qs, qlens, qlens > 0

    def consensus_gen(self, passes: List[np.ndarray], iters: int,
                      pass_buckets: Sequence[int], max_passes: int,
                      quality: "tuple | None" = None):
        """Generator form of consensus(): yields one RefineRequest,
        receives a RefineResult, returns the final draft — or
        (draft, phred_quals) when ``quality=(qv_coeffs, qv_cap)``
        — via StopIteration.value."""
        qs, qlens, row_mask = self.pack(passes, pass_buckets, max_passes)
        res = yield from refine_rounds_gen(
            qs, qlens, row_mask, passes[0], iters)
        if quality is not None:
            codes, quals = res.rr.materialize_with_qual(
                speculative=False, qv_coeffs=quality[0],
                qmax=quality[1])
            return codes, apply_hp_penalty(codes, quals, quality[0])
        return res.draft

    def consensus(self, passes: List[np.ndarray], iters: int,
                  pass_buckets: Sequence[int], max_passes: int,
                  quality: "tuple | None" = None):
        """iters+1 rounds; intermediate rounds insert speculatively (see
        msa.emit_insertions), the final round applies strict majority."""
        return run_rounds(
            self.consensus_gen(passes, iters, pass_buckets, max_passes,
                               quality), self)
