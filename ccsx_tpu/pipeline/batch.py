"""Batched device pipeline: many holes per TPU dispatch.

The per-hole path (pipeline/run.py) dispatches one star-MSA round per hole
per window — correct, but each dispatch is a small (P, W) problem that
leaves the chip mostly idle.  This runner multiplexes the consensus
generators (windowed_gen / consensus_gen) of many in-flight holes and
executes their pending RefineRequests together:

  admit holes ──> per-hole generator (host state machine)
                    │ yields RefineRequest (one window's refinement)
                    ▼
  group by (qmax, tmax, iters) ──> flatten each hole's passes into
  (hole, pass) ROWS and pack rows from many holes into fixed (R, qmax)
  slabs, first-fit-decreasing by hole (pipeline/pack.py); a row->hole
  segment-id vector rides along.  [--pass-buckets restores the older
  (P, qmax, tmax, iters) bucketed grouping as the A/B control, and a
  device mesh keeps it — the (data, pass) shardings need the fixed
  (Z, P) layout.]
                    ▼
  ONE fused jitted dispatch per slab (_refine_step_packed; _refine_step
  for the bucketed control): the speculative refinement rounds loop on
  device (banded DP fill + traceback projection + segment-id column
  vote + draft re-materialization), then the final round + breakpoint
  scan — intermediate drafts never leave the chip
                    ▼
  RefineResults routed back into each generator; finished holes emit
  consensus to the order-preserving writer.

This is the TPU analog of the reference's kt_for over a chunk's ZMWs
(main.c:702-704): the chunk becomes a device batch, the work-stealing
becomes shape-bucketed batching (SURVEY.md §2.2).  Output order is input
order, like the reference's ordered pipeline (kthread.c:202-213).
"""

from __future__ import annotations

import dataclasses
import functools
import sys
import time
from collections import defaultdict
from typing import Dict, List, Optional

import jax
import numpy as np

from ccsx_tpu.config import AlignParams, CcsConfig
from ccsx_tpu.consensus import prepare as prep_mod
from ccsx_tpu.consensus import windowed
from ccsx_tpu.consensus.align_host import MatchResult
from ccsx_tpu.consensus.hole import full_gen_for_zmw
from ccsx_tpu.consensus.star import (
    RefineRequest, RefineResult, RoundRequest, RoundResult, StarMsa,
    banded_impl_effective, bucket_len, pad_to, refine_host, window_counts,
)
from ccsx_tpu.ops import banded
from ccsx_tpu.ops import encode as enc
from ccsx_tpu.ops import traceback
from ccsx_tpu.pipeline import pack as pack_mod
from ccsx_tpu.pipeline import resilience as resil_mod
from ccsx_tpu.utils import faultinject
from ccsx_tpu.utils import trace
from ccsx_tpu.utils.journal import Journal
from ccsx_tpu.utils.metrics import (FailureBudgetExceeded, Metrics,
                                    check_failure_budget)

# the named scopes of a round's stages, which a profiler trace reads
_FILL, _TRACEBACK, _VOTE, _BREAKPOINT = trace.STAGES


# ---- failure taxonomy (the fault-tolerance layer's classification of
# ---- exceptions escaping a jitted device dispatch; ARCHITECTURE.md
# ---- "Failure domains") ---------------------------------------------------

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "OUT OF MEMORY", "FAILED TO ALLOCATE")
# DELIBERATELY narrow: only the TPU-kernel toolchain's own names.  Broad
# words ("compile", "unsupported", "lowering") also appear in ordinary
# Python/data errors — e.g. TypeError "unsupported operand" — and a
# false 'compile' here would abort the whole run over a single bad
# hole.  A kernel compile failure that slips past these markers is
# classified 'data' and replayed on the host path.
_COMPILE_MARKERS = ("MOSAIC", "PALLAS")
# deliberate validation errors in our own code (e.g. banded_pallas's
# "qmax exceeds PALLAS_MAX_QMAX" / "CCSX_PALLAS_GBLOCK" ValueErrors)
# mention the kernel by name but are per-group DATA conditions — the
# compiler toolchain never raises these builtin types
_DATA_EXC_TYPES = (ValueError, TypeError, KeyError, IndexError,
                   AssertionError)


def classify_failure(exc: BaseException) -> str:
    """'hang' | 'oom' | 'compile' | 'data' for an exception from a
    device dispatch.

    'hang' (DeviceHang class) is a dispatch deadline expiry
    (resilience.DeadlineExpired): the call was ABANDONED, so there is
    nothing to retry — re-dispatching onto a wedged backend would burn
    another deadline — and the group goes straight down the host-replay
    rung (and strikes the circuit breaker).  The rest are string-matched
    on the message (+ exception type name): XLA surfaces
    both allocator exhaustion and compiler failures as XlaRuntimeError
    subclasses whose types differ across jaxlib versions, but whose
    status-code prefixes (RESOURCE_EXHAUSTED, ...) are stable.  'oom'
    is a TRANSIENT-DEVICE failure with a recovery ladder (resplit /
    host replay); 'compile' is a program error the ladder re-raises;
    'data' means the inputs or our own code are at fault — replayed
    per-hole on the host path so the blast radius is one quarantined
    hole, never the run."""
    if isinstance(exc, resil_mod.DeadlineExpired):
        return "hang"
    msg = f"{type(exc).__name__}: {exc}".upper()
    if any(m in msg for m in _OOM_MARKERS):
        return "oom"
    if (any(m in msg for m in _COMPILE_MARKERS)
            and not isinstance(exc, _DATA_EXC_TYPES)):
        return "compile"
    return "data"


# ---- failure recovery (shared by BatchExecutor and PairExecutor) ---------

def _out_shape_tag(out):
    """Shape signature of a dispatch's output pytree — the materialize
    span's compile-grace key.  jit recompiles per distinct shape, and on
    a fully lazy runtime the compile can block at MATERIALIZATION rather
    than at dispatch, so the first wait on each (group, output-shape)
    must get the watchdog's compile grace or a healthy cold recompile is
    stamped degraded.  Output shapes change exactly when the compiled
    signature does (the batch dim rides every output), so this is a
    faithful per-executable key — and unlike the dispatch key it is
    computable here, in the executor-generic wait path.  The key also
    carries the output's device id(s): jit compiles one executable PER
    DEVICE, and round-robined slabs materialize on different chips, so
    each chip's first same-shape wait must get its own compile grace
    (same rule as the dispatch span's :d{i} tag)."""
    try:
        leaves = jax.tree_util.tree_leaves(out)
        tag = ",".join("x".join(str(d) for d in getattr(l, "shape", ()))
                       for l in leaves)
        for l in leaves:
            devs = getattr(l, "devices", None)
            if callable(devs):
                tag += ":d" + "-".join(
                    str(i) for i in sorted(d.id for d in devs()))
                break
        return tag
    except Exception:
        return None


def _fetch_finish(idxs, key, out, finish) -> None:
    """The host side of a materialized dispatch: the d2h copy of its
    outputs ("fetch"), then ``finish`` scattering them into results
    ("finish"), each a span of its own."""
    with trace.span("fetch", cat="compute"):
        out = jax.device_get(out)
    with trace.span("finish", cat="compute"):
        finish(idxs, key, out)


def _bounded(resil, label_str, phase, fn):
    """Deadline-bound ``fn`` through the run's Resilience object (a
    plain call when deadlines are off / no resilience is wired)."""
    if resil is None or not resil.enabled:
        return fn()
    return resil.call(fn, label_str, phase)


def _host_replay_all(idxs, key, host_one, results, metrics, label,
                     reason) -> None:
    """The ladder bottom (and the breaker's open-state route): replay each
    request on the bit-exact host path; a host failure becomes that
    request's result (an Exception the driver quarantines per hole)."""
    for i in idxs:
        if metrics is not None:
            metrics.bump(host_fallbacks=1)
        try:
            with trace.span("host_replay", cat="recover",
                            group=label(key), reason=reason):
                results[i] = host_one(i)
        except Exception as he:  # quarantined per hole by the driver
            results[i] = he


def _run_group_sync(idxs, key, dispatch, finish, host_one, results,
                    metrics, depth, max_resplits, backoff_s,
                    label=str, resil=None, probe=False) -> None:
    """Dispatch+materialize one (sub)group synchronously, recovering
    from failures (used on the resplit/retry paths, where the happy
    path's dispatch-all-then-materialize overlap no longer applies).
    ``probe``: this episode carries the breaker's half-open probe
    token — its success/failure (and only its) settles the probe."""
    try:
        out = _bounded(resil, label(key), "dispatch",
                       lambda: dispatch(idxs, key))
        # same watchdog coverage as the happy path: on an async runtime
        # a hang in a RETRIED dispatch would otherwise surface inside
        # finish()'s materialization, invisible to the stall watchdog —
        # exactly on the flaky-device runs most likely to be mid-recovery
        with trace.device_span("materialize", group=label(key),
                               shape=_out_shape_tag(out),
                               attribute=False, n=len(idxs)):
            out = _bounded(resil, label(key), "materialize",
                           lambda: jax.block_until_ready(out))
        _fetch_finish(idxs, key, out, finish)
        if probe and resil is not None:
            resil.breaker.probe_succeeded()
    except Exception as e:
        _recover_group(e, idxs, key, dispatch, finish, host_one, results,
                       metrics, depth, max_resplits, backoff_s,
                       label=label, resil=resil, probe=probe)


def _recover_group(exc, idxs, key, dispatch, finish, host_one, results,
                   metrics, depth, max_resplits, backoff_s,
                   label=str, resil=None, probe=False) -> None:
    """The adaptive-retry ladder for one failed shape group.

    hang    -> (DeviceHang: the dispatch deadline abandoned a wedged
               call) no retry — the backend just proved it can wedge —
               straight to the host replay below; books device_hangs +
               the degraded mark and strikes the circuit breaker
    oom     -> bisect idxs (halves run at half the Z/N bucket), with
               exponential backoff and capped depth; the ladder BOTTOM
               (no more halving) strikes the breaker
    compile -> (a Pallas/Mosaic kernel failed to lower or compile) a
               program error: re-raised, so the run fails loudly
    data / ladder bottom -> replay each request on the host path;
               a host failure becomes that request's result (an
               Exception the driver quarantines per hole).  'data'
               never strikes the breaker: a bad hole says nothing
               about backend health
    """
    kind = classify_failure(exc)
    trace.instant("recover", cat="recover", kind=kind, group=label(key),
                  n=len(idxs), depth=depth)
    if kind == "hang" and resil is not None:
        resil.note_hang(label(key), exc, probe=probe)
    if kind == "compile":
        # a kernel that does not compile is a program error: fail the
        # dispatch loudly instead of quietly running something else
        raise exc
    if kind == "oom" and depth < max_resplits and len(idxs) > 1:
        if metrics is not None:
            metrics.bump(oom_resplits=1)
        print(f"[ccsx-tpu] device OOM on a {len(idxs)}-request group "
              f"{key}: resplitting (depth {depth + 1}): {exc}",
              file=sys.stderr)
        time.sleep(backoff_s * (2 ** depth))
        mid = (len(idxs) + 1) // 2
        for part in (idxs[:mid], idxs[mid:]):
            _run_group_sync(part, key, dispatch, finish, host_one,
                            results, metrics, depth + 1, max_resplits,
                            backoff_s, label=label,
                            resil=resil, probe=probe)
        return
    if kind == "oom" and resil is not None:
        # the OOM ladder bottomed out (depth cap or single request):
        # that is a backend-health strike, unlike a recoverable resplit
        resil.breaker.strike("oom", label(key), probe=probe)
    if kind == "data" and resil is not None and probe:
        # a per-hole data error never strikes — but THE probe's token
        # must still be released or the breaker wedges half-open
        # forever (admit() refuses all dispatch while a probe is
        # outstanding); non-probe data failures leave the probe alone
        resil.breaker.settle_probe()
    print(f"[ccsx-tpu] device dispatch failed ({kind}) for a "
          f"{len(idxs)}-request group {key}; replaying on the host "
          f"path: {exc}", file=sys.stderr)
    _host_replay_all(idxs, key, host_one, results, metrics, label, kind)


def _run_groups_recovering(groups, dispatch, finish, host_one, results,
                           metrics, max_resplits=3,
                           backoff_s=0.05, label=str, resil=None) -> None:
    """Happy path: dispatch every group's device work before
    materializing any result (jit dispatch is async, so group B's
    compute overlaps group A's d2h transfer); failures at either
    phase drop that one group into the recovery ladder.  ``finish``
    receives the outputs as host arrays (_fetch_finish).  ``label``
    maps a group key to the STABLE trace-group string the dispatch
    spans use (e.g. dropping the packed path's per-slab ordinal), so
    materialize spans share the dispatch namespace and the watchdog's
    per-(group, shape) compile grace neither re-arms on every slab nor
    misses a fresh shape's cold compile.

    Resilience (pipeline/resilience.py, ``resil``): an OPEN circuit
    breaker routes whole groups to the host path without touching the
    device (one probe group per --breaker-probe-s interval when
    half-open); a configured --dispatch-deadline bounds both the
    dispatch call and the materialize wait, abandoning wedged calls
    into the ladder's ``hang`` class."""
    _OPEN = object()   # sentinel: breaker refused this group's dispatch
    pending = []
    for key, idxs in groups.items():
        mode = resil.admit() if resil is not None else "closed"
        if mode == "host":
            pending.append((idxs, key, _OPEN, None, False))
            continue
        probe = mode == "probe"
        try:
            out = _bounded(resil, label(key), "dispatch",
                           lambda k=key, i=idxs: dispatch(i, k))
            pending.append((idxs, key, None, out, probe))
        except Exception as e:
            pending.append((idxs, key, e, None, probe))
    for idxs, key, exc, out, probe in pending:
        if exc is _OPEN:
            trace.instant("recover", cat="recover", kind="breaker_open",
                          group=label(key), n=len(idxs))
            _host_replay_all(idxs, key, host_one, results, metrics,
                             label, "breaker_open")
            continue
        try:
            if exc is not None:
                raise exc
            # watchdog coverage for the UNFORCED (untraced) case: on an
            # async runtime the dispatch span closes in ~1 ms and a hung
            # device surfaces HERE, when the outputs materialize — so
            # the blocking wait alone is its own device span
            # (attribute=False: it is wait, not chip work, and must not
            # pollute the compile/execute group table; shape keys the
            # compile grace — a lazy runtime may pay the cold compile in
            # this wait, not at dispatch).  finish() stays OUTSIDE: its
            # host work (overflow replays) is legitimately slow and must
            # not trip the watchdog
            with trace.device_span("materialize", group=label(key),
                                   shape=_out_shape_tag(out),
                                   attribute=False, n=len(idxs)):
                out = _bounded(resil, label(key), "materialize",
                               lambda o=out: jax.block_until_ready(o))
            _fetch_finish(idxs, key, out, finish)
            # only THE probe's own completion settles the breaker — a
            # concurrent pre-trip group finishing must not close it on
            # stale evidence (the admit() token carries the identity)
            if probe and resil is not None:
                resil.breaker.probe_succeeded()
        except Exception as e:
            _recover_group(e, idxs, key, dispatch, finish, host_one,
                           results, metrics, 0, max_resplits, backoff_s,
                           label=label, resil=resil, probe=probe)


@functools.lru_cache(maxsize=128)
def _round_body(params: AlignParams, max_ins: int, tmax: int,
                partitioned: bool = False):
    """The ONE star-round body both jitted steps build on: align every
    (hole, pass) window to its hole's draft (banded DP), project onto
    draft coordinates, vote per column.  _round_step and _refine_step
    share this function so the fused loop cannot drift from the
    single-round spec the differential tests pin.  ``partitioned``: the
    step is GSPMD-partitioned over the --mesh, so the fill stays the
    scan (star.banded_impl_effective)."""
    from ccsx_tpu.consensus import star as star_mod
    from ccsx_tpu.ops import msa as msa_mod

    aligner = star_mod._aligner(params, partitioned)
    projector = traceback.make_projector(tmax, max_ins)
    voter = msa_mod.make_voter(max_ins)

    def body(qs, qlens, row_mask, draft, dlen):
        Z, P, qmax = qs.shape
        ts_b = jax.numpy.broadcast_to(draft[:, None, :], (Z, P, tmax))
        tl_b = jax.numpy.broadcast_to(dlen[:, None], (Z, P))
        with jax.named_scope(_FILL):
            _, moves, offs = aligner(
                qs.reshape(Z * P, qmax), qlens.reshape(Z * P),
                ts_b.reshape(Z * P, tmax), tl_b.reshape(Z * P))
        moves = moves.reshape(Z, P, qmax, -1)
        offs = offs.reshape(Z, P, qmax)
        proj = jax.vmap(jax.vmap(projector, in_axes=(0, 0, 0, 0, None)),
                        in_axes=(0, 0, 0, 0, 0))
        with jax.named_scope(_TRACEBACK):
            aligned, ins_cnt, ins_b, lead_ins = proj(
                moves, offs, qs, qlens, dlen)
        with jax.named_scope(_VOTE):
            cons, ins_base, ins_votes, ncov, match, nwin = jax.vmap(
                voter)(aligned, ins_cnt, ins_b, row_mask)
        return (cons, ins_base, ins_votes, ncov, nwin, match, aligned,
                ins_cnt, lead_ins)

    return body


@functools.lru_cache(maxsize=128)
def _round_step(params: AlignParams, max_ins: int, tmax: int,
                bp_consts: tuple, pack: tuple | None = None,
                partitioned: bool = False):
    """Jitted batched star round: (Z, P, qmax) passes vs (Z, tmax) drafts.

    Z/P/qmax shape specialization is left to jit's trace cache; tmax,
    max_ins (projector output shape) and the breakpoint constants key
    the cache here.  The breakpoint scan + cursor advance run on-device
    (ops/breakpoint.py), so only small per-hole outputs cross to the
    host — not the (Z, P, tmax) match/aligned/ins_cnt tensors.

    pack=(P, qmax) selects the TRANSFER-PACKED variant for single-device
    runs: inputs arrive as ONE (Z, P*qmax + tmax) uint8 buffer + ONE
    (Z, 2P+1) int32 buffer and outputs leave as one uint8 + one int32
    buffer (see _pack_args/_unpack_round).  Host<->device transfer cost
    is dominated by a fixed per-transfer latency, not bandwidth
    (a fixed DMA/launch overhead per transfer), so 5 h2d + 7 d2h per
    dispatch costs ~12 latencies where 2 + 2 cost 4.  The multi-device
    path keeps separate arrays — they carry per-argument NamedShardings
    (_shard_args); ``partitioned`` marks that GSPMD step (_round_body)."""
    import jax.numpy as jnp

    from ccsx_tpu.ops import breakpoint as bp_mod

    body = _round_body(params, max_ins, tmax, partitioned)
    bp_advance = bp_mod.make_bp_advance(tmax, *bp_consts)

    def core(qs, qlens, ts, tlens, row_mask):
        (cons, ins_base, ins_votes, ncov, nwin, match, aligned, ins_cnt,
         lead_ins) = body(qs, qlens, row_mask, ts, tlens)
        with jax.named_scope(_BREAKPOINT):
            bp, advance = jax.vmap(bp_advance)(
                match, cons, aligned, ins_cnt, lead_ins, row_mask, tlens)
        # compact the d2h payload: votes/coverage are bounded by the pass
        # count (<= 64 with the largest pass bucket), so uint8 halves the
        # transfer; the host casts back before arithmetic
        # (msa.emit_insertions)
        return (cons, ins_base, ins_votes.astype(jnp.uint8),
                ncov.astype(jnp.uint8),
                nwin.astype(jnp.uint8), bp, advance)

    if pack is None:
        # jit names the program after the function it traces, which
        # names the dispatch site in a profiler trace's "XLA Modules"
        core.__name__ = "ccsx_round"
        return jax.jit(core)
    P, qmax = pack

    @jax.jit
    def ccsx_round(big, small):
        qs, qlens, ts, tlens, row_mask = _unpack_args_jax(
            big, small, P, qmax, tmax)
        cons, ins_base, ins_votes, ncov, nwin, bp, advance = core(
            qs, qlens, ts, tlens, row_mask)
        Z = big.shape[0]
        big_out = jnp.concatenate([
            cons.astype(jnp.uint8),
            ins_base.reshape(Z, tmax * max_ins).astype(jnp.uint8),
            ins_votes.reshape(Z, tmax * max_ins),
            ncov, nwin], axis=1)
        small_out = jnp.concatenate(
            [bp[:, None], advance], axis=1).astype(jnp.int32)
        return big_out, small_out

    return ccsx_round


def _pack_args(args):
    """Host side of the packed single-device transfer protocol: the 5
    round/refine inputs become one uint8 and one int32 buffer (one h2d
    latency each instead of five)."""
    qs, qlens, ts, tlens, row_mask = args
    Z, P, qmax = qs.shape
    big = np.concatenate([qs.reshape(Z, P * qmax), ts], axis=1)
    small = np.concatenate(
        [qlens, tlens[:, None], row_mask.astype(np.int32)], axis=1)
    return big, small


def _unpack_args_jax(big, small, P: int, qmax: int, tmax: int):
    """Device side of _pack_args (slices compile to views/copies that
    cost nothing next to the transfer latencies they replace)."""
    Z = big.shape[0]
    qs = big[:, :P * qmax].reshape(Z, P, qmax)
    ts = big[:, P * qmax:P * qmax + tmax]
    qlens = small[:, :P]
    tlens = small[:, P]
    row_mask = small[:, P + 1:2 * P + 1] != 0
    return qs, qlens, ts, tlens, row_mask


def _unpack_round(big, small, max_ins: int, tmax: int):
    """Host-side split of a packed round result back into the 7-tuple
    (cons, ins_base, ins_votes, ncov, nwin, bp, advance) with the same
    dtypes the unpacked path ships."""
    Z = big.shape[0]
    R = max_ins
    cons = big[:, :tmax]
    ins_base = big[:, tmax:tmax * (1 + R)].reshape(Z, tmax, R)
    ins_votes = big[:, tmax * (1 + R):tmax * (1 + 2 * R)].reshape(
        Z, tmax, R)
    ncov = big[:, tmax * (1 + 2 * R):tmax * (2 + 2 * R)]
    nwin = big[:, tmax * (2 + 2 * R):tmax * (3 + 2 * R)]
    bp = small[:, 0]
    advance = small[:, 1:]
    return cons, ins_base, ins_votes, ncov, nwin, bp, advance


def _z_bucket(n: int) -> int:
    """Pad the batch Z to the next power of two (bounds jit retraces)."""
    z = 1
    while z < n:
        z *= 2
    return z


def _fused_tmax(tlen: int, quant: int) -> int:
    """Draft capacity for the fused refinement step: one geometric bucket
    above the request's own, so the speculative rounds' liberal inserts
    (msa.emit_insertions) stay on device in the overwhelmingly common
    case.  A draft outgrowing even that is flagged by the step and
    replayed exactly on the host (refine_host)."""
    b = bucket_len(tlen, quant)
    return bucket_len(b + 1, quant)


@functools.lru_cache(maxsize=128)
def _refine_step(params: AlignParams, max_ins: int, tmax: int, iters: int,
                 bp_consts: tuple, pack: tuple | None = None,
                 partitioned: bool = False):
    """ONE jitted dispatch for a window's whole refinement loop.

    pack=(P, qmax) selects the transfer-packed single-device variant
    (same protocol and rationale as _round_step; small_out additionally
    carries dlen and ovf); ``partitioned`` as in _round_step.

    Runs `iters` speculative star rounds in a device while_loop —
    realign to draft, vote, emit insertions liberally, re-materialize
    the draft ON DEVICE (msa.emit_insertions_jax / make_materializer) —
    then the final round with the device breakpoint scan.  Per-hole
    fixpoint masking mirrors refine_host's early-exit bit-exactly: a
    hole whose speculative draft stops changing is frozen (re-rounds on
    a fixed draft are no-ops, so freezing == the host's skip), and the
    loop exits early once every hole is frozen.  This cuts the batched
    pipeline's device dispatches per window from iters+1 to 1 — the
    reference pays no such per-round launch cost (its POA rounds are
    function calls, main.c:486-492), so this is where the TPU pipeline
    wins back launch overhead.
    """
    import jax.numpy as jnp

    from ccsx_tpu.ops import breakpoint as bp_mod
    from ccsx_tpu.ops import msa as msa_mod

    one_round = _round_body(params, max_ins, tmax, partitioned)
    bp_advance = bp_mod.make_bp_advance(tmax, *bp_consts)
    mat_v = jax.vmap(msa_mod.make_materializer(tmax, tmax, max_ins))
    spec_emit = jax.vmap(
        lambda ib, iv, nc: msa_mod.emit_insertions_jax(ib, iv, nc, True))

    def core(qs, qlens, ts, tlens, row_mask):
        Z, P, _ = qs.shape

        def body(carry):
            it, draft, dlen, fixed, ovf, outs = carry
            new = one_round(qs, qlens, row_mask, draft, dlen)
            # a frozen hole keeps its LAST live round's outputs — for a
            # fixpoint hole that round IS the host loop's final round
            # (re-rounding an unchanged draft is a no-op), so carrying
            # the outputs here is what lets the separate final round be
            # folded away entirely
            outs = tuple(
                jnp.where(fixed.reshape((Z,) + (1,) * (n.ndim - 1)), o, n)
                for o, n in zip(outs, new))
            cons, ins_base, ins_votes, ncov = outs[:4]
            with jax.named_scope(_VOTE):
                ins_out = spec_emit(ins_base, ins_votes, ncov)
                nd, nl, o = mat_v(cons, ins_out, dlen)
            # fixpoint: same length AND same padded cells == the host's
            # np.array_equal on the exact-length drafts (pads are PAD on
            # both sides, and a length change forces a cell change)
            now_fixed = (nl == dlen) & (nd == draft).all(axis=1)
            # the round at it == iters is the host loop's mandatory final
            # round: its outputs are kept and nobody grows past it
            last = it >= iters
            # overflow only matters when the speculative draft would be
            # consumed (it < iters); an overflowed hole keeps its
            # in-range draft/dlen and is FROZEN — its device result is
            # discarded for a host replay, and freezing keeps the carry
            # valid for the static shapes and stops it holding the loop
            # open
            o = ~fixed & o & ~last
            grow = ~fixed & ~o & ~now_fixed & ~last
            draft = jnp.where(grow[:, None], nd, draft)
            dlen = jnp.where(grow, nl, dlen)
            return (it + 1, draft, dlen, fixed | now_fixed | o | last,
                    ovf | o, outs)

        def cond(carry):
            return ~carry[3].all()

        # Memory note: carrying the full outs tuple (incl. the (Z,P,tmax)
        # match/aligned/ins_cnt tensors needed only by the post-loop
        # bp_advance) keeps those buffers live across every iteration,
        # roughly tripling the fused step's large per-pass buffers vs the
        # unfused round.  The alternative — carry only (draft, dlen) and
        # recompute the kept round once after the loop (one_round is pure,
        # and a frozen hole's draft/dlen stop changing, so the recompute
        # reproduces the kept outputs exactly) — costs one extra full
        # round of compute per window (~1/(iters+1) e2e).  On v5e the Z
        # buckets fit comfortably, so we spend the memory; flip to the
        # recompute form if a larger chip/bucket ever OOMs here.  (Since
        # the fault-tolerance layer, an OOM here no longer kills the
        # run: BatchExecutor._recover bisects the Z batch and retries —
        # the recompute form remains the right STRUCTURAL fix if
        # resplits ever show up in metrics.oom_resplits at steady state.)
        # pad holes (all-False row_mask) start frozen so they can't keep
        # the while_loop alive
        fixed0 = ~row_mask.any(axis=1)
        ovf0 = jnp.zeros((Z,), bool)
        outs0 = (
            jnp.zeros((Z, tmax), jnp.uint8),            # cons
            jnp.zeros((Z, tmax, max_ins), jnp.uint8),   # ins_base
            jnp.zeros((Z, tmax, max_ins), jnp.int32),   # ins_votes
            jnp.zeros((Z, tmax), jnp.int32),            # ncov
            jnp.zeros((Z, tmax), jnp.int32),            # nwin
            jnp.zeros((Z, P, tmax), bool),              # match
            jnp.zeros((Z, P, tmax), jnp.uint8),         # aligned
            jnp.zeros((Z, P, tmax), jnp.int32),         # ins_cnt
            jnp.zeros((Z, P), jnp.int32),               # lead_ins
        )
        _, _, dlen, _, ovf, outs = jax.lax.while_loop(
            cond, body, (jnp.int32(0), ts, tlens, fixed0, ovf0, outs0))
        (cons, ins_base, ins_votes, ncov, nwin, match, aligned, ins_cnt,
         lead_ins) = outs
        with jax.named_scope(_BREAKPOINT):
            bp, advance = jax.vmap(bp_advance)(
                match, cons, aligned, ins_cnt, lead_ins, row_mask, dlen)
        # uint8 vote/coverage compaction, as in _round_step
        return (cons, ins_base, ins_votes.astype(jnp.uint8),
                ncov.astype(jnp.uint8), nwin.astype(jnp.uint8),
                bp, advance, dlen, ovf)

    if pack is None:
        core.__name__ = "ccsx_refine"    # the program's name, as above
        return jax.jit(core)
    P, qmax = pack

    @jax.jit
    def ccsx_refine(big, small):
        args = _unpack_args_jax(big, small, P, qmax, tmax)
        (cons, ins_base, ins_votes, ncov, nwin, bp, advance, dlen,
         ovf) = core(*args)
        Z = big.shape[0]
        big_out = jnp.concatenate([
            cons.astype(jnp.uint8),
            ins_base.reshape(Z, tmax * max_ins).astype(jnp.uint8),
            ins_votes.reshape(Z, tmax * max_ins),
            ncov, nwin], axis=1)
        small_out = jnp.concatenate(
            [bp[:, None], advance, dlen[:, None],
             ovf[:, None].astype(jnp.int32)], axis=1).astype(jnp.int32)
        return big_out, small_out

    return ccsx_refine


def _unpack_refine(big, small, max_ins: int, tmax: int):
    """Host-side split of a packed refine result back into the 9-tuple
    (cons, ins_base, ins_votes, ncov, nwin, bp, advance, dlen, ovf)."""
    cons, ins_base, ins_votes, ncov, nwin, bp, rest = _unpack_round(
        big, small, max_ins, tmax)
    return (cons, ins_base, ins_votes, ncov, nwin, bp, rest[:, :-2],
            rest[:, -2], rest[:, -1] != 0)


# ---- ragged pass-packed dispatch (pipeline/pack.py plans the slabs;
# ---- these are the device steps and the slab transfer protocol) ----------

@functools.lru_cache(maxsize=128)
def _round_body_packed(params: AlignParams, max_ins: int, tmax: int,
                       nseg: int):
    """One star round over a packed slab: (R, qmax) rows from up to
    ``nseg`` holes, each row aligned to ITS hole's draft (a per-row
    gather replaces the bucketed path's per-hole broadcast), voted by
    segment id (msa.make_segment_voter).  Per-row alignment and
    projection are the same pure functions as _round_body's, so a row's
    tensors do not depend on which slab it rides in — the keystone of
    the packed path's byte-identity."""
    from ccsx_tpu.consensus import star as star_mod
    from ccsx_tpu.ops import msa as msa_mod

    aligner = star_mod._aligner(params)
    projector = traceback.make_projector(tmax, max_ins)
    voter = msa_mod.make_segment_voter(max_ins, nseg)

    def body(qs, qlens, row_mask, seg, draft, dlen):
        ts_r = draft[seg]          # (R, tmax) per-row targets
        tl_r = dlen[seg]           # (R,)
        with jax.named_scope(_FILL):
            _, moves, offs = aligner(qs, qlens, ts_r, tl_r)
        proj = jax.vmap(projector, in_axes=(0, 0, 0, 0, 0))
        with jax.named_scope(_TRACEBACK):
            aligned, ins_cnt, ins_b, lead_ins = proj(
                moves, offs, qs, qlens, tl_r)
        with jax.named_scope(_VOTE):
            cons, ins_base, ins_votes, ncov, match, nwin = voter(
                aligned, ins_cnt, ins_b, row_mask, seg)
        return (cons, ins_base, ins_votes, ncov, nwin, match, aligned,
                ins_cnt, lead_ins)

    return body


@functools.lru_cache(maxsize=128)
def _refine_core_packed(params: AlignParams, max_ins: int, tmax: int,
                        iters: int, nseg: int, bp_consts: tuple):
    """The fused whole-window refinement loop over ONE packed slab —
    _refine_step's ragged twin.  The while_loop carries per-SEGMENT
    (hole-slot) fixpoint state instead of per-Z-slot state: hole-shaped
    carries (draft/dlen/fixed/ovf and the vote outputs) are (H, ...)
    with H = nseg, the per-row tensors the post-loop breakpoint needs
    are (R, ...), and freezing broadcasts hole state onto rows through
    the segment vector.  Same fixpoint/overflow semantics as the
    bucketed step (which tests pin against refine_host, the spec).

    This is the UNJITTED core; _refine_step_packed wraps it in the
    single-device slab wire protocol and _refine_step_packed_fused in
    the multi-chip (D, slab) shard_map — both compile the same
    computation, which is what keeps single-chip and multi-chip output
    byte-identical."""
    import jax.numpy as jnp

    from ccsx_tpu.ops import breakpoint as bp_mod
    from ccsx_tpu.ops import msa as msa_mod

    one_round = _round_body_packed(params, max_ins, tmax, nseg)
    bp_advance = bp_mod.make_bp_advance_packed(tmax, nseg, *bp_consts)
    mat_v = jax.vmap(msa_mod.make_materializer(tmax, tmax, max_ins))
    spec_emit = jax.vmap(
        lambda ib, iv, nc: msa_mod.emit_insertions_jax(ib, iv, nc, True))
    H = nseg

    def core(qs, qlens, row_mask, seg, ts, tlens):
        R = qs.shape[0]

        def body(carry):
            it, draft, dlen, fixed, ovf, outs = carry
            new = one_round(qs, qlens, row_mask, seg, draft, dlen)
            # frozen holes keep their LAST live round's outputs (same
            # final-round folding as _refine_step); outs[:5] are
            # hole-shaped, outs[5:] row-shaped — rows freeze with their
            # hole via the segment gather
            fix_r = fixed[seg]
            outs = tuple(
                jnp.where(fixed.reshape((H,) + (1,) * (n.ndim - 1)), o, n)
                for o, n in zip(outs[:5], new[:5])
            ) + tuple(
                jnp.where(fix_r.reshape((R,) + (1,) * (n.ndim - 1)), o, n)
                for o, n in zip(outs[5:], new[5:])
            )
            cons, ins_base, ins_votes, ncov = outs[:4]
            with jax.named_scope(_VOTE):
                ins_out = spec_emit(ins_base, ins_votes, ncov)
                nd, nl, o = mat_v(cons, ins_out, dlen)
            now_fixed = (nl == dlen) & (nd == draft).all(axis=1)
            last = it >= iters
            o = ~fixed & o & ~last
            grow = ~fixed & ~o & ~now_fixed & ~last
            draft = jnp.where(grow[:, None], nd, draft)
            dlen = jnp.where(grow, nl, dlen)
            return (it + 1, draft, dlen, fixed | now_fixed | o | last,
                    ovf | o, outs)

        def cond(carry):
            return ~carry[3].all()

        # empty hole slots (no real rows — slab tail capacity) start
        # frozen, as pad holes do in _refine_step; the executor never
        # reads them back
        nrows = jax.ops.segment_sum(row_mask.astype(jnp.int32), seg,
                                    num_segments=H,
                                    indices_are_sorted=True)
        fixed0 = nrows == 0
        ovf0 = jnp.zeros((H,), bool)
        outs0 = (
            jnp.zeros((H, tmax), jnp.uint8),            # cons
            jnp.zeros((H, tmax, max_ins), jnp.uint8),   # ins_base
            jnp.zeros((H, tmax, max_ins), jnp.int32),   # ins_votes
            jnp.zeros((H, tmax), jnp.int32),            # ncov
            jnp.zeros((H, tmax), jnp.int32),            # nwin
            jnp.zeros((R, tmax), bool),                 # match
            jnp.zeros((R, tmax), jnp.uint8),            # aligned
            jnp.zeros((R, tmax), jnp.int32),            # ins_cnt
            jnp.zeros((R,), jnp.int32),                 # lead_ins
        )
        _, _, dlen, _, ovf, outs = jax.lax.while_loop(
            cond, body, (jnp.int32(0), ts, tlens, fixed0, ovf0, outs0))
        (cons, ins_base, ins_votes, ncov, nwin, match, aligned, ins_cnt,
         lead_ins) = outs
        with jax.named_scope(_BREAKPOINT):
            bp, advance = bp_advance(match, cons, aligned, ins_cnt,
                                     lead_ins, row_mask, seg, dlen)
        # uint8 vote/coverage compaction, as in _round_step (bounded by
        # the hole's real row count <= max_passes)
        return (cons, ins_base, ins_votes.astype(jnp.uint8),
                ncov.astype(jnp.uint8), nwin.astype(jnp.uint8),
                bp, advance, dlen, ovf)

    return core


def _slab_wire_sizes(R: int, qmax: int, H: int, tmax: int,
                     max_ins: int) -> tuple:
    """(Lbig, Lsmall) — the COMMON padded lengths of the slab wire
    protocol's uint8 and int32 buffers, covering both the input and the
    output payload.  Padding the smaller side to the larger one costs a
    few KB of zeros on latency-dominated transfers (measured r5: the
    fixed ~30-100 ms per-transfer latency dwarfs bandwidth at slab
    sizes) and buys REAL buffer donation: with in/out avals identical,
    XLA aliases each output onto its donated input buffer, so the
    fixpoint loop's dispatch allocates no fresh output HBM and the r7
    per-dispatch alloc/free churn on the packed path disappears.
    (Donation with mismatched sizes is silently dropped by XLA — a
    warning, not an alias — so the padding is what makes
    donate_argnums mean anything.)"""
    big_in = R * qmax + H * tmax
    big_out = H * tmax * (3 + 2 * max_ins)
    small_in = 3 * R + H
    small_out = 3 * H + R
    return max(big_in, big_out), max(small_in, small_out)


def _packed_wire_step(params: AlignParams, max_ins: int, tmax: int,
                      iters: int, nseg: int, bp_consts: tuple,
                      R: int, qmax: int):
    """Unjitted slab wire step: ONE 1-D uint8 + ONE 1-D int32 buffer in
    (see _pack_slab_args; rationale in _round_step), one of each out,
    both at the common _slab_wire_sizes lengths so donation aliases.
    _refine_step_packed jits it per slab shape; the fused multi-chip
    variant vmaps it over a leading device dimension."""
    import jax.numpy as jnp

    core = _refine_core_packed(params, max_ins, tmax, iters, nseg,
                               bp_consts)
    H = nseg
    Lbig, Lsmall = _slab_wire_sizes(R, qmax, H, tmax, max_ins)

    def ccsx_refine_packed(big, small):
        args = _unpack_slab_args_jax(big, small, R, qmax, H, tmax)
        (cons, ins_base, ins_votes, ncov, nwin, bp, advance, dlen,
         ovf) = core(*args)
        big_out = jnp.concatenate([
            cons.reshape(-1), ins_base.reshape(-1),
            ins_votes.reshape(-1), ncov.reshape(-1), nwin.reshape(-1)])
        small_out = jnp.concatenate(
            [bp, dlen, ovf.astype(jnp.int32), advance]).astype(jnp.int32)
        big_out = jnp.pad(big_out, (0, Lbig - big_out.shape[0]))
        small_out = jnp.pad(small_out, (0, Lsmall - small_out.shape[0]))
        return big_out, small_out

    return ccsx_refine_packed


@functools.lru_cache(maxsize=128)
def _refine_step_packed(params: AlignParams, max_ins: int, tmax: int,
                        iters: int, nseg: int, bp_consts: tuple,
                        pack: tuple):
    """Jitted single-device packed refine step at pack=(R, qmax), with
    both wire buffers DONATED: the input slab is dead the moment the
    step owns it, and at the common wire sizes XLA aliases the outputs
    onto it in place (_slab_wire_sizes) — no fresh output allocation
    per dispatch."""
    R, qmax = pack
    step = _packed_wire_step(params, max_ins, tmax, iters, nseg,
                             bp_consts, R, qmax)
    return jax.jit(step, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=64)
def _refine_step_packed_fused(params: AlignParams, max_ins: int,
                              tmax: int, iters: int, nseg: int,
                              bp_consts: tuple, pack: tuple, mesh):
    """ONE fused multi-chip packed dispatch: same-shape slabs stacked
    into a leading device dimension (Dstack, Lbig)/(Dstack, Lsmall) and
    shard_mapped over the 1-D local ('slab',) mesh — one transfer and
    ONE executable call per group per wave, where the r7 round-robin
    issued one device_put + one dispatch per slab per chip and jit
    compiled one executable PER chip (the :d{i} shape tags the flight
    recorder surfaced).  Each chip runs the identical per-slab wire
    step on its own slab with no cross-chip traffic; a dummy (all-zero)
    slab freezes every segment at iteration 0, so padding a tail wave
    up to D costs that chip ~a breakpoint scan on zeros.  Dstack is
    normally D; an OOM-resplit re-plan can exceed D slabs, in which
    case the local leading dim K = Dstack/D > 1 and the vmap carries K
    slabs per chip — still one executable call.  Wire buffers donated,
    as in the single-device step."""
    from jax.sharding import PartitionSpec as PS

    from ccsx_tpu.parallel.mesh import shard_map_nocheck

    R, qmax = pack
    step = _packed_wire_step(params, max_ins, tmax, iters, nseg,
                             bp_consts, R, qmax)

    def ccsx_refine_packed_fused(bigs, smalls):
        return jax.vmap(step)(bigs, smalls)

    sh = shard_map_nocheck(
        ccsx_refine_packed_fused, mesh,
        in_specs=(PS("slab", None), PS("slab", None)),
        out_specs=(PS("slab", None), PS("slab", None)))
    return jax.jit(sh, donate_argnums=(0, 1))


def _pack_slab_args(args, max_ins: int):
    """Host side of the slab transfer protocol: the 6 packed-refine
    inputs become one 1-D uint8 and one 1-D int32 buffer (one h2d
    latency each — same fixed-latency rationale as _pack_args), zero-
    padded to the common _slab_wire_sizes lengths so the device step
    can write its outputs in place over the donated inputs."""
    qs, qlens, row_mask, seg, ts, tlens = args
    R, qmax = qs.shape
    H, tmax = ts.shape
    Lbig, Lsmall = _slab_wire_sizes(R, qmax, H, tmax, max_ins)
    big = np.zeros(Lbig, np.uint8)
    big[:R * qmax] = qs.reshape(-1)
    big[R * qmax:R * qmax + H * tmax] = ts.reshape(-1)
    small = np.zeros(Lsmall, np.int32)
    small[:R] = qlens
    small[R:2 * R] = row_mask
    small[2 * R:3 * R] = seg
    small[3 * R:3 * R + H] = tlens
    return big, small


def _unpack_slab_args_jax(big, small, R: int, qmax: int, H: int,
                          tmax: int):
    """Device side of _pack_slab_args (explicit slice ends: the wire
    buffers carry alignment padding past the payload)."""
    qs = big[:R * qmax].reshape(R, qmax)
    ts = big[R * qmax:R * qmax + H * tmax].reshape(H, tmax)
    qlens = small[:R]
    row_mask = small[R:2 * R] != 0
    seg = small[2 * R:3 * R]
    tlens = small[3 * R:3 * R + H]
    return qs, qlens, row_mask, seg, ts, tlens


def _unpack_slab_refine(big, small, max_ins: int, tmax: int, H: int,
                        R: int):
    """Host-side split of a packed-slab refine result back into the
    9-tuple (cons, ins_base, ins_votes, ncov, nwin, bp, advance, dlen,
    ovf) — hole-shaped fields (H, ...), advance per row (R,)."""
    T, M = tmax, max_ins
    sizes = [H * T, H * T * M, H * T * M, H * T, H * T]
    offs = np.cumsum([0] + sizes)
    cons = big[offs[0]:offs[1]].reshape(H, T)
    ins_base = big[offs[1]:offs[2]].reshape(H, T, M)
    ins_votes = big[offs[2]:offs[3]].reshape(H, T, M)
    ncov = big[offs[3]:offs[4]].reshape(H, T)
    nwin = big[offs[4]:offs[5]].reshape(H, T)
    bp = small[:H]
    dlen = small[H:2 * H]
    ovf = small[2 * H:3 * H] != 0
    advance = small[3 * H:3 * H + R]
    return cons, ins_base, ins_votes, ncov, nwin, bp, advance, dlen, ovf


@functools.lru_cache(maxsize=8)
def _pair_fill(params: AlignParams):
    """Jitted batched local fill with per-pair line hints — the device
    half of strand_match (main.c:255-290), batched across holes."""
    from ccsx_tpu.ops import banded as banded_mod

    return banded_mod.make_batched("local", params, with_line=True)


@functools.lru_cache(maxsize=32)
def _pair_fill_packed(params: AlignParams, qmax: int, tmax: int):
    """Transfer-packed pair fill: one (N, qmax+tmax) uint8 + one (N, 6)
    int32 in, one (N, 7) int32 out — 3 transfer latencies per dispatch
    instead of 12 (5 h2d + 7 scalar-array d2h; the per-transfer latency
    dominates at these sizes, see _round_step)."""
    import jax.numpy as jnp

    fill = _pair_fill(params)

    @jax.jit
    def ccsx_pair_fill(big, small):
        qs = big[:, :qmax]
        ts = big[:, qmax:qmax + tmax]
        qlens, tlens, ls = small[:, 0], small[:, 1], small[:, 2:6]
        r = fill(qs, qlens, ts, tlens, ls)
        return jnp.stack(
            [r.score, r.qb, r.qe, r.tb, r.te, r.aln, r.mat],
            axis=1).astype(jnp.int32)

    return ccsx_pair_fill


class PairExecutor:
    """Batches prep PairRequests (strand_match pairs) across holes.

    One pair per dispatch leaves prep at ~95% of wall time at device-
    round speed (benchmarks/prep_share.py); here pairs from many holes
    are seeded on the host (ops/seed.py), grouped by padded (qmax, tmax)
    bucket, and filled in ONE batched local-mode banded DP per group —
    the same shape-bucketing discipline as the consensus rounds.

    The pre-alignment plane (ISSUE 11, ROADMAP item 4) adds a filter
    and a device seeding stage in front of the DP, both off by knob and
    byte-invariant on:

    * ``prefilter`` — hopeless candidate pairs are rejected BEFORE the
      DP by the sketch rules (seed-gate parity / noise gate /
      band-overlap geometry — every rule only rejects pairs whose
      strand_match acceptance would fail, see ops/sketch.py), in two
      forms by size: pairs at or above ``screen_min_device``
      (sketch.SPECULATE_MIN_QT) are scored by ONE batched
      similarity-sketch dispatch per (qmax, tmax) bucket
      (sketch.screen_step) before even seeding — the long-template
      regime where a doomed arm's seeding sort + DP are worth a
      dedicated wave — while smaller pairs (down to
      sketch.SCREEN_MIN_QT, below which the rules degenerate to the
      legacy gate) get the SAME rules applied for free from their seed
      computation (sketch.reject_from_hit), no extra dispatch.  The
      screen is ADVISORY: a failed screen (device + host rung both
      down) keeps the pair alive rather than quarantining the hole.
      Device-SEEDED pairs never pay a dedicated screen dispatch at
      all: the seed rows are a superset of the screen triple, so the
      rules fire post-seeding from those statistics — one dispatch
      does both jobs.
    * ``seed_device_min_t`` — surviving pairs whose template is at
      least this long seed on the device (ops/seed_device.seed_step,
      bit-equal to seed_diagonal); shorter ones keep the cached host
      sort-join.  0 keeps everything on the host.

    Shares the failure-containment ladder with BatchExecutor
    (_run_groups_recovering) at all three dispatch sites (screen, seed,
    fill): an OOM bisects and retries, and the last resort replays on
    the host twin (screen_host / seed_diagonal /
    HostAligner.strand_match — the per-hole spec paths, so results stay
    identical).

    PairRequest lists may also carry prepare.PairBatch entries (the
    walk's fwd+RC speculation): the batch's arms are evaluated
    SPECULATIVELY in the same wave — the wrong-strand arm dies in the
    screen — and the result slot is the aligned list of (ok, rs) the
    first-accept contract requires.
    """

    # bounded LRU of per-template sorted k-mer indexes (keyed by
    # PairRequest.t_token): the orientation walk pairs MANY passes
    # against one hole's template across successive sweeps, and the
    # token lets those sweeps share one sort (ops/seed.py)
    seed_cache_max = 128

    def __init__(self, params: AlignParams, quant: int = 512,
                 metrics=None, warmup=None, resil=None,
                 prefilter: bool = True, seed_device_min_t: int = 16384,
                 warm_cache: Optional[set] = None):
        self.params = params
        self.quant = quant
        self.metrics = metrics
        # shared Resilience object (pipeline/resilience.py): pair fills
        # ride the same dispatch deadline + circuit breaker as the
        # refine dispatches — a wedged chip wedges both
        self._resil = resil
        self._warmup = warmup      # AOT precompiler (pipeline/warmup.py)
        # inline-warm dedupe (no compiler).  ``warm_cache`` lets a
        # resident server pass ONE set shared by every job's executor:
        # the jit caches behind these keys are process-wide (module-
        # level lru_cache factories), so job 2 re-warming job 1's
        # (qmax, tmax, N) bucket would pay a pointless zero-slab pass
        self._warmed: set = warm_cache if warm_cache is not None \
            else set()
        self._host_aligner = None  # built lazily, on first fallback
        self.prefilter = bool(prefilter)
        self.seed_device_min_t = max(0, int(seed_device_min_t))
        # device-screen floor: below it the filter rides the seed
        # computation instead (reject_from_hit) — an attribute so tests
        # can drive the dispatch site at small shapes
        from ccsx_tpu.ops import sketch as sketch_mod

        self.screen_min_device = sketch_mod.SPECULATE_MIN_QT
        from collections import OrderedDict

        self._seed_cache: "OrderedDict" = OrderedDict()

    # ---- pre-alignment plane routing rules --------------------------------

    def _screens(self, pr) -> bool:
        return (self.prefilter
                and min(len(pr.q), len(pr.t)) >= self.screen_min_device)

    def _seeds_on_device(self, pr) -> bool:
        return (self.seed_device_min_t > 0
                and len(pr.t) >= self.seed_device_min_t)

    @staticmethod
    def _flatten(pairs):
        """Expand PairBatch entries into a flat request list plus the
        (start, count, is_batch) spans to fold results back."""
        flat: List["prep_mod.PairRequest"] = []
        spans: List[tuple] = []
        for pr in pairs:
            if isinstance(pr, prep_mod.PairBatch):
                spans.append((len(flat), len(pr.requests), True))
                flat.extend(pr.requests)
            else:
                spans.append((len(flat), 1, False))
                flat.append(pr)
        return flat, spans

    def warm(self, pairs) -> None:
        """Precompile the padded pair-fill executables this pair list
        will need, through the SAME factory + dispatch path run() uses
        (benchmarks/prep_share.py warms through this instead of its old
        hand-rolled double-run, so its timings and production compile
        through one code path).  Asynchronous with a WarmupCompiler
        (drain() to sync), inline without one.  The predicted N is an
        upper bound — a pair that fails seeding drops out of its bucket
        and can shrink N to a smaller (also canonical pow2) batch,
        which run() then compiles as usual.  Pre-alignment shapes
        (screen + device-seed steps) warm through the same discipline
        so a long-pair wave's first screen books no inline compile."""
        pairs, _ = self._flatten(pairs)
        buckets: Dict[tuple, int] = defaultdict(int)
        screens: Dict[tuple, int] = defaultdict(int)
        seeds: Dict[tuple, int] = defaultdict(int)
        for pr in pairs:
            key = (bucket_len(len(pr.q), self.quant),
                   bucket_len(len(pr.t), self.quant))
            buckets[key] += 1
            if self._screens(pr) and not self._seeds_on_device(pr):
                screens[key] += 1
            if self._seeds_on_device(pr):
                seeds[key] += 1
        for kind, table in (("pair_fill", buckets),
                            ("sketch_screen", screens),
                            ("seed_device", seeds)):
            for (qmax, tmax), n in table.items():
                N = _z_bucket(n)
                key = (kind, qmax, tmax, N)
                build = functools.partial(self._warm_build, kind, qmax,
                                          tmax, N)
                if self._warmup is not None:
                    self._warmup.submit(key, build)
                elif key not in self._warmed:
                    self._warmed.add(key)
                    build()

    def _warm_build(self, kind, qmax, tmax, N) -> None:
        big = np.full((N, qmax + tmax), banded.PAD, np.uint8)
        if kind == "pair_fill":
            step = _pair_fill_packed(self.params, qmax, tmax)
            args = (big, np.zeros((N, 6), np.int32))
            group = f"pair:q{qmax}:t{tmax}"
        elif kind == "sketch_screen":
            from ccsx_tpu.ops import sketch as sketch_mod

            step = sketch_mod.screen_step(qmax, tmax)
            args = (big, np.zeros((N, 2), np.int32))
            group = f"sketch:q{qmax}:t{tmax}"
        else:
            from ccsx_tpu.ops import seed_device as sd_mod

            step = sd_mod.seed_step(qmax, tmax)
            args = (big, np.zeros((N, 2), np.int32))
            group = f"seed:q{qmax}:t{tmax}"
        with trace.device_span("warmup", group=group,
                               shape=f"N{N}", warmup=True):
            jax.block_until_ready(step(*args))

    def _seed_indexes(self, pairs):
        """Per-pair sorted template k-mer indexes for this batch: cache
        hits (token-keyed, LRU) cost nothing, misses are sorted in ONE
        vectorized argsort over the whole batch
        (seed.batch_sorted_indexes), and tokened misses enter the cache
        for the walk's next pairing of the same template."""
        from ccsx_tpu.ops import seed as seed_mod

        indexes: Dict[int, tuple] = {}
        need: List[int] = []          # pair idx needing a fresh sort
        need_owner: Dict[object, int] = {}  # token -> representative idx
        shared: List[tuple] = []      # (pair idx, token) cache/batch share
        for i, pr in enumerate(pairs):
            tok = getattr(pr, "t_token", None)
            if tok is not None:
                hit = self._seed_cache.get(tok)
                if hit is not None:
                    self._seed_cache.move_to_end(tok)
                    indexes[i] = hit
                    continue
                if tok in need_owner:
                    shared.append((i, tok))
                    continue
                need_owner[tok] = i
            need.append(i)
        if need:
            for i, idx in zip(need, seed_mod.batch_sorted_indexes(
                    [pairs[i].t for i in need])):
                indexes[i] = idx
                tok = getattr(pairs[i], "t_token", None)
                if tok is not None:
                    self._seed_cache[tok] = idx
                    while len(self._seed_cache) > self.seed_cache_max:
                        self._seed_cache.popitem(last=False)
        for i, tok in shared:
            indexes[i] = indexes[need_owner[tok]]
        return indexes

    def _pad_pair(self, pairs, idxs, key):
        """(N, qmax+tmax) PAD-filled codes + (N, 2) int32 lengths — the
        shared wire layout of the screen and device-seed dispatches
        (padded tails are inert by construction: PAD >= 4 makes every
        window touching them a bad k-mer, ops/sketch._codes_dev)."""
        qmax, tmax = key
        N = _z_bucket(len(idxs))
        big = np.full((N, qmax + tmax), banded.PAD, np.uint8)
        small = np.zeros((N, 2), np.int32)
        for z, i in enumerate(idxs):
            big[z, :qmax] = pad_to(pairs[i].q, qmax)
            big[z, qmax:] = pad_to(pairs[i].t, tmax)
            small[z, 0] = len(pairs[i].q)
            small[z, 1] = len(pairs[i].t)
        return big, small, N

    def _screen_wave(self, pairs, idxs, results) -> int:
        """The prefilter dispatch site: one batched sketch screen per
        (qmax, tmax) bucket over ``idxs``; rejected pairs get their
        final (False, empty MatchResult) — the same payload the walk
        discards for any failed pair — and the count is returned.
        Screen failures are ADVISORY (pair stays alive): the filter is
        an optimization, never a correctness gate."""
        from ccsx_tpu.ops import sketch as sketch_mod

        triples: List = [None] * len(pairs)
        groups: Dict[tuple, List[int]] = defaultdict(list)
        for i in idxs:
            groups[(bucket_len(len(pairs[i].q), self.quant),
                    bucket_len(len(pairs[i].t), self.quant))].append(i)

        def dispatch(gidxs, key):
            qmax, tmax = key
            big, small, N = self._pad_pair(pairs, gidxs, key)
            faultinject.fire("device_oom")
            if self._warmup is not None:
                self._warmup.settle(("sketch_screen", qmax, tmax, N))
            step = sketch_mod.screen_step(qmax, tmax)
            with trace.device_span(
                    "sketch_screen", group=f"sketch:q{qmax}:t{tmax}",
                    shape=f"N{N}", n=len(gidxs)):
                faultinject.fire("stall")
                faultinject.fire("device_hang")
                return step(big, small)

        def finish(gidxs, key, out):
            for z, i in enumerate(gidxs):
                triples[i] = tuple(int(v) for v in out[z])

        def host_one(i):
            return sketch_mod.screen_host(pairs[i].q, pairs[i].t)

        if self.metrics is not None:
            self.metrics.bump(device_dispatches=len(groups))
        _run_groups_recovering(
            groups, dispatch, finish, host_one, triples, self.metrics,
            label=lambda k: f"sketch:q{k[0]}:t{k[1]}", resil=self._resil)
        rejected = 0
        for i in idxs:
            tr = triples[i]
            if not isinstance(tr, tuple):
                continue   # screen failed for this pair: keep it alive
            pr = pairs[i]
            reason = sketch_mod.reject_reason(
                tr[0], tr[1], tr[2], len(pr.q), len(pr.t), pr.pct,
                self.params.band)
            if reason:
                results[i] = (False,
                              MatchResult(False, 0, 0, 0, 0, 0, 0, 0))
                rejected += 1
        return rejected

    def _seed_wave(self, pairs, idxs, hits, results) -> None:
        """The device k-mer seeding dispatch site: one batched seed per
        (qmax, tmax) bucket; rows fold back into ``hits`` as the same
        SeedHit-or-None the host path produces (bit-equal,
        ops/seed_device.py).  A pair whose seed failed on BOTH rungs
        carries its Exception into ``results`` — the per-request
        quarantine the pair-fill ladder already has."""
        from ccsx_tpu.ops import seed as seed_mod
        from ccsx_tpu.ops import seed_device as sd_mod

        rows: List = [None] * len(pairs)
        groups: Dict[tuple, List[int]] = defaultdict(list)
        for i in idxs:
            groups[(bucket_len(len(pairs[i].q), self.quant),
                    bucket_len(len(pairs[i].t), self.quant))].append(i)

        def dispatch(gidxs, key):
            qmax, tmax = key
            big, small, N = self._pad_pair(pairs, gidxs, key)
            faultinject.fire("device_oom")
            if self._warmup is not None:
                self._warmup.settle(("seed_device", qmax, tmax, N))
            step = sd_mod.seed_step(qmax, tmax)
            with trace.device_span(
                    "seed_device", group=f"seed:q{qmax}:t{tmax}",
                    shape=f"N{N}", n=len(gidxs)):
                faultinject.fire("stall")
                faultinject.fire("device_hang")
                return step(big, small)

        def finish(gidxs, key, out):
            for z, i in enumerate(gidxs):
                rows[i] = [int(v) for v in out[z]]

        def host_one(i):
            hit = seed_mod.seed_diagonal(pairs[i].q, pairs[i].t)
            if hit is None:
                return [0] * 8
            return [1, hit.diag, hit.votes, *(int(v) for v in hit.line),
                    0]

        if self.metrics is not None:
            self.metrics.bump(device_dispatches=len(groups))
        _run_groups_recovering(
            groups, dispatch, finish, host_one, rows, self.metrics,
            label=lambda k: f"seed:q{k[0]}:t{k[1]}", resil=self._resil)
        for i in idxs:
            r = rows[i]
            if isinstance(r, Exception):
                results[i] = r   # quarantines the calling hole
            elif r is not None:
                hits[i] = sd_mod.hit_from_row(r)

    def run(self, pairs):
        """Satisfy all pair requests; results align index-for-index —
        (ok, MatchResult) tuples for PairRequests (the strand_match
        contract), lists of them for PairBatch entries (the
        first-accept contract; speculative arms are all evaluated)."""
        flat, spans = self._flatten(pairs)
        results = self._run_flat(flat)
        out = []
        for start, n, is_batch in spans:
            out.append(list(results[start:start + n]) if is_batch
                       else results[start])
        return out

    def _run_flat(self, pairs: List["prep_mod.PairRequest"]):
        from ccsx_tpu.ops import seed as seed_mod

        results = [None] * len(pairs)
        groups: Dict[tuple, List[int]] = defaultdict(list)
        lines: Dict[int, np.ndarray] = {}

        # stage 1 — the batched device screen, but ONLY for big pairs
        # that will NOT device-seed: the seed dispatch (stage 2) is a
        # superset of the screen (its rows carry total+votes+the median
        # line), so a device-seeded pair gets the same rejection rules
        # for free in stage 3 (reject_from_hit) and a dedicated screen
        # wave would be a second dispatch computing the same hits.
        # Smaller pairs likewise ride their (host) seed statistics.
        from ccsx_tpu.ops import sketch as sketch_mod

        screen_ids = [i for i, pr in enumerate(pairs)
                      if self._screens(pr)
                      and not self._seeds_on_device(pr)]
        rejected = 0
        if screen_ids:
            with trace.span("prefilter", cat="prep", n=len(screen_ids)):
                rejected = self._screen_wave(pairs, screen_ids, results)

        # stage 2 — seeding for the survivors: device for long
        # templates (>= seed_device_min_t), cached host sort-join below
        hits: Dict[int, object] = {}
        dev_ids = [i for i, pr in enumerate(pairs)
                   if results[i] is None and self._seeds_on_device(pr)]
        dev_set = set(dev_ids)
        host_ids = [i for i, pr in enumerate(pairs)
                    if results[i] is None and i not in dev_set]
        sub = [pairs[i] for i in host_ids]
        seed_idx = self._seed_indexes(sub)
        for pos, i in enumerate(host_ids):
            hits[i] = seed_mod.seed_diagonal(pairs[i].q, pairs[i].t,
                                             t_index=seed_idx.get(pos))
        if dev_ids:
            self._seed_wave(pairs, dev_ids, hits, results)
        if self.metrics is not None and (dev_ids or host_ids):
            self.metrics.bump(pairs_seeded_device=len(dev_ids),
                              pairs_seeded_host=len(host_ids))

        # stage 3 — the zero-dispatch filter rung, then the banded fill
        # for every surviving pair.  Every prefilter-eligible pair that
        # did not go through the stage-1 screen — host-seeded pairs
        # above SCREEN_MIN_QT and ALL device-seeded pairs — gets rules
        # (b)/(c) from its seed statistics here (reject_from_hit, at
        # the true median line); stage-1-screened pairs were already
        # filtered pre-seeding and just pass through.
        screen_set = set(screen_ids)
        screened = len(screen_ids)
        for i, pr in enumerate(pairs):
            if results[i] is not None:
                continue
            hit = hits.get(i)
            if hit is None:
                # no shared 13-mers: unalignable at >=60% identity
                results[i] = (False, MatchResult(False, 0, 0, 0, 0, 0, 0, 0))
                continue
            if (self.prefilter and i not in screen_set
                    and min(len(pr.q), len(pr.t))
                    >= sketch_mod.SCREEN_MIN_QT):
                screened += 1
                if sketch_mod.reject_from_hit(hit, len(pr.q), len(pr.t),
                                              pr.pct, self.params.band):
                    results[i] = (False, MatchResult(False, 0, 0, 0, 0,
                                                     0, 0, 0))
                    rejected += 1
                    continue
            if abs(hit.diag) > self.params.band // 4:
                lines[i] = np.asarray(hit.line, np.int32)
            else:
                # near-diagonal: the default corner-to-corner line
                lines[i] = np.array(
                    [0, 0, len(pr.q), len(pr.t)], np.int32)
            groups[(bucket_len(len(pr.q), self.quant),
                    bucket_len(len(pr.t), self.quant))].append(i)

        if self.metrics is not None:
            padded = real = 0
            for (qmax, tmax), idxs in groups.items():
                N = _z_bucket(len(idxs))
                padded += N * qmax * self.params.band
                real += self.params.band * int(
                    sum(len(pairs[i].q) for i in idxs))
            # bump(): the pair gate's pump thread runs this concurrently
            # with the driver's refine sweeps (pipeline/prep_pool.py).
            # pairs_screened counts every pair the filter EXAMINED
            # (device screen + the zero-dispatch seed-statistics rung);
            # pairs_prefiltered the ones it rejected pre-DP.
            self.metrics.bump(pair_alignments=len(lines),
                              device_dispatches=len(groups),
                              pairs_screened=screened,
                              pairs_prefiltered=rejected,
                              dp_cells_padded=padded,
                              dp_cells_real=real)

        def dispatch(idxs, key):
            qmax, tmax = key
            N = _z_bucket(len(idxs))
            # PAD-filled so the dummy tail slots look exactly like the
            # old pad_to(empty) rows (qlen/tlen stay 0 in `small`)
            big = np.full((N, qmax + tmax), banded.PAD, np.uint8)
            small = np.zeros((N, 6), np.int32)
            for z, i in enumerate(idxs):
                big[z, :qmax] = pad_to(pairs[i].q, qmax)
                big[z, qmax:] = pad_to(pairs[i].t, tmax)
                small[z, 0] = len(pairs[i].q)
                small[z, 1] = len(pairs[i].t)
                small[z, 2:6] = lines[i]
            faultinject.fire("device_oom")
            if self._warmup is not None:
                # cancel a queued warmup of this shape / wait out an
                # in-flight one (same discipline as the refine path)
                self._warmup.settle(("pair_fill", qmax, tmax, N))
            step = _pair_fill_packed(self.params, qmax, tmax)
            with trace.device_span(
                    "pair_fill", group=f"pair:q{qmax}:t{tmax}",
                    cells=N * qmax * self.params.band,
                    shape=f"N{N}", n=len(idxs)):
                faultinject.fire("stall")
                faultinject.fire("device_hang")
                return step(big, small)

        def finish(idxs, key, res):
            for z, i in enumerate(idxs):
                score, qb, qe, tb, te, aln, mat = (
                    int(v) for v in res[z])
                rs = MatchResult(
                    ok=False, score=score, qb=qb,
                    qe=qe, tb=tb, te=te,
                    aln=aln, mat=mat)
                pr = pairs[i]
                # acceptance rule, main.c:280
                rs.ok = (rs.aln * 2 > min(len(pr.q), len(pr.t))) and (
                    rs.mat * 100 >= rs.aln * pr.pct)
                results[i] = (rs.ok, rs)

        def host_one(i):
            if self._host_aligner is None:
                from ccsx_tpu.consensus.align_host import HostAligner

                self._host_aligner = HostAligner(self.params)
            pr = pairs[i]
            return self._host_aligner.strand_match(pr.q, pr.t, pr.pct)

        _run_groups_recovering(groups, dispatch, finish, host_one,
                               results, self.metrics,
                               label=lambda k: f"pair:q{k[0]}:t{k[1]}",
                               resil=self._resil)
        return results


class BatchExecutor:
    """Groups refine/round requests by shape, one device dispatch per
    group (fused refinement for RefineRequests — the production window
    protocol — and a single star round for bare RoundRequests).

    With more than one local device, batches are laid out over a 1-D
    ``data`` mesh (ZMW axis sharded, SURVEY.md §5.8): the jitted round is
    pure vmap, so XLA partitions it across the chips of a slice with no
    cross-device traffic in the DP itself.

    Failure containment (per shape group; see classify_failure): a
    device OOM bisects the group and retries the halves at half the Z
    batch (with capped depth and exponential backoff) — memory pressure
    scales with Z, so one oversized bucket costs a resplit instead of
    the run; a Pallas lowering/compile failure is re-raised (a program
    error, not a device condition); anything else — and the bottom of
    the OOM ladder — replays
    each request on the exact host path (bit-identical by the
    differential tests), with per-request host failures returned as
    Exception results the driver quarantines per hole.
    """

    # OOM resplit ladder: up to Z/8 before the per-request host replay
    max_oom_resplits = 3
    oom_backoff_s = 0.05

    def __init__(self, cfg: CcsConfig, metrics=None, warmup=None,
                 devices=None, resil=None):
        self.cfg = cfg
        self.len_quant = cfg.len_bucket_quant
        self.metrics = metrics
        # shared Resilience object (pipeline/resilience.py): dispatch
        # deadline + backend circuit breaker; None = legacy callers
        self._resil = resil
        # AOT warmup precompiler (pipeline/warmup.py), shared with the
        # driver's PairExecutor; None = --no-warmup / legacy callers
        self._warmup = warmup
        # host-replay spec for fused-refine overflows (rare): the exact
        # per-hole loop the fused step mirrors
        self._sm = StarMsa(cfg.align, cfg.max_ins_per_col,
                           cfg.len_bucket_quant)
        self._mesh = None
        # LOCAL devices only: hosts in a distributed run are share-nothing
        # (round-robin hole ownership, distributed.py), so each host's
        # mesh spans its own chips (ICI); a global mesh would make every
        # jit a cross-host SPMD program requiring identical inputs on all
        # processes.  Single-process: local == global, nothing changes.
        # ``devices`` narrows the set (tests pin the single-chip vs
        # multi-chip byte identity with it).
        self.slab_rows = pack_mod.pow2(max(1, cfg.slab_rows))
        self.slab_ladder = max(1, int(getattr(cfg, "slab_shape_ladder",
                                              pack_mod.DEFAULT_LADDER)))
        self._devices = (list(devices) if devices is not None
                         else jax.local_devices())
        self._shape_seen: set = set()  # distinct packed (R,q,t,i) shapes
        # warm_refine's per-group row accumulator: group -> (rows_seen
        # capped at budget, predicted canonical R, submitted warm key),
        # with each hole counted once per group (_group_holes)
        self._group_pred: Dict[tuple, tuple] = {}
        self._group_holes: Dict[tuple, set] = {}
        self._tails_warmed = False     # _warm_tail_groups ran
        n_dev = len(self._devices)
        # ragged pass-packing (pipeline/pack.py) replaces the per-P
        # shape grouping for the production RefineRequest path, and
        # scales across local chips with ONE fused multi-chip dispatch
        # per group per wave (same-shape slabs stacked on a leading
        # device dim under a ('slab',) shard_map — see
        # _refine_step_packed_fused) instead of GSPMD-sharding one big
        # dispatch.  An explicit --mesh selects the bucketed
        # (Z, P)-sharded layout instead — packed slab rows cross hole
        # boundaries, which the (data, pass) shardings cannot express.
        # Output is byte-identical either way (tests/test_packing.py).
        # A single-device host genuinely IGNORES --mesh (as it always
        # has), so packing stays on there — "--mesh ignored" must not
        # silently mean "and the bucketed grouping took over".
        self._packing = bool(cfg.pass_packing) and (
            cfg.mesh_shape is None or n_dev == 1)
        self._slab_mesh = None
        if self._packing and n_dev > 1:
            from ccsx_tpu.parallel.mesh import build_slab_mesh

            self._slab_mesh = build_slab_mesh(self._devices)
        if cfg.pass_packing and cfg.mesh_shape is not None and n_dev > 1:
            print("[ccsx-tpu] pass packing disabled under --mesh "
                  "(bucketed (Z, P) grouping carries the shardings)",
                  file=sys.stderr)
        if n_dev > 1 and not self._packing:
            # (data, pass) mesh: ZMWs shard over 'data'; MSA rows of each
            # hole shard over 'pass' when the pass bucket divides (GSPMD
            # partitions the jitted round from the input shardings alone —
            # the vote's column reductions become psums over 'pass', the
            # same collectives tests/test_sharded_round.py pins bit-exact).
            # cfg.mesh_shape overrides the default pure-data split; a
            # 1-tuple means pure data parallelism; extra devices idle.
            shape = self.validate_mesh(cfg.mesh_shape, n_dev)
            ndev_used = int(np.prod(shape))
            from ccsx_tpu.parallel.mesh import build_mesh

            self._mesh = build_mesh(shape=shape,
                                    devices=self._devices[:ndev_used])
            self._data_dim, self._pass_dim = shape
            if (self._pass_dim > 1
                    and all(b % self._pass_dim for b in cfg.pass_buckets)):
                print(f"[ccsx-tpu] mesh pass dim {self._pass_dim} divides "
                      f"no pass bucket {tuple(cfg.pass_buckets)}: pass "
                      "axis will be replicated (no pass parallelism)",
                      file=sys.stderr)
        elif cfg.mesh_shape is not None:
            print("[ccsx-tpu] --mesh ignored: single device visible",
                  file=sys.stderr)

    @staticmethod
    def normalize_mesh_shape(shape, n_dev: int):
        if shape is None:
            return (n_dev, 1)
        shape = tuple(int(x) for x in shape)
        if len(shape) == 1:
            shape = (shape[0], 1)
        if len(shape) != 2:
            raise ValueError(f"mesh_shape must be (data,) or (data, pass), "
                             f"got {shape}")
        if min(shape) < 1:
            raise ValueError(f"mesh dims must be >= 1: {shape}")
        return shape

    @classmethod
    def validate_mesh(cls, mesh_shape, n_dev: int):
        """Normalize + feasibility-check a mesh shape; ValueError on a
        bad one.  THE single validation point — __init__ and both
        pipeline drivers call this (before any output file opens)."""
        shape = cls.normalize_mesh_shape(mesh_shape, n_dev)
        need = int(np.prod(shape))
        if n_dev > 1 and need > n_dev:
            raise ValueError(
                f"mesh {shape} needs {need} devices, host has {n_dev}")
        return shape

    def _bp_consts(self):
        cfg = self.cfg
        return (cfg.bp_window, cfg.bp_minwin, cfg.bp_rowrate,
                cfg.bp_colrate, cfg.bp_colrate_lowpass)

    def _shard_args(self, args, P: int):
        """device_put the 5 round/refine inputs with the (data, pass)
        NamedShardings (GSPMD partitions the jitted step from these)."""
        if self._mesh is None:
            return args
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as PS

        # replicate the pass axis when the bucket doesn't divide
        pax = "pass" if P % self._pass_dim == 0 else None
        specs = (PS("data", pax, None), PS("data", pax),
                 PS("data", None), PS("data"), PS("data", pax))
        return tuple(jax.device_put(a, NamedSharding(self._mesh, s))
                     for a, s in zip(args, specs))

    def _round_z(self, n: int) -> int:
        Z = _z_bucket(n)
        if self._mesh is not None:
            # the data-axis sharding needs Z divisible by the data
            # dimension (power-of-two Z alone is not enough when it
            # isn't a power of two, e.g. 6 or 12 devices)
            Z = -(-Z // self._data_dim) * self._data_dim
        return Z

    def _count_cells(self, reqs, idxs, P, qmax, Z, iters: int = 1):
        """Padding accounting (metrics.dp_cells_*): real DP fill cells
        (true qlen of real pass-rows) vs dispatched cells (the full
        Z x P x qmax x band x iters block).  The ratio is the device
        occupancy that bucket tuning (pass/length/Z buckets) controls —
        SURVEY §7.3 item 2's named throughput risk, now measured."""
        if self.metrics is None:
            return
        band = self.cfg.align.band
        padded = Z * P * qmax * band * iters
        real = band * iters * int(
            sum(int(reqs[i].qlens[reqs[i].row_mask].sum()) for i in idxs))
        # round-only counters, all in CELL units (x qmax x band x iters)
        # so the length/pass/Z factorization is exact in aggregate
        # across heterogeneous shape groups (metrics.py); bump() — the
        # pair gate's pump thread updates the shared dp_cells_* family
        # concurrently (pipeline/prep_pool.py)
        rows_real = int(sum(int(reqs[i].row_mask.sum()) for i in idxs))
        scale = qmax * band * iters
        self.metrics.bump(dp_cells_padded=padded, dp_cells_real=real,
                          dp_round_cells_padded=padded,
                          dp_round_cells_real=real,
                          dp_rowcells_real=rows_real * scale,
                          dp_rowcells_cap=len(idxs) * P * scale)

    def _count_cells_packed(self, reqs, idxs, qmax: int, R: int,
                            iters: int):
        """Padding accounting for one packed slab.  The slab IS the
        dispatch (no Z axis), so rowcells_cap == round_cells_padded and
        the factorized identity degenerates to z_fill = 1 with pass_fill
        carrying the whole row-fill story; dp_rows_* feed the
        dp_row_fill / packed_holes_per_dispatch counters the packing win
        is read from (metrics.py)."""
        if self.metrics is None:
            return
        band = self.cfg.align.band
        scale = qmax * band * iters
        rows_real = int(sum(int(reqs[i].row_mask.sum()) for i in idxs))
        real = band * iters * int(
            sum(int(reqs[i].qlens[reqs[i].row_mask].sum()) for i in idxs))
        self.metrics.bump(dp_cells_padded=R * scale, dp_cells_real=real,
                          dp_round_cells_padded=R * scale,
                          dp_round_cells_real=real,
                          dp_rowcells_real=rows_real * scale,
                          dp_rowcells_cap=R * scale,
                          dp_rows_real=rows_real, dp_rows_dispatched=R,
                          packed_dispatches=1, packed_holes=len(idxs))

    def _count_cells_packed_fused(self, reqs, idxs, qmax: int, iters: int,
                                  R: int, n_slabs: int, n_slots: int):
        """Padding accounting for one fused multi-chip WAVE (n_slabs
        real slabs at uniform R, padded with dummy slabs to n_slots
        chip-slots).  Dummy slabs freeze every segment at iteration 0 —
        their chips idle rather than fill padding — so dispatched DP
        cells count the REAL slabs only and the dummy-slot idleness is
        read from fused_slot_fill instead of dp_row_fill."""
        if self.metrics is None:
            return
        band = self.cfg.align.band
        scale = qmax * band * iters
        rows_real = int(sum(int(reqs[i].row_mask.sum()) for i in idxs))
        real = band * iters * int(
            sum(int(reqs[i].qlens[reqs[i].row_mask].sum()) for i in idxs))
        padded = n_slabs * R * scale
        self.metrics.bump(dp_cells_padded=padded, dp_cells_real=real,
                          dp_round_cells_padded=padded,
                          dp_round_cells_real=real,
                          dp_rowcells_real=rows_real * scale,
                          dp_rowcells_cap=n_slabs * R * scale,
                          dp_rows_real=rows_real,
                          dp_rows_dispatched=n_slabs * R,
                          packed_dispatches=1, packed_holes=len(idxs),
                          fused_waves=1, fused_slabs_real=n_slabs,
                          fused_slots=n_slots)

    # ---- AOT warmup (pipeline/warmup.py): predict + precompile the
    # ---- canonical packed executables concurrently with ingest/prep ----

    def _warm_key(self, qmax, tmax, iters, R, dstack):
        return ("refine_packed", qmax, tmax, iters, R, dstack)

    def _warm_wait(self, key) -> None:
        """Dispatch-side sync: cancel a still-queued warmup of this
        shape (we compile inline, as without warmup) or wait out an
        in-flight one (the compile is already running on the warmup
        thread; waiting avoids a duplicate).  A failed build re-raises
        here."""
        if self._warmup is not None:
            self._warmup.settle(key)

    def _note_shape(self, R, qmax, tmax, iters) -> None:
        key = (R, qmax, tmax, iters)
        if key not in self._shape_seen:
            self._shape_seen.add(key)
            if self.metrics is not None:
                self.metrics.distinct_slab_shapes = len(self._shape_seen)

    def warm_refine(self, req: RefineRequest, hole_id=None) -> None:
        """Enqueue an AOT compile for the canonical executable this
        request's (qmax, tmax, iters) group is predicted to need —
        called by the driver the moment prep yields the request, so
        cold XLA compiles overlap ingest/prep instead of stalling the
        group's first dispatch.

        The predicted R is the smallest canonical height covering the
        group's ACCUMULATED predicted rows (capped at the budget — the
        steady-state shape): warming every ladder height would book
        compiles for programs never dispatched, which is exactly the
        waste the canonical ladder exists to kill.  When accumulation
        pushes the prediction up a height, the stale queued warm is
        CANCELLED (WarmupCompiler.claim) — during an admission burst
        the queue usually hasn't reached it yet, so most groups build
        exactly one program.  No-op without a warmup compiler, under
        --pass-buckets bucketed grouping or a GSPMD --mesh (their Z
        bucket depends on the sweep size, unknowable at admission —
        canonical slab shapes are what make the packed path
        predictable)."""
        if self._warmup is None or not self._packing:
            return
        qmax = req.qs.shape[1]
        tmax = _fused_tmax(len(req.draft), self.len_quant)
        gk = (qmax, tmax, req.iters)
        rows = max(int(req.row_mask.sum()), pack_mod.SEG_DIV)
        acc, old_r, old_key = self._group_pred.get(gk, (0, None, None))
        # each hole counts ONCE per group: the driver re-warms every
        # still-active hole after every sweep (a hole's next window is
        # a fresh request), and re-adding the same hole's rows each
        # sweep would walk a one-hole group's prediction up to the full
        # budget — warming (and possibly cancelling/churning) programs
        # its slabs never reach.  A hole entering a NEW group (its
        # draft grew a bucket) legitimately counts there too.
        if hole_id is not None:
            seen = self._group_holes.setdefault(gk, set())
            if hole_id in seen:
                return
            seen.add(hole_id)
        acc = min(acc + rows, self.slab_rows)
        R = self.slab_rows
        for h in pack_mod.canonical_heights(self.slab_rows,
                                            self.slab_ladder):
            if h >= acc:
                R = h
            else:
                break
        dstack = (len(self._devices)
                  if self._slab_mesh is not None else 1)
        key = old_key
        if R != old_r:
            if old_key is not None:
                self._warmup.claim(old_key)  # cancel the stale warm
            H = max(1, R // pack_mod.SEG_DIV)
            key = self._warm_key(qmax, tmax, req.iters, R, dstack)
            self._warmup.submit(
                key, functools.partial(self._warm_build, qmax, tmax,
                                       req.iters, R, H, dstack))
        if acc >= self.slab_rows:
            # a group that fills its row budget lives long enough to
            # DRIBBLE: late in the run the admission batch's windows
            # finish in near-lockstep, sweeps shrink, and the group's
            # tail waves snap to the lower canonical heights — each a
            # fresh executable.  Warm those now (r08 scale trace:
            # every group that crossed the budget later dispatched at
            # budget/2), so the endgame transition books no inline
            # compile.  Sweep-time warming cannot catch these — the
            # dribble wave is planned microseconds before its own
            # dispatch claims the key back.  Submit dedupes by key.
            for h in pack_mod.canonical_heights(self.slab_rows,
                                                self.slab_ladder):
                if h != R:
                    hH = max(1, h // pack_mod.SEG_DIV)
                    self._warmup.submit(
                        self._warm_key(qmax, tmax, req.iters, h, dstack),
                        functools.partial(self._warm_build, qmax, tmax,
                                          req.iters, h, hH, dstack))
        self._group_pred[gk] = (acc, R, key)
        if not self._tails_warmed:
            tails = windowed.final_window_lengths(
                self.cfg, req.qlens[req.row_mask])
            if tails is not None:
                self._tails_warmed = True
                self._warm_tail_groups(tails, req.iters, dstack)

    def _warm_tail_groups(self, tails, iters: int, dstack: int) -> None:
        """Warm the groups of windowed holes' final windows, once, from
        the range of their longest pass (windowed.final_window_lengths):
        each length bucket in it, with the draft in that bucket or the
        one below (tmax one bucket above the draft's).  These groups
        appear only when holes reach their ends and are dispatched in
        the sweep right after, too late for the warm a request's own
        group gets.  Each is warmed at every canonical height: most
        sweeps spread a few finishing holes over them, but the cohort
        that the admission ramp starts together ends together and fills
        some past the lower height (a program loaded then, in a warm
        run, lands between two bursts of records)."""
        q = self.len_quant
        qmax, top = (bucket_len(n, q) for n in tails)
        while qmax <= top:
            for tmax in (qmax, bucket_len(qmax + 1, q)):
                for R in pack_mod.canonical_heights(self.slab_rows,
                                                    self.slab_ladder):
                    H = max(1, R // pack_mod.SEG_DIV)
                    self._warmup.submit(
                        self._warm_key(qmax, tmax, iters, R, dstack),
                        functools.partial(self._warm_build, qmax, tmax,
                                          iters, R, H, dstack))
            qmax = bucket_len(qmax + 1, q)

    def _warm_sweep_shapes(self, shapes) -> None:
        """Sweep-time exact warming: by group-construction time the
        sweep's slab plans are known EXACTLY, so submit any shape not
        yet compiled before the dispatch-all loop starts — the warmup
        thread then builds upcoming shapes (late-run dribble waves at
        the lower canonical heights, mostly) while earlier groups
        dispatch.  Unlike admission-time prediction this can never
        build a program that is not about to be used; a shape whose
        build has not started when its own dispatch arrives is claimed
        back and compiled inline, exactly as without warmup."""
        if self._warmup is None:
            return
        for qmax, tmax, iters, R, dstack in shapes:
            H = max(1, R // pack_mod.SEG_DIV)
            self._warmup.submit(
                self._warm_key(qmax, tmax, iters, R, dstack),
                functools.partial(self._warm_build, qmax, tmax, iters,
                                  R, H, dstack),
                urgent=True)

    def _warm_build(self, qmax, tmax, iters, R, H, dstack) -> None:
        """Warmup-thread builder: run the REAL jitted step on an all-
        zero slab and block — the zero row mask freezes every segment,
        so the while_loop exits at iteration 0 and the execution costs
        ~a breakpoint scan; what it buys is the exact jit fast path
        primed (fn.lower().compile() shares the XLA compile but leaves
        a retrace + dispatch-cache miss on the first real call).  The
        warmup=True span books the (group, shape)'s compile, so the
        first real dispatch books none — the trace-visible proof the
        overlap worked."""
        cfg = self.cfg
        Lbig, Lsmall = _slab_wire_sizes(R, qmax, H, tmax,
                                        cfg.max_ins_per_col)
        # same :b<impl> suffix as the real dispatch's span — the warmup
        # compile and the first execute must book under ONE group key or
        # the compile-storm accounting splits across two rows
        group = (f"packed:q{qmax}:t{tmax}:i{iters}"
                 f":b{banded_impl_effective(qmax)}")
        if dstack > 1:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as PS

            step = _refine_step_packed_fused(
                cfg.align, cfg.max_ins_per_col, tmax, iters, H,
                self._bp_consts(), (R, qmax), self._slab_mesh)
            sharding = NamedSharding(self._slab_mesh, PS("slab", None))
            with trace.device_span("warmup", group=group,
                                   shape=f"D{dstack}:R{R}:S{H}",
                                   warmup=True):
                big = jax.device_put(
                    np.zeros((dstack, Lbig), np.uint8), sharding)
                small = jax.device_put(
                    np.zeros((dstack, Lsmall), np.int32), sharding)
                jax.block_until_ready(step(big, small))
        else:
            step = _refine_step_packed(
                cfg.align, cfg.max_ins_per_col, tmax, iters, H,
                self._bp_consts(), pack=(R, qmax))
            with trace.device_span("warmup", group=group,
                                   shape=f"R{R}:S{H}", warmup=True):
                jax.block_until_ready(step(np.zeros(Lbig, np.uint8),
                                           np.zeros(Lsmall, np.int32)))

    def _stack_slab(self, reqs, idxs, qmax, tmax, shape=None):
        """Pack the real pass-rows of the given requests into ONE slab:
        (R, qmax) rows + (H, tmax) per-hole drafts + the row->hole
        segment vector.  Row order is idxs order (the packing plan's
        placement order — or a bisected half of it on the OOM-resplit
        ladder, which re-packs at the smaller covering canonical
        slab).  ``shape`` forces (R, H) — the fused multi-chip path
        stacks every slab of a wave at the wave's uniform shape."""
        rows = [int(reqs[i].row_mask.sum()) for i in idxs]
        R, H = shape if shape is not None else pack_mod.slab_shape(
            rows, self.slab_rows, ladder=self.slab_ladder)
        qs = np.zeros((R, qmax), np.uint8)
        qlens = np.zeros((R,), np.int32)
        row_mask = np.zeros((R,), bool)
        seg = pack_mod.segment_ids(rows, R)
        # empty hole slots: 1-col no-op drafts, like pad holes in
        # _stack_group (pad rows gather a real slot's draft and are
        # masked, so these are only ever the while_loop's frozen slots)
        ts = np.full((H, tmax), banded.PAD, np.uint8)
        ts[:, 0] = 0
        tlens = np.ones((H,), np.int32)
        r0 = 0
        for s, i in enumerate(idxs):
            req = reqs[i]
            m = req.row_mask
            n = rows[s]
            qs[r0:r0 + n] = req.qs[m]
            qlens[r0:r0 + n] = req.qlens[m]
            row_mask[r0:r0 + n] = True
            ts[s] = pad_to(req.draft, tmax)
            tlens[s] = len(req.draft)
            r0 += n
        return qs, qlens, row_mask, seg, ts, tlens

    def _stack_group(self, reqs, idxs, P, qmax, tmax):
        """Pad + stack a shape group's requests into device inputs."""
        Z = self._round_z(len(idxs))
        qs = np.zeros((Z, P, qmax), np.uint8)
        qlens = np.zeros((Z, P), np.int32)
        ts = np.full((Z, tmax), banded.PAD, np.uint8)
        ts[:, 0] = 0                     # pad holes: 1-col no-op drafts
        tlens = np.ones((Z,), np.int32)
        row_mask = np.zeros((Z, P), bool)
        for z, i in enumerate(idxs):
            req = reqs[i]
            qs[z] = req.qs
            qlens[z] = req.qlens
            ts[z] = pad_to(req.draft, tmax)
            tlens[z] = len(req.draft)
            row_mask[z] = req.row_mask
        return qs, qlens, ts, tlens, row_mask

    def run(self, requests) -> list:
        """Satisfy all requests (RefineRequest — the production window
        protocol — and/or bare RoundRequest); results align
        index-for-index (RefineResult / RoundResult respectively)."""
        results: List[object] = [None] * len(requests)
        refine = [i for i, r in enumerate(requests)
                  if isinstance(r, RefineRequest)]
        rounds = [i for i, r in enumerate(requests)
                  if not isinstance(r, RefineRequest)]
        if refine:
            for i, res in zip(refine,
                              self._run_refine([requests[i]
                                                for i in refine])):
                results[i] = res
        if rounds:
            for i, res in zip(rounds,
                              self._run_rounds([requests[i]
                                                for i in rounds])):
                results[i] = res
        return results

    def _run_groups(self, groups, dispatch, finish, host_one, results,
                    label=str):
        _run_groups_recovering(groups, dispatch, finish, host_one,
                               results, self.metrics,
                               self.max_oom_resplits, self.oom_backoff_s,
                               label=label, resil=self._resil)

    def _run_rounds(self, requests: List[RoundRequest]) -> List[RoundResult]:
        cfg = self.cfg
        groups: Dict[tuple, List[int]] = defaultdict(list)
        for i, req in enumerate(requests):
            P, qmax = req.qs.shape
            tmax = bucket_len(len(req.draft), self.len_quant)
            groups[(P, qmax, tmax)].append(i)

        results: List[Optional[RoundResult]] = [None] * len(requests)
        if self.metrics is not None:
            # bare rounds (legacy/test path) count as dispatches only —
            # 'windows' counts RefineRequests (one per window attempt)
            self.metrics.bump(device_dispatches=len(groups))

        def dispatch(idxs, key):
            P, qmax, tmax = key
            args = self._stack_group(requests, idxs, P, qmax, tmax)
            faultinject.fire("device_oom")
            Z = self._round_z(len(idxs))
            # :b<impl> suffix + labeled counter: per-implementation
            # dispatch attribution (scan / pallas / rotband), resolved
            # at dispatch time so a compile-forced scan pin shows up
            bimpl = banded_impl_effective(qmax, self._mesh is not None)
            if self.metrics is not None:
                self.metrics.bump_banded(bimpl)
            with trace.device_span(
                    "round", group=f"round:P{P}:q{qmax}:t{tmax}:b{bimpl}",
                    cells=Z * P * qmax * cfg.align.band,
                    shape=f"Z{Z}", n=len(idxs), Z=Z):
                faultinject.fire("stall")
                faultinject.fire("device_hang")
                if self._mesh is None:
                    # packed single-device transfers, as in _run_refine
                    step = _round_step(cfg.align, cfg.max_ins_per_col,
                                       tmax, self._bp_consts(),
                                       pack=(P, qmax))
                    return step(*_pack_args(args))
                step = _round_step(cfg.align, cfg.max_ins_per_col, tmax,
                                   self._bp_consts(), partitioned=True)
                return step(*self._shard_args(args, P))

        def finish(idxs, key, out):
            P, qmax, tmax = key
            if self._mesh is None:
                (cons, ins_base, ins_votes, ncov, nwin, bp,
                 advance) = _unpack_round(
                    out[0], out[1], cfg.max_ins_per_col, tmax)
            else:
                (cons, ins_base, ins_votes, ncov, nwin, bp, advance) = out
            for z, i in enumerate(idxs):
                results[i] = RoundResult(
                    cons=cons[z], ins_base=ins_base[z],
                    ins_votes=ins_votes[z], ncov=ncov[z], nwin=nwin[z],
                    tlen=len(requests[i].draft),
                    bp=int(bp[z]), advance=advance[z],
                )

        def host_one(i):
            req = requests[i]
            return self._sm.round(req.qs, req.qlens, req.row_mask,
                                  req.draft)

        for (P, qmax, tmax), idxs in groups.items():
            self._count_cells(requests, idxs, P, qmax,
                              self._round_z(len(idxs)))
        self._run_groups(
            groups, dispatch, finish, host_one, results,
            label=lambda k: (f"round:P{k[0]}:q{k[1]}:t{k[2]}:b"
                             + banded_impl_effective(
                                 k[1], self._mesh is not None)))
        return results

    def _run_refine(self, requests: List[RefineRequest]) -> List[RefineResult]:
        """One fused device dispatch per shape group for whole-window
        refinement loops (see _refine_step).  A hole whose speculative
        draft outgrows the fused capacity (_fused_tmax) is replayed
        exactly on the host — the overflow flag makes the fallback
        bit-faithful, and the counter records how rare it is."""
        if self._packing:
            return self._run_refine_packed(requests)
        cfg = self.cfg
        groups: Dict[tuple, List[int]] = defaultdict(list)
        for i, req in enumerate(requests):
            P, qmax = req.qs.shape
            tmax = _fused_tmax(len(req.draft), self.len_quant)
            groups[(P, qmax, tmax, req.iters)].append(i)

        results: List[Optional[RefineResult]] = [None] * len(requests)
        if self.metrics is not None:
            self.metrics.bump(device_dispatches=len(groups),
                              **window_counts(requests))

        def dispatch(idxs, key):
            P, qmax, tmax, iters = key
            args = self._stack_group(requests, idxs, P, qmax, tmax)
            faultinject.fire("device_oom")
            Z = self._round_z(len(idxs))
            bimpl = banded_impl_effective(qmax, self._mesh is not None)
            if self.metrics is not None:
                self.metrics.bump_banded(bimpl)
            with trace.device_span(
                    "refine",
                    group=f"refine:P{P}:q{qmax}:t{tmax}:i{iters}:b{bimpl}",
                    cells=Z * P * qmax * cfg.align.band * iters,
                    shape=f"Z{Z}", n=len(idxs), Z=Z):
                faultinject.fire("stall")
                faultinject.fire("device_hang")
                if self._mesh is None:
                    # single device: packed transfer protocol (2 h2d +
                    # 2 d2h latencies per dispatch instead of 5 + 9)
                    step = _refine_step(cfg.align, cfg.max_ins_per_col,
                                        tmax, iters, self._bp_consts(),
                                        pack=(P, qmax))
                    return step(*_pack_args(args))
                step = _refine_step(cfg.align, cfg.max_ins_per_col, tmax,
                                    iters, self._bp_consts(),
                                    partitioned=True)
                return step(*self._shard_args(args, P))

        def finish(idxs, key, out):
            P, qmax, tmax, iters = key
            if self._mesh is None:
                (cons, ins_base, ins_votes, ncov, nwin, bp, advance,
                 dlen, ovf) = _unpack_refine(
                    out[0], out[1], cfg.max_ins_per_col, tmax)
            else:
                (cons, ins_base, ins_votes, ncov, nwin, bp, advance,
                 dlen, ovf) = out
            for z, i in enumerate(idxs):
                req = requests[i]
                if ovf[z]:
                    if self.metrics is not None:
                        self.metrics.bump(refine_overflows=1)
                    with trace.span("host_replay", cat="recover",
                                    reason="refine_overflow"):
                        results[i] = host_one(i)
                    continue
                rr = RoundResult(
                    cons=cons[z], ins_base=ins_base[z],
                    ins_votes=ins_votes[z], ncov=ncov[z], nwin=nwin[z],
                    tlen=int(dlen[z]), bp=int(bp[z]), advance=advance[z],
                )
                results[i] = RefineResult(rr=rr)

        def host_one(i):
            req = requests[i]
            return refine_host(self._sm.round, req.qs, req.qlens,
                               req.row_mask, req.draft, req.iters)

        for (P, qmax, tmax, iters), idxs in groups.items():
            self._count_cells(requests, idxs, P, qmax,
                              self._round_z(len(idxs)), iters)
        self._run_groups(
            groups, dispatch, finish, host_one, results,
            label=lambda k: (f"refine:P{k[0]}:q{k[1]}:t{k[2]}:i{k[3]}:b"
                             + banded_impl_effective(
                                 k[1], self._mesh is not None)))
        return results

    def _run_refine_packed(
            self, requests: List[RefineRequest]) -> List[RefineResult]:
        """Ragged pass-packed refinement: requests group only by
        (qmax, tmax, iters) — the pass dimension is packed away — and
        each group's (hole, pass) rows are laid into fixed (R, qmax)
        slabs first-fit-decreasing by hole (pipeline/pack.py), one fused
        dispatch per slab.  The recovery ladder is inherited unchanged:
        a slab's idxs are its HOLES, so the OOM rung bisects by hole and
        each half re-packs into a smaller covering slab, and the ladder
        bottom replays per hole on refine_host, exactly as the bucketed
        path does."""
        cfg = self.cfg
        nrows = [int(r.row_mask.sum()) for r in requests]
        results: List[Optional[RefineResult]] = [None] * len(requests)
        if self.metrics is not None:
            self.metrics.bump(**window_counts(requests))

        def host_one(i):
            req = requests[i]
            return refine_host(self._sm.round, req.qs, req.qlens,
                               req.row_mask, req.draft, req.iters)

        shape_groups: Dict[tuple, List[int]] = defaultdict(list)
        for i, req in enumerate(requests):
            if nrows[i] == 0:
                # a request with no live pass-rows (degenerate; the
                # windowed driver never produces one) has no rows to
                # pack — the host path is its spec
                if self.metrics is not None:
                    self.metrics.bump(host_fallbacks=1)
                try:
                    with trace.span("host_replay", cat="recover",
                                    reason="no_rows"):
                        results[i] = host_one(i)
                except Exception as e:  # quarantined per hole
                    results[i] = e
                continue
            qmax = req.qs.shape[1]
            tmax = _fused_tmax(len(req.draft), self.len_quant)
            shape_groups[(qmax, tmax, req.iters)].append(i)

        # one fused multi-chip dispatch per group per WAVE when >1 local
        # device: D consecutive slabs of the plan stack on a leading
        # device dim and run as ONE executable call over the ('slab',)
        # mesh (_refine_step_packed_fused) — one transfer + one dispatch
        # where the r7 round-robin issued one of each per slab per chip
        # (and compiled one executable per chip).  Single device: one
        # dispatch per slab, as before.  A wave (or slab) is also the
        # recovery unit: its idxs are its HOLES, so the OOM rung bisects
        # by hole and each half re-plans at the smaller covering
        # canonical slab.
        D = len(self._devices)
        fused = self._slab_mesh is not None

        def _plan_wave(idxs):
            """Deterministic (plan, R, H) for a wave's holes — dispatch
            and finish both re-derive it, so OOM-bisected halves stay
            self-consistent.  All slabs of a wave share the wave's
            largest canonical R (one executable per wave)."""
            rows = [nrows[i] for i in idxs]
            plan = pack_mod.plan_slabs(rows, self.slab_rows)
            R = max(pack_mod.slab_shape([rows[j] for j in s],
                                        self.slab_rows,
                                        ladder=self.slab_ladder)[0]
                    for s in plan)
            return plan, R, max(1, R // pack_mod.SEG_DIV)

        groups: Dict[tuple, List[int]] = {}
        sweep_shapes = set()
        for key, idxs in shape_groups.items():
            slabs = pack_mod.plan_slabs([nrows[i] for i in idxs],
                                        self.slab_rows)
            if fused:
                for w in range(0, len(slabs), D):
                    chunk = slabs[w:w + D]
                    wave = [idxs[j] for s in chunk for j in s]
                    wkey = key + (w // D,)
                    groups[wkey] = wave
                    _, R, _ = _plan_wave(wave)
                    sweep_shapes.add(key + (R, D))
                    self._count_cells_packed_fused(
                        requests, wave, key[0], key[2], R,
                        len(chunk), D)
            else:
                for s_no, slab in enumerate(slabs):
                    sl_idxs = [idxs[j] for j in slab]
                    groups[key + (s_no,)] = sl_idxs
                    R, _ = pack_mod.slab_shape(
                        [nrows[i] for i in sl_idxs], self.slab_rows,
                        ladder=self.slab_ladder)
                    sweep_shapes.add(key + (R, 1))
                    self._count_cells_packed(requests, sl_idxs, key[0],
                                             R, key[2])
        self._warm_sweep_shapes(sweep_shapes)

        if self.metrics is not None:
            self.metrics.bump(device_dispatches=len(groups))

        def dispatch(idxs, key):
            qmax, tmax, iters, _ = key
            faultinject.fire("device_oom")
            band = cfg.align.band
            bimpl = banded_impl_effective(qmax)
            if self.metrics is not None:
                self.metrics.bump_banded(bimpl)
            if not fused:
                with trace.span("pack", cat="compute"):
                    args = self._stack_slab(requests, idxs, qmax, tmax)
                    big, small = _pack_slab_args(args,
                                                 cfg.max_ins_per_col)
                R = args[0].shape[0]
                H = args[4].shape[0]
                self._warm_wait(self._warm_key(qmax, tmax, iters, R, 1))
                self._note_shape(R, qmax, tmax, iters)
                step = _refine_step_packed(
                    cfg.align, cfg.max_ins_per_col, tmax, iters, H,
                    self._bp_consts(), pack=(R, qmax))
                with trace.device_span(
                        "refine_packed",
                        group=f"packed:q{qmax}:t{tmax}:i{iters}:b{bimpl}",
                        cells=R * qmax * band * iters,
                        shape=f"R{R}:S{H}",
                        plan={"slab": key[3], "rows": R,
                              "holes": len(idxs)}):
                    faultinject.fire("stall")
                    faultinject.fire("device_hang")
                    return step(big, small)
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as PS

            plan, R, H = _plan_wave(idxs)
            # an OOM-resplit re-plan can exceed D slabs; K > 1 then
            # carries K slabs per chip — still one executable call
            K = -(-len(plan) // D)
            Lbig, Lsmall = _slab_wire_sizes(R, qmax, H, tmax,
                                            cfg.max_ins_per_col)
            with trace.span("pack", cat="compute"):
                bigs = np.zeros((K * D, Lbig), np.uint8)
                smalls = np.zeros((K * D, Lsmall), np.int32)
                for d, s in enumerate(plan):
                    args = self._stack_slab(requests,
                                            [idxs[j] for j in s],
                                            qmax, tmax, shape=(R, H))
                    bigs[d], smalls[d] = _pack_slab_args(
                        args, cfg.max_ins_per_col)
            # dummy tail slabs stay all-zero: an empty row mask freezes
            # every segment, so that chip exits the while_loop at
            # iteration 0
            self._warm_wait(self._warm_key(qmax, tmax, iters, R, K * D))
            self._note_shape(R, qmax, tmax, iters)
            step = _refine_step_packed_fused(
                cfg.align, cfg.max_ins_per_col, tmax, iters, H,
                self._bp_consts(), (R, qmax), self._slab_mesh)
            sharding = NamedSharding(self._slab_mesh, PS("slab", None))
            with trace.device_span(
                    "refine_packed",
                    group=f"packed:q{qmax}:t{tmax}:i{iters}:b{bimpl}",
                    cells=len(plan) * R * qmax * band * iters,
                    shape=f"D{K * D}:R{R}:S{H}",
                    plan={"wave": key[3], "slabs": len(plan),
                          "chips": D, "rows": R,
                          "holes": len(idxs)}):
                faultinject.fire("stall")
                faultinject.fire("device_hang")
                big = jax.device_put(bigs, sharding)
                small = jax.device_put(smalls, sharding)
                return step(big, small)

        def _finish_slab(sl_idxs, tmax, big, small, R, H):
            (cons, ins_base, ins_votes, ncov, nwin, bp, advance, dlen,
             ovf) = _unpack_slab_refine(big, small,
                                        cfg.max_ins_per_col, tmax, H, R)
            r0 = 0
            for s, i in enumerate(sl_idxs):
                req = requests[i]
                n = nrows[i]
                rows = slice(r0, r0 + n)
                r0 += n
                if ovf[s]:
                    if self.metrics is not None:
                        self.metrics.bump(refine_overflows=1)
                    with trace.span("host_replay", cat="recover",
                                    reason="refine_overflow"):
                        results[i] = host_one(i)
                    continue
                # scatter row advances back into the request's (P,)
                # pass order; masked pass rows consumed nothing — the
                # same 0 the fixed-P device path computes for them
                adv = np.zeros(req.qs.shape[0], np.int32)
                adv[req.row_mask] = advance[rows]
                rr = RoundResult(
                    cons=cons[s], ins_base=ins_base[s],
                    ins_votes=ins_votes[s], ncov=ncov[s], nwin=nwin[s],
                    tlen=int(dlen[s]), bp=int(bp[s]), advance=adv,
                )
                results[i] = RefineResult(rr=rr)

        def finish(idxs, key, out):
            qmax, tmax, iters, _ = key
            big, small = out
            if not fused:
                R, H = pack_mod.slab_shape(
                    [nrows[i] for i in idxs], self.slab_rows,
                    ladder=self.slab_ladder)
                _finish_slab(idxs, tmax, big, small, R, H)
                return
            plan, R, H = _plan_wave(idxs)
            for d, s in enumerate(plan):
                _finish_slab([idxs[j] for j in s], tmax,
                             big[d], small[d], R, H)

        self._run_groups(
            groups, dispatch, finish, host_one, results,
            label=lambda k: (f"packed:q{k[0]}:t{k[1]}:i{k[2]}"
                             f":b{banded_impl_effective(k[0])}"))
        return results


@dataclasses.dataclass
class _Hole:
    idx: int
    zmw: object
    gen: object = None         # consensus generator (None => skipped)
    req: object = None         # pending PairRequest | RefineRequest
    done: bool = False
    resumed: bool = False      # written by a previous run; skip + no journal
    cns: Optional[tuple] = None  # (seq_bytes, qual_bytes|None)
    err: Optional[Exception] = None


def _start_hole(hole: _Hole, cfg: CcsConfig) -> None:
    """Start the combined prep+consensus generator (first step only;
    PairRequests and RefineRequests both flow through the driver)."""
    try:
        faultinject.fire("compute")
        hole.gen = full_gen_for_zmw(hole.zmw, cfg)
        hole.req = next(hole.gen)
    except StopIteration as e:
        # skipped (<3 passes -> None) or consensus without device work
        hole.done, hole.cns = True, _finish(e.value)
    except Exception as e:  # quarantine: one bad hole must not kill the run
        hole.done, hole.err = True, e


def _advance_hole(hole: _Hole, rr) -> None:
    """Feed the matching result (MatchResult / RefineResult) back in."""
    try:
        hole.req = hole.gen.send(rr)
    except StopIteration as e:
        hole.done, hole.req, hole.cns = True, None, _finish(e.value)
    except Exception as e:
        hole.done, hole.req, hole.err = True, None, e


def _feed_hole(hole: _Hole, result) -> None:
    """Route an executor result back into a hole's generator — unless it
    is an Exception (an executor's last-resort host replay failed for
    this one request), which quarantines the hole, not the run.  A
    PairBatch result (a list) quarantines on its first embedded
    Exception the same way."""
    if isinstance(result, list):
        exc = next((r for r in result if isinstance(r, Exception)), None)
        if exc is not None:
            result = exc
    if isinstance(result, Exception):
        hole.done, hole.req, hole.err = True, None, result
        try:
            hole.gen.close()
        except Exception:
            pass
    else:
        _advance_hole(hole, result)


def _finish(result):
    """Generator result -> (seq_bytes, qual|None) or None (skipped)."""
    return enc.to_record(result)


def _grow_window(window: int, cap: int, growth: int) -> int:
    """One step of the reference's adaptive chunk policy scaled to the
    admission window (main.c:686-691: 1024 -> x4 -> cap 16384, i.e.
    start at cap/growth^2 and multiply by growth until the cap)."""
    return min(window * max(2, int(growth)), cap)


def drive_batched(stream, writer, cfg: CcsConfig, journal: Journal,
                  metrics: Metrics, inflight: Optional[int] = None,
                  shared=None) -> int:
    """The batched scheduler loop over an open ZMW stream and writer.

    Shared by the single-process driver (run_pipeline_batched) and the
    multi-host sharded driver (parallel/distributed.py).  If the writer
    exposes ``put_at(idx, name, seq, qual)`` it receives each record's
    hole ordinal too (the distributed shard writer needs it to restore
    global order at merge time).

    ``shared``: the resident server's runtime (pipeline/serve.py
    SharedRuntime) when this driver runs as ONE TENANT JOB of a
    ``ccsx-tpu serve`` process instead of owning the process.  Duck-
    typed attributes, all optional:

    * ``warm`` — a server-lifetime WarmupCompiler (not closed here;
      its key-dedup makes job N+1 skip every executable job 1 built)
    * ``warm_cache`` — one set shared by every job's PairExecutor for
      the inline-warm dedupe (the no-compiler path)
    * ``guard`` — a drain surrogate (utils/drain.FlagGuard) the server
      raises on cancel / deadline / server drain; replaces the
      process-signal DrainGuard (signal handlers belong to the
      server's main thread, not to a job thread)
    * ``admission`` — a per-job handle on the server's fair shared
      admission window (serve.JobAdmission): a slot is acquired per
      hole admitted and released when the hole finishes computing, so
      N tenants split the device window instead of stacking N windows

    With ``shared`` set the driver also does NOT install a tracer or
    start telemetry — the server owns the process-global tracer (one
    compile table across jobs is exactly the zero-recompile criterion)
    and the HTTP stack.

    ``inflight``: an EXPLICIT admission window pins it (the old fixed
    behavior); None selects the reference's adaptive chunk-growth
    policy (main.c:686-691 scaled to cfg.zmw_microbatch as the cap:
    start at cap/growth^2, multiply by cfg.chunk_growth per filled
    admission round) so small inputs skip full-window admission latency
    while big ones stay bounded.

    Host prep runs on the background prep plane
    (pipeline/prep_pool.py) unless cfg.prep_threads == 0: ingest +
    the orientation walk + its pair alignments happen on pool threads
    concurrently with this loop's device sweeps, and the driver only
    pays ``t_prep_blocked`` when it has nothing dispatchable.  Output
    bytes, ordered emission, and the journal invariant are identical
    either way (tests/test_prep_overlap.py).
    """
    from ccsx_tpu.io import bam as bam_mod
    from ccsx_tpu.io import zmw as zmw_mod
    from ccsx_tpu.pipeline.prep_pool import (PrepPool,
                                             resolve_prep_threads)
    from ccsx_tpu.pipeline.run import guarded_stream
    from ccsx_tpu.utils.drain import DrainGuard

    # non-positive --inflight keeps its historical meaning of "use the
    # default" (which is now the adaptive window), rather than pinning
    # a degenerate 1-hole window
    explicit_window = inflight is not None and int(inflight) > 0
    cap = max(1, int(inflight) if explicit_window
              else int(cfg.zmw_microbatch))
    growth = max(2, int(getattr(cfg, "chunk_growth", 4)))
    window = cap if explicit_window else max(1, cap // (growth * growth))
    n_prep = resolve_prep_threads(cfg)
    # AOT warmup precompiler (--no-warmup disables): as soon as prep
    # yields a hole's first RefineRequest, the group's canonical
    # executables compile on this background thread, concurrently with
    # ingest/prep — the first dispatch of a warmed shape then runs at
    # steady-state speed (and books as execute in the tracer)
    warm = None
    own_warm = True
    if shared is not None and getattr(shared, "warm", None) is not None:
        warm = shared.warm
        own_warm = False       # server-lifetime: never closed here
    elif getattr(cfg, "warmup_compile", True):
        from ccsx_tpu.pipeline.warmup import WarmupCompiler

        warm = WarmupCompiler()
    # the fair shared-admission handle (serve.JobAdmission), None for
    # a process-owning run: one slot per admitted-and-still-computing
    # hole, released the moment the hole finishes
    adm = getattr(shared, "admission", None)
    # resilient execution (pipeline/resilience.py): one dispatch-
    # deadline runner + circuit breaker shared by BOTH executors, so
    # pair-fill and refine failures count against the same backend.
    # Deliberately PER JOB under serve: a tenant that wedges the chip
    # trips only its own breaker to the host rung
    resil = resil_mod.Resilience(cfg, metrics=metrics)
    executor = BatchExecutor(cfg, metrics=metrics, warmup=warm,
                             resil=resil)
    pair_executor = PairExecutor(cfg.align, quant=cfg.len_bucket_quant,
                                 metrics=metrics, warmup=warm,
                                 resil=resil,
                                 prefilter=cfg.prefilter,
                                 seed_device_min_t=cfg.seed_device_min_t,
                                 warm_cache=getattr(shared, "warm_cache",
                                                    None))

    def warm_hole(h) -> None:
        if warm is not None and isinstance(h.req, RefineRequest):
            executor.warm_refine(h.req, hole_id=h.idx)
    resume = journal.holes_done
    # restore the journaled failure count: a --max-failed-holes budget
    # is judged over the whole logical run, resumes included (journaled
    # failures are skipped as done and would otherwise never re-count)
    metrics.holes_failed = journal.holes_failed
    metrics.holes_prior_emitted = journal.holes_emitted
    put_at = getattr(writer, "put_at", None)

    active: List[_Hole] = []
    finished: Dict[int, _Hole] = {}
    next_idx = 0       # next hole index to admit (inline-prep mode)
    next_emit = 0      # next hole index to write
    exhausted = False
    pool = None        # PrepPool, constructed inside the try below
    rc = 0

    def emit_ready():
        nonlocal next_emit
        if next_emit not in finished:
            return
        # one span per run of holes retired: write + journal
        with trace.span("emit", cat="write"):
            while next_emit in finished:
                h = finished.pop(next_emit)
                if h.resumed:
                    next_emit += 1
                    if pool is not None:
                        pool.release()
                    continue
                wrote = False
                if h.err is not None:
                    metrics.holes_failed += 1
                    print(f"[ccsx-tpu] hole {h.zmw.movie}/{h.zmw.hole} "
                          f"failed: {h.err}", file=sys.stderr)
                    # failure-rate abort (--max-failed-holes): quarantine
                    # is no longer unbounded — a count budget aborts here,
                    # a fraction budget at end of run (metrics.py)
                    check_failure_budget(metrics, cfg)
                elif h.cns is not None and h.cns[0]:
                    name = f"{h.zmw.movie}/{h.zmw.hole}/ccs"
                    seq, qual = h.cns
                    with metrics.timer("write"), \
                            trace.span("write_record", cat="write"):
                        if put_at is not None:
                            put_at(h.idx, name, seq, qual)
                        else:
                            writer.put(name, seq, qual)
                    metrics.holes_out += 1
                    wrote = True
                # flush-before-cursor + write fault point + advance: the
                # shared crash invariant lives in Journal.retire
                journal.retire(writer, wrote, metrics)
                # rank_death models a sharded rank SIGKILLed mid-run (the
                # shepherd's restart-and-resume acceptance case): fired at
                # a retirement point so the dead rank leaves a valid
                # journal + durable records behind, exactly like a real
                # OOM-kill between holes
                faultinject.fire("rank_death")
                # sigterm delivers a REAL signal at the same point — the
                # graceful-drain path, made deterministic
                faultinject.fire("sigterm")
                metrics.tick()
                next_emit += 1
                if pool is not None:
                    pool.release()  # free one slot of ingest-ahead budget

    def admit(h):
        if h.done:
            finished[h.idx] = h
            if adm is not None:
                adm.release()  # never computed: free the slot at once
        else:
            warm_hole(h)
            active.append(h)

    # graceful drain (utils/drain.py) + the input_corrupt/salvage
    # ingest rungs: every ingestion path — inline admission AND the
    # prep pool's background workers — consumes the wrapped stream.
    # Installed HERE, immediately before the try whose finally restores
    # the handlers: installing any earlier would leak them if an
    # executor/resilience constructor above raised.  A serve job gets
    # its owner's FlagGuard instead — the server's main thread owns
    # the real signal handlers
    if shared is not None and getattr(shared, "guard", None) is not None:
        guard = shared.guard
    else:
        guard = DrainGuard.install()
    stream = guarded_stream(stream, cfg, metrics, guard)
    # the flight recorder (utils/trace.py): span JSONL under --trace,
    # and the stall watchdog + group attribution regardless — the
    # watchdog must be live on every batched run, or the next hang
    # leaves no diagnostics.  Constructed INSIDE the try
    # (finally tolerates tracer=None) so neither a watchdog thread nor
    # an open trace file can leak, and an unwritable --trace path gets
    # the same polite rc-1 refusal as an unwritable output path
    tracer = None
    telem = None
    try:
        if shared is None:
            try:
                tracer = trace.Tracer(cfg.trace_path,
                                      stall_timeout=cfg.stall_timeout_s,
                                      metrics=metrics)
            except OSError as e:
                print(f"Cannot open trace file for write! ({e})",
                      file=sys.stderr)
                return 1
            trace.install(tracer)
            # live telemetry endpoints (--telemetry-port; sharded runs
            # arrive here with the port already rank-offset).  None
            # when off; a bind failure degrades to a warning, never
            # kills a run
            if cfg.telemetry_port:
                from ccsx_tpu.utils import telemetry

                telem = telemetry.start(metrics, cfg.telemetry_port)
        if n_prep > 0:
            # the overlapped prep plane: ingest + the orientation walk
            # move to background threads (constructed after the tracer
            # so its spans record, inside the try so its threads cannot
            # leak past the finally)
            pool = PrepPool(stream, cfg, pair_executor, metrics,
                            threads=n_prep, max_outstanding=4 * cap,
                            resume=resume)
        ramped = explicit_window    # a sweep has held a full cap
        while True:
            admitted_full = False
            # the pool poll (or inline ingest + prep) and admission
            with trace.span("admit", cat="host"):
                if pool is not None:
                    # drain whatever prep has finished, up to the window —
                    # NEVER blocking here: with device work pending, the
                    # sweep must run while prep keeps working in background
                    while len(active) < window:
                        if adm is not None and not adm.try_acquire():
                            break  # at fair share; sweep what we hold
                        h = pool.poll()
                        if h is None:
                            if adm is not None:
                                adm.release()  # nothing arrived for it
                            break
                        admit(h)
                    admitted_full = len(active) >= window
                else:
                    # inline prep (--prep-threads 0): admit up to the
                    # window; bound TOTAL outstanding holes (incl.
                    # instantly-finished ones parked for ordered emission)
                    # so a filtered run can't grow memory unboundedly
                    while (not exhausted and len(active) < window
                           and next_idx - next_emit < 4 * cap):
                        if adm is not None and not adm.try_acquire():
                            break  # at fair share; sweep what we hold
                        try:
                            with metrics.timer("ingest"), \
                                    trace.span("ingest_hole", cat="ingest"):
                                z = next(stream)
                                faultinject.fire("ingest")
                        except StopIteration:
                            if adm is not None:
                                adm.release()
                            exhausted = True
                            break
                        metrics.holes_in += 1
                        h = _Hole(idx=next_idx, zmw=z)
                        next_idx += 1
                        if metrics.holes_in <= resume:
                            h.done = h.resumed = True
                        else:
                            # prep host work (grouping + first generator
                            # step) timed as its own stage AND as driver-
                            # blocked prep (inline prep is all critical
                            # path); the walk's pair alignments are batched
                            # below (benchmarks/prep_share.py is the
                            # criterion that forced this)
                            with metrics.timer("prep"), \
                                    metrics.timer("prep_blocked"), \
                                    trace.span("prep_hole", cat="prep",
                                               hole=str(z.hole)):
                                _start_hole(h, cfg)
                        admit(h)
                    admitted_full = len(active) >= window
            emit_ready()
            # until a sweep has held a full cap, each sweep waits for its
            # window to fill (or the input to end): with holes handed
            # over in input order, the ramp's sweeps then hold the same
            # holes on every run, whatever the pace of prep, and so do
            # the sweeps after it (whose holes take the slots the ramp's
            # free), so the records reach the writer in the same bursts
            short = (pool is not None and not ramped
                     and len(active) < window and not pool.drained())
            if not active or short:
                if pool is None:
                    if exhausted:
                        break
                    continue
                if pool.drained():
                    break
                # nothing dispatchable: the driver is genuinely blocked
                # on prep — the critical-path seconds prep_share reads.
                # Accumulate while prep keeps DELIVERING (sweeping the
                # first hole the instant it appears would fragment the
                # sweep into near-empty slabs and per-hole dispatches);
                # the moment prep pauses with work in hand — or the
                # window fills — sweep what we have (past the ramp).
                with trace.span("admit", cat="host"):
                    while len(active) < window and not pool.drained():
                        if adm is not None and not adm.try_acquire():
                            # at fair share while another tenant wants the
                            # window: wait on the admission condition (a
                            # release anywhere re-checks), not on the pool
                            adm.wait(0.05 if active else 0.2)
                            emit_ready()
                            if active:
                                break
                            metrics.heartbeat()
                            continue
                        # only the wait itself books as blocked — emission
                        # (write + journal fsync) has its own stage, and
                        # prep_share is the acceptance counter
                        with metrics.timer("prep_blocked"):
                            h = pool.get(timeout=0.05 if active else 1.0)
                        # emit as we accumulate: instantly-done holes
                        # (resumed/skipped) must retire HERE to keep
                        # releasing ingest budget, or a done stretch longer
                        # than the 4x bound live-locks against the pool
                        emit_ready()
                        if h is None:
                            if adm is not None:
                                adm.release()
                            if active and ramped:
                                break
                            metrics.heartbeat()
                            continue
                        admit(h)
                # a window filled while blocked still earns growth
                admitted_full = len(active) >= window
                metrics.heartbeat()
                if not active:
                    continue
            ramped = ramped or len(active) >= cap
            # one batched sweep over every pending request, split by
            # kind: prep pair alignments (strand_match walks) and
            # consensus rounds each batch across holes
            pair_holes = [h for h in active
                          if isinstance(h.req, (prep_mod.PairRequest,
                                                prep_mod.PairBatch))]
            round_holes = [h for h in active
                           if not isinstance(h.req,
                                             (prep_mod.PairRequest,
                                              prep_mod.PairBatch))]
            if pair_holes:
                # inline-mode only in practice (the pool finishes the
                # walk before handing a hole over); this sweep blocks
                # the driver, so it books as prep_blocked as well
                with metrics.timer("prep"), \
                        metrics.timer("prep_blocked"), \
                        trace.span("pair_sweep", cat="prep",
                                   n=len(pair_holes)):
                    pres = pair_executor.run([h.req for h in pair_holes])
                    for h, r in zip(pair_holes, pres):
                        _feed_hole(h, r)
            if round_holes:
                with metrics.timer("compute"), \
                        trace.span("refine_sweep", cat="compute",
                                   n=len(round_holes)):
                    rres = executor.run([h.req for h in round_holes])
                    # route the results back into the holes' generators,
                    # which yield their next windows' requests
                    with trace.span("window_next", cat="host",
                                    n=len(round_holes)):
                        for h, rr in zip(round_holes, rres):
                            _feed_hole(h, rr)
            still: List[_Hole] = []
            for h in active:
                if h.done:
                    finished[h.idx] = h
                    if adm is not None:
                        adm.release()  # finished computing: free the
                        # slot before emission (which can lag on an
                        # out-of-order tail) so a sibling job's denied
                        # admission unblocks now
                else:
                    # a sweep can grow a hole's draft into a fresh
                    # (qmax, tmax) group — predict next wave's shapes
                    warm_hole(h)
                    still.append(h)
            active = still
            emit_ready()
            if not explicit_window and admitted_full and window < cap:
                # adaptive chunk growth (main.c:686-691 semantics): a
                # filled admission round earns the next window size
                window = _grow_window(window, cap, growth)
            # interval-driven progress events even while nothing has
            # retired yet (a holes<=inflight run drains at the very end)
            metrics.heartbeat()
        # fraction-form --max-failed-holes settles at end of run, when
        # the processed-hole denominator is final (metrics.py) — but
        # not on a drain, whose denominator is a partial run's
        if not guard.requested:
            check_failure_budget(metrics, cfg, final=True)
    except FailureBudgetExceeded as e:
        from ccsx_tpu import exitcodes

        print(f"Error: {e}; aborting instead of emitting a degraded "
              "output at rc 0", file=sys.stderr)
        rc = exitcodes.RC_FAILED_HOLES
    except (bam_mod.BamError, zmw_mod.InvalidZmwName, ValueError) as e:
        print(f"Error: invalid input stream: {e}", file=sys.stderr)
        rc = 1
    except OSError as e:
        print(f"Error: write failed: {e}", file=sys.stderr)
        rc = 1
    finally:
        guard.restore()
        # settle this job's admission slots whatever the exit path —
        # a crashed tenant must not strand capacity the fair window
        # still counts against its share
        if adm is not None:
            adm.reset()
        try:
            writer.close()
        except OSError as e:
            print(f"Error: write failed! ({e})", file=sys.stderr)
            rc = 1
        # settle the (possibly rate-limit-lagging) cursor AFTER the
        # writer has made the records durable
        journal.close()
        # stop the prep plane first (its workers/pump write prep spans
        # and metrics): error paths may leave in-prep holes — dropped,
        # the rc already reflects the failure
        if pool is not None:
            pool.close()
        # stop the warmup thread (drops queued compiles; an in-flight
        # build finishes) BEFORE the tracer closes, so no warmup span
        # outlives the trace file.  A server-lifetime compiler stays
        # up — its queue is the next job's head start
        if warm is not None and own_warm:
            warm.close()
        # stop the watchdog + export the trace BEFORE the final metrics
        # event, so a degraded mark set mid-run is in the "final".
        # Under serve the PROCESS-GLOBAL tracer is the server's (one
        # compile table across jobs); uninstalling it here would blind
        # every sibling job's attribution
        if shared is None:
            trace.uninstall()
        if tracer is not None:
            tracer.close()
        # endpoints down BEFORE the final event: a scraper must never
        # see a half-closed Metrics object
        if telem is not None:
            telem.close()
        metrics.report()
    if rc == 0 and guard.requested:
        from ccsx_tpu import exitcodes

        print("[ccsx-tpu] drained cleanly; resume with the same "
              "command to continue", file=sys.stderr)
        rc = exitcodes.RC_INTERRUPTED
    return rc


def mesh_precheck(cfg: CcsConfig) -> int:
    """0 when cfg.mesh_shape is feasible (or unset); 1 with a stderr
    message otherwise.  Shared by both pipeline drivers — call after
    resolve_device and BEFORE opening any output file."""
    if cfg.mesh_shape is None:
        return 0
    import jax

    try:
        # local devices: the per-host mesh never spans hosts (see
        # BatchExecutor.__init__)
        BatchExecutor.validate_mesh(cfg.mesh_shape,
                                    len(jax.local_devices()))
    except ValueError as e:
        print(f"Error: invalid --mesh: {e}", file=sys.stderr)
        return 1
    return 0


def run_pipeline_batched(in_path: str, out_path: str, cfg: CcsConfig,
                         journal_path: Optional[str] = None,
                         inflight: Optional[int] = None,
                         metrics: Optional[Metrics] = None,
                         shared=None) -> int:
    """Batched end-to-end driver (CLI --batch; default on TPU backends).

    ``metrics``/``shared``: the serving plane (pipeline/serve.py) runs
    each tenant job through this exact entry point, handing in the
    job-labelled Metrics it scrapes for /jobs/<id> and the server's
    SharedRuntime (see drive_batched) — so a served job and a CLI run
    are the same code path end to end, which is what makes the
    byte-identity acceptance test meaningful."""
    from ccsx_tpu.pipeline.run import (holes_total_hint, open_writer,
                                       open_zmw_stream)
    from ccsx_tpu.utils.device import resolve_device

    # metrics constructed before the stream so both ingest paths can
    # book their filtered-hole accounting into it
    if metrics is None:
        metrics = Metrics(verbose=cfg.verbose,
                          stream=cfg.metrics_stream())
    metrics.holes_total = holes_total_hint(in_path, cfg)
    try:
        stream = open_zmw_stream(in_path, cfg, metrics=metrics)
    except (OSError, RuntimeError) as e:
        print(f"Error: Failed to open infile! ({e})", file=sys.stderr)
        metrics.close_stream()  # no final event for a non-run
        return 1

    # resolve the backend and validate the mesh BEFORE the writer opens:
    # a bad --mesh must not truncate an existing output file
    resolve_device(cfg.device)
    if mesh_precheck(cfg):
        metrics.close_stream()
        return 1

    # load under this run's fingerprint + reconcile the output tail with
    # the cursor (truncate a torn tail / refuse an untrustworthy resume)
    # BEFORE the writer opens for append
    journal = Journal.for_run(journal_path, in_path, cfg, out_path)
    try:
        writer = open_writer(out_path, append=bool(journal.holes_done),
                             bam=cfg.bam_out,
                             journaled=bool(journal_path))
    except OSError as e:
        print(f"Cannot open file for write! ({e})", file=sys.stderr)
        metrics.close_stream()
        return 1
    # None = the adaptive admission window (explicit --inflight pins it)
    return drive_batched(stream, writer, cfg, journal, metrics, inflight,
                         shared=shared)
