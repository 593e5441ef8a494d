"""A/B/C promotion harness: scan vs Pallas v1 vs rotband v2 DP fill.

Runs on whatever backend JAX resolves (the real chip when available:
interpret=False on TPU).  Two parts:

  1. correctness — bit-exact comparison of BOTH kernels (v1 band-local
     ops/banded_pallas.py, v2 rotating-band ops/banded_rotband.py)
     against the scan spec at small shapes (the same checks as
     tests/test_banded_pallas.py, but with interpret=False so the
     Mosaic-compiled kernels themselves are what run);
  2. throughput — all three arms timed INTERLEAVED at the bench.py
     shapes (Z=16, P=8, W=1024 by default) under the forced-execution
     marginal method ONLY (benchmarks/marginal_time.py), reporting
     zmw_windows/s and DP
     cells/s for each — and a machine-readable DECISION RECORD
     (winner, margin, backend, method) that bench.py vs_prev consumes.
     This record is what settles ROADMAP item 1: the first run on a
     live device backend names the production implementation.

Usage:  python benchmarks/pallas_ab.py [--json out.json]

Reference workload being timed: the banded-striped SIMD fill inside
bsalign's POA (reference main.c:552-572, band=128 at main.c:849).
"""

import argparse
import json
import os
import sys

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from marginal_time import marginal_time as _marginal_time  # noqa: E402


def _bench_args(Z, P, W, tlen, seed=0):
    sys.path.insert(0, _REPO)
    import __graft_entry__ as ge

    return ge._example_batch(Z=Z, P=P, W=W, tlen=tlen, seed=seed)


def check_bit_exact(interpret: bool) -> int:
    """Both kernels vs scan at small shapes; returns problems checked.

    With interpret=False on a TPU backend this is the HARDWARE
    bit-exactness arm for v1 and v2 alike."""
    from ccsx_tpu.config import AlignParams
    from ccsx_tpu.ops import banded, banded_pallas, banded_rotband
    from ccsx_tpu.utils import synth

    rng = np.random.default_rng(7)
    Qmax, Tmax, N = 256, 256, 8
    qs = np.full((N, Qmax), banded.PAD, np.uint8)
    qlens = np.zeros(N, np.int32)
    ts = np.full((N, Tmax), banded.PAD, np.uint8)
    tlens = np.zeros(N, np.int32)
    for i in range(N):
        tl = int(rng.integers(40, 200))
        tpl = rng.integers(0, 4, tl).astype(np.uint8)
        q = synth.mutate(rng, tpl, 0.03, 0.05, 0.05)[:Qmax]
        qs[i, : len(q)] = q
        qlens[i] = len(q)
        ts[i, :tl] = tpl
        tlens[i] = tl
    params = AlignParams()
    scan_f = banded.make_batched("global", params, with_moves=True)
    r1, m1, o1 = scan_f(qs, qlens, ts, tlens)
    m1 = np.asarray(m1)
    for name, mod in (("pallas", banded_pallas),
                      ("rotband", banded_rotband)):
        r2, m2, o2 = mod.batched_align_global_moves(
            qs, qlens, ts, tlens, params, interpret=interpret)
        np.testing.assert_array_equal(
            np.asarray(r1.score), np.asarray(r2.score), err_msg=name)
        np.testing.assert_array_equal(
            np.asarray(r1.mat), np.asarray(r2.mat), err_msg=name)
        np.testing.assert_array_equal(
            np.asarray(r1.aln), np.asarray(r2.aln), err_msg=name)
        np.testing.assert_array_equal(
            np.asarray(o1), np.asarray(o2), err_msg=name)
        m2 = np.asarray(m2)
        for i in range(N):
            ql = int(qlens[i])
            np.testing.assert_array_equal(
                m1[i, :ql], m2[i, :ql],
                err_msg=f"{name} moves mismatch, problem {i}")
        # and the slim kernel (the production consensus config)
        r3, m3, o3 = mod.batched_align_global_moves(
            qs, qlens, ts, tlens, params, interpret=interpret,
            with_stats=False)
        np.testing.assert_array_equal(
            np.asarray(r1.score), np.asarray(r3.score), err_msg=name)
        np.testing.assert_array_equal(
            np.asarray(o1), np.asarray(o3), err_msg=name)
        m3 = np.asarray(m3)
        for i in range(N):
            ql = int(qlens[i])
            np.testing.assert_array_equal(
                m1[i, :ql], m3[i, :ql],
                err_msg=f"{name} slim moves mismatch, problem {i}")
    return N


_STEP_CACHE = {}


def _round_step(impl: str, W: int):
    """Jitted full-round step for one banded impl (cached: the interleaved
    timing loop revisits each impl several times and must not re-trace)."""
    key = ("round", impl, W)
    if key in _STEP_CACHE:
        return _STEP_CACHE[key]
    import jax

    from ccsx_tpu.config import AlignParams
    from ccsx_tpu.consensus import star
    from ccsx_tpu.ops import msa, traceback

    params = AlignParams()
    projector = traceback.make_projector(W, 4)
    voter = msa.make_voter(4)
    # NOTE: the impl dispatch happens at TRACE time (star._aligner reads
    # banded_impl_effective() when the jitted step first runs).  The caller
    # (time_impl) holds the CCSX_BANDED_IMPL override through trace/compile,
    # which is when tracing occurs — do not call the returned step
    # outside such a scope or the wrong impl gets traced and cached.
    aligner = star._aligner(params)

    @jax.jit
    def step(qs, qlens, ts, tlens, row_mask):
        Zb, Pb, qmax = qs.shape
        ts_b = jax.numpy.broadcast_to(
            ts[:, None, :], (Zb, Pb, ts.shape[-1]))
        tl_b = jax.numpy.broadcast_to(tlens[:, None], (Zb, Pb))
        _, moves, offs = aligner(
            qs.reshape(Zb * Pb, qmax), qlens.reshape(Zb * Pb),
            ts_b.reshape(Zb * Pb, -1), tl_b.reshape(Zb * Pb))
        moves = moves.reshape(Zb, Pb, qmax, -1)
        offs = offs.reshape(Zb, Pb, qmax)
        proj = jax.vmap(jax.vmap(projector, in_axes=(0, 0, 0, 0, None)),
                        in_axes=(0, 0, 0, 0, 0))
        aligned, ins_cnt, ins_b, _lead = proj(
            moves, offs, qs, qlens, tlens)
        cons, ins_base, ins_votes, ncov, match, nwin = jax.vmap(voter)(
            aligned, ins_cnt, ins_b, row_mask)
        return cons, ncov

    _STEP_CACHE[key] = step
    return step


def time_impl(impl: str, Z, P, W, tlen, iters=100, repeats=3):
    """Time one full consensus round step with the given banded impl.

    Uses the forced-execution marginal method (_marginal_time — the r5
    first-cut artifact pallas_ab_tpu_r05.json predates it and its
    round/fill numbers are RPC-latency readings, not chip time); returns
    zmw_windows/s per window.  The CCSX_BANDED_IMPL override is held
    (try/finally) through trace/compile so a failure can't leak it into
    the process."""
    prior = os.environ.get("CCSX_BANDED_IMPL")
    os.environ["CCSX_BANDED_IMPL"] = impl
    try:
        step = _round_step(impl, W)
        args = _bench_args(Z, P, W, tlen)
        runs = [Z / dt for dt in _marginal_time(
            step, *args, iters=iters, repeats=repeats)]
    finally:
        if prior is None:
            os.environ.pop("CCSX_BANDED_IMPL", None)
        else:
            os.environ["CCSX_BANDED_IMPL"] = prior
    return runs


def time_fill_only(impl: str, Z, P, W, tlen, iters=300,
                   repeats=3):
    """Time just the DP fill (no projection/vote) — isolates the kernel.

    Compiles once; returns a list of result dicts, one per window."""
    import jax

    key = ("fill", impl)
    if key in _STEP_CACHE:
        fill = _STEP_CACHE[key]
    else:
        from ccsx_tpu.config import AlignParams
        from ccsx_tpu.ops import banded, banded_pallas

        params = AlignParams()
        if impl in ("pallas", "rotband"):
            from ccsx_tpu.ops import banded_rotband

            mod = banded_rotband if impl == "rotband" else banded_pallas
            interp = jax.default_backend() != "tpu"

            @jax.jit
            def fill(qs, qlens, ts, tlens):
                # with_stats=False: the consensus-round configuration
                # (star._aligner) — slim carry, 1-array F scan
                return mod.batched_align_global_moves(
                    qs, qlens, ts, tlens, params, interpret=interp,
                    with_stats=False)
        else:
            scan_f = banded.make_batched("global", params, with_moves=True,
                                         with_stats=False)

            @jax.jit
            def fill(qs, qlens, ts, tlens):
                return scan_f(qs, qlens, ts, tlens)
        _STEP_CACHE[key] = fill

    from ccsx_tpu.config import AlignParams as _AP

    band = _AP().band  # the band the fill actually runs at
    qs, qlens, ts, tlens, _ = _bench_args(Z, P, W, tlen)
    n = Z * P
    qs_f = qs.reshape(n, W)
    qlens_f = qlens.reshape(n)
    ts_f = np.ascontiguousarray(
        np.broadcast_to(ts[:, None, :], (Z, P, ts.shape[-1]))).reshape(n, -1)
    tlens_f = np.ascontiguousarray(
        np.broadcast_to(tlens[:, None], (Z, P))).reshape(n)
    cells = n * W * band
    return [{"zmw_windows_per_sec": Z / dt,
             "dp_cells_per_sec": cells / dt,
             "ms_per_dispatch": dt * 1e3}
            for dt in _marginal_time(fill, qs_f, qlens_f, ts_f, tlens_f,
                                     iters=iters, repeats=repeats)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    ap.add_argument("--Z", type=int, default=16)
    ap.add_argument("--P", type=int, default=8)
    ap.add_argument("--W", type=int, default=1024)
    ap.add_argument("--tlen", type=int, default=1000)
    ap.add_argument("--mode", choices=["time", "check", "both"],
                    default="both")
    ap.add_argument("--gblocks", default="",
                    help="comma list, e.g. 8,16,32: also sweep the "
                         "kernel's problem block (fill-only)")
    args = ap.parse_args()
    # validate up front: a malformed list must not crash AFTER the
    # expensive timing block and lose its results
    try:
        gblock_list = [int(x) for x in args.gblocks.split(",") if x]
    except ValueError:
        ap.error(f"--gblocks {args.gblocks!r}: expected a comma "
                 "list of integers")
    if any(g < 1 for g in gblock_list):
        ap.error(f"--gblocks values must be >= 1: {gblock_list}")

    sys.path.insert(0, _REPO)
    from ccsx_tpu.utils.device import resolve_device

    resolve_device("auto")
    import jax

    backend = jax.default_backend()
    interpret = backend == "cpu"
    out = {"backend": backend, "interpret": interpret,
           "shapes": {"Z": args.Z, "P": args.P, "W": args.W,
                      "tlen": args.tlen}}

    # in --mode both the check runs strictly after the timing block.
    # Scan and kernel windows are INTERLEAVED and medians reported, so
    # drift in the chip's available throughput hits every arm equally.
    ARMS = ("scan", "pallas", "rotband")
    if args.mode in ("time", "both"):
        import statistics

        rounds = {impl: [] for impl in ARMS}
        fills = {impl: [] for impl in ARMS}
        # a window where every marginal sample is nonpositive raises
        # RuntimeError (marginal_time's honest refusal) — on a noisy
        # shared chip that is one lost WINDOW, not a lost A/B: count it,
        # keep the samples already collected, and keep interleaving
        lost = []
        for rep in range(5):
            for impl in ARMS:
                try:
                    rounds[impl] += time_impl(
                        impl, args.Z, args.P, args.W, args.tlen,
                        iters=50, repeats=1)
                except RuntimeError as e:
                    lost.append(f"round/{impl}/rep{rep}: {e}")
                try:
                    fills[impl] += time_fill_only(
                        impl, args.Z, args.P, args.W, args.tlen,
                        iters=50, repeats=1)
                except RuntimeError as e:
                    lost.append(f"fill/{impl}/rep{rep}: {e}")
        if lost:
            out["windows_lost"] = lost
            print(f"[pallas_ab] {len(lost)} timing window(s) lost to "
                  "nonpositive marginals (kept going)", file=sys.stderr)
        for impl in ARMS:
            if rounds[impl]:
                out[f"round_{impl}"] = statistics.median(rounds[impl])
            else:
                out[f"round_{impl}"] = None  # every window lost: honest null
            out[f"round_{impl}_runs"] = rounds[impl]
            if fills[impl]:
                fr = sorted(fills[impl],
                            key=lambda d: d["dp_cells_per_sec"])
                out[f"fill_{impl}"] = fr[len(fr) // 2]
            else:
                out[f"fill_{impl}"] = None
            out[f"fill_{impl}_runs"] = [
                f["dp_cells_per_sec"] for f in fills[impl]]
            if rounds[impl] and fills[impl]:
                print(f"{impl}: round {out[f'round_{impl}']:.0f} "
                      "zmw_windows/s (median), fill "
                      f"{out[f'fill_{impl}']['dp_cells_per_sec']:.3e} "
                      "cells/s", file=sys.stderr)

        # ---- the DECISION RECORD (the promotion protocol's verdict,
        # ---- consumed by bench.py vs_prev): winner by the full-round
        # ---- median — the metric star._aligner's dispatch actually
        # ---- moves — with the fill-only medians carried alongside;
        # ---- margin = winner/runner-up.  Method is marginal-fetch by
        # ---- construction (this file has no other timing path).
        round_rates = {impl: out.get(f"round_{impl}") for impl in ARMS
                       if out.get(f"round_{impl}")}
        fill_rates = {
            impl: out[f"fill_{impl}"]["dp_cells_per_sec"]
            for impl in ARMS if out.get(f"fill_{impl}")}
        metric, rates = ("round_zmw_windows_per_sec", round_rates)
        if not rates:
            # every round window lost (degenerate chip): fall back to
            # the fill medians rather than emitting no verdict at all
            metric, rates = ("fill_dp_cells_per_sec", fill_rates)
        if rates:
            ranked = sorted(rates, key=rates.get, reverse=True)
            winner = ranked[0]
            margin = (rates[winner] / rates[ranked[1]]
                      if len(ranked) > 1 else None)
            out["decision"] = {
                "winner": winner,
                "margin": round(margin, 4) if margin else None,
                "metric": metric,
                "round_rates": round_rates,
                "fill_rates": fill_rates,
                "backend": backend,
                "interpret": interpret,
                "method": "marginal-fetch",
            }
            print(f"[decision] winner={winner} "
                  f"margin={out['decision']['margin']} "
                  f"metric={metric} backend={backend} "
                  f"interpret={interpret}", file=sys.stderr)

    if args.mode in ("time", "both") and gblock_list:
        # gblock sweep, fill-only.  NB the env is read at TRACE time of
        # the cached @jax.jit fill closure in time_fill_only — it is the
        # _STEP_CACHE.pop that forces a fresh closure (fresh jit cache)
        # per value; without it every g would re-time the first kernel.
        prior = os.environ.get("CCSX_PALLAS_GBLOCK")
        try:
            for impl in ("pallas", "rotband"):
                out[f"fill_{impl}_gblock"] = {}
                for g in gblock_list:
                    os.environ["CCSX_PALLAS_GBLOCK"] = str(g)
                    _STEP_CACHE.pop(("fill", impl), None)
                    try:
                        fr = sorted(
                            time_fill_only(impl, args.Z, args.P, args.W,
                                           args.tlen, iters=50, repeats=3),
                            key=lambda d: d["dp_cells_per_sec"])
                    except RuntimeError as e:
                        # same lost-window policy as the interleaved arms
                        out[f"fill_{impl}_gblock"][g] = None
                        print(f"{impl} gblock={g}: window lost ({e})",
                              file=sys.stderr)
                        continue
                    out[f"fill_{impl}_gblock"][g] = fr[len(fr) // 2]
                    print(f"{impl} gblock={g}: "
                          f"{fr[len(fr) // 2]['dp_cells_per_sec']:.3e} "
                          "cells/s", file=sys.stderr)
        finally:
            if prior is None:
                os.environ.pop("CCSX_PALLAS_GBLOCK", None)
            else:
                os.environ["CCSX_PALLAS_GBLOCK"] = prior
            _STEP_CACHE.pop(("fill", "pallas"), None)
            _STEP_CACHE.pop(("fill", "rotband"), None)

    if args.mode in ("check", "both"):
        n = check_bit_exact(interpret)
        out["bit_exact_problems"] = n
        print(f"bit-exact vs scan: {n} problems OK "
              f"(interpret={interpret}, backend={backend})", file=sys.stderr)

    print(json.dumps(out, indent=1))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
