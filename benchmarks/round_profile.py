"""Per-stage breakdown of one consensus round (VERDICT r3 item 4).

ARCHITECTURE.md's perf notes previously argued the VPU roofline from a
hand-counted ~20 ops/cell; this tool replaces the argument with
measurement, two ways:

  1. staged timing — the round's three stages (banded DP fill,
     traceback projection, column vote) are jitted and timed SEPARATELY
     on device, plus the fused full round.  The deltas attribute round
     time to stages and quantify what XLA's fusion of the full round
     buys.  Timing uses the forced-execution marginal method (see
     _time): every timed loop ends in a host fetch, so the timing
     does not depend on whether ``block_until_ready`` waits.
  2. a ``jax.profiler`` trace of the warm full round is written to
     --trace-dir for op-level inspection (the artifact the roofline
     claim can be checked against).

Run on the TPU host:  python benchmarks/round_profile.py --json out.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

Z, P, W, TLEN = 16, 8, 1024, 1000   # bench.py's canonical round shapes
ITERS, WINDOWS = 20, 6   # ITERS raised on TPU in main() (signal >> d2h jitter)


def _time(fn, *args):
    """Best-window marginal seconds per fn(*args) call (the shared
    forced-execution method — full rationale in marginal_time.py)."""
    from marginal_time import marginal_time

    return min(marginal_time(fn, *args, iters=ITERS, repeats=WINDOWS))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--device", default="auto",
                    choices=["auto", "tpu", "cpu"])
    ap.add_argument("--trace-dir", default=None,
                    help="also write a jax.profiler trace here")
    ap.add_argument("--json", default=None)
    a = ap.parse_args()

    from ccsx_tpu.utils.device import resolve_device

    resolve_device(a.device)
    import jax
    import jax.numpy as jnp

    # on TPU the stages are ~0.1-1 ms: raise ITERS so the marginal
    # (ITERS-1) x stage time dominates the +-ms jitter of the two
    # checksum fetches.  CPU stages are ~0.1-0.5 s; 20 is plenty.
    global ITERS
    if jax.default_backend() != "cpu":
        ITERS = 200

    from ccsx_tpu.config import AlignParams
    from ccsx_tpu.consensus import star
    from ccsx_tpu.ops import msa, traceback
    import __graft_entry__ as ge

    params = AlignParams()
    aligner = star._aligner(params)
    projector = traceback.make_projector(W, 4)
    voter = msa.make_voter(4)
    qs, qlens, ts, tlens, row_mask = ge._example_batch(
        Z=Z, P=P, W=W, tlen=TLEN)

    # flatten to the shapes the round uses internally (bench.py step)
    ts_b = np.ascontiguousarray(np.broadcast_to(
        np.asarray(ts)[:, None, :], (Z, P, np.asarray(ts).shape[-1])))
    tl_b = np.ascontiguousarray(np.broadcast_to(
        np.asarray(tlens)[:, None], (Z, P)))
    qs_f = np.asarray(qs).reshape(Z * P, -1)
    ql_f = np.asarray(qlens).reshape(Z * P)
    ts_f = ts_b.reshape(Z * P, -1)
    tl_f = tl_b.reshape(Z * P)

    # ---- stage 1: banded DP fill (moves emission included) ----
    fill = jax.jit(lambda q, ql, t, tl: aligner(q, ql, t, tl))
    t_fill = _time(fill, qs_f, ql_f, ts_f, tl_f)
    _, moves, offs = jax.block_until_ready(fill(qs_f, ql_f, ts_f, tl_f))

    # ---- stage 2: traceback projection ----
    moves_r = jnp.asarray(moves).reshape(Z, P, qs_f.shape[-1], -1)
    offs_r = jnp.asarray(offs).reshape(Z, P, -1)
    proj = jax.jit(jax.vmap(jax.vmap(projector, in_axes=(0, 0, 0, 0, None)),
                            in_axes=(0, 0, 0, 0, 0)))
    qs_r = jnp.asarray(qs)
    ql_r = jnp.asarray(qlens)
    tl_r = jnp.asarray(tlens)
    t_proj = _time(proj, moves_r, offs_r, qs_r, ql_r, tl_r)
    aligned, ins_cnt, ins_b, _lead = jax.block_until_ready(
        proj(moves_r, offs_r, qs_r, ql_r, tl_r))

    # ---- stage 3: column vote ----
    vote = jax.jit(jax.vmap(voter))
    rm = jnp.asarray(row_mask)
    t_vote = _time(vote, aligned, ins_cnt, ins_b, rm)

    # ---- fused full round (the bench.py step) ----
    @jax.jit
    def full(qs, qlens, ts, tlens, row_mask):
        Zb, Pb, qmax = qs.shape
        tsb = jnp.broadcast_to(ts[:, None, :], (Zb, Pb, ts.shape[-1]))
        tlb = jnp.broadcast_to(tlens[:, None], (Zb, Pb))
        _, mv, of = aligner(qs.reshape(Zb * Pb, qmax),
                            qlens.reshape(Zb * Pb),
                            tsb.reshape(Zb * Pb, -1),
                            tlb.reshape(Zb * Pb))
        mv = mv.reshape(Zb, Pb, qmax, -1)
        of = of.reshape(Zb, Pb, qmax)
        pj = jax.vmap(jax.vmap(projector, in_axes=(0, 0, 0, 0, None)),
                      in_axes=(0, 0, 0, 0, 0))
        al, ic, ib, _ = pj(mv, of, qs, qlens, tlens)
        return jax.vmap(voter)(al, ic, ib, row_mask)

    qs3 = qs_r.reshape(Z, P, -1)
    ql3 = ql_r.reshape(Z, P)
    t_full = _time(full, qs3, ql3, jnp.asarray(ts), tl_r, rm)

    if a.trace_dir:
        with jax.profiler.trace(a.trace_dir):
            for _ in range(5):
                # np.asarray: the fetch forces execution inside the
                # trace window
                np.asarray(full(qs3, ql3, jnp.asarray(ts),
                                tl_r, rm)[0])

    cells = Z * P * W * 128
    res = {
        "backend": jax.default_backend(),
        "shapes": {"Z": Z, "P": P, "W": W, "tlen": TLEN, "band": 128},
        "banded_impl": star.banded_impl_effective(W),
        "projector_impl": os.environ.get("CCSX_PROJECTOR", "") or "walk",
        "stage_seconds": {
            "fill": round(t_fill, 6),
            "projection": round(t_proj, 6),
            "vote": round(t_vote, 6),
            "sum_of_stages": round(t_fill + t_proj + t_vote, 6),
            "fused_full_round": round(t_full, 6),
        },
        "stage_share_pct": {
            "fill": round(100 * t_fill / (t_fill + t_proj + t_vote), 1),
            "projection": round(100 * t_proj / (t_fill + t_proj + t_vote), 1),
            "vote": round(100 * t_vote / (t_fill + t_proj + t_vote), 1),
        },
        "fusion_gain_pct": round(
            100 * (1 - t_full / (t_fill + t_proj + t_vote)), 1),
        "fill_cells_per_sec": round(cells / t_fill),
        "round_cells_per_sec": round(cells / t_full),
        "round_zmw_windows_per_sec": round(Z / t_full, 1),
        "trace_dir": a.trace_dir,
    }
    print(json.dumps(res, indent=1))
    if a.json:
        with open(a.json, "w") as f:
            json.dump(res, f, indent=1)


if __name__ == "__main__":
    main()
