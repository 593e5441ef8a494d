"""Benchmark: batched star-MSA consensus round throughput.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The measured unit is ZMW-windows consensed per second by the batched device
round (banded DP fill + traceback projection + column vote over a
(Z, P, W) batch) — the hot compute of the pipeline (reference: the bsalign
POA inside ccs_for2's window loop, main.c:552-572, where ~all CPU time
goes; SURVEY.md §3.3).

vs_baseline compares against bench_baseline.json: the native C++ banded
SIMD fill (native/baseline_simd.cpp — the bsalign-fill workload, band=128,
vectorized build MEASURED, SIMD factor MEASURED vec/scalar on identical
source) per-core, projected x64 linearly to the BASELINE.md target
machine.  The reference binary itself is not buildable here (its bsalign
dependency is cloned at build time, README.md:11 — no network), so the
one remaining projection — linear core scaling — is explicit; the old
guessed 8x SIMD credit is gone (VERDICT r4 item 4).
Recalibrate with:  python bench.py --calibrate
"""

import json
import os
import sys
import time

# benchmark shapes (kept canonical so compiles cache): Z zmws x P passes x W window
Z, P, W, TLEN = 16, 8, 1024, 1000
ITERS, WINDOWS = 25, 8
_HERE = os.path.dirname(os.path.abspath(__file__))
BASELINE_PATH = os.path.join(_HERE, "bench_baseline.json")

# >20% drop vs the previous bench artifact prints the loud warning and
# sets the top-level "regressed" field
REGRESSION_DROP = 0.8


def _load_bench_line(path):
    """Extract the bench JSON line from an artifact: the driver's
    BENCH_r*.json wraps it under "parsed"; a raw `python bench.py`
    capture IS the line.  None when unusable."""
    try:
        with open(path) as f:
            d = json.load(f)
    except (OSError, ValueError):
        return None
    line = d.get("parsed") if isinstance(d.get("parsed"), dict) else d
    if not isinstance(line, dict) or "dp_cells_per_sec" not in line:
        return None
    return line


def find_prev_bench(root=_HERE):
    """The most recent prior bench artifact to gate against: the
    highest-numbered usable BENCH_r*.json.  (bench_baseline.json is the
    NATIVE-fill yardstick and already reported as vs_baseline — it is
    not a prior bench line, so it never backs vs_prev.)  Returns
    (artifact_name, line) or (None, None)."""
    import glob
    import re

    cands = []
    for p in glob.glob(os.path.join(root, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", p)
        if m:
            cands.append((int(m.group(1)), p))
    for _, p in sorted(cands, reverse=True):
        line = _load_bench_line(p)
        if line is not None:
            return os.path.basename(p), line
    return None, None


def latest_quality_artifacts(root=_HERE, n=2):
    """The ``n`` highest-numbered usable benchmarks/quality_r*.json
    artifacts, newest first, as (name, summary) pairs.  A usable one
    carries a gate_biased Q20 yield (the realistic-error regime,
    ROADMAP item 5 — the product-defining number the bench trajectory
    must gate alongside the perf ones)."""
    import glob
    import re

    cands = []
    for p in glob.glob(os.path.join(root, "benchmarks",
                                    "quality_r*.json")):
        m = re.search(r"quality_r(\d+)\.json$", p)
        if m:
            cands.append((int(m.group(1)), p))
    out = []
    for _, p in sorted(cands, reverse=True):
        try:
            with open(p) as f:
                d = json.load(f)
        except (OSError, ValueError):
            continue
        gb = d.get("gate_biased")
        g1 = d.get("gate_1")
        gb_y = gb.get("q20_yield") if isinstance(gb, dict) else None
        iid_y = g1.get("q20_yield") if isinstance(g1, dict) else None
        if gb_y is None:
            continue
        out.append((os.path.basename(p),
                    {"gate_biased_q20_yield": gb_y,
                     "iid_q20_yield": iid_y}))
        if len(out) >= n:
            break
    return out


def compare_quality(line, prev, vp, regressed):
    """The quality leg of the vs_prev gate: gate_biased Q20 yield from
    the newest quality artifact vs the prior bench line's (or, before
    bench lines carried one, the second-newest quality artifact).  A
    >20% relative drop flags ``regressed`` exactly like a perf drop —
    quality backsliding must trip the same wire (ROADMAP item 5 tail).
    Yield is a bytes-level property, so no backend gating applies."""
    quals = latest_quality_artifacts()
    if quals:
        name, summary = quals[0]
        line["quality"] = {"artifact": name, **summary}
    cur = (line.get("quality") or {}).get("gate_biased_q20_yield")
    prev_q = ((prev or {}).get("quality")
              or {}).get("gate_biased_q20_yield")
    prev_src = "prev bench line"
    if prev_q is None and len(quals) > 1:
        prev_src, prev_q = quals[1][0], \
            quals[1][1]["gate_biased_q20_yield"]
    if cur is None or prev_q is None:
        return
    vp["gate_biased_q20_yield"] = {"prev": prev_q, "cur": cur,
                                   "prev_source": prev_src}
    if prev_q > 0 and cur < prev_q * REGRESSION_DROP:
        regressed.append(
            f"gate_biased q20_yield {prev_q}->{cur} (quality "
            "regression, realistic-error regime)")


def latest_fleet_artifacts(root=_HERE, n=2):
    """The ``n`` highest-numbered usable benchmarks/fleet_r*.json
    artifacts (the elastic-fleet churn soak, benchmarks/fleet.py),
    newest first, as (name, summary) pairs.  Usable = carries the
    derived scale-out ratio; the summary also keeps the one-bit
    byte-identity verdict and the killed-at-halfway overhead."""
    import glob
    import re

    cands = []
    for p in glob.glob(os.path.join(root, "benchmarks",
                                    "fleet_r*.json")):
        m = re.search(r"fleet_r(\d+)\.json$", p)
        if m:
            cands.append((int(m.group(1)), p))
    out = []
    for _, p in sorted(cands, reverse=True):
        try:
            with open(p) as f:
                d = json.load(f)
        except (OSError, ValueError):
            continue
        derived = d.get("derived") or {}
        if derived.get("scaleout_k4") is None:
            continue
        out.append((os.path.basename(p),
                    {"scaleout_k4": derived["scaleout_k4"],
                     "kill_overhead_x": derived.get("kill_overhead_x"),
                     "ok": d.get("ok")}))
        if len(out) >= n:
            break
    return out


def compare_fleet(line, prev, vp, regressed):
    """The fleet leg of the vs_prev gate: scale-out efficiency (K=1
    wall / K=4 wall) from the newest fleet_r*.json artifact vs the
    prior bench line's (or the second-newest artifact).  A >20%
    relative drop in scale-out — or ANY non-byte-identical trial in
    the newest soak — trips ``regressed`` exactly like a perf drop:
    elastic scheduling that stops scaling (or stops being exact) is a
    regression of the whole plane.  Wall ratios of a CPU-hosted soak
    compare fine across rounds (same harness, same corpus), so no
    backend gating applies."""
    arts = latest_fleet_artifacts()
    if arts:
        name, summary = arts[0]
        line["fleet"] = {"artifact": name, **summary}
        if summary.get("ok") is False:
            regressed.append(
                f"fleet soak {name} has non-byte-identical trials "
                "(fleet churn changed the output bytes)")
    cur = (line.get("fleet") or {}).get("scaleout_k4")
    prev_s = ((prev or {}).get("fleet") or {}).get("scaleout_k4")
    prev_src = "prev bench line"
    if prev_s is None and len(arts) > 1:
        prev_src, prev_s = arts[1][0], arts[1][1]["scaleout_k4"]
    if cur is None or prev_s is None:
        return
    vp["fleet_scaleout_k4"] = {"prev": prev_s, "cur": cur,
                               "prev_source": prev_src}
    if prev_s > 0 and cur < prev_s * REGRESSION_DROP:
        regressed.append(
            f"fleet scaleout_k4 {prev_s}->{cur} (elastic scheduling "
            "regression)")


def latest_serve_artifacts(root=_HERE, n=2):
    """The ``n`` highest-numbered usable benchmarks/serve_r*.json
    artifacts (the serving-plane chaos soak, benchmarks/serve_chaos.py),
    newest first, as (name, summary) pairs.  Usable = carries the
    steady-wave record (sustained zmws/s through the resident server
    plus its steady-state recompile count); the summary also keeps the
    one-bit all-trials verdict."""
    import glob
    import re

    cands = []
    for p in glob.glob(os.path.join(root, "benchmarks",
                                    "serve_r*.json")):
        m = re.search(r"serve_r(\d+)\.json$", p)
        if m:
            cands.append((int(m.group(1)), p))
    out = []
    for _, p in sorted(cands, reverse=True):
        try:
            with open(p) as f:
                d = json.load(f)
        except (OSError, ValueError):
            continue
        steady = d.get("steady") or {}
        if steady.get("zmws_per_sec") is None:
            continue
        out.append((os.path.basename(p),
                    {"zmws_per_sec": steady["zmws_per_sec"],
                     "recompiles": steady.get("recompiles"),
                     "ok": d.get("ok")}))
        if len(out) >= n:
            break
    return out


def compare_serve(line, prev, vp, regressed):
    """The serving leg of the vs_prev gate: sustained steady-wave
    zmws/s through the resident server from the newest serve_r*.json
    artifact vs the prior bench line's (or the second-newest artifact).
    A >20% relative drop — or ANY failed trial in the newest soak, or
    a NONZERO steady-state recompile count — trips ``regressed``: a
    server that stops isolating tenants, stops being byte-exact, or
    starts recompiling in steady state has lost the whole point of
    residency.  CPU-hosted soak rates compare fine across rounds (same
    harness, same corpus), so no backend gating applies."""
    arts = latest_serve_artifacts()
    if arts:
        name, summary = arts[0]
        line["serve"] = {"artifact": name, **summary}
        if summary.get("ok") is False:
            regressed.append(
                f"serve soak {name} has failed trials (tenant "
                "isolation / byte identity broke)")
        if summary.get("recompiles"):
            regressed.append(
                f"serve soak {name} booked {summary['recompiles']} "
                "steady-state recompiles (warm residency broke)")
    cur = (line.get("serve") or {}).get("zmws_per_sec")
    prev_s = ((prev or {}).get("serve") or {}).get("zmws_per_sec")
    prev_src = "prev bench line"
    if prev_s is None and len(arts) > 1:
        prev_src, prev_s = arts[1][0], arts[1][1]["zmws_per_sec"]
    if cur is None or prev_s is None:
        return
    vp["serve_zmws_per_sec"] = {"prev": prev_s, "cur": cur,
                                "prev_source": prev_src}
    if prev_s > 0 and cur < prev_s * REGRESSION_DROP:
        regressed.append(
            f"serve steady zmws_per_sec {prev_s}->{cur} (resident-"
            "server throughput regression)")


def latest_serve_fleet_artifacts(root=_HERE, n=2):
    """The ``n`` highest-numbered usable benchmarks/serve_fleet_r*.json
    artifacts (the replica-fleet churn soak,
    benchmarks/serve_fleet_chaos.py), newest first, as (name, summary)
    pairs.  Usable = carries the steady fleet record (sustained zmws/s
    across the replica fleet plus the per-replica steady-state
    recompile total); the summary also keeps the job-accounting
    verdicts (lost / duplicated / byte identity)."""
    import glob
    import re

    cands = []
    for p in glob.glob(os.path.join(root, "benchmarks",
                                    "serve_fleet_r*.json")):
        m = re.search(r"serve_fleet_r(\d+)\.json$", p)
        if m:
            cands.append((int(m.group(1)), p))
    out = []
    for _, p in sorted(cands, reverse=True):
        try:
            with open(p) as f:
                d = json.load(f)
        except (OSError, ValueError):
            continue
        steady = d.get("steady") or {}
        if steady.get("zmws_per_sec") is None:
            continue
        out.append((os.path.basename(p),
                    {"zmws_per_sec": steady["zmws_per_sec"],
                     "recompiles": steady.get("recompiles"),
                     "lost_jobs": d.get("lost_jobs"),
                     "duplicated_jobs": d.get("duplicated_jobs"),
                     "byte_identical": d.get("byte_identical"),
                     "ok": d.get("ok")}))
        if len(out) >= n:
            break
    return out


def compare_serve_fleet(line, prev, vp, regressed):
    """The replica-fleet leg of the vs_prev gate: sustained fleet-wide
    zmws/s under replica churn (SIGKILL mid-wave + mid-run join) from
    the newest serve_fleet_r*.json artifact vs the prior bench line's
    (or the second-newest artifact).  A >20% relative drop trips
    ``regressed`` — and so, OUTRIGHT, does any lost or duplicated job,
    any non-byte-identical output, any failed trial, or a nonzero
    per-replica steady-state recompile count: a fleet that loses jobs
    under churn (or double-emits them past the exclusive retirement
    fence) has lost the whole point of the lease domain."""
    arts = latest_serve_fleet_artifacts()
    if arts:
        name, summary = arts[0]
        line["serve_fleet"] = {"artifact": name, **summary}
        if summary.get("ok") is False:
            regressed.append(
                f"serve-fleet soak {name} has failed trials")
        if summary.get("lost_jobs") or summary.get("duplicated_jobs"):
            regressed.append(
                f"serve-fleet soak {name} lost "
                f"{summary.get('lost_jobs')} / duplicated "
                f"{summary.get('duplicated_jobs')} job(s) under churn "
                "(the zero-lost-jobs invariant broke)")
        if summary.get("byte_identical") is False:
            regressed.append(
                f"serve-fleet soak {name} produced non-byte-identical "
                "job outputs")
        if summary.get("recompiles"):
            regressed.append(
                f"serve-fleet soak {name} booked "
                f"{summary['recompiles']} steady-state recompiles "
                "across its replicas (warm residency broke)")
    cur = (line.get("serve_fleet") or {}).get("zmws_per_sec")
    prev_s = ((prev or {}).get("serve_fleet") or {}).get("zmws_per_sec")
    prev_src = "prev bench line"
    if prev_s is None and len(arts) > 1:
        prev_src, prev_s = arts[1][0], arts[1][1]["zmws_per_sec"]
    if cur is None or prev_s is None:
        return
    vp["serve_fleet_zmws_per_sec"] = {"prev": prev_s, "cur": cur,
                                      "prev_source": prev_src}
    if prev_s > 0 and cur < prev_s * REGRESSION_DROP:
        regressed.append(
            f"serve-fleet steady zmws_per_sec {prev_s}->{cur} "
            "(fleet throughput regression under churn)")


def latest_pallas_ab_artifacts(root=_HERE, n=2):
    """The ``n`` highest-numbered usable benchmarks/pallas_ab*_r*.json
    artifacts (the scan / Pallas v1 / rotband v2 promotion harness,
    benchmarks/pallas_ab.py), newest first, as (name, summary) pairs.
    Usable = carries a "decision" record (winner, margin, per-arm
    rates), i.e. a --mode time run that produced a verdict; pure
    --mode check artifacts are skipped."""
    import glob
    import re

    cands = []
    for p in glob.glob(os.path.join(root, "benchmarks",
                                    "pallas_ab*_r*.json")):
        m = re.search(r"pallas_ab.*_r(\d+)\.json$", p)
        if m:
            cands.append((int(m.group(1)), p))
    out = []
    for _, p in sorted(cands, reverse=True):
        try:
            with open(p) as f:
                d = json.load(f)
        except (OSError, ValueError):
            continue
        dec = d.get("decision")
        if not isinstance(dec, dict) or not dec.get("winner"):
            continue
        out.append((os.path.basename(p),
                    {"winner": dec.get("winner"),
                     "margin": dec.get("margin"),
                     "metric": dec.get("metric"),
                     "round_rates": dec.get("round_rates"),
                     "backend": dec.get("backend"),
                     "interpret": dec.get("interpret")}))
        if len(out) >= n:
            break
    return out


def compare_dp_kernel(line, prev, vp, regressed):
    """The DP-kernel leg of the vs_prev gate: the three-arm promotion
    record (scan vs Pallas v1 vs rotband v2, marginal-fetch timed)
    from the newest pallas_ab artifact vs the prior bench line's (or
    the second-newest artifact).  Absolute rates only compare within
    the same backend — an interpret-mode CPU record never gates a TPU
    one.  A winner FLIP is informational (logged into vs_prev, the
    promotion protocol decides what to do with it); what trips
    ``regressed`` is the winning arm's throughput dropping >20% on
    the same backend — the promoted kernel itself got slower."""
    arts = latest_pallas_ab_artifacts()
    if arts:
        name, summary = arts[0]
        line["dp_kernel"] = {"artifact": name, **summary}
    cur = line.get("dp_kernel")
    prev_d = (prev or {}).get("dp_kernel")
    prev_src = "prev bench line"
    if prev_d is None and len(arts) > 1:
        prev_src, prev_d = arts[1]
    if not cur or not prev_d:
        return
    ent = {"prev_winner": prev_d.get("winner"),
           "cur_winner": cur.get("winner"),
           "prev_source": prev_src}
    if cur.get("winner") != prev_d.get("winner"):
        ent["winner_flipped"] = True
        print(f"[bench] dp-kernel winner flipped "
              f"{prev_d.get('winner')} -> {cur.get('winner')} "
              "(informational; see the promotion protocol in "
              "ccsx_tpu/consensus/star.py)", file=sys.stderr)
    if cur.get("backend") == prev_d.get("backend"):
        w = cur.get("winner")
        cur_r = (cur.get("round_rates") or {}).get(w)
        prev_r = (prev_d.get("round_rates") or {}).get(w)
        if cur_r and prev_r:
            ent["winner_rate"] = {"prev": prev_r, "cur": cur_r}
            if cur_r < prev_r * REGRESSION_DROP:
                regressed.append(
                    f"dp-kernel winning arm '{w}' "
                    f"{prev_r:.0f}->{cur_r:.0f} zmw_windows/s "
                    f"({cur.get('backend')} backend)")
    vp["dp_kernel"] = ent


def compare_with_prev(line, prev, artifact):
    """Mutates ``line``: adds "vs_prev" (ratios vs the prior artifact
    for dp_cells_per_sec and per-config e2e zmws_per_sec) and, on a
    >20% drop in either, the top-level "regressed" field + a loud
    stderr warning — the self-comparing trajectory VERDICT asked for.
    Only same-backend artifacts are compared (an XLA:CPU run against a
    TPU number is not a regression signal), and only e2e configs run
    at the same hole count (zmws_per_sec is hole-count sensitive)."""
    vp = {"artifact": artifact, "prev_backend": prev.get("backend")}
    if prev.get("degraded"):
        vp["prev_degraded"] = prev["degraded"]
    regressed = []
    if prev.get("backend") != line.get("backend"):
        vp["skipped"] = (f"prev backend {prev.get('backend')!r} != "
                         f"{line.get('backend')!r}; not comparable")
    else:
        if prev.get("dp_cells_per_sec") and line.get("dp_cells_per_sec"):
            r = line["dp_cells_per_sec"] / prev["dp_cells_per_sec"]
            vp["dp_cells_per_sec"] = round(r, 3)
            if r < REGRESSION_DROP:
                regressed.append(f"dp_cells_per_sec x{r:.2f}")
        prev_e2e = {e.get("config"): e for e in prev.get("e2e", [])
                    if isinstance(e, dict)}
        ratios = {}
        # per-group compile counts (the r7 storm gate): compiles are
        # exact counts even untraced, so every same-config pair
        # compares.  Flag a regression when any config's worst packed
        # group now compiles more than the prior artifact's worst AND
        # is past the canonical-ladder budget of 2 — growth within the
        # ladder is legitimate tail variation, a return to 4-5 is the
        # storm.
        def _max_compiles(entry):
            groups = entry.get("groups") or {}
            packed = [st.get("compiles", 0) for k, st in groups.items()
                      if str(k).startswith("packed:")]
            return max(packed) if packed else None

        compiles_cmp = {}
        for e in line.get("e2e", []):
            pe = prev_e2e.get(e.get("config"))
            if not pe:
                continue
            cur_c, prev_c = _max_compiles(e), _max_compiles(pe)
            if cur_c is not None and prev_c is not None:
                compiles_cmp[str(e["config"])] = {"prev": prev_c,
                                                  "cur": cur_c}
                if cur_c > max(prev_c, 2):
                    regressed.append(
                        f"e2e c{e['config']} packed group compiles "
                        f"{prev_c}->{cur_c} (compile storm)")
        if compiles_cmp:
            vp["group_compiles_max"] = compiles_cmp
        # prep-share gate (ISSUE 8): the prep plane keeps host prep off
        # the critical path, so a config whose blocked-prep share climbs
        # back above the acceptance ceiling AND clearly above the prior
        # artifact's is a regression of the overlap itself.  The 0.10
        # floor keeps small-number noise (tiny e2e configs, ~seconds of
        # wall) from tripping it; prior artifacts without the counter
        # simply don't compare.
        prep_cmp = {}
        for e in line.get("e2e", []):
            pe = prev_e2e.get(e.get("config"))
            cur_p = (e or {}).get("prep_share")
            prev_p = (pe or {}).get("prep_share") if pe else None
            if cur_p is None or prev_p is None:
                continue
            prep_cmp[str(e["config"])] = {"prev": prev_p, "cur": cur_p}
            if cur_p > 0.10 and cur_p > prev_p * 1.5:
                regressed.append(
                    f"e2e c{e['config']} prep_share "
                    f"{prev_p}->{cur_p} (prep back on the critical "
                    "path)")
        if prep_cmp:
            vp["prep_share"] = prep_cmp
        # breaker/hang-rescued runs are not perf numbers: a config that
        # completed via an open circuit breaker (or abandoned, host-
        # replayed dispatches) measured the HOST path's wall, not the
        # device's — flag it and keep it out of the ratio geomean
        rescued = []
        for e in line.get("e2e", []):
            pe = prev_e2e.get(e.get("config"))
            cur_rescued = bool(e.get("breaker_trips")
                               or e.get("device_hangs"))
            prev_rescued = bool(pe and (pe.get("breaker_trips")
                                        or pe.get("device_hangs")))
            if cur_rescued:
                rescued.append(str(e.get("config")))
            if (not pe or not pe.get("zmws_per_sec")
                    or not e.get("zmws_per_sec")
                    or pe.get("holes_in") != e.get("holes_in")
                    # traced runs force per-dispatch execution; their
                    # wall numbers are a different discipline than the
                    # untraced async overlap — never cross-compare
                    or bool(pe.get("traced")) != bool(e.get("traced"))
                    or cur_rescued or prev_rescued):
                continue
            ratios[str(e["config"])] = round(
                e["zmws_per_sec"] / pe["zmws_per_sec"], 3)
        if rescued:
            vp["breaker_rescued_configs"] = rescued
            print("[bench] WARNING: e2e config(s) "
                  + ",".join(rescued) + " completed only via the "
                  "resilience layer (open breaker / abandoned "
                  "dispatches); their wall times measure the host "
                  "path and are excluded from vs_prev",
                  file=sys.stderr)
        if ratios:
            import math

            g = math.exp(sum(math.log(r) for r in ratios.values())
                         / len(ratios))
            vp["zmws_per_sec"] = round(g, 3)
            vp["zmws_per_sec_configs"] = ratios
            if g < REGRESSION_DROP:
                regressed.append(f"e2e zmws_per_sec x{g:.2f}")
    # the quality, fleet, serve, and dp-kernel legs ride every
    # comparison (all gate off committed artifacts; the dp-kernel leg
    # does its own backend gating internally)
    compare_quality(line, prev, vp, regressed)
    compare_fleet(line, prev, vp, regressed)
    compare_serve(line, prev, vp, regressed)
    compare_serve_fleet(line, prev, vp, regressed)
    compare_dp_kernel(line, prev, vp, regressed)
    line["vs_prev"] = vp
    if regressed:
        line["regressed"] = regressed
        print("[bench] " + "!" * 20 + " REGRESSION vs " + str(artifact)
              + ": " + "; ".join(regressed) + " (>20% drop) "
              + "!" * 20, file=sys.stderr)
    return vp


def measure():
    import jax
    import numpy as np

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from ccsx_tpu.config import AlignParams
    from ccsx_tpu.consensus import star
    from ccsx_tpu.ops import msa, traceback
    import __graft_entry__ as ge

    params = AlignParams()
    projector = traceback.make_projector(W, 4)
    voter = msa.make_voter(4)
    # the production aligner dispatch: the v1 Pallas fill on a TPU at
    # qmax <= 4096, the vmapped lax.scan elsewhere
    # (star.banded_impl_effective); CCSX_BANDED_IMPL forces one for A/B
    aligner = star._aligner(params)

    def round_core(qs, qlens, ts, tlens, row_mask):
        Zb, Pb, qmax = qs.shape
        ts_b = jax.numpy.broadcast_to(ts[:, None, :], (Zb, Pb, ts.shape[-1]))
        tl_b = jax.numpy.broadcast_to(tlens[:, None], (Zb, Pb))
        _, moves, offs = aligner(
            qs.reshape(Zb * Pb, qmax), qlens.reshape(Zb * Pb),
            ts_b.reshape(Zb * Pb, -1), tl_b.reshape(Zb * Pb))
        moves = moves.reshape(Zb, Pb, qmax, -1)
        offs = offs.reshape(Zb, Pb, qmax)
        proj = jax.vmap(jax.vmap(projector, in_axes=(0, 0, 0, 0, None)),
                        in_axes=(0, 0, 0, 0, 0))
        aligned, ins_cnt, ins_b, _lead = proj(moves, offs, qs, qlens, tlens)
        cons, ins_base, ins_votes, ncov, match, nwin = jax.vmap(voter)(
            aligned, ins_cnt, ins_b, row_mask)
        return cons, ncov

    # Forced-execution marginal timing — the ONE method all benches
    # share (benchmarks/marginal_time.py: every timed loop ends in a
    # host fetch of a checksum, so the timing does not depend on
    # whether block_until_ready waits).  The trade: no cross-round
    # overlap is counted — a
    # round is itself a (Z*P)-problem batch, so the chip is already
    # saturated within one round.
    # APPEND, never insert(0): the benchmarks dir holds generically
    # named modules (e2e, quality, ...) that would otherwise shadow
    # same-named imports resolved later in this process
    sys.path.append(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    from marginal_time import marginal_time

    args = [jax.device_put(a) for a in
            ge._example_batch(Z=Z, P=P, W=W, tlen=TLEN)]
    # on an accelerator a round is sub-ms: raise the loop count so the
    # marginal (iters-1) x round signal clears the +-ms jitter of the
    # two checksum fetches (CPU rounds are ~0.5 s; ITERS=25 is plenty).
    # CCSX_BENCH_ITERS/WINDOWS resize the measurement
    iters = ITERS if jax.default_backend() == "cpu" else 200

    def env_int(name, default, lo):
        try:
            return max(int(os.environ.get(name, "") or default), lo)
        except ValueError:
            return default

    iters = env_int("CCSX_BENCH_ITERS", iters, 2)
    windows = env_int("CCSX_BENCH_WINDOWS", WINDOWS, 1)
    runs = marginal_time(round_core, *args, iters=iters,
                         repeats=windows, settle=0.2)
    return Z / min(runs)  # best window, ZMW-windows per second


def main():
    calibrate = "--calibrate" in sys.argv
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    if calibrate:
        # re-measure the native CPU yardstick and store the projections
        # (append, not insert(0) — see the note in measure())
        sys.path.append(os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
        import cpu_baseline

        b = cpu_baseline.build_baseline()
        with open(BASELINE_PATH, "w") as f:
            json.dump(b, f, indent=1)
        print(json.dumps({"calibrated": b}))
        return

    from ccsx_tpu.utils.device import resolve_device

    resolve_device("auto")
    value = measure()

    baseline = simd_factor = None
    cells_per_zw = P * W * 128  # fallback geometry
    if os.path.exists(BASELINE_PATH):
        with open(BASELINE_PATH) as f:
            b = json.load(f)
        baseline = b.get("zmw_windows_per_sec")
        simd_factor = b.get("simd_factor")
        if simd_factor is None:
            # old-schema artifact (r1-r4: guessed 8x credit, full-matrix
            # Gotoh baseline): its zmw_windows_per_sec is NOT the
            # measured vectorized fill this field now claims — refuse
            # the ratio until `python bench.py --calibrate` regenerates
            print("[bench] baseline artifact predates the measured-SIMD "
                  "schema; re-run `python bench.py --calibrate` "
                  "(vs_baseline suppressed)", file=sys.stderr)
            baseline = None
        # the unit conversion must match the baseline's, or the ratio
        # silently compares mismatched units; if the bench geometry has
        # drifted from the artifact, refuse the ratio until --calibrate
        stored = b.get("cells_per_zmw_window")
        if stored is not None and stored != cells_per_zw:
            print(f"[bench] geometry drift: baseline artifact has "
                  f"{stored} cells/zmw-window, bench shapes give "
                  f"{cells_per_zw}; re-run `python bench.py --calibrate` "
                  "(vs_baseline suppressed)", file=sys.stderr)
            baseline = None

    import jax

    backend = jax.default_backend()
    # Like-for-like baseline scope: the 64-core linear projection is the
    # yardstick for DEVICE runs only.  An XLA:CPU run on this host is a
    # 1-ish-core measurement — dividing it by a 64-core projection
    # reports a meaningless 0.001 that pollutes the trajectory (r5 TPU
    # hang -> CPU fallback did exactly that), so CPU runs compare
    # against the measured PER-CORE native fill instead.
    baseline_scope = None
    if baseline:
        if backend == "cpu":
            cores = b.get("projected_cores") or 64
            baseline = baseline / cores
            baseline_scope = "per_core_cpu"
        else:
            baseline_scope = "64core_projection"
    line = {
        "metric": "consensus round throughput "
                  f"(Z={Z} zmw x P={P} passes x W={W} window, "
                  f"backend={backend})",
        "backend": backend,
        "value": round(value, 3),
        "unit": "zmw_windows/s",
        # vs the MEASURED vectorized banded fill
        # (benchmarks/cpu_baseline.py) at the scope above;
        # baseline_simd_factor echoes the measured vec/scalar ratio
        "vs_baseline": round(value / baseline, 3) if baseline else None,
        "vs_baseline_scope": baseline_scope,
        "baseline_simd_factor": simd_factor,
        # one zmw-window = P x W x band DP cells (geometry taken from
        # the baseline artifact so the two sides can't diverge)
        "dp_cells_per_sec": round(value * cells_per_zw),
    }
    if backend == "cpu" and os.environ.get(
            "JAX_PLATFORMS", "").strip().lower() != "cpu":
        # an auto-resolved run that LANDED on CPU (JAX found no
        # accelerator): mark it so downstream trajectory parsing never
        # mistakes XLA:CPU throughput for a device regression
        line["degraded"] = ("no usable accelerator; CPU numbers at "
                            "per-core baseline scope")

    # e2e holes/sec over the five BASELINE configs (full CLI: ingest,
    # prep, consensus, write) on the same resolved backend, after the
    # round metric.
    # CCSX_BENCH_E2E=0 skips; CCSX_BENCH_E2E_HOLES resizes (default 16 —
    # the fused window refinement makes dispatch count ~independent of
    # the hole count, so more holes amortize the per-dispatch cost).
    if os.environ.get("CCSX_BENCH_E2E", "1") != "0":
        holes = int(os.environ.get("CCSX_BENCH_E2E_HOLES", "16"))
        # soft deadline: cold compiles can take minutes per config;
        # losing the whole JSON line to a driver timeout is worse than
        # skipping tail configs
        deadline = time.monotonic() + float(
            os.environ.get("CCSX_BENCH_DEADLINE", "420"))
        # append, not insert(0) — see the note in measure()
        sys.path.append(os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
        import e2e as e2e_mod

        # flight-recorder passthrough (utils/trace.py): CCSX_BENCH_TRACE
        # is a path prefix — each config's span JSONL + Chrome export
        # lands at <prefix>.c<N>.jsonl, and the per-shape-group
        # compile/execute table rides each e2e entry below, so the
        # bench artifact carries its own attribution evidence
        trace_prefix = os.environ.get("CCSX_BENCH_TRACE")
        # CCSX_BENCH_TELEMETRY=<port>: serve the live telemetry plane
        # during each e2e config, so a long battery is watchable with
        # `ccsx-tpu top host:<port>` instead of being a black box until
        # its JSON line lands (configs run sequentially, so one port
        # serves them all; the server auto-bumps if it is held)
        try:
            telemetry_port = int(
                os.environ.get("CCSX_BENCH_TELEMETRY", "0") or 0)
        except ValueError:
            telemetry_port = 0
        results = []
        for cfg in (1, 2, 3, 4, 5):
            if time.monotonic() > deadline:
                results.append({"config": cfg,
                                "skipped": "bench deadline exceeded"})
                continue
            try:
                r = e2e_mod.run_config(
                    cfg, holes, "auto",
                    trace_path=(f"{trace_prefix}.c{cfg}.jsonl"
                                if trace_prefix else None),
                    telemetry_port=telemetry_port)
                results.append({k: r.get(k) for k in (
                    "config", "backend", "holes_in", "holes_out",
                    "zmws_per_sec", "dp_row_fill",
                    "packed_holes_per_dispatch", "prep_share",
                    "prep_overlap_share", "groups", "degraded",
                    "traced", "mean_identity")})
            except Exception as exc:  # keep the primary metric alive
                results.append({"config": cfg, "error": repr(exc)[:200]})
        line["e2e"] = results

    # bench regression gate: self-compare against the most recent prior
    # BENCH_r*.json so the trajectory stops being write-only
    prev_art, prev = find_prev_bench()
    if prev is not None:
        compare_with_prev(line, prev, prev_art)
    else:
        vp = {"artifact": None,
              "note": "no prior BENCH_r*.json artifact; vs_baseline "
                      "reports the native yardstick"}
        regressed = []
        # the quality, fleet, serve, and dp-kernel gates still apply:
        # two artifacts can exist before any bench artifact does
        compare_quality(line, None, vp, regressed)
        compare_fleet(line, None, vp, regressed)
        compare_serve(line, None, vp, regressed)
        compare_serve_fleet(line, None, vp, regressed)
        compare_dp_kernel(line, None, vp, regressed)
        line["vs_prev"] = vp
        if regressed:
            line["regressed"] = regressed
            print("[bench] " + "!" * 20 + " ARTIFACT REGRESSION: "
                  + "; ".join(regressed) + " " + "!" * 20,
                  file=sys.stderr)

    print(json.dumps(line))


if __name__ == "__main__":
    main()
