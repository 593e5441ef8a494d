"""Compile-lean dispatch (r8): canonical slab shapes, the AOT warmup
precompiler, donated wire buffers, and the fused multi-chip packed
dispatch.

The conftest harness forces 8 virtual CPU devices, so every test here
exercises the REAL multi-chip code path (shard_map over the ('slab',)
mesh); the single-device contrasts pin byte-identity through the
``devices`` seam.  The compile-budget test at the bottom is the CI
regression guard for the r7 compile storm: the 64-hole scale config,
traced, must keep every packed group at or under its canonical-ladder
compile budget.
"""

import json
import threading
import time

import jax
import numpy as np
import pytest

from ccsx_tpu import cli
from ccsx_tpu.config import CcsConfig
from ccsx_tpu.pipeline import pack as pack_mod
from ccsx_tpu.pipeline.batch import BatchExecutor, PairExecutor
from ccsx_tpu.pipeline.warmup import WarmupCompiler
from ccsx_tpu.utils import faultinject, synth, trace
from ccsx_tpu.utils.metrics import Metrics

from test_packing import SPECS, _assert_refine_matches_host, _requests


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# ---- WarmupCompiler unit tier ---------------------------------------------


def test_warmup_compiler_runs_each_key_once():
    wc = WarmupCompiler()
    try:
        ran = []
        for _ in range(3):
            wc.submit("k", lambda: ran.append(1))
        assert wc.drain(timeout=10)
        assert ran == [1]
        # resubmitting a finished key is refused too
        assert not wc.submit("k", lambda: ran.append(1))
    finally:
        wc.close()


def test_warmup_compiler_claim_semantics():
    """queued -> cancelled (dispatch compiles inline); running -> wait
    Event; done/unknown -> None."""
    wc = WarmupCompiler()
    try:
        gate = threading.Event()
        started = threading.Event()
        ran = []

        def slow():
            started.set()
            gate.wait(10)

        wc.submit("slow", slow)
        started.wait(10)
        wc.submit("queued", lambda: ran.append(1))
        # 'queued' never started: claim cancels it
        assert wc.claim("queued") is None
        # 'slow' is mid-build: claim returns its completion event
        ev = wc.claim("slow")
        assert ev is not None and not ev.is_set()
        gate.set()
        assert ev.wait(10)
        assert wc.drain(timeout=10)
        assert ran == []            # the cancelled builder never ran
        assert wc.claim("slow") is None       # done
        assert wc.claim("never-submitted") is None
        # a cancelled key is RESUBMITTABLE (prediction refinement
        # cancels a height the dribble-tail warm re-wants later — a
        # permanent tombstone would drop that warm, r08 bug)
        assert wc.submit("queued", lambda: ran.append(2))
        assert wc.drain(timeout=10)
        assert ran == [2]
    finally:
        wc.close()


def test_warmup_build_failure_reraised_at_settle():
    """A builder that fails to compile is not swallowed: the dispatch
    that settles its key re-raises the build's own error."""
    wc = WarmupCompiler(debounce_s=0.0)
    try:
        def boom():
            raise RuntimeError("Mosaic failed to compile (injected)")

        wc.submit("bad", boom, urgent=True)
        wc.submit("good", lambda: None, urgent=True)
        assert wc.drain(timeout=10)
        with pytest.raises(RuntimeError, match="Mosaic"):
            wc.settle("bad")
        wc.settle("good")
        wc.settle("never-submitted")
    finally:
        wc.close()


def test_warmup_urgent_jumps_debouncing_queue():
    """An urgent (sweep-time exact) job must not wait behind a still-
    debouncing prediction at the FIFO head — its dispatch is imminent
    and would claim it back into an inline compile."""
    wc = WarmupCompiler(debounce_s=5.0, workers=1)
    try:
        ran = []
        wc.submit("pred", lambda: ran.append("pred"))
        wc.submit("exact", lambda: ran.append("exact"), urgent=True)
        t0 = time.monotonic()
        while "exact" not in ran and time.monotonic() - t0 < 3:
            time.sleep(0.02)
        assert ran == ["exact"]  # built while the prediction debounces
    finally:
        wc.close()


def test_warmup_compiler_builder_failure_contained(capsys):
    wc = WarmupCompiler()
    try:
        wc.submit("boom", lambda: 1 / 0)
        ok = []
        wc.submit("ok", lambda: ok.append(1))
        assert wc.drain(timeout=10)
        assert ok == [1]
    finally:
        wc.close()
    assert "warmup compile failed" in capsys.readouterr().err


# ---- fused multi-chip packed dispatch -------------------------------------


def test_fused_multichip_byte_identical_to_single_device(rng):
    """The tentpole acceptance pin: the 8-fake-device fused super-batch
    produces byte-identical results to a single-device run of the same
    requests (and both match the host refinement spec)."""
    cfg = CcsConfig(is_bam=False, slab_rows=16)
    sm, reqs = _requests(rng, cfg)
    ex_multi = BatchExecutor(cfg)
    assert ex_multi._slab_mesh is not None      # fused path active
    ex_single = BatchExecutor(cfg, devices=jax.local_devices()[:1])
    assert ex_single._slab_mesh is None
    rm = ex_multi.run(list(reqs))
    rs = ex_single.run(list(reqs))
    for req, a, b in zip(reqs, rm, rs):
        _assert_refine_matches_host(sm, cfg, req, a)
        np.testing.assert_array_equal(a.draft, b.draft)
        np.testing.assert_array_equal(a.rr.cons, b.rr.cons)
        np.testing.assert_array_equal(a.rr.advance, b.rr.advance)
        assert a.rr.tlen == b.rr.tlen and a.rr.bp == b.rr.bp


def test_fused_one_dispatch_one_compile_per_group_per_wave(rng):
    """The dispatch-count contract the r7 flight recorder demanded:
    with D=2 chips and a plan of 2 slabs per shape group, each group
    issues exactly ceil(slabs/D) fused dispatches (vs one per slab per
    chip under round-robin) and compiles exactly once."""
    cfg = CcsConfig(is_bam=False, slab_rows=16)
    _, reqs = _requests(rng, cfg)
    metrics = Metrics()
    tr = trace.Tracer(None, metrics=metrics)   # attribution only
    trace.install(tr)
    try:
        ex = BatchExecutor(cfg, metrics=metrics,
                           devices=jax.local_devices()[:2])
        ex.run(list(reqs))
    finally:
        trace.uninstall()
        tr.close()
    packed = {k: st for k, st in metrics.group_stats.items()
              if k.startswith("packed:")}
    assert packed, "no packed groups attributed"
    # SPECS pack into 2 slabs of one (qmax, tmax, iters) group: D=2
    # chips -> ONE wave -> one dispatch, one executable
    for key, st in packed.items():
        assert st["dispatches"] == 1, (key, st)
        assert st["compiles"] == 1, (key, st)
    assert metrics.fused_waves == len(packed)
    assert metrics.distinct_slab_shapes == len(packed)


def test_fused_oom_bisect_and_host_ladder(rng):
    """OOM recovery on the fused super-batch: a bisected wave re-plans
    its halves at the smaller covering canonical slab and stays
    bitwise; a persistent OOM rides the ladder down to per-hole host
    replay."""
    cfg = CcsConfig(is_bam=False, slab_rows=16)
    sm, reqs = _requests(rng, cfg)
    try:
        faultinject.arm("device_oom@1")
        m1 = Metrics()
        ex = BatchExecutor(cfg, metrics=m1,
                           devices=jax.local_devices()[:2])
        assert ex._slab_mesh is not None
        res = ex.run(list(reqs))
        assert m1.oom_resplits >= 1 and m1.host_fallbacks == 0
        for req, r in zip(reqs, res):
            _assert_refine_matches_host(sm, cfg, req, r)

        faultinject.arm("device_oom@1+")
        m2 = Metrics()
        res = BatchExecutor(cfg, metrics=m2,
                            devices=jax.local_devices()[:2]).run(
            list(reqs))
        assert m2.host_fallbacks >= 1
        for req, r in zip(reqs, res):
            _assert_refine_matches_host(sm, cfg, req, r)
    finally:
        faultinject.disarm()


# ---- AOT warmup through the executor --------------------------------------


def test_warmup_first_dispatch_books_execute(rng, tmp_path):
    """The overlap proof the tracer must show: after warm_refine +
    drain, every real refine_packed dispatch books as steady-state
    execute — the compile was paid by the warmup spans (warmup: true,
    compile: true), off the dispatch path."""
    cfg = CcsConfig(is_bam=False, slab_rows=16)
    _, reqs = _requests(rng, cfg)
    p = str(tmp_path / "t.jsonl")
    metrics = Metrics()
    tr = trace.Tracer(p, metrics=metrics)
    trace.install(tr)
    wc = WarmupCompiler()
    try:
        ex = BatchExecutor(cfg, metrics=metrics, warmup=wc)
        for req in reqs:
            ex.warm_refine(req)
        assert wc.drain(timeout=120)
        ex.run(list(reqs))
    finally:
        wc.close()
        trace.uninstall()
        tr.close()
    recs = [r for r in _read_jsonl(p) if r.get("ev") == "span"]
    warm = [r for r in recs if r.get("warmup")]
    disp = [r for r in recs if r["name"] == "refine_packed"]
    assert warm and disp
    assert all(r["compile"] is False for r in disp), \
        "a warmed shape's first dispatch must book as execute"
    assert any(r["compile"] for r in warm)
    packed = {k: st for k, st in metrics.group_stats.items()
              if k.startswith("packed:")}
    for key, st in packed.items():
        assert st["compiles"] >= 1
        assert st["dispatches"] >= 1
    # stats' summarize() applies the same warmup rule: the re-derived
    # table must agree with the live one on compiles and dispatches
    summ = trace.summarize([p])
    for key, st in packed.items():
        assert summ["groups"][key]["compiles"] == st["compiles"]
        assert summ["groups"][key]["dispatches"] == st["dispatches"]


def test_pair_executor_warm_api(rng):
    """PairExecutor.warm precompiles the padded pair-fill executables
    (benchmarks/prep_share.py's warmup path); a warmed run produces
    identical results."""
    from ccsx_tpu.config import AlignParams
    from ccsx_tpu.consensus import prepare as prep_mod

    pairs = []
    for _ in range(8):
        tpl = rng.integers(0, 4, 600).astype(np.uint8)
        q = synth.mutate(rng, tpl, 0.02, 0.02, 0.02)
        pairs.append(prep_mod.PairRequest(q, tpl, 75))
    cold = PairExecutor(AlignParams()).run(pairs)
    pe = PairExecutor(AlignParams())
    pe.warm(pairs)           # no compiler attached: warms inline
    warmed = pe.run(pairs)
    for (ok_a, a), (ok_b, b) in zip(cold, warmed):
        assert ok_a == ok_b and a.score == b.score and a.qb == b.qb


# ---- CLI plumbing ----------------------------------------------------------


@pytest.mark.slow  # ~15s warmup-on/off CLI A/B (r15 budget audit);
# tier-1 keeps the compile-budget guard (test_compile_budget_scale64)
# and the WarmupCompiler unit pins
def test_cli_no_warmup_and_ladder_flags(tmp_path, rng):
    """--no-warmup and --slab-shape-ladder reach the config, and a
    ladder-1 run (every slab full height) stays byte-identical — the
    canonical ladder is a tiling knob, never semantics."""
    args = cli.build_parser().parse_args(
        ["--no-warmup", "--slab-shape-ladder", "1", "in", "out"])
    cfg = cli.config_from_args(args)
    assert cfg.warmup_compile is False
    assert cfg.slab_shape_ladder == 1
    cfg_d = cli.config_from_args(
        cli.build_parser().parse_args(["in", "out"]))
    assert cfg_d.warmup_compile is True
    assert cfg_d.slab_shape_ladder == 2

    zs = [synth.make_zmw(rng, template_len=600, n_passes=5 + h,
                         movie="mv", hole=str(h)) for h in range(3)]
    fa = tmp_path / "in.fa"
    fa.write_text(synth.make_fasta(zs))
    outs = {}
    for tag, extra in (("default", []),
                       ("lean", ["--no-warmup", "--slab-shape-ladder",
                                 "1"])):
        o = tmp_path / f"{tag}.fa"
        assert cli.main(["-A", "-m", "1000", *extra, "--batch", "on",
                         str(fa), str(o)]) == 0
        outs[tag] = o.read_text()
    assert outs["default"] == outs["lean"]


def test_cli_bad_ladder_rejected(capsys):
    args = cli.build_parser().parse_args(
        ["--slab-shape-ladder", "0", "in", "out"])
    with pytest.raises(SystemExit):
        cli.config_from_args(args)
    assert "--slab-shape-ladder" in capsys.readouterr().err


# ---- stats warning ---------------------------------------------------------


def test_stats_compile_storm_warning():
    """`ccsx-tpu stats` renders the loud compiles>1 warning (the r7
    storm guard) and stays quiet on a clean table."""
    def summary(compiles):
        return {"paths": ["t.jsonl"], "n_spans": 1,
                "groups": {"packed:q512:t1024:i2": {
                    "compiles": compiles, "dispatches": 5,
                    "dp_cells": 10}},
                "stage_seconds": {}, "slowest": [], "occupancy": {},
                "stalls": [], "degraded": None}

    loud = trace.format_summary(summary(4))
    assert "compiles>1 in steady state" in loud
    assert "x4" in loud
    assert "compiles>1" not in trace.format_summary(summary(1))


# ---- bench.py satellite units ---------------------------------------------


def _bench_mod():
    import importlib
    import sys as _sys
    _sys.path.insert(0, "/root/repo")
    import bench
    return importlib.reload(bench)


def test_bench_vs_prev_group_compile_gate():
    """The regression gate flags a packed group whose compile count
    grows past both the prior artifact and the canonical-ladder budget
    of 2 — and stays quiet for in-budget variation."""
    bench = _bench_mod()

    def line_with(compiles):
        return {"backend": "cpu", "dp_cells_per_sec": 100,
                "e2e": [{"config": 1, "backend": "cpu", "holes_in": 4,
                         "zmws_per_sec": 1.0, "traced": False,
                         "groups": {"packed:q512:t1024:i2":
                                    {"compiles": compiles,
                                     "dispatches": 5}}}]}

    cur, prev = line_with(4), line_with(2)
    bench.compare_with_prev(cur, prev, "BENCH_rX.json")
    assert cur["vs_prev"]["group_compiles_max"]["1"] == {"prev": 2,
                                                         "cur": 4}
    assert any("compile storm" in r for r in cur.get("regressed", []))

    ok = line_with(2)
    bench.compare_with_prev(ok, line_with(1), "BENCH_rX.json")
    assert "regressed" not in ok


def test_bench_vs_prev_quality_gate():
    """The quality leg of vs_prev (ROADMAP item 5 tail): a >20% drop in
    gate_biased Q20 yield vs the prior bench line flags `regressed`
    exactly like a perf drop; in-tolerance drift stays quiet; and the
    current line always embeds the newest quality artifact's yields."""
    bench = _bench_mod()
    line = {"backend": "cpu"}
    vp, reg = {}, []
    bench.compare_quality(line, {"quality":
                                 {"gate_biased_q20_yield": 0.30}},
                          vp, reg)
    # the repo's committed artifact (0.14) is a >20% drop from 0.30
    assert line["quality"]["artifact"].startswith("quality_r")
    assert vp["gate_biased_q20_yield"]["prev"] == 0.30
    assert reg and "q20_yield" in reg[0]
    # drift within tolerance: quiet
    vp2, reg2 = {}, []
    cur_y = line["quality"]["gate_biased_q20_yield"]
    bench.compare_quality({"backend": "cpu"},
                          {"quality":
                           {"gate_biased_q20_yield": cur_y * 1.1}},
                          vp2, reg2)
    assert reg2 == []
    # and the full compare_with_prev path carries it end to end
    cur = {"backend": "cpu", "dp_cells_per_sec": 100, "e2e": []}
    prev = {"backend": "cpu", "dp_cells_per_sec": 100, "e2e": [],
            "quality": {"gate_biased_q20_yield": 0.30}}
    bench.compare_with_prev(cur, prev, "BENCH_rX.json")
    assert any("q20_yield" in r for r in cur.get("regressed", []))


def test_bench_vs_prev_dp_kernel_gate(monkeypatch):
    """The dp-kernel leg of vs_prev (the r14 promotion harness): every
    bench line embeds the newest pallas_ab decision record; a winner
    flip is informational, but the winning arm's round throughput
    dropping >20% on the SAME backend trips `regressed`; a backend
    change gates nothing."""
    bench = _bench_mod()
    rec = {"winner": "rotband", "margin": 1.18,
           "metric": "round_zmw_windows_per_sec",
           "round_rates": {"scan": 80000.0, "pallas": 90000.0,
                           "rotband": 100000.0},
           "backend": "tpu", "interpret": False}
    arts = [("pallas_ab_tpu_r07.json", dict(rec))]
    monkeypatch.setattr(bench, "latest_pallas_ab_artifacts",
                        lambda *a, **k: arts)
    # same backend, winner steady, rate up: embeds + stays quiet
    line, vp, reg = {}, {}, []
    prev = {"dp_kernel": {**rec, "artifact": "pallas_ab_tpu_r06.json",
                          "round_rates": {"rotband": 95000.0}}}
    bench.compare_dp_kernel(line, prev, vp, reg)
    assert line["dp_kernel"]["artifact"] == "pallas_ab_tpu_r07.json"
    assert vp["dp_kernel"]["cur_winner"] == "rotband"
    assert "winner_flipped" not in vp["dp_kernel"]
    assert reg == []
    # winning arm >20% slower on the same backend: tripped
    line, vp, reg = {}, {}, []
    prev_fast = {"dp_kernel": {**rec,
                               "round_rates": {"rotband": 130000.0}}}
    bench.compare_dp_kernel(line, prev_fast, vp, reg)
    assert any("dp-kernel" in r for r in reg)
    # winner flip: informational, not a regression by itself
    line, vp, reg = {}, {}, []
    prev_scan = {"dp_kernel": {**rec, "winner": "scan",
                               "round_rates": {"scan": 80000.0}}}
    bench.compare_dp_kernel(line, prev_scan, vp, reg)
    assert vp["dp_kernel"].get("winner_flipped") is True
    assert reg == []
    # different backend (cpu interpret record vs tpu): no rate gate
    line, vp, reg = {}, {}, []
    prev_cpu = {"dp_kernel": {**rec, "backend": "cpu",
                              "round_rates": {"rotband": 9e9}}}
    bench.compare_dp_kernel(line, prev_cpu, vp, reg)
    assert reg == []
    # no prev record anywhere but a second artifact: it is the baseline
    arts.append(("pallas_ab_tpu_r06.json",
                 {**rec, "round_rates": {"rotband": 130000.0}}))
    line, vp, reg = {}, {}, []
    bench.compare_dp_kernel(line, None, vp, reg)
    assert vp["dp_kernel"]["prev_source"] == "pallas_ab_tpu_r06.json"
    assert any("dp-kernel" in r for r in reg)


# ---- CI compile-budget guard (the r7 storm, pinned) ------------------------


def test_compile_budget_scale64(tmp_path, rng):
    """The tier-1 regression guard for the r7 compile storm: the
    64-hole scale config (mixed lognormal-ish pass counts, mixed
    lengths), run traced through the full CLI, must keep EVERY packed
    refine group at or under its canonical-ladder compile budget
    (ladder=2, +1 for an oversize pow2 slab — r7 measured 4-5 here),
    and in aggregate must average ~one compile per group."""
    counts = np.clip(np.round(rng.lognormal(np.log(8), 0.45, 64)),
                     5, 20).astype(int)
    tlens = rng.integers(300, 900, 64)
    zs = [synth.make_zmw(rng, int(tlens[h]), int(counts[h]), movie="mv",
                         hole=str(h)) for h in range(64)]
    fa = tmp_path / "in.fa"
    fa.write_text(synth.make_fasta(zs))
    out, m = tmp_path / "o.fa", tmp_path / "m.jsonl"
    t = tmp_path / "t.jsonl"
    assert cli.main(["-A", "-m", "1000", "--batch", "on", "--inflight",
                     "64", "--metrics", str(m), "--trace", str(t),
                     str(fa), str(out)]) == 0
    final = _read_jsonl(m)[-1]
    assert final["event"] == "final"
    packed = {k: st for k, st in final["groups"].items()
              if k.startswith("packed:")}
    assert packed, "scale config produced no packed groups"
    budget = CcsConfig().slab_shape_ladder + 1
    over = {k: st["compiles"] for k, st in packed.items()
            if st["compiles"] > budget}
    assert not over, (
        f"COMPILE STORM: packed groups exceeded their compile budget "
        f"of {budget}: {over} (r7 paid 4-5 per group; canonical slab "
        f"shapes must hold the line)")
    # aggregate bound: one compile per canonical height per group (the
    # warmup thread may precompile a group's dribble-tail height that a
    # short run never dispatches — overlapped, never on the dispatch
    # path); r7's storm averaged 4-5 per group
    total_c = sum(st["compiles"] for st in packed.values())
    ladder = CcsConfig().slab_shape_ladder
    assert total_c <= ladder * len(packed), (
        f"more XLA programs than canonical heights: "
        f"{total_c}/{len(packed)} groups (ladder {ladder})")
    assert final["distinct_slab_shapes"] is not None
    assert final.get("degraded") is None
    assert out.read_text().count(">mv/") >= 60
