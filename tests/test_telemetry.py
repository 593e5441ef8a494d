"""Live telemetry plane (utils/telemetry.py + report.py): endpoint
scrape during a real run, /healthz degradation under an injected stall,
`top` multi-rank aggregation, the HTML report's golden structure on the
committed r8 artifacts, the schema-drift guard, and the satellite
behaviors (filter counts, resource gauges, watchdog rate-limiting).
"""

import io
import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from ccsx_tpu import cli
from ccsx_tpu.utils import faultinject, synth, telemetry, trace
from ccsx_tpu.utils import report as report_mod
from ccsx_tpu.utils.metrics import (HIST_BUCKETS, Metrics, hist_quantile,
                                    merge_hist, resource_gauges, size_class)

BENCH_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmarks")
R8_TRACE = os.path.join(BENCH_DIR, "trace_r08_scale64.jsonl")
R8_METRICS = os.path.join(BENCH_DIR, "metrics_r08_scale64.jsonl")


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faultinject.disarm()


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _write_fasta(tmp_path, rng, n_holes=3, tlen=700, n_passes=5):
    zs = [synth.make_zmw(rng, template_len=tlen, n_passes=n_passes,
                         movie="mv", hole=str(h)) for h in range(n_holes)]
    fa = tmp_path / "in.fa"
    fa.write_text(synth.make_fasta(zs))
    return zs, fa


class _Buf(io.StringIO):
    """A StringIO Metrics.report() can 'close' while the test still
    reads it afterwards."""

    def close(self):
        pass


def _get(port, path, timeout=1.0):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        # 503 (degraded healthz) still carries a JSON body
        return e.code, e.read().decode()


# ---- endpoint server over a live run ---------------------------------------


@pytest.mark.slow  # ~11s: full-CLI on/off A/B scrape (r20 budget
# audit); the endpoint unit tests here and the live-HTTP pins in
# test_serve.py (liveness/readiness against a running core) keep the
# serving surface tier-1
def test_endpoint_scrape_during_real_run(tmp_path, rng):
    """The acceptance path: /progress + /metrics + /healthz answer
    during a real batched CPU run, counters are monotone across
    scrapes, and the OUTPUT IS BYTE-IDENTICAL with telemetry on vs
    off."""
    _, fa = _write_fasta(tmp_path, rng, n_holes=4)
    out_on = str(tmp_path / "on.fa")
    out_off = str(tmp_path / "off.fa")
    port = _free_port()
    res = {}

    def run():
        res["rc"] = cli.main(["-A", "-m", "1000", "--batch", "on",
                              "--telemetry-port", str(port),
                              str(fa), out_on])

    t = threading.Thread(target=run)
    t.start()
    scrapes, prom, health = [], None, None
    while t.is_alive():
        try:
            _, body = _get(port, "/progress", timeout=0.5)
            scrapes.append(json.loads(body))
            _, prom = _get(port, "/metrics", timeout=0.5)
            code, hbody = _get(port, "/healthz", timeout=0.5)
            health = (code, json.loads(hbody))
        except (urllib.error.URLError, OSError, ValueError):
            pass
        time.sleep(0.02)
    t.join()
    assert res["rc"] == 0
    assert scrapes, "run finished before a single scrape landed"
    # counters monotone across scrapes
    for key in ("holes_in", "holes_out", "windows", "device_dispatches"):
        seq = [s[key] for s in scrapes]
        assert seq == sorted(seq), (key, seq)
    assert all("progress" in s for s in scrapes)
    assert scrapes[-1]["status"] == "ok"
    # healthy run: /healthz said ok with the rc-relevant detail
    assert health is not None
    assert health[0] == 200 and health[1]["status"] == "ok"
    assert set(telemetry.HEALTH_DETAIL_KEYS) == set(health[1]["detail"])
    # prometheus text carries the north-star counters
    assert prom is not None
    assert "ccsx_holes_out " in prom or "ccsx_holes_out{" in prom
    assert "# TYPE ccsx_holes_out counter" in prom
    # the server is down after the run
    with pytest.raises((urllib.error.URLError, OSError)):
        _get(port, "/healthz", timeout=0.5)
    # byte-identity: same input without telemetry
    assert cli.main(["-A", "-m", "1000", "--batch", "on",
                     str(fa), out_off]) == 0
    assert open(out_on, "rb").read() == open(out_off, "rb").read()


def test_healthz_flips_degraded_under_injected_stall(tmp_path, rng,
                                                     monkeypatch,
                                                     capsys):
    """/healthz must flip to degraded (HTTP 503) WHILE the stalled
    dispatch is still open — within one watchdog interval — and the
    run must still complete (degraded, never killed)."""
    monkeypatch.setenv("CCSX_FAULT_STALL_S", "4.5")
    _, fa = _write_fasta(tmp_path, rng)
    port = _free_port()
    res = {}

    def run():
        res["rc"] = cli.main(
            ["-A", "-m", "1000", "--batch", "on",
             "--stall-timeout", "0.2", "--inject-faults", "stall@1",
             "--telemetry-port", str(port),
             "--metrics", str(tmp_path / "m.jsonl"),
             str(fa), str(tmp_path / "o.fa")])

    t = threading.Thread(target=run)
    t.start()
    flipped_at = None
    t0 = time.monotonic()
    while t.is_alive() and time.monotonic() - t0 < 30:
        try:
            code, body = _get(port, "/healthz", timeout=0.5)
        except (urllib.error.URLError, OSError):
            time.sleep(0.05)
            continue
        h = json.loads(body)
        if h["status"] == "degraded":
            flipped_at = time.monotonic() - t0
            assert code == 503
            assert h["detail"]["stalls"] >= 1
            break
        time.sleep(0.05)
    t.join()
    assert res["rc"] == 0                    # degraded, never killed
    assert flipped_at is not None, "/healthz never reported degraded"
    events = [json.loads(ln)
              for ln in open(tmp_path / "m.jsonl") if ln.strip()]
    assert events[-1]["event"] == "final"
    assert events[-1]["degraded"].startswith("stall watchdog")


def test_port_auto_bump_when_taken():
    port = _free_port()
    blocker = socket.socket()
    blocker.bind(("0.0.0.0", port))
    blocker.listen(1)
    try:
        m = Metrics()
        srv = telemetry.TelemetryServer(m, port, host="127.0.0.1")
        try:
            assert port < srv.port < port + telemetry.PORT_TRIES
            code, body = _get(srv.port, "/progress")
            assert code == 200 and json.loads(body)["holes_out"] == 0
        finally:
            srv.close()
    finally:
        blocker.close()


# ---- `top` aggregation -----------------------------------------------------


def _mk_metrics(holes_out, total=None, degraded=None):
    m = Metrics()
    m.holes_in = m.holes_out = holes_out
    m._ticked = holes_out
    m.windows = holes_out * 3
    m.device_dispatches = holes_out * 2
    m.holes_total = total
    m.degraded = degraded
    m._rate_ring.extend([(0.0, 0), (10.0, holes_out)])
    return m


def test_top_aggregates_two_rank_endpoints(capsys):
    """The acceptance aggregate: two per-rank endpoints sum their
    counters, progress is the MIN rank pct, and one degraded rank
    degrades the whole."""
    m0 = _mk_metrics(60, total=100)
    m1 = _mk_metrics(30, total=100, degraded="stall watchdog fired: x")
    # per-rank latency histograms: `top` must merge them by SUMMING
    # per-`le` bucket counts (quantiles do not compose)
    for v in (0.2, 0.2, 0.4):
        m0.observe("queue_wait_s", v, "small")
    for v in (0.9, 0.9, 0.9):
        m1.observe("queue_wait_s", v, "small")
    s0 = telemetry.TelemetryServer(m0, _free_port(), host="127.0.0.1")
    s1 = telemetry.TelemetryServer(m1, _free_port(), host="127.0.0.1")
    try:
        srcs = [telemetry.read_source(f"127.0.0.1:{s0.port}"),
                telemetry.read_source(f"127.0.0.1:{s1.port}")]
        agg = telemetry.aggregate(srcs)
        assert agg["holes_out"] == 90          # summed
        assert agg["windows"] == 270
        assert agg["pct"] == 30.0              # min rank progress
        assert agg["total"] == 200
        assert agg["any_degraded"] is True
        assert srcs[1]["status"] == "degraded"
        # summed buckets: 6 observations total, and the fleet p50 is
        # computed from the MERGED distribution (0.5 — the bucket where
        # the combined cumulative count crosses 3), not from averaging
        # the two per-rank medians
        merged = agg["hist"]["queue_wait_s"]["small"]
        assert merged["count"] == 6
        assert agg["queue_wait_p50"] == 0.5
        # the rendered frame carries the aggregate + the degraded mark
        rc = cli.main(["top", "--once", "--no-color",
                       f"127.0.0.1:{s0.port}", f"127.0.0.1:{s1.port}"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "DEGRADED" in out
        assert "out 90" in out
        assert "stall watchdog fired: x" in out
        assert "latency:" in out               # fleet quantile headline
        assert "qw50/95" in out                # per-source columns
    finally:
        s0.close()
        s1.close()


def test_top_unreachable_endpoint_degrades_aggregate():
    port = _free_port()   # nothing listening
    src = telemetry.read_source(f"127.0.0.1:{port}", timeout=0.3)
    assert src["status"] == "unreachable"
    agg = telemetry.aggregate([src])
    assert agg["any_degraded"] is True and agg["live"] == 0


def test_top_tails_metrics_jsonl(tmp_path, capsys):
    """Endpoint-less mode: `top` renders from the last event of a
    --metrics JSONL file."""
    buf = io.StringIO()
    m = _mk_metrics(7, total=10)
    m.stream = buf
    m.emit("progress")
    p = tmp_path / "m.jsonl"
    p.write_text(buf.getvalue() + "not json\n")   # torn tail tolerated
    src = telemetry.read_source(str(p))
    assert src["status"] == "ok" and src["snap"]["holes_out"] == 7
    assert cli.main(["top", "--once", "--no-color", str(p)]) == 0
    assert "out 7" in capsys.readouterr().out


def test_top_finished_run_from_final_event(tmp_path):
    buf = _Buf()
    m = _mk_metrics(5)
    m.stream = buf
    m.report()
    p = tmp_path / "m.jsonl"
    p.write_text(buf.getvalue())
    src = telemetry.read_source(str(p))
    assert src["status"] == "finished"
    agg = telemetry.aggregate([src])
    assert agg["finished"] is True


# ---- `report` --------------------------------------------------------------


def test_report_golden_structure_on_r8_artifacts(tmp_path, capsys):
    """The committed r8 scale-64 artifacts render into a report whose
    structure carries every section the ISSUE names."""
    out = str(tmp_path / "r8.html")
    rc = cli.main(["report", R8_TRACE, R8_METRICS, "-o", out])
    assert rc == 0
    page = open(out, encoding="utf-8").read()
    assert page.startswith("<!DOCTYPE html>")
    # sections
    for section in ("Timeline", "Stage self-time breakdown",
                    "Shape-group compile/dispatch table",
                    "Occupancy &amp; fill", "Progress: ETA vs actual",
                    "Stall &amp; recovery log"):
        assert section in page, section
    assert "<svg" in page                       # timeline strip rendered
    assert "packed:" in page                    # r8's packed groups
    assert "healthy run" in page                # r8 ran clean
    # r8 predates the progress estimator: the ETA section must degrade
    # gracefully, not lie
    assert "no ETA samples" in page
    # self-contained: no external fetches of any kind
    assert "http://" not in page and "https://" not in page
    assert "<script" not in page


def test_report_renders_progress_and_stalls(tmp_path):
    """A metrics stream WITH progress events and a stall renders the
    ETA curve and the incident log."""
    buf = _Buf()
    m = _mk_metrics(50, total=100)
    m.t0 = time.monotonic() - 20.0    # a deterministic nonzero elapsed
    m.stream = buf
    m.emit("progress")
    m.degraded = "stall watchdog fired: dispatch x"
    m.stalls = 1
    m.emit("stall", span="refine_packed", group="packed:q1", open_s=9.9)
    m.report()
    mp = tmp_path / "m.jsonl"
    mp.write_text(buf.getvalue())
    out = str(tmp_path / "r.html")
    assert cli.main(["report", str(mp), "-o", out]) == 0
    page = open(out, encoding="utf-8").read()
    assert "DEGRADED" in page
    assert "predicted remaining" in page        # ETA curve rendered
    assert "ETA samples" in page


def test_report_default_out_path():
    assert (report_mod.default_out_path("x/t.jsonl")
            == "x/t.report.html")


def test_collect_fleet_tolerates_torn_records(tmp_path):
    """A cid whose every span record is malformed (a torn JSONL line
    missing 'dur' — exactly what a killed replica leaves behind) must
    be dropped, not crash the alignment with an empty span list; good
    jobs in the same dir still stitch."""
    d = tmp_path / "spool"
    d.mkdir()
    good = {"ev": "span", "name": "refine", "cat": "device",
            "ts": 100.0, "dur": 0.5, "tid": "T", "cid": "cgood"}
    torn = {"ev": "span", "name": "refine", "cat": "device",
            "ts": 101.0, "tid": "T", "cid": "ctorn"}   # no 'dur'
    (d / "a.jsonl").write_text(
        json.dumps(good) + "\n" + json.dumps(torn) + "\n")
    data = report_mod.collect_fleet(str(d))
    assert set(data["jobs"]) == {"cgood"}
    assert data["jobs"]["cgood"]["t_end"] == 0.5


# ---- schema-drift guard ----------------------------------------------------


def _populated_snapshot():
    """A Metrics snapshot with every optional field forced present, so
    key-set comparisons see the full schema."""
    m = Metrics()
    for f in ("holes_in", "holes_out", "holes_failed", "holes_filtered",
              "stalls", "windows", "pair_alignments",
              "pairs_screened", "pairs_prefiltered",
              "pairs_seeded_device", "pairs_seeded_host",
              "device_dispatches", "refine_overflows", "oom_resplits",
              "host_fallbacks", "compile_fallbacks", "dp_cells_real",
              "dp_cells_padded", "dp_round_cells_real",
              "dp_round_cells_padded", "dp_rowcells_real",
              "dp_rowcells_cap", "dp_rows_real", "dp_rows_dispatched",
              "packed_dispatches", "packed_holes",
              "distinct_slab_shapes", "fused_waves",
              "fused_slabs_real", "fused_slots", "ingest_bytes",
              "device_hangs", "breaker_trips", "breaker_probes",
              "holes_corrupt"):
        setattr(m, f, 7)
    m.filtered_reasons["few_passes"] = 7
    m.corrupt_reasons["bgzf_bad_deflate"] = 7
    m.banded_dispatches["scan"] = 7
    m.holes_total = 100
    m.degraded = "x"
    m.breaker_state = "open"
    m.breaker_strike_log = [{"ts": 1.0, "kind": "hang", "group": "g"}]
    m.group_stats["g"] = {"compiles": 1, "dispatches": 3,
                          "dp_cells": 40}
    m.job = "j0007"
    m.cid = "cfeedfacecafe"
    # one observation into EVERY latency family, so the key-set guards
    # and the exposition test cover the full histogram contract
    m.observe("queue_wait_s", 0.3, "small")
    m.observe("job_wall_s", 70.0, "large")
    m.observe("first_dispatch_s", 0.1, "small")
    m.observe("lease_acquire_s", 0.001, "job")
    return m.snapshot()


def test_schema_guard_every_consumed_key_exists():
    """Every counter name consumed by stats, top, and report exists in
    Metrics.snapshot() — a rename cannot silently zero a column."""
    snap = _populated_snapshot()
    for name, keys in [
            ("prometheus counters", telemetry.PROM_COUNTERS),
            ("prometheus gauges", telemetry.PROM_GAUGES),
            ("top sum keys", telemetry.TOP_SUM_KEYS),
            ("healthz detail", telemetry.HEALTH_DETAIL_KEYS),
            ("stats occupancy", trace.OCCUPANCY_KEYS),
            ("stats resilience", trace.RESILIENCE_KEYS),
            ("report tiles", report_mod.REPORT_TILE_KEYS),
            ("report header", report_mod.REPORT_HEADER_KEYS)]:
        missing = set(keys) - set(snap)
        assert not missing, f"{name} consume unknown keys: {missing}"
    # the progress sub-schema (total known -> pct/eta_s present)
    assert set(telemetry.PROGRESS_KEYS) == set(snap["progress"])
    # the per-group sub-schema (the ONE shared finalizer's output)
    assert set(telemetry.GROUP_FIELDS) == set(snap["groups"]["g"])


def test_schema_guard_every_snapshot_key_documented():
    """...and vice versa: every key snapshot() can emit is exported by
    /metrics (or explicitly structured) — a NEW counter cannot be
    invisible to the dashboard by accident."""
    snap = _populated_snapshot()
    documented = (set(telemetry.PROM_COUNTERS)
                  | set(telemetry.PROM_GAUGES)
                  | set(telemetry.PROM_STRUCTURED))
    undocumented = set(snap) - documented
    assert not undocumented, (
        f"snapshot keys invisible to the telemetry plane: "
        f"{undocumented} — add them to PROM_COUNTERS/PROM_GAUGES (or "
        f"PROM_STRUCTURED with a renderer) in utils/telemetry.py")


def test_prometheus_render_wellformed():
    snap = _populated_snapshot()
    # a second group + a second filter reason: labeled families must
    # still emit exactly ONE TYPE line per metric name (strict
    # exposition-format parsers reject duplicate TYPE lines)
    snap["groups"]["h"] = dict(snap["groups"]["g"])
    snap["filtered_reasons"]["too_short"] = 3
    text = telemetry.render_prometheus(snap, resource_gauges())
    assert text.endswith("\n")
    type_lines = []
    for line in text.splitlines():
        if line.startswith("#"):
            assert line.startswith("# TYPE ccsx_")
            type_lines.append(line)
            continue
        name, _, value = line.rpartition(" ")
        assert name.startswith("ccsx_")
        float(value)                      # every sample parses
    assert len(type_lines) == len(set(type_lines))
    assert 'ccsx_group_dispatches{group="g"} 3' in text
    assert 'ccsx_group_dispatches{group="h"} 3' in text
    assert "ccsx_degraded 1" in text
    assert "ccsx_peak_rss_bytes" in text
    assert "ccsx_progress_pct" in text


# ---- latency histograms + SLO burn gauges ----------------------------------


def test_hist_schema_guard_both_directions():
    """HIST_FAMILIES <-> snapshot, both ways: a family renamed in
    Metrics cannot silently vanish from /metrics, and a new snapshot
    family cannot ship unrendered.  The SLO gauges must also reference
    real families and EXACT bucket bounds (the burn fraction is read
    off a cumulative bucket, never interpolated)."""
    snap = _populated_snapshot()
    fams = {f for f, _, _ in telemetry.HIST_FAMILIES}
    assert fams == set(snap["hist"]), (
        "histogram families drifted between Metrics.observe call sites "
        "and telemetry.HIST_FAMILIES")
    for _gauge, fam, threshold, objective in telemetry.SLO_BURN_GAUGES:
        assert fam in fams
        assert threshold in HIST_BUCKETS
        assert 0 < objective < 1


def test_prometheus_histogram_exposition_wellformed():
    """Every family renders the exposition shape promtool and
    histogram_quantile() expect: cumulative nondecreasing `le` buckets
    over the shared ladder, a +Inf bucket equal to _count, and _sum —
    all under the family's declared label key."""
    snap = _populated_snapshot()
    text = telemetry.render_prometheus(snap, resource_gauges())
    samples = {}
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        samples[name] = float(value)
    labels = {"queue_wait_s": "small", "job_wall_s": "large",
              "first_dispatch_s": "small",
              "lease_acquire_s": "job"}
    for fam, label_key, prom in telemetry.HIST_FAMILIES:
        assert f"# TYPE ccsx_{prom} histogram" in text, prom
        base = f'{label_key}="{labels[fam]}"'
        cum = [samples[f'ccsx_{prom}_bucket{{{base},le="{format(b, "g")}"}}']
               for b in HIST_BUCKETS]
        inf = samples[f'ccsx_{prom}_bucket{{{base},le="+Inf"}}']
        cum.append(inf)
        assert cum == sorted(cum), f"{prom}: buckets not cumulative"
        assert inf == samples[f"ccsx_{prom}_count{{{base}}}"] == 1
        assert f"ccsx_{prom}_sum{{{base}}}" in samples


def test_slo_burn_gauge_math():
    """burn = (fraction over threshold) / (1 - objective): 19 waits
    under the 1s queue-wait threshold + 1 over, at a 95% objective, is
    exactly burn 1.0 (spending the error budget at the sustainable
    rate).  A family with NO observations emits nothing — an idle
    fleet has no burn, not a fake 0."""
    m = Metrics()
    for _ in range(19):
        m.observe("queue_wait_s", 0.5, "small")
    m.observe("queue_wait_s", 70.0, "small")
    text = "\n".join(telemetry.slo_burn_lines(m.hist_snapshot()))
    assert "ccsx_slo_queue_wait_burn 1.0" in text
    assert "slo_job_wall_burn" not in text
    assert telemetry.slo_burn_lines({}) == []


def test_hist_merge_and_quantile_math():
    """merge_hist sums per-`le` counts elementwise; hist_quantile
    interpolates inside the crossing bucket (Prometheus-style) and
    answers the top bound for +Inf-landing targets."""
    a, b = Metrics(), Metrics()
    for v in (0.2, 0.2, 0.4):
        a.observe("queue_wait_s", v, "small")
    for v in (0.9, 0.9, 0.9):
        b.observe("queue_wait_s", v, "small")
    sa = a.hist_snapshot()["queue_wait_s"]["small"]
    sb = b.hist_snapshot()["queue_wait_s"]["small"]
    m = merge_hist([sa, sb])
    assert m["count"] == 6
    assert m["counts"] == [x + y for x, y in zip(sa["counts"],
                                                 sb["counts"])]
    assert hist_quantile(m, 0.5) == 0.5
    # torn/foreign snapshots are skipped, not fatal
    assert merge_hist([sa, None, {"counts": [1]}, "x"])["count"] == 3
    assert hist_quantile({"counts": [], "count": 0}, 0.5) is None
    # everything past the ladder top: the top bound is the honest p99
    top = Metrics()
    top.observe("job_wall_s", 9999.0, "large")
    s = top.hist_snapshot()["job_wall_s"]["large"]
    assert hist_quantile(s, 0.99) == HIST_BUCKETS[-1]


def test_size_class_bands():
    assert size_class(None) == "unknown"
    assert size_class(0) == "unknown"
    assert size_class(16) == "small"
    assert size_class(17) == "medium"
    assert size_class(256) == "medium"
    assert size_class(257) == "large"


def test_merge_hists_folds_job_snapshot_into_core():
    """serve's _finish path: a finished job's hist snapshot folds into
    the server-lifetime Metrics by summed buckets."""
    core, job = Metrics(), Metrics()
    core.observe("first_dispatch_s", 0.1, "small")
    job.observe("first_dispatch_s", 0.2, "small")
    job.observe("lease_acquire_s", 0.05, "job")
    core.merge_hists(job.hist_snapshot())
    snap = core.hist_snapshot()
    assert snap["first_dispatch_s"]["small"]["count"] == 2
    assert snap["lease_acquire_s"]["job"]["count"] == 1
    core.merge_hists({"first_dispatch_s": {"small": {"bad": 1}},
                      "junk": "x"})     # malformed entries are skipped
    assert core.hist_snapshot()["first_dispatch_s"]["small"]["count"] == 2


def test_port_range_clamped_at_65535():
    """A rank-offset base near the top of the port space degrades
    (OSError start() turns into a warning) instead of crashing the
    run with an uncaught OverflowError from socket."""
    m = Metrics()
    with pytest.raises(OSError):
        telemetry.TelemetryServer(m, 65536)
    assert telemetry.start(m, 70000) is None    # warns, never raises


def test_top_finished_degraded_headline(tmp_path, capsys):
    """A run that FINISHED with a tripped watchdog must not headline
    green: degraded outranks finished."""
    buf = _Buf()
    m = _mk_metrics(5, total=5, degraded="stall watchdog fired: x")
    m.stream = buf
    m.report()
    p = tmp_path / "m.jsonl"
    p.write_text(buf.getvalue())
    assert cli.main(["top", "--once", "--no-color", str(p)]) == 0
    out = capsys.readouterr().out
    assert "FINISHED DEGRADED" in out


# ---- progress/ETA estimator ------------------------------------------------


def test_progress_eta_estimator_math():
    m = Metrics()
    m._ticked = 50
    m.holes_total = 100
    # ring: 40 holes over the last 10 s -> 4.0/s windowed rate
    m._rate_ring.extend([(100.0, 10), (110.0, 50)])
    p = m.progress_snapshot()
    assert p["done"] == 50 and p["total"] == 100
    assert p["rate_zmws_per_sec"] == 4.0
    assert p["pct"] == 50.0
    assert p["eta_s"] == 12.5             # 50 remaining / 4 per sec


def test_progress_unknown_total_rate_only():
    m = Metrics()
    m._ticked = 5
    p = m.progress_snapshot()
    assert p["total"] is None
    assert "pct" not in p and "eta_s" not in p
    assert p["rate_zmws_per_sec"] >= 0


def test_periodic_interval_emission():
    buf = io.StringIO()
    m = Metrics(stream=buf, progress_every=0, progress_interval_s=0.05)
    m._last_interval_emit = time.monotonic() - 1.0   # overdue
    m.holes_in = m.holes_out = 1
    m.tick()
    events = [json.loads(ln) for ln in buf.getvalue().splitlines()]
    assert [e["event"] for e in events] == ["progress"]
    assert events[0]["progress"]["done"] == 1


# ---- satellite: filter counts (both ingest paths) --------------------------


def test_filter_counts_surface_in_metrics(tmp_path, rng):
    """A run whose input contains sub-threshold holes reports them in
    holes_filtered + reason buckets — on whichever ingest path the
    driver picked (native in-library counts at EOF, or the pure-Python
    per-hole path)."""
    zs = [synth.make_zmw(rng, template_len=700, n_passes=5, movie="mv",
                         hole=str(h)) for h in range(3)]
    # 2 holes with too few passes (min_pass_count = 3+2)
    zs += [synth.make_zmw(rng, template_len=700, n_passes=3, movie="mv",
                          hole=str(10 + h)) for h in range(2)]
    fa = tmp_path / "in.fa"
    fa.write_text(synth.make_fasta(zs))
    mpath = tmp_path / "m.jsonl"
    assert cli.main(["-A", "-m", "1000", "--batch", "on",
                     "--metrics", str(mpath),
                     str(fa), str(tmp_path / "o.fa")]) == 0
    fin = [json.loads(ln) for ln in open(mpath) if ln.strip()][-1]
    assert fin["event"] == "final"
    assert fin["holes_out"] == 3
    assert fin["holes_filtered"] == 2
    assert fin["filtered_reasons"] == {"few_passes": 2}


def test_native_streamer_reports_filter_counts(tmp_path, rng):
    """The native C++ streamer's in-library filter counts reach
    Metrics (the r7 span-table blind spot)."""
    from ccsx_tpu import native

    if not native.available():
        pytest.skip("native IO library unavailable")
    from ccsx_tpu.config import CcsConfig
    from ccsx_tpu.native.io import stream_zmws_native

    zs = [synth.make_zmw(rng, template_len=700, n_passes=5, movie="mv",
                         hole="keep")]
    zs += [synth.make_zmw(rng, template_len=700, n_passes=2, movie="mv",
                          hole=f"few{h}") for h in range(3)]
    zs += [synth.make_zmw(rng, template_len=100, n_passes=6, movie="mv",
                          hole="short")]
    fa = tmp_path / "in.fa"
    fa.write_text(synth.make_fasta(zs))
    cfg = CcsConfig(is_bam=False, min_subread_len=1000)
    m = Metrics()
    out = list(stream_zmws_native(str(fa), cfg, metrics=m))
    assert [z.hole for z in out] == ["keep"]
    assert m.holes_filtered == 4
    assert m.filtered_reasons == {"few_passes": 3, "too_short": 1}


# ---- satellite: resource gauges -------------------------------------------


def test_resource_gauges_on_final():
    g = resource_gauges()
    assert set(g) == {"peak_rss_bytes", "device_buffer_bytes"}
    assert g["peak_rss_bytes"] > 0        # Linux: ru_maxrss available
    buf = _Buf()
    m = Metrics(stream=buf)
    m.report()
    fin = json.loads(buf.getvalue().splitlines()[-1])
    assert fin["event"] == "final"
    assert fin["peak_rss_bytes"] > 0
    assert "device_buffer_bytes" in fin


# ---- satellite: watchdog dump rate limiting --------------------------------


def test_stall_dumps_rate_limited(tmp_path, capsys):
    """One FULL stack dump, then compact one-line repeats — a long
    hang stalling span after span cannot flood stderr/trace/metrics
    with megabytes of identical stacks."""
    buf = io.StringIO()
    m = Metrics(stream=buf)
    p = str(tmp_path / "t.jsonl")
    tr = trace.Tracer(p, stall_timeout=0.1, metrics=m)
    with tr.device_span("refine", group="g", shape="A"):
        pass                               # consume compile grace
    for _ in range(3):
        with tr.device_span("refine", group="g", shape="A"):
            time.sleep(0.5)
    tr.close()
    err = capsys.readouterr().err
    assert err.count("dumping state") == 1          # ONE full dump
    assert err.count('File "') >= 1
    assert err.count("compact repeat") == 2
    assert m.stalls == 3
    stalls = [json.loads(ln) for ln in open(p) if ln.strip()]
    stalls = [r for r in stalls if r.get("ev") == "stall"]
    assert len(stalls) == 3
    assert "stacks" in stalls[0]
    assert all("stacks" not in r and r.get("repeat")
               for r in stalls[1:])
    events = [json.loads(ln) for ln in buf.getvalue().splitlines()]
    stall_events = [e for e in events if e["event"] == "stall"]
    assert len(stall_events) == 3
    assert m.degraded
