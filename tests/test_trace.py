"""Flight recorder (utils/trace.py): span tracing, Chrome export,
compile/execute attribution, the stall watchdog, the `stats`
subcommand, and the bench regression gate.

Fast unit tier — the tier-1 suite has ~100 s of headroom inside its
870 s budget, so the two pipeline-level tests here reuse the same tiny
shapes test_metrics.py compiles and everything else is pure-host.
"""

import importlib.util
import io
import json
import os
import threading
import time

import numpy as np
import pytest

from ccsx_tpu import cli
from ccsx_tpu.utils import faultinject, synth, trace
from ccsx_tpu.utils.metrics import Metrics


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faultinject.disarm()


def _write_fasta(tmp_path, rng, n_holes=3, tlen=700, n_passes=5):
    zs = [synth.make_zmw(rng, template_len=tlen, n_passes=n_passes,
                         movie="mv", hole=str(h)) for h in range(n_holes)]
    fa = tmp_path / "in.fa"
    fa.write_text(synth.make_fasta(zs))
    return zs, fa


def _read_jsonl(path):
    return [json.loads(line) for line in open(path) if line.strip()]


# ---- tracer unit tier ------------------------------------------------------


def test_span_nesting_and_record_fields(tmp_path):
    p = str(tmp_path / "t.jsonl")
    tr = trace.Tracer(p)
    with tr.span("outer", cat="compute", n=2):
        with tr.span("inner", cat="device" if False else "prep"):
            time.sleep(0.01)
    tr.close()
    recs = _read_jsonl(p)
    assert recs[0]["ev"] == "meta"
    spans = {r["name"]: r for r in recs if r["ev"] == "span"}
    outer, inner = spans["outer"], spans["inner"]
    # inner closes first (JSONL is close-ordered), and nests inside
    # outer's [start, start+dur] interval
    assert recs[1]["name"] == "inner"
    assert inner["mono"] >= outer["mono"]
    assert inner["mono"] + inner["dur"] <= outer["mono"] + outer["dur"] + 1e-6
    assert inner["dur"] >= 0.01
    assert outer["args"] == {"n": 2}
    assert abs(outer["ts"] - time.time()) < 60


def test_thread_safety_every_line_valid(tmp_path):
    p = str(tmp_path / "t.jsonl")
    tr = trace.Tracer(p)

    def work(i):
        for j in range(100):
            with tr.span(f"w{i}", cat="compute", j=j):
                pass

    threads = [threading.Thread(target=work, args=(i,), name=f"wk{i}")
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tr.close()
    recs = _read_jsonl(p)  # json.loads would raise on a torn line
    spans = [r for r in recs if r["ev"] == "span"]
    assert len(spans) == 800
    for i in range(8):
        mine = [r for r in spans if r["name"] == f"w{i}"]
        assert len(mine) == 100
        assert all(r["tid"] == f"wk{i}" for r in mine)


def test_device_span_attribution_first_call_is_compile(tmp_path):
    m = Metrics()
    tr = trace.Tracer(str(tmp_path / "t.jsonl"), metrics=m)
    for _ in range(3):
        with tr.device_span("refine", group="g:q1:t1:i1", cells=100):
            time.sleep(0.002)
    tr.close()
    # counts only: device seconds come from a profiler trace
    assert m.snapshot()["groups"]["g:q1:t1:i1"] == {
        "compiles": 1, "dispatches": 3, "dp_cells": 300}
    recs = _read_jsonl(str(tmp_path / "t.jsonl"))
    compiles = [r for r in recs
                if r["ev"] == "span" and r.get("compile")]
    assert len(compiles) == 1


def test_device_span_recompile_per_shape(tmp_path):
    """The same group key dispatched at a different jit-specializing
    shape (the bucketed batch dim) is a RECOMPILE, not steady-state
    execute — compiles counts per (group, shape)."""
    m = Metrics()
    tr = trace.Tracer(str(tmp_path / "t.jsonl"), metrics=m)
    for shape in ("Z4", "Z8", "Z4"):
        with tr.device_span("round", group="round:P8:q1:t1",
                            shape=shape, cells=10):
            pass
    tr.close()
    st = m.snapshot()["groups"]["round:P8:q1:t1"]
    assert st["compiles"] == 2         # Z4 and Z8 each compiled once
    assert st["dispatches"] == 3
    recs = _read_jsonl(str(tmp_path / "t.jsonl"))
    flags = [r["compile"] for r in recs if r["ev"] == "span"]
    assert flags == [True, True, False]


def test_failed_dispatch_not_attributed(tmp_path):
    """A dispatch that raises (the OOM the recovery ladder bisects and
    re-dispatches) is recorded error=true but NOT booked into the group
    table — its cells would otherwise be double-counted by the retry."""
    m = Metrics()
    tr = trace.Tracer(str(tmp_path / "t.jsonl"), metrics=m)
    with pytest.raises(RuntimeError):
        with tr.device_span("refine", group="g", cells=100):
            raise RuntimeError("RESOURCE_EXHAUSTED: injected")
    with tr.device_span("refine", group="g", cells=50):
        pass
    tr.close()
    st = m.snapshot()["groups"]["g"]
    assert st["dispatches"] == 1 and st["dp_cells"] == 50
    assert st["compiles"] == 1         # the retry is the compile call
    recs = [r for r in _read_jsonl(str(tmp_path / "t.jsonl"))
            if r["ev"] == "span"]
    assert recs[0]["args"]["error"] is True
    assert "compile" not in recs[0]


def test_materialize_span_watched_but_not_attributed(tmp_path, capsys):
    """attribute=False (the finish-phase materialization wait): the
    watchdog sees it — the untraced async-runtime hang surfaces at
    materialization, not dispatch — but it never enters group tables."""
    m = Metrics()
    p = str(tmp_path / "t.jsonl")
    tr = trace.Tracer(p, stall_timeout=0.15, metrics=m)
    with tr.device_span("materialize", group="(8, 1536)",
                        attribute=False):
        pass          # consume the first-of-shape compile grace
    with tr.device_span("materialize", group="(8, 1536)",
                        attribute=False):
        time.sleep(0.5)
    with tr.device_span("refine", group="g", cells=10):
        pass
    tr.close()
    err = capsys.readouterr().err
    assert "STALL WATCHDOG" in err and "(8, 1536)" in err
    assert m.degraded
    assert set(m.group_stats) == {"g"}     # materialize not attributed
    d = trace.summarize([p])
    assert set(d["groups"]) == {"g"}       # ...from the trace either
    # but it IS on the timeline and eligible for the slowest list
    names = {s["group"] for s in d["slowest"]}
    assert "(8, 1536)" in names


def test_bench_vs_prev_traced_discipline_not_compared():
    """Traced e2e numbers (forced per-dispatch execution) must never be
    compared against untraced (async overlap) ones."""
    bench = _load_bench_module()
    prev = {"backend": "cpu", "e2e": [
        {"config": 2, "holes_in": 4, "zmws_per_sec": 2.0}]}
    line = {"backend": "cpu", "e2e": [
        {"config": 2, "holes_in": 4, "zmws_per_sec": 1.0, "traced": True}]}
    bench.compare_with_prev(line, prev, "BENCH_r9.json")
    assert "zmws_per_sec" not in line["vs_prev"]
    assert "regressed" not in line


def test_span_eof_stopiteration_not_an_error(tmp_path):
    """The drivers wrap next(stream) in an ingest span; EOF must not
    leave a spurious error=true span at the end of every clean trace."""
    p = str(tmp_path / "t.jsonl")
    tr = trace.Tracer(p)
    with pytest.raises(StopIteration):
        with tr.span("ingest_hole", cat="ingest"):
            next(iter(()))
    tr.close()
    spans = [r for r in _read_jsonl(p) if r["ev"] == "span"]
    assert len(spans) == 1
    assert "error" not in spans[0].get("args", {})


def test_nested_span_self_time_disjoint(tmp_path):
    """Category sums stay disjoint: an enclosing sweep span carries
    "self" (dur minus nested children) and summarize() uses it."""
    p = str(tmp_path / "t.jsonl")
    tr = trace.Tracer(p)
    with tr.span("refine_sweep", cat="compute"):
        with tr.device_span("refine", group="g"):
            time.sleep(0.05)
    tr.close()
    recs = {r["name"]: r for r in _read_jsonl(p) if r["ev"] == "span"}
    outer, dev = recs["refine_sweep"], recs["refine"]
    assert "self" not in dev           # leaves: self == dur, omitted
    # self, dur, and child dur are each independently rounded to 6
    # decimals in the records, so allow half-ulp slack from all three
    assert outer["self"] <= outer["dur"] - dev["dur"] + 2e-6
    d = trace.summarize([p])
    assert d["stage_seconds"]["device"] >= 0.05
    # compute's stage share excludes the nested device time
    assert d["stage_seconds"]["compute"] < 0.05


def test_chrome_export_is_loadable(tmp_path):
    p = str(tmp_path / "t.jsonl")
    tr = trace.Tracer(p)
    with tr.span("host_work", cat="prep"):
        pass
    with tr.device_span("refine", group="g", cells=10):
        pass
    tr.instant("recover", cat="recover", kind="oom")
    tr.close()
    cp = trace.chrome_path(p)
    assert cp.endswith(".chrome.json")
    with open(cp) as f:
        chrome = json.load(f)
    events = chrome["traceEvents"]
    xs = [e for e in events if e.get("ph") == "X"]
    assert len(xs) == 2
    for e in xs:
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert e["cat"] in trace.CATEGORIES and "tid" in e
    assert any(e.get("ph") == "i" for e in events)
    assert any(e.get("ph") == "M" and e.get("name") == "thread_name"
               for e in events)


def test_watchdog_fires_while_span_open(tmp_path, capsys):
    buf = io.StringIO()
    m = Metrics(stream=buf)
    p = str(tmp_path / "t.jsonl")
    tr = trace.Tracer(p, stall_timeout=0.15, metrics=m)
    with tr.device_span("refine_packed", group="packed:q9:t9:i9",
                        plan={"rows": 8, "holes": 2}):
        pass          # first-of-shape: consumes the compile grace
    with tr.device_span("refine_packed", group="packed:q9:t9:i9",
                        plan={"rows": 8, "holes": 2}):
        time.sleep(1.0)   # steady state: bare --stall-timeout applies
    tr.close()
    err = capsys.readouterr().err
    assert "STALL WATCHDOG" in err
    assert "packed:q9:t9:i9" in err
    assert "File \"" in err            # the thread-stack dump
    assert "\"rows\": 8" in err        # the in-flight slab plan
    assert m.degraded and m.degraded.startswith("stall watchdog")
    stalls = [r for r in _read_jsonl(p) if r["ev"] == "stall"]
    assert len(stalls) == 1            # fires once per stalled span
    # fired WHILE the dispatch was open (within one timeout interval of
    # the deadline, well before the 1.0 s sleep released the span), and
    # the record carries the stacks
    assert 0.15 <= stalls[0]["open_s"] < 1.0
    assert any("sleep" in s for s in stalls[0]["stacks"].values())
    events = [json.loads(line) for line in buf.getvalue().splitlines()]
    assert [e["event"] for e in events if e["event"] == "stall"] == ["stall"]
    assert all("ts" in e for e in events)


def test_watchdog_quiet_on_healthy_spans(tmp_path, capsys):
    m = Metrics()
    tr = trace.Tracer(None, stall_timeout=60.0, metrics=m)
    with tr.device_span("refine", group="g"):
        pass
    tr.close()
    assert "STALL" not in capsys.readouterr().err
    assert m.degraded is None
    # path=None: attribution still counts (watchdog-only mode)
    assert m.group_stats["g"]["dispatches"] == 1


def test_stall_fault_point_spec():
    plan = faultinject.parse_spec("stall@2")
    assert plan == {"stall": [2, False]}


def test_watchdog_compile_grace_first_of_shape(tmp_path, capsys):
    """The first span of a (group, shape) gets COMPILE_GRACE x the
    stall budget: a cold multi-minute XLA compile is not a hang."""
    m = Metrics()
    tr = trace.Tracer(str(tmp_path / "t.jsonl"), stall_timeout=0.15,
                      metrics=m)
    with tr.device_span("round", group="g", shape="Z4"):
        time.sleep(0.5)    # > timeout, < timeout * COMPILE_GRACE
    assert "STALL" not in capsys.readouterr().err
    assert m.degraded is None
    with tr.device_span("round", group="g", shape="Z8"):
        time.sleep(0.5)    # a NEW shape: compile grace again
    assert "STALL" not in capsys.readouterr().err
    with tr.device_span("round", group="g", shape="Z4"):
        time.sleep(0.5)    # steady state: bare timeout, fires
    tr.close()
    assert "STALL WATCHDOG" in capsys.readouterr().err
    assert "compile grace" not in str(m.degraded)
    assert m.degraded and m.degraded.startswith("stall watchdog")


def test_retry_path_materialize_span_stable_group(tmp_path):
    """The recovery/retry path (_run_group_sync) materializes inside a
    watchdog-visible 'materialize' device span — an async-runtime hang
    in a RETRIED dispatch must not be invisible — and the span carries
    the STABLE dispatch-namespace group label plus an output-shape tag
    (compile grace re-arms per fresh shape, not per slab ordinal)."""
    from ccsx_tpu.pipeline import batch as batch_mod

    assert batch_mod._out_shape_tag(np.zeros((4, 2))) == "4x2"
    p = str(tmp_path / "t.jsonl")
    tr = trace.Tracer(p)
    trace.install(tr)
    try:
        results = [None]
        batch_mod._run_group_sync(
            [0], (1, 2, 3, 7), lambda idxs, key: np.zeros((4, 2)),
            lambda idxs, key, out: None, lambda i: None, results,
            None, 0, 3, 0.0, label=lambda k: f"packed:q{k[0]}:t{k[1]}")
    finally:
        trace.uninstall()
        tr.close()
    mats = [r for r in _read_jsonl(p) if r.get("ev") == "span"
            and r["name"] == "materialize"]
    assert len(mats) == 1
    assert mats[0]["args"]["group"] == "packed:q1:t2"   # no slab ordinal
    assert mats[0]["args"]["shape"] == "4x2"
    assert "compile" not in mats[0]    # attribute=False: timeline only


# ---- pipeline integration --------------------------------------------------


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """ONE traced batched CLI run shared by the integration asserts
    (same shapes as test_metrics.py, so the jit cache is warm)."""
    tmp = tmp_path_factory.mktemp("traced")
    rng = np.random.default_rng(0)
    _, fa = _write_fasta(tmp, rng)
    out, m, t = str(tmp / "o.fa"), str(tmp / "m.jsonl"), str(tmp / "t.jsonl")
    rc = cli.main(["-A", "-m", "1000", "--batch", "on", "--metrics", m,
                   "--trace", t, str(fa), out])
    assert rc == 0
    return {"trace": t, "metrics": m, "out": out}


def test_traced_run_group_table_matches_spans(traced_run):
    """The acceptance identity: per-shape-group compile, dispatch and
    cell counts from the trace spans equal the group table in the
    final metrics event."""
    recs = _read_jsonl(traced_run["trace"])
    # attribution rule: only spans carrying a "compile" key enter the
    # group table (materialize/failed spans are timeline-only)
    dev = [r for r in recs if r["ev"] == "span" and r["cat"] == "device"
           and "compile" in r]
    assert dev, "no device spans recorded"
    assert any(r["name"] == "materialize" for r in recs
               if r["ev"] == "span")      # finish-phase wait is traced
    sums = {}
    for r in dev:
        st = sums.setdefault(r["args"]["group"],
                             {"compiles": 0, "dispatches": 0,
                              "dp_cells": 0})
        if r.get("compile"):
            st["compiles"] += 1
        if r.get("warmup"):
            # AOT warmup span (pipeline/warmup.py): books the shape's
            # compile, never a dispatch — the same rule device_span
            # and stats' summarize() apply
            continue
        st["dispatches"] += 1
        st["dp_cells"] += r["args"].get("cells", 0)
    finals = [e for e in _read_jsonl(traced_run["metrics"])
              if e["event"] == "final"]
    assert len(finals) == 1
    assert finals[0]["groups"] == sums
    # every metrics event (satellite bugfix) carries the wall-clock ts
    assert all("ts" in e for e in _read_jsonl(traced_run["metrics"]))


def test_traced_run_span_taxonomy_and_chrome(traced_run):
    recs = _read_jsonl(traced_run["trace"])
    cats = {r["cat"] for r in recs if r["ev"] == "span"}
    # ingest + prep + compute + device all present in one batched run
    assert {"ingest", "prep", "compute", "device"} <= cats
    chrome = json.load(open(trace.chrome_path(traced_run["trace"])))
    assert any(e.get("cat") == "device" for e in chrome["traceEvents"])


def test_stats_subcommand_summary(traced_run, capsys):
    rc = cli.main(["stats", traced_run["trace"], traced_run["metrics"]])
    assert rc == 0
    out = capsys.readouterr().out
    assert "shape groups:" in out
    assert "packed:" in out                 # the packed refine group
    assert "stage breakdown" in out
    assert "slowest device dispatches:" in out
    assert "occupancy recap:" in out
    assert "degraded: none" in out


def test_stats_subcommand_missing_file(capsys):
    assert cli.main(["stats", "/nonexistent/x.jsonl"]) == 1
    assert "Error: stats:" in capsys.readouterr().err


def test_injected_stall_fires_watchdog_in_pipeline(tmp_path, rng,
                                                   monkeypatch, capsys):
    """The end-to-end acceptance path: an injected stall inside a
    device dispatch trips the watchdog, which dumps thread stacks + the
    in-flight shape group and degrades (not kills) the run.  The first
    dispatch of a shape carries the 10x compile grace (0.2 s -> 2 s
    budget), so the injected sleep must outlast it."""
    monkeypatch.setenv("CCSX_FAULT_STALL_S", "2.6")
    _, fa = _write_fasta(tmp_path, rng)
    out, m = str(tmp_path / "o.fa"), str(tmp_path / "m.jsonl")
    rc = cli.main(["-A", "-m", "1000", "--batch", "on",
                   "--stall-timeout", "0.2", "--inject-faults", "stall@1",
                   "--metrics", m, str(fa), out])
    assert rc == 0                          # degraded, never killed
    err = capsys.readouterr().err
    assert "STALL WATCHDOG" in err
    assert "packed:" in err                 # the in-flight shape group
    assert "File \"" in err                 # thread stacks
    events = _read_jsonl(m)
    assert any(e["event"] == "stall" for e in events)
    fin = events[-1]
    assert fin["event"] == "final"
    assert fin["degraded"].startswith("stall watchdog")
    assert fin["holes_out"] == 3            # the run still completed


def test_unwritable_trace_path_polite_rc1(tmp_path, rng, capsys):
    """An unwritable --trace path refuses with rc 1 (like an unwritable
    output path), not a traceback — and the finally still settles."""
    _, fa = _write_fasta(tmp_path, rng)
    rc = cli.main(["-A", "-m", "1000", "--batch", "on",
                   "--trace", str(tmp_path / "no-such-dir" / "t.jsonl"),
                   str(fa), str(tmp_path / "o.fa")])
    assert rc == 1
    assert "Cannot open trace file" in capsys.readouterr().err
    assert trace.current() is None         # nothing left installed


def test_device_spans_never_block_the_dispatch(tmp_path, monkeypatch):
    """With or without a trace file, a device span closes when the
    dispatch call returns: nothing in it waits on the device, and the
    group table carries counts only."""
    import jax

    def refuse(*a, **k):
        raise AssertionError("a span blocked on the device")

    monkeypatch.setattr(jax, "block_until_ready", refuse)
    for path in (None, str(tmp_path / "t.jsonl")):
        m = Metrics()
        tr = trace.Tracer(path, stall_timeout=0, metrics=m)
        with tr.device_span("refine", group="g", cells=10):
            pass
        tr.close()
        snap = m.snapshot()
        assert snap["groups"] == {"g": {"compiles": 1, "dispatches": 1,
                                        "dp_cells": 10}}
        assert "groups_forced" not in snap


def _host_event_names(trace_dir):
    import glob

    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    return {ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host") for line in plane.lines
            for ev in line.events}


def test_spans_annotate_the_profiler_trace(tmp_path):
    """Every span is a ccsx.<name> annotation on the profiler's host
    plane, whether a Tracer is installed or not."""
    import jax

    tr = trace.Tracer(None, stall_timeout=0)
    d = str(tmp_path / "prof")
    with jax.profiler.trace(d):
        with trace.span("pack", cat="compute", rows=8):
            pass
        trace.install(tr)
        try:
            with trace.span("emit", cat="write"):
                pass
            with trace.device_span("refine_packed", group="g", cells=1):
                pass
        finally:
            trace.uninstall()
            tr.close()
    names = _host_event_names(d)
    assert {"ccsx.pack", "ccsx.emit", "ccsx.refine_packed"} <= names


def test_annotation_metadata_only_while_profiling(monkeypatch):
    """A span's arguments become annotation metadata only while a
    profiler session records; otherwise the annotation is built from
    its name alone."""
    import jax

    made = []

    class Fake:
        enabled = False

        @classmethod
        def is_enabled(cls):
            return cls.enabled

        def __init__(self, name, **kw):
            made.append((name, kw))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Fake)
    trace.annotation("pack", {"rows": 8})
    Fake.enabled = True
    trace.annotation("pack", {"rows": 8})
    assert made == [("ccsx.pack", {}), ("ccsx.pack", {"rows": 8})]


# ---- bench regression gate (satellite) ------------------------------------


def _load_bench_module():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "ccsx_bench_gate", os.path.join(root, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_find_prev_picks_highest_round(tmp_path):
    bench = _load_bench_module()
    raw = {"backend": "cpu", "dp_cells_per_sec": 100, "e2e": []}
    (tmp_path / "BENCH_r2.json").write_text(json.dumps(raw))
    wrapped = {"n": 10, "parsed": {"backend": "cpu",
                                   "dp_cells_per_sec": 200, "e2e": []}}
    (tmp_path / "BENCH_r10.json").write_text(json.dumps(wrapped))
    (tmp_path / "BENCH_r11.json").write_text("not json")  # skipped
    art, line = bench.find_prev_bench(str(tmp_path))
    assert art == "BENCH_r10.json"          # numeric, not lexicographic
    assert line["dp_cells_per_sec"] == 200  # unwrapped from "parsed"


def test_bench_vs_prev_regression_flag(capsys):
    bench = _load_bench_module()
    prev = {"backend": "cpu", "dp_cells_per_sec": 1000,
            "e2e": [{"config": 2, "holes_in": 4, "zmws_per_sec": 2.0}]}
    line = {"backend": "cpu", "dp_cells_per_sec": 500,
            "e2e": [{"config": 2, "holes_in": 4, "zmws_per_sec": 1.9}]}
    bench.compare_with_prev(line, prev, "BENCH_r9.json")
    assert line["vs_prev"]["dp_cells_per_sec"] == 0.5
    assert line["vs_prev"]["zmws_per_sec"] == 0.95
    assert line["regressed"] == ["dp_cells_per_sec x0.50"]
    assert "REGRESSION" in capsys.readouterr().err
    # within 20%: no flag
    ok = {"backend": "cpu", "dp_cells_per_sec": 900,
          "e2e": [{"config": 2, "holes_in": 4, "zmws_per_sec": 1.9}]}
    bench.compare_with_prev(ok, prev, "BENCH_r9.json")
    assert "regressed" not in ok


def test_bench_vs_prev_backend_mismatch_skipped():
    bench = _load_bench_module()
    prev = {"backend": "tpu", "dp_cells_per_sec": 1e12, "e2e": []}
    line = {"backend": "cpu", "dp_cells_per_sec": 1.0, "e2e": []}
    bench.compare_with_prev(line, prev, "BENCH_r9.json")
    assert "skipped" in line["vs_prev"]
    assert "regressed" not in line
    # hole-count mismatch: that config is not compared
    prev2 = {"backend": "cpu", "dp_cells_per_sec": 100,
             "e2e": [{"config": 1, "holes_in": 16, "zmws_per_sec": 9.0}]}
    line2 = {"backend": "cpu", "dp_cells_per_sec": 100,
             "e2e": [{"config": 1, "holes_in": 4, "zmws_per_sec": 1.0}]}
    bench.compare_with_prev(line2, prev2, "BENCH_r9.json")
    assert "zmws_per_sec" not in line2["vs_prev"]
    assert "regressed" not in line2
