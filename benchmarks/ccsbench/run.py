#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/ccsbench/run.py --workload <cell> --seed <n>
        --seconds <s> --trace <0|1>

Set-up: device check (a TPU, as many chips as the cell asks for), the
native library (``make`` only rebuilds what is stale), the cell's
seeded input pool as a BGZF subreads BAM (cached per configuration,
traffic and seed), and the ramp: the program's batched driver
(``ccsx_tpu.pipeline.batch.drive_batched``, the entry ``cli.main``
reaches with ``--batch auto`` on a TPU) runs over the pool, streamed
again under fresh hole names, until ``ramp_holes`` records have reached
the writer and a cohort has come out with no compile before it.  The
driver emits a cohort of holes in one burst, so the window runs from the
end of one burst to the end of the first burst that completes at least
``--seconds`` later, and counts its records at the writer; the writer
refuses records after the close, and the process exits without waiting
for the driver to reach its next one.  The window's records
are judged against their seeded templates by the plain reference
(reference.py), and the last stdout line is the result.

With ``--trace 1`` the profiler records short slices at seeded random
phases, one in each quarter of the window, and one more at the burst
that opens it (the cohort boundary and the admission after it), and
the cell's per-layer metrics are reported instead of its end-to-end
ones.
"""

from __future__ import annotations

import time

T_PROC = time.monotonic()

import argparse                                               # noqa: E402
import hashlib                                                # noqa: E402
import json                                                   # noqa: E402
import math                                                   # noqa: E402
import os                                                     # noqa: E402
import shutil                                                 # noqa: E402
import subprocess                                             # noqa: E402
import sys                                                    # noqa: E402
import threading                                              # noqa: E402
import traceback                                              # noqa: E402
import types                                                  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, ROOT]       # the benchmark, then the program

import cells                                                  # noqa: E402
import gen                                                    # noqa: E402
import reference                                              # noqa: E402

CACHE = os.path.join(HERE, ".cache")
MOVIE = "m84000_bench"
COUNTERS = ("holes_out", "holes_failed", "host_fallbacks",
            "device_dispatches", "dp_rows_real", "dp_rows_dispatched",
            "t_ingest", "t_prep", "t_prep_blocked", "t_compute",
            "prep_queue_depth")
SETUP_DEADLINE_S = 1000.0    # a first (compiling) run may take 1200 s
RAMP_EXTRA = 1               # bursts the ramp waits for a quiet one
CLOSE_WAIT_S = 150.0         # the longest wait for a record after --seconds
QUIET_S = 1.0                # a gap this long between records ends a burst
BURST_MAX_S = 5.0
TRACE_SLICES = 4             # traced slices over the window, one at a
TRACE_S = 0.1                # seeded phase in each quarter; each ~30 MB
                             # and ~15 s to stop on a v5e


# The driver threads of this process.  A run does not wait for its
# driver to reach its next record after the close (up to a cohort's
# time): the process exits under it; a test joins them.
DRIVERS: list = []


class RunError(Exception):
    """The run cannot give a result: exit non-zero, print none."""


def say(what: str, **kv) -> None:
    print(f"ccsbench {what}: {json.dumps(kv)}", flush=True)


def device_check(chips: int, require_tpu: bool = True) -> dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    say("device", **dev)
    if require_tpu and dev["platform"] != "tpu":
        raise RunError(f"platform is {dev['platform']!r}, not 'tpu'")
    if dev["count"] < chips:
        raise RunError(f"{dev['count']} devices, the cell needs {chips}")
    return dev


def build_native() -> None:
    ndir = os.path.join(ROOT, "ccsx_tpu", "native")
    r = subprocess.run(["make", "-s", "-C", ndir], capture_output=True,
                       text=True)
    if r.returncode:
        raise RunError(f"native build failed: {r.stderr[-2000:]}")


def pool_input(cell: cells.Cell, seed: int):
    """(bam path, pool) of the cell's seeded input, generated once per
    (configuration, traffic, seed) into the checkout's cache, under a
    key of their whole contents and the generator's source."""
    n = int(cell.traffic["pool_holes"])
    pool = gen.make_pool(cell.config, n, seed)
    with open(gen.__file__, "rb") as f:
        source = hashlib.sha256(f.read()).hexdigest()
    key = hashlib.sha256(json.dumps([cell.config, cell.traffic, seed,
                                     source], sort_keys=True).encode()
                         ).hexdigest()
    d = os.path.join(CACHE, "input", f"{cell.config['name']}."
                     f"{cell.traffic['name']}.{seed}.{key[:12]}")
    bam = os.path.join(d, "pool.bam")
    if not os.path.exists(bam):
        os.makedirs(d, exist_ok=True)
        gen.write_bam(bam, MOVIE, pool)
    return bam, pool


def program_config(cell: cells.Cell, bam: str, out: str, extra=()):
    """The program's CcsConfig from the configuration's CLI arguments,
    checked against every setting the configuration file states;
    ``extra`` arguments (a control, in tests) depart from it on purpose
    and skip the check."""
    from ccsx_tpu import cli

    args = cli.build_parser().parse_args(
        [*cell.config["cli"], *extra, "--batch", "auto", bam, out])
    cfg = cli.config_from_args(args)
    if extra:
        return cfg
    stated = cell.config["program"]
    got = {"band": cfg.align.band, "refine_iters": cfg.refine_iters,
           "max_passes": cfg.max_passes, "max_window": cfg.max_window,
           "slab_rows": cfg.slab_rows, "min_count": cfg.min_fulllen_count,
           "min_len": cfg.min_subread_len, "max_len": cfg.max_subread_len,
           "zmw_microbatch": cfg.zmw_microbatch,
           "pass_packing": cfg.pass_packing}
    wrong = {k: (got[k], v) for k, v in stated.items() if got[k] != v}
    if wrong:
        raise RunError(f"the program does not run the configuration as "
                       f"stated (got, stated): {wrong}")
    return cfg


class CompileCounter:
    """Compile events (persistent-cache loads included) with their
    thread and function names, from JAX's monitoring hooks."""

    def __init__(self):
        import jax

        self.events = []
        self.lowered = set()   # threads between lowering and compile
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, *a, **k):
        thread = threading.current_thread().name
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered.add(thread)
        elif event == "/jax/core/compile/backend_compile_duration":
            self.lowered.discard(thread)
            self.events.append((time.monotonic(),
                                thread + ":" + k.get("fun_name", "?"),
                                duration))


class Window:
    """The writer the program writes through: each record with its
    time and the program's counters right after it; records after
    ``stop_after`` raise, which ends the driver at its next record."""

    def __init__(self, inner, metrics):
        import jax

        self._inner = inner
        self._ann = jax.profiler.TraceAnnotation
        self._metrics = metrics
        self.records = []          # (t, name, seq, counters)
        self.stop_after = None

    def put(self, name, seq, qual=None):
        now = time.monotonic()
        if self.stop_after is not None and now > self.stop_after:
            raise OSError("benchmark window closed")
        with self._ann("bench.write"):
            self._inner.put(name, seq, qual)
        self.records.append((now, name, bytes(seq),
                             snapshot(self._metrics)))

    def flush(self):
        self._inner.flush()

    def close(self):
        self._inner.close()


def burst_end(win: Window, i: int, alive) -> int:
    """Index of the last record of the burst that record ``i`` is in:
    the records that follow it with no gap of QUIET_S (a burst longer
    than BURST_MAX_S ends where it has reached)."""
    while True:
        n = len(win.records)
        last = win.records[n - 1][0]
        now = time.monotonic()
        if (now - last >= QUIET_S or last - win.records[i][0] > BURST_MAX_S
                or not alive()):
            return n - 1
        time.sleep(0.05)


def _wait_record(win: Window, i: int, th, deadline: float, what: str):
    while len(win.records) <= i:
        if not th.is_alive():
            raise RunError(f"the driver ended {what} after "
                           f"{len(win.records)} records")
        if time.monotonic() > deadline:
            raise RunError(f"no record {i + 1} {what} by "
                           f"{deadline - T_PROC:.0f} s")
        time.sleep(0.01)


def open_window(cell: cells.Cell, win: Window, th, compiles,
                at_burst=None):
    """The ramp: runs until ``ramp_holes`` records have reached the
    writer, then to the end of the first burst of records before which
    no compile ended since the burst before it and none is under way (a
    compile is timed at its end; the warmup compiler builds predicted
    shapes in the background for 35-130 s).  It waits at most
    RAMP_EXTRA bursts for such a one: each takes a cohort's time, and a
    run has 360 s (its window compiles are printed).  ``at_burst`` is
    called at the first record of each burst after the first, so the
    last call falls on the burst that opens the window.  Returns the
    index of the record that ends the ramp and the seconds since the
    burst before it."""
    ramp = int(cell.traffic["ramp_holes"])
    deadline = T_PROC + SETUP_DEADLINE_S
    prev, i, extra = None, 0, 0
    while True:
        _wait_record(win, i, th, deadline, "during the ramp")
        if at_burst is not None and prev is not None:
            at_burst()
        e = burst_end(win, i, th.is_alive)
        if e + 1 >= ramp and prev is not None:
            quiet = not (compiles.lowered
                         or any(t > prev for t, _, _ in compiles.events))
            if quiet or extra >= RAMP_EXTRA:
                return e, win.records[e][0] - prev
            extra += 1
        prev, i = win.records[e][0], e + 1


class Slicer:
    """Profiler traces of a few seconds at most, each in a directory of
    its own under ``tdir`` and inside a ``bench.window`` span."""

    def __init__(self, tdir: str):
        import jax

        self.opts = jax.profiler.ProfileOptions()
        self.opts.python_tracer_level = 0
        self.tdir = tdir
        self.dirs = []

    def take(self, seconds: float) -> str:
        import jax

        d = os.path.join(self.tdir, str(len(self.dirs)))
        jax.profiler.start_trace(d, profiler_options=self.opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            time.sleep(seconds)
        jax.profiler.stop_trace()
        self.dirs.append(d)
        return d


def trace_slices(slicer: Slicer, t_open: float, span: float,
                 seed: int) -> list:
    """TRACE_SLICES slices of TRACE_S seconds over the expected window
    ``[t_open, t_open + span]``, one in each of its equal parts at a
    phase drawn from the seed, so that every instant of the window is
    as likely to be traced (the device's trace buffer holds about a
    second of this program's operations).  A slice whose phase passed
    while an earlier one stopped is taken at once if its part has not
    ended, else left out.  Returns their directories."""
    import numpy as np

    u = np.random.default_rng([seed, 7]).random(TRACE_SLICES)
    step = span / TRACE_SLICES
    dirs = []
    for i in range(TRACE_SLICES):
        now = time.monotonic()
        if now > t_open + (i + 1) * step - TRACE_S:
            continue
        time.sleep(max(0.0, t_open + (i + u[i]) * step - now))
        dirs.append(slicer.take(TRACE_S))
    return dirs


def close_window(win: Window, th, t_min: float, min_index: int) -> int:
    """Index of the record that ends the window: the last of the first
    burst that reaches the writer at or after ``t_min``."""
    deadline = t_min + CLOSE_WAIT_S
    j = min_index
    while True:
        _wait_record(win, j, th, deadline, "in the window")
        if win.records[j][0] >= t_min:
            return burst_end(win, j, th.is_alive)
        j += 1


def stream(bam: str, cfg, metrics, pool_n: int):
    """The pool, read by the program's own ingest again and again,
    each pass under fresh hole names (pass k: hole + k * pool_n)."""
    import dataclasses

    import jax

    from ccsx_tpu.pipeline.run import open_zmw_stream

    ann = jax.profiler.TraceAnnotation
    k = 0
    it = iter(open_zmw_stream(bam, cfg, metrics=metrics))
    while True:
        with ann("bench.ingest"):
            z = next(it, None)
        if z is None:
            k += 1
            it = iter(open_zmw_stream(bam, cfg, metrics=metrics))
            continue
        yield (z if k == 0 else
               dataclasses.replace(z, hole=str(int(z.hole) + k * pool_n)))


def expected_names(pool, cli: dict, count: int):
    """The first ``count`` record names the reference's read step and
    ordered writer give for the streamed pool."""
    keep = [h for h in pool
            if reference.kept(len(h.passes), sum(len(p) for p in h.passes),
                              cli)]
    if not keep:
        raise RunError("the reference keeps no hole of the pool")
    out, k = [], 0
    while len(out) < count:
        out.extend(f"{MOVIE}/{h.hole + k * len(pool)}/ccs" for h in keep)
        k += 1
    return out[:count]


def snapshot(metrics) -> dict:
    return {k: getattr(metrics, k) for k in COUNTERS}


def memory_peak(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.local_devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def judge(cell: cells.Cell, pool, recs, k: int) -> dict:
    """Score the records against the reference.  Every record must be
    the next hole the reference's read step and ordered writer give
    (``order_faults`` counts records out of place or unknown and holes
    skipped before the last record); the window's holes, those emitted
    and those due between its first and last record and never emitted,
    are scored against their templates (``err_rate``, over the window's
    template bases; ``worst_hole_err``, the worst emitted hole's), and
    those never emitted are counted (``missing``)."""
    names = [r[1] for r in recs]
    expect = expected_names(pool, cell.config["program"], len(names) + 64)
    pos = {n: i for i, n in enumerate(expect)}
    seen = [pos[n] for n in names if n in pos]
    unknown = len(names) - len(seen)
    out_of_place = sum(b <= a for a, b in zip(seen, seen[1:]))
    skipped = (max(seen) + 1 - len(set(seen))) if seen else 0
    in_win = recs[k + 1:]
    win_pos = [pos[r[1]] for r in in_win if r[1] in pos]
    emitted = set(win_pos)
    due_missing = ([expect[i] for i in range(min(win_pos), max(win_pos))
                    if i not in emitted] if win_pos else [])
    by_hole = {h.hole: h for h in pool}

    def template(name):
        return by_hole.get(int(name.split("/")[1]) % len(pool))

    errors = bases = 0
    worst = 0.0
    outliers = []
    for _, n, s, _ in in_win:
        h = template(n)
        if h is None:
            errors += max(len(s), 1)
            continue
        e = reference.hole_errors(s, h.template)
        errors += e
        bases += len(h.template)
        worst = max(worst, e / len(h.template))
        if e > 0.05 * len(h.template):
            outliers.append({"name": n, "errors": e, "template": len(h.template),
                             "consensus": len(s), "passes": len(h.passes)})
    if outliers:
        say("outlier holes", holes=outliers)
    for n in due_missing:
        errors += len(template(n).template)
        bases += len(template(n).template)
    return {"records": in_win, "errors": errors, "bases": bases,
            "worst": worst, "missing": len(due_missing),
            "checks": {"err_rate": errors / bases if bases else 1.0,
                       "worst_hole_err": worst,
                       "missing": len(due_missing),
                       "order_faults": unknown + out_of_place + skipped}}


def verdict(cell: cells.Cell, checks: dict):
    lim = cell.limits
    rows = {k: {"value": v, "limit": lim[k]["limit"]}
            for k, v in checks.items()}
    ok = all(r["value"] <= r["limit"] for r in rows.values())
    return ok, rows


def run(cell: cells.Cell, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, cli_extra=(),
        min_records: int = 1) -> dict:
    """One run of ``cell``; returns the result object.  For tests
    alone: ``require_tpu`` False runs on the CPU, ``cli_extra`` adds
    program arguments, and ``min_records`` keeps the window open until
    it holds that many records."""
    if require_tpu:
        # the checkout's own cache, whatever the machine sets, holding
        # every program (JAX leaves out those that compile in under 1 s,
        # which every run would then compile again)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    dev = device_check(cell.chips, require_tpu)
    build_native()
    t0 = time.monotonic()
    bam, pool = pool_input(cell, seed)
    say("input", holes=len(pool), subreads=sum(len(h.passes) for h in pool),
        bases=sum(len(p) for h in pool for p in h.passes),
        seconds=time.monotonic() - t0)

    from ccsx_tpu.pipeline.batch import drive_batched
    from ccsx_tpu.pipeline.run import open_writer
    from ccsx_tpu.utils.device import resolve_device
    from ccsx_tpu.utils.journal import Journal
    from ccsx_tpu.utils.metrics import Metrics

    out = os.path.join(os.path.dirname(bam), f"out.{os.getpid() % 2}.fa")
    cfg = program_config(cell, bam, out, cli_extra)
    compiles = CompileCounter()
    metrics = Metrics(verbose=0, stream=None)
    resolve_device(cfg.device)
    journal = Journal.for_run(None, bam, cfg, out)
    win = Window(open_writer(out, append=False, bam=False, journaled=False),
                 metrics)
    def drive():
        drive_batched(stream(bam, cfg, metrics, len(pool)), win, cfg,
                      journal, metrics, None)

    tdir = os.path.join(CACHE, "trace", f"{cell.name}.{seed}")
    slicer = boundary = None
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        slicer, boundary = Slicer(tdir), []

    th = threading.Thread(target=drive, name="ccsbench-drive", daemon=True)
    th.start()
    DRIVERS.append(th)
    k, period = open_window(
        cell, win, th, compiles,
        at_burst=(lambda: boundary.append(slicer.take(TRACE_S)))
        if trace else None)
    t_open, at_open = win.records[k][0], win.records[k][3]
    setup_s = t_open - T_PROC
    say("setup", setup_s=setup_s, ramp_records=k + 1, period_s=period,
        record_times=[round(r[0] - T_PROC, 3) for r in win.records[:k + 1]],
        compiles=len(compiles.events),
        compile_s=sum(d for _, _, d in compiles.events))
    if trace:
        # the window ends at the first burst after --seconds
        slices = trace_slices(slicer, t_open,
                              period * max(1, math.ceil(seconds / period)),
                              seed)
    c = close_window(win, th, t_open + seconds, k + min_records)
    t_close, at_close = win.records[c][0], win.records[c][3]
    win.stop_after = t_close
    peak = memory_peak(cell.chips)
    window_compiles = [(t - t_open, n, d) for t, n, d in compiles.events
                       if t_open < t <= t_close]
    if window_compiles:
        say("window compiles", events=window_compiles)

    t_judge = time.monotonic()
    j = judge(cell, pool, win.records[:c + 1], k)
    t_judge = time.monotonic() - t_judge
    in_win, errors, bases = j["records"], j["errors"], j["bases"]
    ok, rows = verdict(cell, j["checks"])
    d_fallback = at_close["host_fallbacks"] - at_open["host_fallbacks"]
    say("counters", at_open=at_open, at_close=at_close)
    say("window", seconds=t_close - t_open, records=len(in_win),
        times=[round(r[0] - t_open, 3) for r in in_win],
        worst_hole_err=j["worst"], missing=j["missing"], errors=errors,
        bases=bases, judge_s=t_judge)
    tr = None
    if trace:
        import trace_reduce

        paths = [trace_reduce.find_xplane(d) for d in slices + boundary[-1:]]
        if len(paths) < 2 or None in paths or not boundary:
            raise RunError("the profiler wrote no trace")
        parts = [trace_reduce.reduce(p) for p in paths]
        tr = trace_reduce.combine(parts[:-1])
        tr["boundary"] = parts[-1]
        tr["all"] = trace_reduce.combine(parts)
        say("trace", slices=len(paths),
            bytes=[os.path.getsize(p) for p in paths],
            idle_shares=[p["idle_share"] for p in parts],
            boundary_gaps=parts[-1]["idle_gaps"])
        shutil.rmtree(tdir, ignore_errors=True)
    ctx = types.SimpleNamespace(
        cell=cell, config=cell.config, pool=pool,
        window_s=t_close - t_open, setup_s=setup_s,
        records=in_win, errors=errors, bases=bases,
        at_open=at_open, at_close=at_close,
        delta=lambda k: at_close[k] - at_open[k],
        compiles=window_compiles, trace=tr)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics_out = {}
    for m in wanted:
        v = cells.reader(m["name"])(ctx)
        if v is not None:
            metrics_out[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": cell.chips, "memory_peak_bytes": peak}
    res = {"correct": bool(ok),
           "attempted": len(in_win) + j["missing"],
           "failed": j["missing"] + d_fallback,
           "metrics": metrics_out, "device": device}
    if tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        res["breakdown"] = {"device_ops": tr["all"]["device_ops"],
                            "idle_gaps": tr["all"]["idle_gaps"]}
    res["checks"] = rows
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        cell = cells.load(a.workload)
        res = run(cell, a.seed, a.seconds, bool(a.trace))
    except Exception as e:          # any failure: no result line
        if not isinstance(e, RunError):
            traceback.print_exc()
        print(f"ccsbench: FAIL: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        return 1
    for k, r in res["checks"].items():
        print(f"ccsbench check {k}: {r['value']} (limit {r['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the driver thread may still hold the device: leave without joining
    os._exit(rc)
