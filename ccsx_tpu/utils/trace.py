"""Dispatch flight recorder: span tracing, per-group dispatch counts,
and a hang watchdog.

Why this exists (VERDICT r5): `BENCH_r05.json` is a device attempt that
hung with no diagnostics; a hang needs to leave a report behind, and
the host's work needs names on the same clock as the device's.  Four
pieces:

* **Span tracer** (``--trace <path>``): thread-safe; every unit of work
  — ingest hole, prep batch, device dispatch, recovery rung, host
  replay, writer flush, journal update — is one JSONL record with wall
  ``ts``, run-relative ``mono``, ``dur`` seconds, thread, and args.  At
  close the JSONL is additionally exported as Chrome trace-event format
  (``<path minus .jsonl>.chrome.json``), loadable in Perfetto /
  chrome://tracing.  A device span times the host's dispatch call
  (trace, compile on a shape's first call, enqueue), never the device:
  dispatch is asynchronous and no span blocks on it.

* **Profiler annotations**: every ``span``/``device_span`` also enters
  ``jax.profiler.TraceAnnotation("ccsx.<name>")``, with or without a
  Tracer or a trace file, so a profiler trace (``jax.profiler.trace``)
  shows the program's spans on the device's clock.  The jitted programs
  the dispatch sites run are named ``ccsx_*`` (the trace's "XLA
  Modules"), and the stages of a consensus round are the named scopes
  in ``STAGES`` (in each operation's name path on "XLA Ops").  Device
  time per program and per stage comes from such a trace.

* **Per-shape-group counts**: the first device span of each
  (group key, batch-dim shape) is a COMPILE call (XLA traces + compiles
  on first execution of a shape — including recompiles when a group's
  bucketed batch dim changes).  The table — compiles, dispatches,
  dp_cells per group — accumulates into ``Metrics.group_stats`` and
  rides every metrics event via ``Metrics.snapshot()``, so recompile
  storms are visible in any metrics JSONL.

* **Stall watchdog** (``--stall-timeout``, default 120 s, 0 disables):
  a daemon thread that fires when a device-dispatch span stays open
  longer than the timeout (first-of-shape spans get ``COMPILE_GRACE`` x
  the budget — cold compiles are not hangs), and dumps — to stderr, the trace file, and
  the metrics stream — every Python thread stack, the in-flight shape
  group / slab plan, and a metrics snapshot, then marks the run
  degraded (``Metrics.degraded``, carried by every later event incl.
  final).  The watchdog needs no trace file: span open/close tracking
  around dispatches is always on (two perf_counter reads), and since
  a dispatch span closes in ~1 ms on an async runtime with the hang
  surfacing later, the executors' finish phase runs inside a
  watchdog-visible ``materialize`` device span (``attribute=False`` —
  timeline-only, never in the group table) — so the next "tpu attempt
  hung" produces an actionable report whichever side it hangs on.
  Deterministically testable via the ``stall`` fault-injection point
  (utils/faultinject.py), which sleeps inside a device dispatch.

``ccsx-tpu stats <trace/metrics JSONL>...`` summarizes artifacts into
the group table, a per-category stage breakdown, an occupancy recap,
and the top-N slowest dispatches (``stats_main`` below).

Wiring: the drivers construct a Tracer next to their Metrics and
``install()`` it process-globally for the run; call sites use the
module-level ``span`` / ``device_span`` / ``instant`` helpers, which
only annotate for the profiler when nothing is installed.
"""

from __future__ import annotations

import contextlib
import contextvars
import heapq
import json
import os
import sys
import threading
import time
import traceback
from typing import Dict, Optional

from ccsx_tpu.utils import blackbox

# span taxonomy (ARCHITECTURE.md "Observability"): every span carries
# one of these categories, which the stats stage-breakdown sums over
CATEGORIES = ("ingest", "prep", "compute", "device", "recover", "write",
              "journal", "host")

# metrics-snapshot keys the stats occupancy recap consumes — a module
# constant so the telemetry schema-drift guard (tests/test_telemetry.py)
# can prove a Metrics rename cannot silently zero a stats column
OCCUPANCY_KEYS = ("dp_occupancy", "dp_round_occupancy", "dp_length_fill",
                  "dp_pass_fill", "dp_z_fill", "dp_row_fill",
                  "packed_holes_per_dispatch", "prep_share",
                  "prep_overlap_share", "zmws_per_sec",
                  "device_dispatches", "holes_out", "elapsed_s")

# metrics-snapshot keys the stats resilience recap consumes (the
# dispatch-deadline / circuit-breaker / recovery story of a run) —
# schema-guarded like OCCUPANCY_KEYS (tests/test_telemetry.py)
RESILIENCE_KEYS = ("device_hangs", "breaker_state", "breaker_trips",
                   "breaker_probes", "host_fallbacks", "oom_resplits",
                   "compile_fallbacks", "holes_failed", "holes_corrupt",
                   "stalls")

# the named scopes (jax.named_scope) of a consensus round's stages, in
# round order: the banded DP fill, the traceback projection, the column
# vote with the in-loop draft rebuild, and the breakpoint scan.  A
# profiler trace finds each stage's device time by these names in its
# operations' name paths (benchmarks/ccsbench/stages.py)
STAGES = ("fill", "traceback", "vote", "breakpoint")

# every span's profiler annotation is named ANNOTATION_PREFIX + name
ANNOTATION_PREFIX = "ccsx."

_current: Optional["Tracer"] = None

# ---- correlation ids (ISSUE 18) --------------------------------------------
#
# The fleet-wide correlation id: minted once at job submission
# (gateway.submit_job / serve's solo submit) and entered here by
# whichever thread is currently working that job (serve's per-job
# thread, a fleet range worker, a helper pulling a sibling's range).
# Scope is a ContextVar, NOT a process global: serve runs jobs
# CONCURRENTLY (--max-active), so a process-wide cid would stamp one
# job's spans with another's id and unbalanced scope exits would leak
# a finished job's cid onto everything after it.  The job's device
# work fans across executor/prep/pump threads, which plain
# threading.Thread starts in a fresh context — those spawns go through
# ``faultinject.inherit()`` (the prep pool and deadline runner
# already do, for exactly this reason), which copies the spawning
# context and therefore carries the cid.  Spans additionally CAPTURE
# the cid at open, so a record written later from another thread (the
# stall watchdog's dump) still names the right job.  Every trace
# record and blackbox mirror written while a scope is open carries
# {"cid": ...}.

_cid_var: "contextvars.ContextVar[Optional[str]]" = \
    contextvars.ContextVar("ccsx_cid", default=None)


def current_cid() -> Optional[str]:
    return _cid_var.get()


@contextlib.contextmanager
def cid_scope(cid: Optional[str]):
    """Stamp ``cid`` on every trace/blackbox record emitted by this
    context (and threads spawned through ``faultinject.inherit()``-
    wrapped targets) for the duration of the with-block (None =
    no-op: the ambient scope, if any, stays in force).  Token-based
    restore: overlapping scopes on concurrent job threads cannot
    clobber each other or leave a stale cid behind."""
    if cid is None:
        yield
        return
    token = _cid_var.set(cid)
    try:
        yield
    finally:
        _cid_var.reset(token)

# the stall watchdog multiplies its timeout by this for the FIRST
# device span of each (group, shape): first calls pay the XLA compile
# (up to minutes), and a healthy cold run must not be stamped degraded.
# Steady-state spans get the bare --stall-timeout.
COMPILE_GRACE = 10.0

# stall-report rate limit: the FIRST report is the full dump (all
# thread stacks + plan + metrics snapshot, can be megabytes with many
# threads); later reports within this window are compact one-liners —
# a long genuine hang stalls span after span, and without the limit it
# floods stderr/trace/metrics with identical stacks.  After the window
# a fresh full dump is allowed (a second, later hang deserves stacks).
FULL_DUMP_EVERY_S = 600.0


def install(tracer: "Tracer") -> None:
    """Make ``tracer`` the process-global target of span()/device_span()
    for the duration of a run (drivers pair this with uninstall() +
    close() in their finally blocks)."""
    global _current
    _current = tracer


def uninstall() -> None:
    global _current
    _current = None


def current() -> Optional["Tracer"]:
    return _current


def annotation(name: str, args: Optional[dict] = None):
    """The profiler's view of a span: a ``TraceAnnotation`` named
    ``ccsx.<name>`` on this thread.  No profiler session can record
    before JAX is imported, so a process without it gets a no-op;
    otherwise an inactive profiler costs the annotation's one check,
    and ``args`` become its metadata only while a session records (no
    string is formatted on the hot path)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    ann = jax.profiler.TraceAnnotation
    if args and ann.is_enabled():
        return ann(ANNOTATION_PREFIX + name, **args)
    return ann(ANNOTATION_PREFIX + name)


class Span:
    __slots__ = ("sid", "name", "cat", "args", "t0", "ts",
                 "tid", "cid", "reported", "grace")

    def __init__(self, sid, name, cat, args):
        self.sid = sid
        self.name = name
        self.cat = cat
        self.args = args
        self.t0 = time.perf_counter()
        self.ts = time.time()
        self.tid = threading.current_thread().name
        # captured at open: records derived from this span later, on
        # OTHER threads (watchdog stall dumps), still name the right
        # job even while concurrent jobs hold different ambient cids
        self.cid = _cid_var.get()
        self.reported = False   # watchdog: this span already dumped
        self.grace = 1.0        # stall-timeout multiplier (COMPILE_GRACE
        #   for first-of-shape device spans; set by device_span)


class Tracer:
    """Thread-safe span recorder + group counts + stall watchdog.

    ``path=None`` runs watchdog/attribution only (no records written);
    ``stall_timeout=0`` disables the watchdog.  ``metrics`` (optional)
    receives the group table (``metrics.group_stats``), the degraded
    mark, and a "stall" event when the watchdog fires.
    """

    def __init__(self, path: Optional[str] = None,
                 stall_timeout: float = 0.0, metrics=None):
        self.path = path or None
        self.stall_timeout = max(float(stall_timeout or 0.0), 0.0)
        self.metrics = metrics
        # the group table lives on the Metrics object when there is one,
        # so Metrics.snapshot() carries it without a back-reference
        self.group_stats: Dict[str, dict] = (
            metrics.group_stats if metrics is not None else {})
        self.stalled = False
        self._stall_dumps = 0      # reports so far (rate-limit state)
        self._last_full_dump = -float("inf")
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()
        self._t0_wall = time.time()
        self._seen: set = set()
        # (group, shape) pairs whose first span has OPENED — drives the
        # watchdog's compile grace, so it is tracked at open (attribution
        # _seen is tracked at close, and only for attributed successes)
        self._grace_seen: set = set()
        self._open: Dict[int, Span] = {}
        self._sid = 0
        # per-thread open-span stack: nested child seconds accumulate
        # here so records can carry "self" (dur minus children) and the
        # stats stage breakdown does not double-count a device span
        # inside its enclosing sweep span
        self._tls = threading.local()
        self._f = open(self.path, "w", encoding="utf-8") \
            if self.path else None
        if self._f is not None or blackbox.get() is not None:
            # the meta record also opens the blackbox ring's story for
            # file-less tracers (serve's Tracer(None, ...))
            self._write({"ev": "meta", "pid": os.getpid(),
                         "ts": self._t0_wall,
                         "stall_timeout_s": self.stall_timeout})
        self._stop = threading.Event()
        self._wd: Optional[threading.Thread] = None
        if self.stall_timeout > 0:
            self._wd = threading.Thread(target=self._watch, daemon=True,
                                        name="ccsx-stall-watchdog")
            self._wd.start()

    # ---- record plumbing -------------------------------------------------

    def _write(self, rec: dict) -> None:
        if "cid" not in rec:
            cid = _cid_var.get()
            if cid is not None:
                rec["cid"] = cid
        # mirror into the crash-persistent ring (no-op when
        # CCSX_BLACKBOX is unset): the mmap'd copy is what survives a
        # SIGKILL that the per-record flush below cannot outrun
        blackbox.record(rec)
        f = self._f
        if f is None:
            return
        line = json.dumps(rec, default=str) + "\n"
        with self._lock:
            if self._f is None:
                return
            self._f.write(line)
            # flushed per record so a killed/hung run still leaves a
            # readable trace behind — the whole point of the recorder
            self._f.flush()

    def _push(self) -> None:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        st.append(0.0)

    def _pop(self, dur: float) -> float:
        """Close the top of this thread's span stack: credit ``dur`` to
        the parent, return the self time (dur minus nested children)."""
        st = self._tls.stack
        child = st.pop()
        if st:
            st[-1] += dur
        return dur - child

    def _span_rec(self, sp: Span, dur: float, **extra) -> dict:
        rec = {"ev": "span", "name": sp.name, "cat": sp.cat,
               "ts": round(sp.ts, 6),
               "mono": round(sp.t0 - self._t0, 6),
               "dur": round(dur, 6), "tid": sp.tid}
        if sp.cid is not None:
            rec["cid"] = sp.cid
        rec.update(extra)
        if sp.args:
            rec["args"] = sp.args
        return rec

    # ---- public span API -------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "host", **args):
        """A plain (non-device) span: always a profiler annotation;
        records only when a trace file is open or the blackbox ring is
        armed (CCSX_BLACKBOX)."""
        with annotation(name, args):
            if self._f is None and blackbox.get() is None:
                yield
                return
            sp = Span(-1, name, cat, args)
            self._push()
            try:
                yield
            except StopIteration:
                # generator-protocol control flow (a driver's span
                # around next(stream) hitting EOF), not an error
                raise
            except BaseException:
                sp.args = dict(sp.args, error=True)
                raise
            finally:
                dur = time.perf_counter() - sp.t0
                self_s = self._pop(dur)
                rec = self._span_rec(sp, dur)
                if self_s < dur - 1e-9:    # had children: carry self
                    rec["self"] = round(self_s, 6)
                self._write(rec)

    @contextlib.contextmanager
    def device_span(self, name: str, group: Optional[str] = None,
                    cells: int = 0, plan=None, shape=None,
                    attribute: bool = True, warmup: bool = False,
                    **args):
        """A device-dispatch span: a profiler annotation, watchdog-
        registered while open, counted in the group table at close.
        ``group`` keys the table; ``cells`` is the dispatched DP cell
        count; ``plan`` is the free-form slab/shape plan the watchdog
        dumps when the span stalls.  ``shape`` is the part of the
        dispatched shape the group key does NOT carry (e.g. the
        bucketed batch dim Z/R/N): jit recompiles per distinct shape,
        so a compile is detected per (group, shape) — a group whose
        batch dim oscillates shows compiles > 1.  A dispatch that raises
        is recorded (error=true) but NOT counted: the recovery ladder
        re-dispatches the work, and counting both the failed attempt
        and its retried halves would double-count cells.

        ``attribute=False`` makes a watchdog-visible span that stays
        OUT of the group table — the finish-phase materialization span:
        on an async runtime a dispatch span closes in ~1 ms and the
        actual hang surfaces later, when the finish callback blocks
        materializing the outputs, so that blocking wait must itself be
        a device span or the watchdog is blind to exactly the r5 hang.
        Attribution convention: only records carrying a "compile" key
        (true or false) enter group tables — failed and attribute=False
        spans carry none.

        ``warmup=True`` marks an AOT precompile span (pipeline/
        warmup.py): it consumes the (group, shape)'s compile slot — so
        the first REAL dispatch of a warmed shape books no compile, the
        trace-visible proof the compile overlapped the stream — and
        counts the compile in the group table WITHOUT counting a
        dispatch or cells (nothing was dispatched for a consumer).  A
        warmup span for an already-seen shape books nothing.  Warmup
        records carry top-level "warmup": true next to the "compile"
        key; the stats re-derivation applies the same rule
        (summarize)."""
        a = dict(args)
        key = group or name
        a["group"] = key
        if cells:
            a["cells"] = int(cells)
        if shape is not None:
            a["shape"] = shape
        if plan is not None:
            a["plan"] = plan
        with annotation(name, a):
            with self._lock:
                self._sid += 1
                sid = self._sid
            sp = Span(sid, name, "device", a)
            with self._lock:
                # first span of a (group, shape) is the compile
                # candidate: it gets COMPILE_GRACE x the stall timeout
                # (a cold compile can take minutes and is not a hang)
                gkey = (key, shape)
                if gkey not in self._grace_seen:
                    self._grace_seen.add(gkey)
                    sp.grace = COMPILE_GRACE
                self._open[sid] = sp
            # span-BEGIN mirror, ring only: a SIGKILL mid-dispatch never
            # reaches the close record below, so the begin entry is the
            # ONLY evidence of what was in flight — inflight() pairs it
            # with the close by (tid, name)
            bb = blackbox.get()
            if bb is not None:
                brec = {"ev": "begin", "name": name, "group": key,
                        "ts": round(sp.ts, 6), "tid": sp.tid}
                if shape is not None:
                    brec["shape"] = str(shape)
                if sp.cid is not None:
                    brec["cid"] = sp.cid
                bb.record(brec)
            pushed = self._f is not None
            if pushed:
                self._push()
            failed = False
            try:
                yield
            except BaseException:
                failed = True
                sp.args = dict(sp.args, error=True)
                raise
            finally:
                dur = time.perf_counter() - sp.t0
                # device spans are normally leaves (self == dur), but
                # keep the accounting honest if one acquires children
                self_s = self._pop(dur) if pushed else dur
                first = False
                with self._lock:
                    self._open.pop(sid, None)
                    if attribute and not failed:
                        skey = (key, shape)
                        first = skey not in self._seen
                        self._seen.add(skey)
                        st = self.group_stats.setdefault(key, {
                            "compiles": 0, "dispatches": 0,
                            "dp_cells": 0})
                        # the first call of a (group, shape) traces and
                        # compiles; a warmup books only that compile, a
                        # redundant warmup of a seen shape nothing
                        if first:
                            st["compiles"] += 1
                        if not warmup:
                            st["dispatches"] += 1
                            st["dp_cells"] += int(cells or 0)
                if failed or not attribute:
                    rec = self._span_rec(sp, dur)
                elif warmup:
                    rec = self._span_rec(sp, dur, compile=first,
                                         warmup=True)
                else:
                    rec = self._span_rec(sp, dur, compile=first)
                if self_s < dur - 1e-9:
                    rec["self"] = round(self_s, 6)
                self._write(rec)

    def instant(self, name: str, cat: str = "host", **args) -> None:
        """A zero-duration marker (Chrome 'instant' event)."""
        if self._f is None and blackbox.get() is None:
            return
        rec = {"ev": "instant", "name": name, "cat": cat,
               "ts": round(time.time(), 6),
               "mono": round(time.perf_counter() - self._t0, 6),
               "tid": threading.current_thread().name}
        if args:
            rec["args"] = args
        self._write(rec)

    # ---- stall watchdog --------------------------------------------------

    def _watch(self) -> None:
        # check at timeout/4 so a stall is reported within one timeout
        # interval of exceeding it (bounded below for tiny test timeouts)
        interval = max(0.05, min(self.stall_timeout / 4.0, 5.0))
        while not self._stop.wait(interval):
            now = time.perf_counter()
            with self._lock:
                stalled = [s for s in self._open.values()
                           if not s.reported
                           and now - s.t0 > self.stall_timeout * s.grace]
                for s in stalled:
                    s.reported = True
            for s in stalled:
                self._stall_dump(s, now - s.t0)

    def _stall_dump(self, sp: Span, age: float) -> None:
        """The actionable hang report — stderr + trace file + metrics
        stream, then the run is marked degraded.  Rate-limited: the
        first report is the FULL dump (all thread stacks, the in-flight
        shape group/plan, a metrics snapshot); reports within
        FULL_DUMP_EVERY_S of the last full dump are compact one-liners
        (a long genuine hang stalls span after span, and megabytes of
        identical stacks help nobody)."""
        self.stalled = True
        now = time.perf_counter()
        full = now - self._last_full_dump >= FULL_DUMP_EVERY_S
        self._stall_dumps += 1
        if self.metrics is not None:
            # the watchdog thread runs concurrently with driver/pool
            # bump()s — take the counter lock like every other writer
            self.metrics.bump(stalls=1)
        if full:
            self._last_full_dump = now
            names = {t.ident: t.name for t in threading.enumerate()}
            stacks = {}
            for tid, frame in sys._current_frames().items():
                label = f"{names.get(tid, '?')}({tid})"
                stacks[label] = "".join(traceback.format_stack(frame))
            snap = (self.metrics.snapshot()
                    if self.metrics is not None else {})
            out = [
                f"[ccsx-tpu] STALL WATCHDOG: device dispatch {sp.name!r} "
                f"group={sp.args.get('group')!r} open for {age:.1f}s "
                f"(> {self.stall_timeout * sp.grace:g}s stall budget"
                + (f" = {sp.grace:g}x compile grace"
                   if sp.grace > 1 else "")
                + ") — dumping state",
                f"[ccsx-tpu]   in-flight: "
                f"args={json.dumps(sp.args, default=str)}",
            ]
            for label, stack in stacks.items():
                out.append(f"[ccsx-tpu]   -- thread {label} --")
                out.append(stack.rstrip("\n"))
            out.append(f"[ccsx-tpu]   metrics: "
                       f"{json.dumps(snap, default=str)}")
            print("\n".join(out), file=sys.stderr)
        else:
            print(f"[ccsx-tpu] STALL WATCHDOG: dispatch {sp.name!r} "
                  f"group={sp.args.get('group')!r} open {age:.1f}s "
                  f"(report #{self._stall_dumps}; full dump above, "
                  "compact repeat)", file=sys.stderr)
        sys.stderr.flush()
        rec = {"ev": "stall", "name": sp.name,
               "group": sp.args.get("group"),
               "open_s": round(age, 3),
               "ts": round(time.time(), 6),
               "mono": round(time.perf_counter() - self._t0, 6),
               "tid": sp.tid, "args": sp.args}
        if sp.cid is not None:
            # the watchdog thread has no ambient scope: the stalled
            # span's captured cid names the job that hung
            rec["cid"] = sp.cid
        if full:
            rec["stacks"] = {k: v[-4000:] for k, v in stacks.items()}
        else:
            rec["repeat"] = self._stall_dumps
        self._write(rec)
        if self.metrics is not None:
            self.metrics.degraded = (
                f"stall watchdog fired: dispatch {sp.name} "
                f"group={sp.args.get('group')} open > "
                f"{self.stall_timeout * sp.grace:g}s")
            self.metrics.emit("stall", span=sp.name,
                              group=sp.args.get("group"),
                              open_s=round(age, 3),
                              **({} if full
                                 else {"repeat": self._stall_dumps}))

    # ---- lifecycle -------------------------------------------------------

    def close(self) -> None:
        """Stop the watchdog, close the JSONL, write the Chrome export."""
        self._stop.set()
        if self._wd is not None:
            self._wd.join(timeout=10.0)
            self._wd = None
        with self._lock:
            f, self._f = self._f, None
        if f is None:
            return
        try:
            f.close()
        except OSError:
            pass
        try:
            export_chrome(self.path)
        except (OSError, ValueError) as e:
            print(f"[ccsx-tpu] trace: Chrome export failed: {e}",
                  file=sys.stderr)


# ---- module-level shims (profiler annotations only when no tracer is
# ---- installed) ------------------------------------------------------------

def span(name: str, cat: str = "host", **args):
    t = _current
    if t is None:
        return annotation(name, args)
    return t.span(name, cat, **args)


def device_span(name: str, group: Optional[str] = None, cells: int = 0,
                plan=None, warmup: bool = False, **args):
    t = _current
    if t is None:
        return annotation(name, dict(args, group=group or name))
    return t.device_span(name, group=group, cells=cells, plan=plan,
                         warmup=warmup, **args)


def instant(name: str, cat: str = "host", **args) -> None:
    t = _current
    if t is not None:
        t.instant(name, cat, **args)


# ---- Chrome trace-event export --------------------------------------------

def chrome_path(path: str) -> str:
    base = path[:-6] if path.endswith(".jsonl") else path
    return base + ".chrome.json"


def export_chrome(path: str) -> str:
    """Convert a span JSONL into Chrome trace-event JSON (the {"
    traceEvents": [...]} object format Perfetto and chrome://tracing
    load).  Streams line by line at BOTH ends — one event in memory at
    a time — so the export of a million-hole trace cannot OOM the
    process after an otherwise-successful run.  Returns the output
    path."""
    out = chrome_path(path)
    pid = os.getpid()
    tids: Dict[str, int] = {}

    with open(path, encoding="utf-8") as f, \
            open(out, "w", encoding="utf-8") as fo:
        fo.write('{"displayTimeUnit": "ms", "traceEvents": [')
        n = 0

        def emit(e):
            nonlocal n
            fo.write(("," if n else "") + json.dumps(e))
            n += 1

        def tid_of(name):
            if name not in tids:
                tids[name] = len(tids) + 1
                emit({"ph": "M", "name": "thread_name", "pid": pid,
                      "tid": tids[name], "args": {"name": name}})
            return tids[name]

        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            ev = rec.get("ev")
            if ev == "meta":
                pid = rec.get("pid", pid)
                emit({"ph": "M", "name": "process_name",
                      "pid": pid, "tid": 0,
                      "args": {"name": "ccsx-tpu"}})
            elif ev == "span":
                args = dict(rec.get("args", {}))
                if rec.get("compile"):
                    args["compile"] = True
                if rec.get("warmup"):
                    args["warmup"] = True
                emit({
                    "ph": "X", "name": rec["name"], "cat": rec["cat"],
                    "ts": round(rec["mono"] * 1e6, 3),
                    "dur": round(rec["dur"] * 1e6, 3),
                    "pid": pid, "tid": tid_of(rec.get("tid", "main")),
                    "args": args})
            elif ev == "instant":
                emit({
                    "ph": "i", "s": "t", "name": rec["name"],
                    "cat": rec.get("cat", "host"),
                    "ts": round(rec["mono"] * 1e6, 3), "pid": pid,
                    "tid": tid_of(rec.get("tid", "main")),
                    "args": rec.get("args", {})})
            elif ev == "stall":
                emit({
                    "ph": "i", "s": "g",
                    "name": f"STALL: {rec.get('group')}", "cat": "device",
                    "ts": round(rec["mono"] * 1e6, 3), "pid": pid,
                    "tid": tid_of(rec.get("tid", "main")),
                    "args": {"open_s": rec.get("open_s")}})
        fo.write("]}")
    return out


def finalize_group_table(raw: Dict[str, dict]) -> dict:
    """Render raw per-group counts (compiles/dispatches/dp_cells) for
    output, sorted by group.  THE one finalizer: Metrics._group_table
    (metrics events) and summarize() (trace files) both call it, so the
    'same' table from either source cannot drift.  Device seconds per
    program come from a profiler trace (module docstring), not from
    the host clock around an asynchronous dispatch."""
    return {key: {"compiles": st["compiles"],
                  "dispatches": st["dispatches"],
                  "dp_cells": st["dp_cells"]}
            for key, st in sorted(raw.items())}


# ---- `ccsx-tpu stats`: summarize trace/metrics JSONL artifacts ------------

def summarize(paths, top: int = 10) -> dict:
    """Digest any mix of trace JSONL and metrics JSONL files (records
    are distinguished per line: trace records carry "ev", metrics
    events carry "event") into the group table, stage breakdown,
    occupancy recap, and top-N slowest device dispatches.  One
    streaming pass — running sums plus a bounded min-heap for the
    slowest list — so summarizing a million-hole trace cannot OOM the
    process (the same discipline export_chrome applies)."""
    stalls = []
    final = None
    last_metrics = None
    n_spans = 0
    groups: Dict[str, dict] = {}
    stages: Dict[str, float] = {}
    slow_heap: list = []    # min-heap of (dur, seq, rendered entry)
    seq = 0
    for path in paths:
        with open(path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("ev") == "stall":
                    stalls.append(rec)
                    continue
                if "event" in rec:
                    last_metrics = rec
                    if rec["event"] == "final":
                        final = rec
                    continue
                if rec.get("ev") != "span":
                    continue
                sp = rec
                n_spans += 1
                # "self" (dur minus nested children) keeps the category
                # sums disjoint: a sweep span must not re-count the
                # device spans recorded inside it
                stages[sp["cat"]] = (stages.get(sp["cat"], 0.0)
                                     + sp.get("self", sp["dur"]))
                if sp["cat"] != "device":
                    continue
                entry = {
                    "dur_s": round(sp["dur"], 4),
                    "group": str(sp.get("args", {}).get("group",
                                                        sp["name"])),
                    "compile": bool(sp.get("compile")),
                    "at_s": round(sp["mono"], 3), "tid": sp.get("tid"),
                }
                seq += 1
                if len(slow_heap) < top:
                    heapq.heappush(slow_heap, (sp["dur"], seq, entry))
                elif slow_heap and sp["dur"] > slow_heap[0][0]:
                    heapq.heapreplace(slow_heap, (sp["dur"], seq, entry))
                if "compile" not in sp:
                    # failed or attribute=False (materialize) spans: in
                    # the timeline and the slowest list, NOT in the
                    # group table — the same rule device_span applied
                    # to Metrics.group_stats
                    continue
                key = str(sp.get("args", {}).get("group", sp["name"]))
                st = groups.setdefault(key, {
                    "compiles": 0, "dispatches": 0, "dp_cells": 0})
                if sp["compile"]:
                    st["compiles"] += 1
                if sp.get("warmup"):
                    # AOT warmup span (pipeline/warmup.py): the shape's
                    # compile, no dispatch — same rule device_span
                    # applied to Metrics.group_stats
                    continue
                st["dispatches"] += 1
                st["dp_cells"] += int(sp.get("args", {}).get("cells", 0))
    groups = finalize_group_table(groups)

    mrec = final or last_metrics
    occupancy = {}
    resilience = {}
    if mrec:
        for k in OCCUPANCY_KEYS:
            if mrec.get(k) is not None:
                occupancy[k] = mrec[k]
        for k in RESILIENCE_KEYS:
            if mrec.get(k) is not None:
                resilience[k] = mrec[k]
        if mrec.get("breaker_strike_log"):
            resilience["breaker_strike_log"] = \
                mrec["breaker_strike_log"]
    slowest = [e for _, _, e in
               sorted(slow_heap, key=lambda t: (-t[0], t[1]))]
    return {
        "paths": list(paths),
        "groups": groups or (mrec or {}).get("groups") or {},
        "stage_seconds": {k: round(v, 4)
                          for k, v in sorted(stages.items())},
        "slowest": slowest,
        "occupancy": occupancy,
        "resilience": resilience,
        "stalls": [{"group": s.get("group"), "open_s": s.get("open_s")}
                   for s in stalls],
        "degraded": (mrec or {}).get("degraded"),
        "n_spans": n_spans,
    }


def format_summary(d: dict) -> str:
    lines = [f"== ccsx-tpu stats: {' '.join(d['paths'])} =="]
    lines.append(f"spans: {d['n_spans']}")
    if d["groups"]:
        lines.append("shape groups:")
        lines.append(f"  {'group':<40} {'compiles':>8} {'disp':>6} "
                     f"{'dp_cells':>14}")
        for key, st in sorted(d["groups"].items()):
            lines.append(
                f"  {key:<40} {st['compiles']:>8} "
                f"{st['dispatches']:>6} {st['dp_cells']:>14}")
        # compile-storm guard (the r7 finding: packed groups paying 4-5
        # compiles each, one per distinct tail-slab R, invisible until
        # traced).  Canonical slab shapes bound a packed group to the
        # ladder size (default 2, --slab-shape-ladder); anything above
        # 1 deserves eyes, anything above 2 is the storm come back
        storms = {k: st["compiles"] for k, st in d["groups"].items()
                  if st["compiles"] > 1}
        if storms:
            worst = max(storms.items(), key=lambda kv: kv[1])
            bang = "!!" * 10 if worst[1] > 2 else "!!"
            lines.append(
                f"  {bang} compiles>1 in steady state: {len(storms)} "
                f"group(s) recompiled (worst {worst[0]} x{worst[1]}) — "
                "canonical-ladder budget is 2 (--slab-shape-ladder); "
                f">2 means the r7 compile storm is back {bang}")
    if d["stage_seconds"]:
        lines.append("stage breakdown (span self-seconds by category; "
                     "nested children excluded):")
        lines.append("  " + "  ".join(
            f"{k}={v:.4f}" for k, v in d["stage_seconds"].items()))
    if d["slowest"]:
        lines.append(f"top {len(d['slowest'])} slowest device dispatches:")
        for i, s in enumerate(d["slowest"], 1):
            tag = " (compile)" if s["compile"] else ""
            lines.append(f"  {i:>2}. {s['dur_s']:.4f}s {s['group']}{tag} "
                         f"@{s['at_s']}s [{s['tid']}]")
    if d["occupancy"]:
        lines.append("occupancy recap: " + "  ".join(
            f"{k}={v}" for k, v in d["occupancy"].items()))
    res = d.get("resilience") or {}
    # only worth a line when something actually happened (hangs, trips,
    # fallbacks, quarantines, salvaged input corruption) or the breaker
    # is not in its rest state
    if res and (any(res.get(k) for k in
                    ("device_hangs", "breaker_trips", "host_fallbacks",
                     "oom_resplits", "holes_failed", "holes_corrupt",
                     "stalls"))
                or res.get("breaker_state", "closed") != "closed"):
        lines.append("resilience recap: " + "  ".join(
            f"{k}={v}" for k, v in res.items()
            if k != "breaker_strike_log"))
        for s in res.get("breaker_strike_log", []):
            lines.append(f"  strike: kind={s.get('kind')} "
                         f"group={s.get('group')} ts={s.get('ts')}")
    for s in d["stalls"]:
        lines.append(f"STALL: group={s['group']} open_s={s['open_s']}")
    lines.append(f"degraded: {d['degraded'] or 'none'}")
    return "\n".join(lines)


def stats_main(argv) -> int:
    """The `ccsx-tpu stats` subcommand (dispatched from cli.main)."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="ccsx-tpu stats",
        description="Summarize trace/metrics JSONL artifacts: shape-group "
                    "attribution, stage breakdown, occupancy recap, "
                    "slowest dispatches.")
    ap.add_argument("paths", nargs="+",
                    help="trace (--trace) and/or metrics (--metrics) "
                         "JSONL files; any mix")
    ap.add_argument("--top", type=int, default=10,
                    help="slowest dispatches to list [10]")
    ap.add_argument("--json", default=None,
                    help="also write the summary as JSON to this path")
    a = ap.parse_args(argv)
    try:
        d = summarize(a.paths, top=a.top)
    except OSError as e:
        print(f"Error: stats: {e}", file=sys.stderr)
        return 1
    print(format_summary(d))
    if a.json:
        with open(a.json, "w", encoding="utf-8") as f:
            json.dump(d, f, indent=1, default=str)
    return 0
