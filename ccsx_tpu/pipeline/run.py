"""End-to-end driver: input stream -> consensus -> ordered FASTA output.

The reference overlaps read/compute/write with a 3-step ordered pipeline
(kt_pipeline, main.c:856) and fans compute out over threads (kt_for,
main.c:702-704).  Here: a bounded thread pool computes holes concurrently
while the writer drains futures strictly in submission order, so output is
`>movie/hole/ccs` in input order (main.c:714) for any thread count.
"""

from __future__ import annotations

import collections
import os
import sys
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

from ccsx_tpu.config import CcsConfig
from ccsx_tpu.consensus.align_host import HostAligner
from ccsx_tpu.consensus.hole import ccs_hole
from ccsx_tpu.io import bam as bam_mod
from ccsx_tpu.io import fastx, zmw
from ccsx_tpu.io.corruption import CorruptionError, SalvageSink
from ccsx_tpu.utils import faultinject
from ccsx_tpu.utils import trace
from ccsx_tpu.utils.device import resolve_device
from ccsx_tpu.utils.journal import Journal
from ccsx_tpu.utils.metrics import (FailureBudgetExceeded, Metrics,
                                    check_failure_budget)


def open_zmw_stream(path: str, cfg: CcsConfig, metrics=None):
    """Filtered ZMW iterator for BAM or FASTA/Q input ('-' = stdin).

    Uses the native C++ streamer (parser + group-by-hole + filters in one
    pass, ccsx_tpu/native) when the library is available and the input is a
    real path; otherwise the pure-Python parsers.  Opens the file eagerly —
    the parsers are generators, and a deferred open() would crash past the
    caller's error handling.  ``metrics`` (optional) receives the
    filtered-hole accounting from either path: per-hole live on the
    Python path, reason-bucketed at EOF from the native reader.

    ``cfg.salvage`` selects salvage-mode ingest on whichever stack
    serves: classified corruption is booked into Metrics
    (holes_corrupt + corrupt_reasons + the degraded mark) and resynced
    past instead of killing the stream (io/corruption.py).
    """
    from ccsx_tpu import native

    salvage = bool(getattr(cfg, "salvage", False))
    if path != "-" and native.available():
        from ccsx_tpu.native.io import stream_zmws_prefetch

        return stream_zmws_prefetch(path, cfg, metrics=metrics)
    sink = SalvageSink(metrics, getattr(cfg, "max_record_bytes", 0)) \
        if salvage else None
    if cfg.is_bam:
        if path == "-":
            records = bam_mod.read_bam_records(
                sys.stdin.buffer, salvage=sink,
                max_record_bytes=getattr(cfg, "max_record_bytes", 0))
        else:
            open(path, "rb").close()   # eager-open contract (OSError now)
            records = bam_mod.read_bam_records(
                path, salvage=sink,
                max_record_bytes=getattr(cfg, "max_record_bytes", 0))
    else:
        f = sys.stdin.buffer if path == "-" else open(path, "rb")
        records = fastx.read_fastx(f, salvage=sink)
    return zmw.stream_zmws(records, cfg, metrics=metrics, salvage=sink)


def guarded_stream(stream, cfg: CcsConfig, metrics, guard=None):
    """The drivers' shared ingest guard, wrapped around any open ZMW
    stream (single-process, batched, and sharded drivers all route
    ingestion through here — prep-pool workers included, since the
    pool consumes the wrapped iterator):

    * graceful drain: once ``guard.requested`` (SIGTERM/SIGINT,
      utils/drain.py) the stream reports exhausted — admission stops,
      in-flight work finishes, and the driver exits RC_INTERRUPTED;
    * the ``input_corrupt`` fault point (utils/faultinject.py): with
      --salvage the injected corruption drops that one hole and the
      stream CONTINUES; without it, the clean rc-1 path;
    * the salvage rung for classified corruption raised by the stream
      itself (e.g. the range-sharded reader, which classifies but has
      no resync): with --salvage the event is booked and the stream
      ENDS there — a generator that raised is closed, so the remaining
      range is lost either way; booking + rc 0 degraded beats killing
      the whole run.  (The salvage-mode readers resync internally and
      never raise here.)
    * an absolute --max-failed-holes budget is re-checked per admitted
      hole, so reader-booked corruption events (which bypass the
      drivers' per-failure checks) abort the ingest promptly instead
      of salvage-scanning the whole file first.
    """
    sink = SalvageSink(metrics) if getattr(cfg, "salvage", False) \
        else None
    it = iter(stream)
    while True:
        if guard is not None and guard.requested:
            return
        try:
            z = next(it)
        except StopIteration:
            return
        except CorruptionError as e:
            if sink is None:
                raise
            sink.record(e.reason)
            print(f"[ccsx-tpu] salvage: classified corruption from the "
                  f"stream ({e.reason}: {e}); ending ingestion — "
                  "emitting what was salvaged", file=sys.stderr)
            return
        try:
            faultinject.fire("input_corrupt")
        except CorruptionError as e:
            if sink is None:
                raise
            sink.record(e.reason)
            print(f"[ccsx-tpu] salvage: dropped corrupt input unit "
                  f"({e.reason}: {e})", file=sys.stderr)
            continue
        # count-form budgets abort mid-ingest (fractions settle at end
        # of run where the denominator is final)
        check_failure_budget(metrics, cfg)
        yield z


def count_raw_holes(in_path: str, cfg: CcsConfig) -> int:
    """RAW hole count of the input — the fleet scheduler's range-table
    denominator (pipeline/fleet.py).  BAM inputs use (or build) the
    BGZF hole index sidecar; FASTA/Q inputs take one name-only counting
    pass using the same consecutive-(movie,hole) keying as the sharded
    BAM indexer, so range-table ordinals always line up with what
    ``slice_raw_holes`` streams."""
    from ccsx_tpu.io import bamindex

    if cfg.is_bam:
        idx = bamindex.load_index(in_path) or bamindex.build_index(
            in_path,
            max_record_bytes=getattr(cfg, "max_record_bytes", 0))
        return idx["n_holes"]
    n = 0
    prev = None
    with open(in_path, "rb") as f:
        for rec in fastx.read_fastx(f):
            key = bamindex._hole_key(rec.name)
            if key != prev:
                n += 1
                prev = key
    return n


def slice_raw_holes(records, lo: int, hi: int):
    """Pass through only the records of raw holes [lo, hi) — the
    FASTA/Q twin of bamindex.read_hole_range (which seeks; plain text
    cannot, so the lead-in is parsed and dropped).  Stops at hole hi,
    so a front range never pays for the file's tail."""
    from ccsx_tpu.io import bamindex

    if lo >= hi:
        return
    seen = -1
    prev = None
    for rec in records:
        key = bamindex._hole_key(rec.name)
        if key != prev:
            seen += 1
            prev = key
            if seen >= hi:
                return
        if seen >= lo:
            yield rec


def holes_total_hint(in_path: str, cfg: CcsConfig):
    """RAW hole count of the input when cheaply knowable (the BGZF hole
    index sidecar, `ccsx-tpu --make-index`), else None — feeds the
    progress/ETA estimator's total (Metrics.holes_total).  Raw holes:
    filtered holes count toward progress `done`, so the basis matches."""
    if not cfg.is_bam or in_path == "-" or not os.path.exists(in_path):
        return None
    try:
        from ccsx_tpu.io import bamindex

        idx = bamindex.load_index(in_path)
    except (OSError, ValueError):
        return None
    return idx["n_holes"] if idx else None


class _PyWriter:
    """FASTA/FASTQ writer over a Python file object (stdout / fallback /
    journaled runs).  Tracks ``bytes_out`` — the exact output size after
    every record — which the journal records as its torn-tail recovery
    offset; the shared fastx.format_record counts UTF-8-encoded bytes,
    not len(str), so a non-ASCII read name (split_name accepts any
    movie string) cannot skew the offset and mis-truncate a resume."""

    def __init__(self, f, own: bool, start_bytes: int = 0):
        self._f = f
        self._own = own
        self.bytes_out = start_bytes

    def put(self, name: str, seq: bytes, qual: bytes | None = None) -> None:
        # disk_full fault point (ENOSPC): fires BEFORE any bytes land,
        # so the journaled offset stays behind the durable output and a
        # resume recomputes the interrupted hole (no torn record past
        # the cursor)
        faultinject.fire("disk_full")
        rec, nbytes = fastx.format_record(name, seq, qual)
        self._f.write(rec)
        self.bytes_out += nbytes

    def flush(self) -> None:
        self._f.flush()

    def close(self) -> None:
        if self._own:
            self._f.close()


def open_writer(path: str, append: bool, bam: bool = False,
                journaled: bool = False):
    """Async native writer for real paths; Python writer for stdout;
    buffered BAM writer under --bam.

    stdout stays Python-level so redirection (tests, `ccsx-tpu ... -`) works.
    ``journaled`` runs also use the Python writer: the journal's crash
    contract needs a synchronous, flushable stream with byte accounting
    (the record must be durable before the journal cursor claims it),
    which the async native writer cannot order — and write time is ~0%
    of wall (ARCHITECTURE.md stage attribution), so nothing is lost.
    """
    from ccsx_tpu import native

    if bam:
        if path == "-":
            raise OSError("--bam output requires a file path, not stdout")
        if append:
            raise OSError("--bam output does not support --journal resume "
                          "(the BGZF container cannot be appended)")
        return bam_mod.BamWriter(path)
    if path != "-" and native.available() and not journaled:
        from ccsx_tpu.native.io import NativeFastaWriter

        return NativeFastaWriter(path, append=append)
    if path == "-":
        return _PyWriter(sys.stdout, own=False)
    start = os.path.getsize(path) if append and os.path.exists(path) else 0
    # UTF-8 pinned (not the locale default) so bytes_out's encode-based
    # accounting always matches what reaches the file
    return _PyWriter(open(path, "a" if append else "w", encoding="utf-8"),
                     own=True, start_bytes=start)


def run_pipeline(in_path: str, out_path: str, cfg: CcsConfig,
                 journal_path: Optional[str] = None) -> int:
    if getattr(cfg, "prep_threads", None):
        # the per-hole path already overlaps prep with compute through
        # its -j worker pool (each worker preps + computes whole holes);
        # the prep plane is a batched-scheduler construct
        print("[ccsx-tpu] --prep-threads has no effect with --batch off "
              "(use -j; the per-hole path overlaps prep per worker)",
              file=sys.stderr)
    # metrics constructed before the stream so both ingest paths can
    # book their filtered-hole accounting into it
    metrics = Metrics(verbose=cfg.verbose, stream=cfg.metrics_stream())
    metrics.holes_total = holes_total_hint(in_path, cfg)
    try:
        stream = open_zmw_stream(in_path, cfg, metrics=metrics)
    except (OSError, RuntimeError) as e:
        print(f"Error: Failed to open infile! ({e})", file=sys.stderr)
        metrics.close_stream()  # no final event for a non-run
        return 1
    # load under this run's fingerprint + reconcile the output tail
    # (truncate torn / refuse untrustworthy) before the writer opens
    journal = Journal.for_run(journal_path, in_path, cfg, out_path)
    resume = journal.holes_done
    # restore the journaled failure count so --max-failed-holes is
    # judged over the whole logical run, resumes included
    metrics.holes_failed = journal.holes_failed
    metrics.holes_prior_emitted = journal.holes_emitted
    try:
        writer = open_writer(out_path, append=bool(resume),
                             bam=cfg.bam_out,
                             journaled=bool(journal_path))
    except OSError as e:
        print(f"Cannot open file for write! ({e})", file=sys.stderr)
        metrics.close_stream()
        return 1

    resolve_device(cfg.device)
    aligner = HostAligner(cfg.align)

    def compute(z):
        stats: dict = {}
        try:
            faultinject.fire("compute")
            with trace.span("hole_compute", cat="compute",
                            hole=str(z.hole)):
                return z, ccs_hole(z, aligner, cfg, stats), None, stats
        except Exception as e:  # quarantine: one bad hole must not kill the run
            return z, None, e, stats

    def write_result(item):
        z, rec, err, stats = item
        # per-hole counters aggregated here (driver side) so worker
        # threads never touch the Metrics object concurrently.
        # device_dispatches is a lower-bound estimate on this path: each
        # window runs >=1 refinement round of 3 jitted calls (aligner,
        # projector, voter); the batched executor's count is exact (one
        # fused dispatch per shape group)
        metrics.windows += stats.get("windows", 0)
        metrics.bump(window_growths=stats.get("window_growths", 0),
                     window_forced_flushes=stats.get(
                         "window_forced_flushes", 0))
        metrics.device_dispatches += 3 * stats.get("windows", 0)
        wrote = False
        with metrics.timer("write"), \
                trace.span("write_record", cat="write"):
            if err is not None:
                metrics.holes_failed += 1
                print(f"[ccsx-tpu] hole {z.movie}/{z.hole} failed: {err}",
                      file=sys.stderr)
                # failure-rate abort (--max-failed-holes): a count
                # budget aborts immediately, a fraction budget settles
                # at end of run (utils/metrics.py)
                check_failure_budget(metrics, cfg)
            elif rec is not None and rec[0]:
                writer.put(f"{z.movie}/{z.hole}/ccs", rec[0], rec[1])
                metrics.holes_out += 1
                wrote = True
        # flush-before-cursor + write fault point + advance: the shared
        # crash invariant lives in Journal.retire
        journal.retire(writer, wrote, metrics)
        # deterministic drain testing: a real SIGTERM delivered at a
        # retirement point (the graceful-drain acceptance case)
        faultinject.fire("sigterm")
        metrics.tick()

    rc = 0
    pool = ThreadPoolExecutor(max_workers=max(cfg.threads, 1)) \
        if cfg.threads > 1 else None
    pending = collections.deque()
    # graceful drain (utils/drain.py): SIGTERM/SIGINT stop admission;
    # in-flight holes finish, writer + journal settle, rc 75 resumable
    from ccsx_tpu.utils.drain import DrainGuard

    guard = DrainGuard.install()
    stream = guarded_stream(stream, cfg, metrics, guard)
    # flight recorder: the per-hole path has no batched device-dispatch
    # spans for the watchdog to watch (host compute dominates), but the
    # span trace — ingest, per-hole compute (worker threads included),
    # host pair alignments, writes, journal updates — records the same
    # taxonomy the batched driver does.  Constructed INSIDE the try
    # (finally tolerates tracer=None) so neither a watchdog thread nor
    # an open trace file can leak, and an unwritable --trace path gets
    # the same polite rc-1 refusal as an unwritable output path
    tracer = None
    telem = None
    try:
        try:
            tracer = trace.Tracer(cfg.trace_path,
                                  stall_timeout=cfg.stall_timeout_s,
                                  metrics=metrics)
        except OSError as e:
            print(f"Cannot open trace file for write! ({e})",
                  file=sys.stderr)
            return 1
        trace.install(tracer)
        # live telemetry endpoints (--telemetry-port; None when off —
        # a bind failure degrades to a warning, never kills the run)
        if cfg.telemetry_port:
            from ccsx_tpu.utils import telemetry

            telem = telemetry.start(metrics, cfg.telemetry_port)
        while True:
            try:
                with metrics.timer("ingest"), \
                        trace.span("ingest_hole", cat="ingest"):
                    z = next(stream)
                    faultinject.fire("ingest")
            except StopIteration:
                break
            metrics.holes_in += 1
            if metrics.holes_in <= resume:
                continue  # already written in a previous run
            metrics.heartbeat()
            if pool is None:
                with metrics.timer("compute"):
                    item = compute(z)
                write_result(item)
            else:
                pending.append(pool.submit(compute, z))
                # bounded window keeps memory flat; drain in order
                while len(pending) > 2 * cfg.threads:
                    with metrics.timer("compute"):
                        item = pending.popleft().result()
                    write_result(item)
        while pending:
            with metrics.timer("compute"):
                item = pending.popleft().result()
            write_result(item)
        # fraction-form --max-failed-holes settles at end of run — but
        # not on a drain: the denominator is a partial run's
        if not guard.requested:
            check_failure_budget(metrics, cfg, final=True)
    except FailureBudgetExceeded as e:
        from ccsx_tpu import exitcodes

        print(f"Error: {e}; aborting instead of emitting a degraded "
              "output at rc 0", file=sys.stderr)
        rc = exitcodes.RC_FAILED_HOLES
    except (bam_mod.BamError, zmw.InvalidZmwName, ValueError) as e:
        print(f"Error: invalid input stream: {e}", file=sys.stderr)
        rc = 1
    except OSError as e:
        print(f"Error: write failed: {e}", file=sys.stderr)
        rc = 1
    finally:
        guard.restore()
        if pool is not None:
            pool.shutdown(wait=True)
        try:
            writer.close()
        except OSError as e:
            print(f"Error: write failed! ({e})", file=sys.stderr)
            rc = 1
        # settle the (possibly rate-limit-lagging) cursor AFTER the
        # writer has made the records durable
        journal.close()
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
