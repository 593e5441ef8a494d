"""ccsx-compatible CLI (reference: main.c:723-870).

Same flags and conventions as the reference's getopt loop
("hm:M:c:j:X:PAv", main.c:758): positional INPUT OUTPUT with '-'/stdin/
stdout, -A for FASTA/Q, -P for whole-read (primitive) mode, -X hole
exclusion, -c >= 3 enforced.  TPU-era extensions are long options.
"""

from __future__ import annotations

import argparse
import sys
from typing import Optional

from ccsx_tpu.config import CcsConfig


USAGE = """\
Program: ccsx-tpu
Version: 1.0.0
Usage  : ccsx-tpu  [options] <INPUT> <OUTPUT>
Generate circular consensus sequences (ccs) from subreads.

Options:
-h             Output this help
-v             debug
-m     <int>   Minimum total length of subreads in a hole to use for generating CCS. [5000]
-M     <int>   Maximum total length of subreads in a hole to use for generating CCS. [500000]
-c     <int>   Minimum number of subreads required to generate CCS. [3]
-A             For fasta/fastq input,gzip allowed
-P             primitive bsalign,subread shred by default
-X\t\t<str>   Exclude ZMWs from output file,a comma-separated list of ID
-j     <int>   Number of threads to use. [2]

Arguments:
input          Input file.
output         Output file.

TPU extensions (long options):
--device {auto,tpu,cpu}   --batch {auto,on,off}   --inflight <int>
--mesh D,P                --fastq                 --bam
--refine-iters <int>      --max-passes <int>      --window-growth {flush,grow}
--journal <path>          --metrics <path>        --profile <dir>
--trace <path>            (dispatch flight recorder: span JSONL +
                           Chrome/Perfetto trace export; never blocks a
                           dispatch.  The per-shape-group compile and
                           dispatch counts ride every --metrics event.
                           Device time per program and per stage comes
                           from a profiler trace (--profile), which
                           names the ccsx.* spans, ccsx_* programs and
                           the fill/traceback/vote/breakpoint scopes)
--stall-timeout <sec>     (hang watchdog: a device dispatch open this
                           long dumps all thread stacks + the in-flight
                           shape group and marks the run degraded;
                           first-of-shape dispatches get 10x the budget
                           for cold compiles; 0 disables) [120]
--telemetry-port <port>   (live telemetry endpoints for the run: GET
                           /metrics Prometheus text, /healthz
                           ok|degraded incl. stall/fallback detail,
                           /progress JSON with the windowed-rate ETA;
                           auto-bumps when taken, per-rank offset under
                           --hosts; 0 = off) [0]
--dispatch-deadline <sec> (bounded-wait device dispatch: a call open
                           past the deadline is ABANDONED — thread
                           parked, result discarded — and its group
                           replays on the bit-exact host path; first
                           call of a shape gets 10x for cold compiles;
                           0 = off: a wedged dispatch stalls forever,
                           today's behavior) [0]
--breaker-strikes <int>   (backend circuit breaker: this many device
                           failures — hangs, OOM ladder-bottoms,
                           compile failures — within 60s trip the
                           breaker and remaining work runs on the host
                           path; 0 disables) [3]
--breaker-probe-s <sec>   (half-open re-probe interval for a tripped
                           breaker: one group is dispatched as a probe,
                           success closes the breaker; 0 = stay open
                           for the rest of the run) [0]
--max-failed-holes <v>    (failure-rate abort: an integer count >= 0
                           or a fraction in (0,1) of processed holes;
                           exceeding it exits rc 2 instead of emitting
                           a near-empty output at rc 0) [unbounded]
--salvage                 (hostile-input salvage: classified input
                           corruption — torn BGZF blocks, corrupt BAM
                           records, truncated FASTQ, bad ZMW names —
                           is booked, the reader RESYNCS, and every
                           undamaged hole still emits; the run exits 0
                           marked degraded, corrupt holes spend the
                           --max-failed-holes budget.  Off = today's
                           fail-fast rc 1 on the first corrupt byte)
--max-record-bytes <n>    (allocation bound on one BAM record: a
                           corrupt length field larger than this is
                           rejected BEFORE allocating) [268435456]
--hosts <int> --host-id <int> --coordinator <addr> --merge-shards <N>
--merge-unmarked          (merge a legacy shard set without .done markers)
--make-index              (index INPUT for byte-range sharded ingest)
--fleet-dir <dir>         (run as an elastic-fleet pull worker against
                           <out>.fleet: acquire a leased work-range,
                           stream it, retire it with a range .done
                           marker, pull the next; normally launched by
                           `shepherd --fleet-ranges`, not by hand)
--fleet-worker <name>     (worker name recorded in leases/markers,
                           with --fleet-dir) [w<pid>]
--slab-rows <int>         (ragged pass-packing row budget; default 128)
--slab-shape-ladder <int> (canonical tail-slab heights per packed shape
                           group: budget >> k for k < N — bounds each
                           group to N XLA programs; 1 = all slabs
                           full-height) [2]
--no-warmup               (disable the AOT warmup precompiler: cold
                           compiles then stall the first dispatch of
                           each shape instead of overlapping ingest)
--prep-threads <int>      (overlapped prep plane: background threads
                           ingest + run the orientation walk ahead of
                           the admission window so host prep overlaps
                           device compute; 0 = inline prep on the
                           driver thread, the old behavior; output
                           bytes identical either way) [auto]
--banded-impl {scan,pallas,rotband}
                          (force the banded DP fill: the lax.scan
                           spec, the v1 band-local Pallas kernel, or
                           the v2 rotating-band kernel — all three
                           bit-identical, so a pure A/B knob; also
                           settable as CCSX_BANDED_IMPL.  Unset: v1 on
                           a TPU at qmax <= 4096 outside --mesh, else
                           the scan; 2.4x the scan's speed on a v5e)
--prefilter {on,off}      (device pre-alignment screen: one batched
                           dispatch scores each wave of strand_match
                           pair candidates and rejects hopeless ones
                           before the banded DP — conservative by
                           construction, output bytes identical
                           either way; 'off' disables the screen and
                           the walk's fwd+RC speculation — seeding
                           routing stays with --seed-device-min-t)
                           [on]
--seed-device-min-t <n>   (host/device k-mer seeding crossover: pairs
                           whose template is >= n bases seed on the
                           device (ops/seed_device.py, bit-equal to
                           the host sort-join); shorter pairs keep the
                           cached host path.  0 disables device
                           seeding) [16384]
--pass-buckets a,b,...    (bucketed-grouping A/B control: disables pass
                           packing and pads passes to these buckets)
--inject-faults p@N,...   (deterministic fault injection; testing only)

Subcommands:
ccsx-tpu shepherd --hosts N [opts] <INPUT> <OUTPUT>
                          (rank supervisor for sharded runs: launches
                           the N ranks as subprocesses, monitors
                           shard-journal heartbeats + per-rank
                           /healthz, restarts dead or stalled ranks
                           with exponential backoff up to
                           --max-rank-restarts — they resume from
                           their shard journals — then auto-merges;
                           turns merge_shards' "re-run the dead rank"
                           instruction into a supervised loop.
                           With --fleet-ranges M the shepherd becomes
                           the ELASTIC scheduler: the input splits
                           into M >> N leased work-ranges pulled by
                           the ranks; a dead rank's ranges requeue to
                           survivors (no in-place restart needed), a
                           drained rank (rc 75) is a voluntary leave,
                           stale leases expire after --lease-timeout
                           (SIGKILL + requeue), and
                           `shepherd --join <out>.fleet --hosts K`
                           adds K workers to a running fleet mid-run.
                           With --serve-replicas N [--gateway-port P]
                           the shepherd supervises a SERVE fleet
                           instead: N `serve --fleet` replicas + the
                           gateway as children — crashes restart with
                           backoff up to --max-replica-restarts, a
                           drained replica (rc 0/75) is not restarted
                           (its spool jobs stay with the survivors),
                           SIGTERM fans out a bounded-grace drain;
                           flags after the shepherd's own are the
                           serve/compute flags, e.g. `shepherd
                           --serve-replicas 3 --fleet SPOOL -A`)
ccsx-tpu stats <jsonl>... (summarize --trace / --metrics artifacts:
                           shape-group attribution table, stage
                           breakdown, occupancy recap, slowest
                           dispatches; any mix of files)
ccsx-tpu top <src>...     (live ANSI dashboard over telemetry
                           endpoints host:port and/or --metrics JSONL
                           files; multi-rank sources aggregate —
                           counters sum, min progress, any-degraded;
                           --once for one frame)
ccsx-tpu report <jsonl>.. (self-contained HTML run report from trace/
                           metrics JSONL: timeline strip, group
                           compile/dispatch table, stage breakdown,
                           occupancy tiles, stall/recovery log,
                           ETA-vs-actual curve; -o <out.html>.
                           With --fleet <dir>: stitch a fleet/spool
                           dir's per-process JSONL into ONE merged
                           wall-aligned timeline per job, keyed by
                           the correlation id minted at submission)
ccsx-tpu serve [opts]     (resident multi-tenant consensus server:
                           one warm runtime — executors, warmup
                           compiles, tracer — shared by jobs
                           submitted over HTTP on the telemetry
                           stack: POST /jobs (input path or streamed
                           BAM/FASTQ body), GET /jobs/<id> status,
                           GET /jobs/<id>/output, DELETE cancels;
                           /healthz liveness vs /readyz readiness.
                           Per-job fault isolation: own journal,
                           failure budget, breaker scope, metrics
                           label; fair shared admission window;
                           --job-deadline + bounded retry; queue cap
                           -> 429 + Retry-After; SIGTERM drains to a
                           resumable rc 75 and a restart requeues
                           unfinished jobs from <spool>/state.json.
                           Compute flags after the serve flags are
                           the normal run options.
                           With --fleet <spool> the server is one
                           REPLICA of a fleet sharing <spool> as a
                           job lease domain: jobs are leased
                           (O_EXCL acquire, heartbeat renew,
                           exclusive done marker), replica death
                           requeues them to survivors, jobs with
                           >= --fanout-holes holes fan out across
                           replicas through the range queue, and
                           each replica serves on port+slot)
ccsx-tpu gateway --spool S (thin balancer over a serve fleet: POST
                           /jobs health-routed on replica /readyz
                           — 503 + Retry-After when all drain, 429
                           at the spool cap — fleet job API served
                           from the spool, /replicas discovery from
                           slot leases, and ccsx_fleet_* autoscale
                           gauges — spool depth, leases held, per-
                           replica admission-window pressure — on
                           /metrics; no jax: keeps routing while
                           every replica's accelerator is wedged)
ccsx-tpu blackbox <path>.. (render crash-persistent flight-recorder
                           dumps: each process with CCSX_BLACKBOX=DIR
                           set mirrors its last events into an mmap
                           ring DIR/blackbox.<pid>.bin that survives
                           SIGKILL; headlines the in-flight job/range/
                           span at death, then the event tail.  A
                           directory argument expands to every ring
                           inside it; --tail N)
ccsx-tpu lint [files...]  (repo-native static analysis, pure ast — no
                           jax: int32-overflow hazards in ops/ traced
                           code, bare writes in lease/journal/spool
                           domains, off-lock Metrics mutation,
                           ContextVar set without token restore,
                           and the static telemetry schema
                           cross-check.
                           Suppressions live in lint_baseline.json
                           (committed, every entry justified) or
                           inline `# lint: ok[check] reason`; --json
                           for machine output, --gauge-file to
                           publish the lint_findings dashboard gauge;
                           exit 0 iff clean.  Also: make lint)
"""


def usage() -> int:
    """Reference-parity help text (usage(), main.c:723-749), incl. its
    quirk: the usage text claims `-j [2]` while the code default is 1
    (main.c:740 vs main.c:754) — reproduced faithfully; our default is
    1 like the reference's code.  Returns 1 like the reference."""
    print(USAGE, end="")
    return 1


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ccsx-tpu",
        description="Generate circular consensus sequences (ccs) from subreads.",
        add_help=False,
    )
    p.add_argument("-h", "--help", action="store_true", dest="help")
    p.add_argument("input", nargs="?", default="-",
                   help="Input file (BAM, or FASTA/Q with -A); '-' = stdin")
    p.add_argument("output", nargs="?", default="-",
                   help="Output FASTA; '-' = stdout")
    p.add_argument("-m", type=int, default=5000, dest="min_len",
                   help="Minimum total length of subreads in a hole [5000]")
    p.add_argument("-M", type=int, default=500000, dest="max_len",
                   help="Maximum total length of subreads in a hole [500000]")
    p.add_argument("-c", type=int, default=3, dest="min_count",
                   help="Minimum number of subreads required [3]")
    p.add_argument("-A", action="store_true", dest="fastx",
                   help="Input is fasta/fastq (gzip allowed)")
    p.add_argument("-P", action="store_true", dest="primitive",
                   help="Whole-read consensus (no windowed shred)")
    p.add_argument("-X", default=None, dest="exclude",
                   help="Exclude ZMWs: comma-separated hole IDs")
    p.add_argument("-j", type=int, default=1, dest="threads",
                   help="Number of host worker threads [1]")
    p.add_argument("-v", action="count", default=0, dest="verbose",
                   help="Debug verbosity (repeatable)")
    # TPU-era extensions
    p.add_argument("--device", default="auto", choices=["auto", "tpu", "cpu"])
    p.add_argument("--mesh", default=None, metavar="D,P",
                   help="Batched-pipeline device mesh as data,pass (e.g. "
                        "4,2); default: all devices on the data axis")
    p.add_argument("--refine-iters", type=int, default=2)
    p.add_argument("--max-passes", type=int, default=32)
    p.add_argument("--pass-buckets", default=None, metavar="A,B,...",
                   help="bucketed-grouping A/B control: DISABLES ragged "
                        "pass packing and pads passes to these buckets "
                        "(ascending ints; ARCHITECTURE.md perf notes). "
                        "Output is byte-identical either way")
    p.add_argument("--slab-rows", type=int, default=None, metavar="R",
                   help="pass-packing slab row budget (power of two; "
                        "rows from many holes share one (R, qmax) "
                        "dispatch) [128]")
    p.add_argument("--slab-shape-ladder", type=int, default=None,
                   metavar="N", dest="slab_shape_ladder",
                   help="canonical tail-slab heights per packed shape "
                        "group (budget >> k for k < N): bounds each "
                        "group to N XLA programs in steady state; 1 = "
                        "every slab dispatches at the full row budget "
                        "[2]")
    p.add_argument("--no-warmup", action="store_true", dest="no_warmup",
                   help="disable the AOT warmup precompiler "
                        "(pipeline/warmup.py): compiles then block the "
                        "first dispatch of each shape instead of "
                        "overlapping ingest/prep")
    p.add_argument("--prep-threads", type=int, default=None,
                   dest="prep_threads", metavar="N",
                   help="overlapped prep plane (pipeline/prep_pool.py): "
                        "N background threads ingest + run the "
                        "orientation walk ahead of the admission "
                        "window, overlapping host prep with device "
                        "compute; 0 = inline prep (the old behavior). "
                        "Output bytes are identical either way "
                        "[auto-size to the host]")
    p.add_argument("--banded-impl", default="", dest="banded_impl",
                   choices=["", "scan", "pallas", "rotband"],
                   help="force the banded DP fill (consensus/"
                        "star.banded_impl): 'scan' = the lax.scan spec, "
                        "'pallas' = the v1 band-local kernel, "
                        "'rotband' = the v2 rotating-band kernel.  "
                        "Bit-identical output either way (pinned); a "
                        "pure performance A/B knob.  Also settable as "
                        "CCSX_BANDED_IMPL.  Unset: the v1 kernel on a "
                        "TPU where qmax <= 4096 and the step is not "
                        "--mesh partitioned, else the scan (an N=128, "
                        "qmax 4096 fill on a v5e: v1 0.77 s, rotband "
                        "1.68 s, scan 1.84 s)")
    p.add_argument("--prefilter", default="on", choices=["on", "off"],
                   dest="prefilter",
                   help="device pre-alignment screen (ops/sketch.py): "
                        "score each wave of strand_match pair "
                        "candidates in one batched dispatch and "
                        "reject hopeless ones before the banded DP. "
                        "Conservative: output bytes are identical on "
                        "or off (pinned); 'off' disables the screen "
                        "and the walk's fwd+RC speculation (the A/B "
                        "control — seeding routing is governed by "
                        "--seed-device-min-t alone) [on]")
    p.add_argument("--seed-device-min-t", type=int, default=None,
                   dest="seed_device_min_t", metavar="N",
                   help="host/device k-mer seeding crossover: pairs "
                        "whose template is >= N bases use the batched "
                        "device seeder (bit-equal to the host "
                        "sort-join, ops/seed_device.py); shorter "
                        "pairs keep the cached host path.  0 "
                        "disables device seeding [16384]")
    p.add_argument("--fastq", action="store_true", dest="fastq",
                   help="Write FASTQ with per-base vote-margin qualities "
                        "instead of FASTA (extension; the reference "
                        "emits FASTA only)")
    p.add_argument("--bam", action="store_true", dest="bam_out",
                   help="Write unaligned BAM (qual fields + rq aux tag; "
                        "implies --fastq's quality computation)")
    p.add_argument("--window-growth", default="flush",
                   choices=["flush", "grow"],
                   help="When no breakpoint is found at max-window: "
                        "'flush' forces a flush (bounded kernel shapes), "
                        "'grow' keeps growing like the reference [flush]")
    p.add_argument("--batch", default="auto",
                   choices=["auto", "on", "off"],
                   help="Batched device pipeline: many holes per TPU "
                        "dispatch [auto: on for TPU backends]")
    p.add_argument("--inflight", type=int, default=None,
                   help="Pin the batched pipeline's admission window "
                        "to exactly N holes.  Default (or <= 0): the "
                        "adaptive window — starts at zmw_microbatch/16 "
                        "and grows x4 per filled round up to "
                        "zmw_microbatch (the reference's chunk policy, "
                        "main.c:686-691)")
    p.add_argument("--journal", default=None,
                   help="Progress journal path for resumable runs")
    p.add_argument("--metrics", default=None,
                   help="Append JSON-lines metrics events to this path")
    p.add_argument("--trace", default=None,
                   help="Dispatch flight recorder: write span JSONL "
                        "here (+ a Chrome trace-event export at close; "
                        "utils/trace.py).  Never blocks a dispatch; the "
                        "per-group compile and dispatch counts ride "
                        "every metrics event.  Device time per program "
                        "and per stage comes from a profiler trace "
                        "(--profile), where the ccsx.* spans, the "
                        "ccsx_* programs "
                        "and the fill/traceback/vote/breakpoint scopes "
                        "are named")
    p.add_argument("--stall-timeout", type=float, default=120.0,
                   dest="stall_timeout", metavar="SEC",
                   help="Hang watchdog: dump thread stacks + the "
                        "in-flight shape group when a device dispatch "
                        "stays open this long, and mark the run "
                        "degraded (0 disables; the first dispatch of "
                        "each shape gets 10x this budget — cold XLA "
                        "compiles are not hangs) [120]")
    p.add_argument("--telemetry-port", type=int, default=0,
                   dest="telemetry_port", metavar="PORT",
                   help="Serve live telemetry for this run on a daemon "
                        "thread: GET /metrics (Prometheus text), "
                        "/healthz (ok|degraded + stall/fallback "
                        "detail), /progress (JSON, windowed-rate ETA). "
                        "The port auto-bumps when taken; sharded runs "
                        "offset per rank.  0 = off [0]")
    p.add_argument("--profile", default=None,
                   help="Write a jax.profiler trace to this directory")
    # multi-host (parallel/distributed.py): run one process per host with
    # --hosts N --host-id R, then merge with --merge-shards N
    p.add_argument("--hosts", type=int, default=None,
                   help="Total hosts in a sharded run")
    p.add_argument("--host-id", type=int, default=None,
                   help="This host's rank in [0, --hosts)")
    p.add_argument("--coordinator", default=None,
                   help="jax.distributed coordinator address host:port "
                        "(optional; enables cross-host collectives)")
    p.add_argument("--merge-shards", type=int, default=None, metavar="N",
                   help="Merge OUTPUT.shard0..N-1 into OUTPUT and exit")
    p.add_argument("--merge-unmarked", action="store_true",
                   help="With --merge-shards: merge a shard set that has "
                        "NO completion markers at all (a legacy set "
                        "predating markers; indistinguishable from a "
                        "node-wide mid-run kill, so never assumed)")
    p.add_argument("--make-index", action="store_true",
                   help="Build INPUT's BGZF hole index sidecar "
                        "(<INPUT>.ccsx_idx) for byte-range sharded "
                        "multi-host ingest, then exit")
    # elastic fleet plane (pipeline/fleet.py): pull workers over a
    # leased work-range queue; normally launched by
    # `ccsx-tpu shepherd --fleet-ranges M`, not by hand
    p.add_argument("--fleet-dir", default=None, dest="fleet_dir",
                   metavar="DIR",
                   help="Run as a fleet pull worker against this "
                        "fleet directory (<out>.fleet): acquire a "
                        "range lease, stream it, retire it, pull the "
                        "next until the queue drains")
    p.add_argument("--fleet-worker", default=None, dest="fleet_worker",
                   metavar="NAME",
                   help="Worker name recorded in leases and range "
                        "done markers (with --fleet-dir; defaults to "
                        "w<pid>)")
    # resilient execution (pipeline/resilience.py)
    p.add_argument("--dispatch-deadline", type=float, default=0.0,
                   dest="dispatch_deadline", metavar="SEC",
                   help="Bounded-wait device dispatch: abandon a call "
                        "open past this deadline (thread parked, "
                        "result discarded) and replay its group on the "
                        "bit-exact host path; the first call of each "
                        "shape gets 10x for cold compiles.  0 = off — "
                        "a wedged dispatch stalls the run forever, "
                        "with the watchdog observing only [0]")
    p.add_argument("--breaker-strikes", type=int, default=None,
                   dest="breaker_strikes", metavar="N",
                   help="Backend circuit breaker: N device failures "
                        "(hangs, OOM ladder-bottoms, compile failures) "
                        "within 60s trip it open — remaining work runs "
                        "on the host path.  0 disables [3]")
    p.add_argument("--breaker-probe-s", type=float, default=None,
                   dest="breaker_probe_s", metavar="SEC",
                   help="Half-open re-probe interval for a tripped "
                        "breaker: one group dispatches as a probe and "
                        "success closes it.  0 = stay open for the "
                        "rest of the run [0]")
    p.add_argument("--max-failed-holes", default=None,
                   dest="max_failed_holes", metavar="V",
                   help="Failure-rate abort: an integer count (>= 0, "
                        "checked per failure) or a fraction of "
                        "processed holes in (0, 1) (checked at end of "
                        "run).  Exceeding it exits rc 2 instead of "
                        "emitting a near-empty output at rc 0 "
                        "[unbounded]")
    # hostile-input ingest plane (io/corruption.py)
    p.add_argument("--salvage", action="store_true", dest="salvage",
                   help="Salvage-mode ingest: classified input "
                        "corruption (io/corruption.py taxonomy) is "
                        "counted + resynced past — BGZF rescans for "
                        "the next valid block, BAM for the next "
                        "plausible record, FASTA/Q for the next "
                        "'>'/'@' line — instead of killing the run; "
                        "every undamaged hole still emits, the run is "
                        "marked degraded, and corrupt holes spend the "
                        "--max-failed-holes budget.  Default off: "
                        "fail-fast rc 1 on the first corrupt byte")
    p.add_argument("--max-record-bytes", type=int, default=None,
                   dest="max_record_bytes", metavar="N",
                   help="Allocation bound on one BAM alignment record "
                        "(enforced BEFORE allocating; a corrupt int32 "
                        "length must not drive a multi-GB allocation) "
                        "[268435456]")
    p.add_argument("--inject-faults", default=None, metavar="SPEC",
                   help="Deterministic fault injection for testing "
                        "recovery paths: point@N[+],... with points "
                        "ingest, compute, device_oom, stall, "
                        "device_hang, rank_death, write, journal, "
                        "input_corrupt, disk_full, sigterm "
                        "(utils/faultinject.py; CCSX_FAULTS env "
                        "equivalent)")
    return p


def config_from_args(args) -> CcsConfig:
    if args.min_count < 3:
        # mirror main.c:786-789
        print(f"Error! min fulllen count=[{args.min_count}] (>=3) !",
              file=sys.stderr)
        raise SystemExit(-1)
    exclude = None
    if args.exclude:
        exclude = frozenset(x for x in args.exclude.split(",") if x)
    mesh_shape = None
    if getattr(args, "mesh", None):
        try:
            mesh_shape = tuple(int(x) for x in args.mesh.split(","))
            if len(mesh_shape) != 2 or min(mesh_shape) < 1:
                raise ValueError
        except ValueError:
            print(f"Error: --mesh expects D,P integers, got {args.mesh!r}",
                  file=sys.stderr)
            raise SystemExit(1)
    pass_buckets = None
    if getattr(args, "pass_buckets", None):
        try:
            pass_buckets = tuple(
                int(x) for x in args.pass_buckets.split(","))
            if (not pass_buckets or min(pass_buckets) < 1
                    or list(pass_buckets) != sorted(set(pass_buckets))):
                raise ValueError
        except ValueError:
            print("Error: --pass-buckets expects ascending positive "
                  f"integers, got {args.pass_buckets!r}", file=sys.stderr)
            raise SystemExit(1)
        if pass_buckets[-1] < args.max_passes:
            # an undersized bucket list would silently defeat shape
            # bucketing: holes above the last bucket ship with their raw
            # pass count, one XLA compile per distinct count
            print(f"Error: --pass-buckets last bucket "
                  f"{pass_buckets[-1]} must cover --max-passes "
                  f"{args.max_passes}", file=sys.stderr)
            raise SystemExit(1)
    slab_rows = getattr(args, "slab_rows", None)
    if slab_rows is not None and slab_rows < 1:
        print(f"Error: --slab-rows must be >= 1, got {slab_rows}",
              file=sys.stderr)
        raise SystemExit(1)
    slab_ladder = getattr(args, "slab_shape_ladder", None)
    if slab_ladder is not None and not 1 <= slab_ladder <= 8:
        # > 8 heights would walk below budget/128 — that is the r7
        # compile storm with extra steps, refuse it
        print(f"Error: --slab-shape-ladder must be in [1, 8], got "
              f"{slab_ladder}", file=sys.stderr)
        raise SystemExit(1)
    stall_timeout = getattr(args, "stall_timeout", 120.0)
    if stall_timeout < 0:
        print(f"Error: --stall-timeout must be >= 0, got "
              f"{stall_timeout}", file=sys.stderr)
        raise SystemExit(1)
    telemetry_port = getattr(args, "telemetry_port", 0) or 0
    if not 0 <= telemetry_port <= 65535:
        print(f"Error: --telemetry-port must be in [0, 65535], got "
              f"{telemetry_port}", file=sys.stderr)
        raise SystemExit(1)
    prep_threads = getattr(args, "prep_threads", None)
    if prep_threads is not None and not 0 <= prep_threads <= 64:
        print(f"Error: --prep-threads must be in [0, 64], got "
              f"{prep_threads}", file=sys.stderr)
        raise SystemExit(1)
    seed_device_min_t = getattr(args, "seed_device_min_t", None)
    if seed_device_min_t is not None and seed_device_min_t < 0:
        print(f"Error: --seed-device-min-t must be >= 0, got "
              f"{seed_device_min_t}", file=sys.stderr)
        raise SystemExit(1)
    dispatch_deadline = getattr(args, "dispatch_deadline", 0.0) or 0.0
    if dispatch_deadline < 0:
        print(f"Error: --dispatch-deadline must be >= 0, got "
              f"{dispatch_deadline}", file=sys.stderr)
        raise SystemExit(1)
    breaker_strikes = getattr(args, "breaker_strikes", None)
    if breaker_strikes is not None and breaker_strikes < 0:
        print(f"Error: --breaker-strikes must be >= 0, got "
              f"{breaker_strikes}", file=sys.stderr)
        raise SystemExit(1)
    breaker_probe = getattr(args, "breaker_probe_s", None)
    if breaker_probe is not None and breaker_probe < 0:
        print(f"Error: --breaker-probe-s must be >= 0, got "
              f"{breaker_probe}", file=sys.stderr)
        raise SystemExit(1)
    max_failed = getattr(args, "max_failed_holes", None)
    if max_failed is not None:
        import math

        try:
            max_failed = float(max_failed)
            # reject what the semantics cannot honor: non-finite values
            # (would crash int()/comparisons mid-run), negatives, and
            # non-integer counts > 1 (int() would silently truncate
            # 1.5 to a tighter budget than asked).  0 is a valid count:
            # "no failures tolerated".
            if (not math.isfinite(max_failed) or max_failed < 0
                    or (max_failed >= 1
                        and max_failed != int(max_failed))):
                raise ValueError
        except ValueError:
            print("Error: --max-failed-holes expects an integer count "
                  ">= 0 or a fraction in (0, 1), got "
                  f"{args.max_failed_holes!r}", file=sys.stderr)
            raise SystemExit(1)
    banded_impl = getattr(args, "banded_impl", "") or ""
    if banded_impl:
        import os

        # dispatch reads the env (consensus/star.banded_impl) so the
        # knob reaches every jitted aligner without threading the config
        # through; an explicit flag wins over an inherited env var
        os.environ["CCSX_BANDED_IMPL"] = banded_impl
    max_record_bytes = getattr(args, "max_record_bytes", None)
    if max_record_bytes is not None and max_record_bytes < 4096:
        # a bound below any real record would reject every input; 4096
        # still lets tests drive the oversize classification cheaply
        print(f"Error: --max-record-bytes must be >= 4096, got "
              f"{max_record_bytes}", file=sys.stderr)
        raise SystemExit(1)
    return CcsConfig(
        min_subread_len=args.min_len,
        max_subread_len=args.max_len,
        min_fulllen_count=args.min_count,
        split_subread=not args.primitive,
        is_bam=not args.fastx,
        exclude_holes=exclude,
        threads=args.threads,
        verbose=args.verbose,
        refine_iters=args.refine_iters,
        max_passes=args.max_passes,
        emit_quality=args.fastq or args.bam_out,
        bam_out=args.bam_out,
        window_growth=args.window_growth,
        mesh_shape=mesh_shape,
        device=args.device,
        metrics_path=args.metrics,
        trace_path=getattr(args, "trace", None),
        stall_timeout_s=stall_timeout,
        telemetry_port=telemetry_port,
        # an explicit bucket list selects the bucketed-grouping control
        # path; the default is ragged pass packing (pipeline/pack.py)
        pass_packing=pass_buckets is None,
        warmup_compile=not getattr(args, "no_warmup", False),
        prep_threads=prep_threads,
        dispatch_deadline_s=dispatch_deadline,
        max_failed_holes=max_failed,
        salvage=bool(getattr(args, "salvage", False)),
        prefilter=getattr(args, "prefilter", "on") != "off",
        banded_impl=banded_impl,
        **({"seed_device_min_t": seed_device_min_t}
           if seed_device_min_t is not None else {}),
        **({"max_record_bytes": max_record_bytes}
           if max_record_bytes is not None else {}),
        **({"breaker_strikes": breaker_strikes}
           if breaker_strikes is not None else {}),
        **({"breaker_probe_s": breaker_probe}
           if breaker_probe is not None else {}),
        **({"pass_buckets": pass_buckets} if pass_buckets else {}),
        **({"slab_rows": slab_rows} if slab_rows else {}),
        **({"slab_shape_ladder": slab_ladder}
           if slab_ladder is not None else {}),
    )


def main(argv: Optional[list] = None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "shepherd":
        # rank supervisor for sharded runs: subprocess ranks, heartbeat
        # monitoring, restart-with-backoff, auto-merge
        from ccsx_tpu.pipeline.supervisor import shepherd_main

        return shepherd_main(argv[1:])
    if argv and argv[0] == "stats":
        # trace/metrics JSONL summarizer subcommand (no jax import, no
        # backend init — safe on a host whose accelerator is hung)
        from ccsx_tpu.utils import trace as trace_mod

        return trace_mod.stats_main(argv[1:])
    if argv and argv[0] == "top":
        # live telemetry dashboard (same no-jax discipline as stats)
        from ccsx_tpu.utils import telemetry

        return telemetry.top_main(argv[1:])
    if argv and argv[0] == "report":
        # static HTML run report from trace/metrics JSONL artifacts
        from ccsx_tpu.utils import report as report_mod

        return report_mod.report_main(argv[1:])
    if argv and argv[0] == "serve":
        # resident multi-tenant consensus server (pipeline/serve.py)
        from ccsx_tpu.pipeline.serve import serve_main

        return serve_main(argv[1:])
    if argv and argv[0] == "gateway":
        # serve-fleet balancer/aggregator (pipeline/gateway.py) — the
        # same no-jax discipline as stats/top: it must keep routing
        # while every replica's accelerator is wedged
        from ccsx_tpu.pipeline.gateway import gateway_main

        return gateway_main(argv[1:])
    if argv and argv[0] == "blackbox":
        # crash-persistent flight-recorder dump renderer (utils/
        # blackbox.py) — no jax: the whole point is reading a DEAD
        # process' last events from a possibly-wedged host
        from ccsx_tpu.utils import blackbox

        return blackbox.blackbox_main(argv[1:])
    if argv and argv[0] == "lint":
        # repo-native static analysis (ccsx_tpu/lint/) — pure ast, no
        # jax by contract: it gates tier-1 on the 1-core box in
        # seconds (tests/test_lint.py asserts the no-jax discipline)
        from ccsx_tpu.lint.core import lint_main

        return lint_main(argv[1:])
    args = build_parser().parse_args(argv)
    if args.help:
        return usage()  # rc 1, like the reference (main.c:761)
    try:
        cfg = config_from_args(args)
    except SystemExit as e:
        return int(e.code or 0)

    if args.inject_faults:
        from ccsx_tpu.utils import faultinject

        try:
            faultinject.arm(args.inject_faults)
        except ValueError as e:
            print(f"Error: --inject-faults: {e}", file=sys.stderr)
            return 1

    if args.fleet_dir is not None:
        # fleet pull worker (pipeline/fleet.py): the fleet dir's
        # state file is the authority on input/output/ranges; the
        # scheduler topology flags cannot combine with it
        if (args.hosts is not None or args.host_id is not None
                or args.merge_shards is not None or args.make_index):
            print("Error: --fleet-dir is a pull worker; it cannot "
                  "combine with --hosts/--host-id/--merge-shards/"
                  "--make-index (the fleet scheduler owns those)",
                  file=sys.stderr)
            return 1
        if args.bam_out:
            print("Error: --bam is not supported with --fleet-dir "
                  "(use --fastq and convert the merged output)",
                  file=sys.stderr)
            return 1
        if args.batch == "off":
            print("Error: --batch off is not supported with "
                  "--fleet-dir", file=sys.stderr)
            return 1
        from ccsx_tpu.pipeline.fleet import run_fleet_worker

        return run_fleet_worker(args.fleet_dir, cfg,
                                worker=args.fleet_worker,
                                inflight=args.inflight)

    # imports deferred so --help stays fast and backend selection happens
    # after the config is known
    if args.make_index:
        if not cfg.is_bam:
            print("Error: --make-index requires BAM input (BGZF "
                  "container)", file=sys.stderr)
            return 1
        from ccsx_tpu.io import bam as bam_mod
        from ccsx_tpu.io import bamindex

        try:
            idx = bamindex.build_index(
                args.input,
                max_record_bytes=getattr(cfg, "max_record_bytes", 0))
        except (OSError, bam_mod.BamError) as e:
            print(f"Error: --make-index failed: {e}", file=sys.stderr)
            return 1
        print(f"[ccsx-tpu] indexed {idx['n_holes']} holes / "
              f"{idx['n_records']} records -> "
              f"{args.input}{bamindex.INDEX_SUFFIX}", file=sys.stderr)
        return 0

    if args.merge_shards is not None:
        from ccsx_tpu.parallel.distributed import merge_shards

        try:
            n = merge_shards(args.output, args.merge_shards,
                             allow_unmarked=args.merge_unmarked)
        except (OSError, ValueError) as e:
            # incomplete/dead shards or unreadable files: a designed,
            # expected operational refusal — clean rc 1, no traceback
            print(f"Error: {e}", file=sys.stderr)
            return 1
        print(f"[ccsx-tpu] merged {n} records from {args.merge_shards} "
              "shards", file=sys.stderr)
        return 0

    if args.bam_out and args.fastq:
        print("Error: --fastq and --bam are mutually exclusive",
              file=sys.stderr)
        return 1
    if args.bam_out and args.journal is not None:
        # the BGZF container is written whole at close, so a journal
        # could never be resumed — reject the trap up front
        print("Error: --bam does not support --journal (the BAM "
              "container cannot be appended on resume)", file=sys.stderr)
        return 1
    sharded = args.hosts is not None and args.hosts > 1
    if sharded:
        if args.host_id is None:
            print("Error: --hosts requires --host-id", file=sys.stderr)
            return 1
        if args.bam_out:
            # shard files are text FASTA/FASTQ merged by merge_shards;
            # write FASTQ shards and convert after the merge instead
            print("Error: --bam is not supported with --hosts "
                  "(use --fastq and convert the merged output)",
                  file=sys.stderr)
            return 1
        if args.batch == "off":
            # the sharded driver is built on the batched scheduler (its
            # shard writer needs per-hole ordinals); honoring 'off' would
            # silently run batched anyway, so reject it instead
            print("Error: --batch off is not supported with --hosts",
                  file=sys.stderr)
            return 1
        if args.coordinator is not None:
            from ccsx_tpu.parallel.distributed import init_distributed

            init_distributed(args.coordinator, args.hosts, args.host_id)

    # Resolve the backend FIRST (honoring --device cpu before any backend
    # initializes) and decide --batch auto from the resolved backend.
    from ccsx_tpu.utils.device import resolve_device

    try:
        backend = resolve_device(cfg.device)
    except RuntimeError as e:       # --device tpu off a TPU, or init
        print(f"Error: {e}", file=sys.stderr)
        return 1
    batch = args.batch
    if batch == "auto":
        batch = "on" if backend == "tpu" else "off"
    if cfg.mesh_shape is not None and batch == "off" and not sharded:
        # (sharded runs always use the batched executor, mesh included)
        print("[ccsx-tpu] --mesh has no effect with --batch off",
              file=sys.stderr)

    def _run():
        if sharded:
            from ccsx_tpu.parallel.distributed import run_pipeline_sharded

            return run_pipeline_sharded(
                args.input, args.output, cfg, args.host_id, args.hosts,
                journal_path=args.journal, inflight=args.inflight)
        if batch == "on":
            from ccsx_tpu.pipeline.batch import run_pipeline_batched

            return run_pipeline_batched(args.input, args.output, cfg,
                                        journal_path=args.journal,
                                        inflight=args.inflight)
        from ccsx_tpu.pipeline.run import run_pipeline

        return run_pipeline(args.input, args.output, cfg,
                            journal_path=args.journal)

    if args.profile:
        import jax

        with jax.profiler.trace(args.profile):
            return _run()
    return _run()


if __name__ == "__main__":
    sys.exit(main())
