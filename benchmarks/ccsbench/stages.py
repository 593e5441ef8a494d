"""Device time per stage and per program from a profiler trace, and
idle gaps labelled by the program's own spans.

Reads the same ``.xplane.pb`` and the same traced interval as
``trace_reduce.reduce`` (the ``bench.window`` span, cut where a
device's buffer ran full), with the same ``Devices``:

* ``scopes``: device self-time per stage, seconds (mean over devices).
  Each instant of a device's busy time goes to the innermost operation
  on its op line running then (the latest started of those still
  open); an operation goes to the first of ``STAGES`` among the parts
  of its op-name path, and to ``other`` where none is there.  The trace
  names an operation (its HLO instruction) but not its path: the path
  is the instruction's ``op_name`` metadata in its program's HLO, which
  the trace keeps in the ``/host:metadata`` plane (``op_paths``).  The
  operations XLA adds itself carry no path (a scan's per-step slices
  of its inputs, the bulk of the fill's time on a v5e): such an
  operation goes where the innermost operation around it that has a
  path goes, and to ``other`` at the top.
* ``programs``: device busy seconds per program (mean over devices):
  the union of each "XLA Modules" event name's intervals, the name
  without JAX's ``jit_`` prefix and the run-id suffix.
* ``span_gaps``: the idle gaps as ``trace_reduce`` finds them, each
  labelled by the innermost ``ccsx.*`` span of the program that
  overlaps it most (longest overlap, then the shortest span), on any
  host plane; else the harness's ``bench.*`` span, else ``host.other``.

``combine`` sums slices as ``trace_reduce.combine`` does.  The stage
names are the program's own (``ccsx_tpu.utils.trace.STAGES``), copied
here so that the benchmark imports nothing of the program; a test
keeps the two equal.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Tuple

import trace_reduce

STAGES = ("fill", "traceback", "vote", "breakpoint")
OTHER = "other"
PROGRAM_SPAN_PREFIX = "ccsx."
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
_RUN_ID = re.compile(r"\(\d+\)$")
# a scope under a batching transform: 'vmap(breakpoint)'
_TRANSFORMED = re.compile(r"^(?:vmap|pmap|shmap)\((.*)\)$")

Event = Tuple[str, float, float]


def stage_of(path: str) -> str:
    """The first of STAGES among the '/'-separated parts of an op-name
    path (a part under vmap reads 'vmap(<scope>)'), else OTHER."""
    for part in path.split("/"):
        m = _TRANSFORMED.match(part)
        while m:
            part = m.group(1)
            m = _TRANSFORMED.match(part)
        if part in STAGES:
            return part
    return OTHER


def program_name(module_event: str) -> str:
    """'jit_ccsx_refine_packed(123)' -> 'ccsx_refine_packed'."""
    name = _RUN_ID.sub("", module_event.strip())
    return name[4:] if name.startswith("jit_") else name


# ---- the op-name paths, from the trace file's protobuf ---------------------
#
# jax.profiler.ProfileData does not expose a plane's event metadata, so
# the few messages needed are read off the protobuf wire format here:
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 and
# .stat_metadata = 5 (maps: key 1, value 2); XEventMetadata.name = 2,
# .stats = 5; XStat.metadata_id = 1, .bytes_value = 6; XStatMetadata
# .name = 2 (tsl/profiler/protobuf/xplane.proto).  HloProto.hlo_module
# = 1; HloModuleProto.computations = 3; HloComputationProto.instructions
# = 2; HloInstructionProto.name = 1, .metadata = 7; OpMetadata.op_name
# = 2 (xla/service/hlo.proto, xla/xla_data.proto).

def _varint(b, i: int) -> Tuple[int, int]:
    r = shift = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return r, i


def _fields(b):
    """(field number, value) of one message: varints as ints, length-
    delimited fields as memoryviews, fixed-width ones as raw bytes."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            ln = 8 if wire == 1 else 4
            v, i = b[i:i + ln], i + ln
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _instruction_paths(hlo_proto) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for g, comp in _fields(module):
            if g != 3:
                continue
            for h, ins in _fields(comp):
                if h != 2:
                    continue
                name = path = ""
                for k, v in _fields(ins):
                    if k == 1:
                        name = _text(v)
                    elif k == 7:
                        for m, w in _fields(v):
                            if m == 2:
                                path = _text(w)
                out[name] = path
    return out


def op_paths(path: str) -> Dict[str, Dict[str, str]]:
    """{program as the trace names it, id included: {HLO instruction:
    op-name path}} from the trace's metadata plane."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        name = next((_text(v) for g, v in _fields(plane) if g == 2), "")
        if name != METADATA_PLANE:
            continue
        fields = list(_fields(plane))
        stat_names = {}
        for g, v in fields:
            if g == 5:
                entry = dict(_fields(v))
                stat_names[entry.get(1)] = next(
                    (_text(w) for m, w in _fields(entry.get(2, b""))
                     if m == 2), "")
        for g, v in fields:
            if g != 4:
                continue
            meta = dict(_fields(v)).get(2, b"")
            name, proto = "", None
            for m, w in _fields(meta):
                if m == 2:
                    name = _text(w)
                elif m == 5:
                    stat = dict(_fields(w))
                    if stat_names.get(stat.get(1)) == HLO_PROTO_STAT:
                        proto = stat.get(6)
            if proto is not None:
                out[name] = _instruction_paths(proto)
    return out


def self_times(events: List[Event], lo: float, hi: float
               ) -> Dict[str, float]:
    """{key: ns} over [lo, hi]: each instant covered by an event goes to
    the innermost one, the latest started of those still open (outer
    first where two start together); an event keyed None passes its
    time to the innermost open one that has a key, else to OTHER."""
    evs = sorted(((max(s, lo), -min(e, hi), k) for k, s, e in events
                  if e > lo and s < hi), key=lambda x: (x[0], x[1]))
    bounds = sorted({s for s, _, _ in evs} | {-ne for _, ne, _ in evs})
    out: Dict[str, float] = {}
    stack: list = []
    j = 0
    for a, b in zip(bounds, bounds[1:]):
        while j < len(evs) and evs[j][0] <= a:
            stack.append(evs[j])
            j += 1
        stack = [ev for ev in stack if -ev[1] > a]
        if stack:
            k = next((ev[2] for ev in reversed(stack) if ev[2] is not None),
                     OTHER)
            out[k] = out.get(k, 0.0) + (b - a)
    return out


def label_gap(gap, program_spans: List[Event], bench_spans: List[Event]
              ) -> str:
    best: Optional[Tuple[float, float]] = None
    name = None
    for n, s, e in program_spans:
        o = trace_reduce.overlap(gap, (s, e))
        if o > 0 and (best is None or (o, -(e - s)) > best):
            best, name = (o, -(e - s)), n
    return name or trace_reduce.label_gap(gap, bench_spans)


def _op_key(ev) -> Tuple[Optional[str], str]:
    """(program, HLO instruction) of an op event.  The CPU's events
    carry both as stats; a TPU's op event is named by its instruction's
    text ('%while.216 = (...) while(...)') and runs inside its
    program's "XLA Modules" event (program None: found by time)."""
    stats = dict(ev.stats)
    if "hlo_op" in stats:
        return (f"{stats.get('hlo_module')}({stats.get('program_id')})",
                stats["hlo_op"])
    return None, ev.name.split(" = ", 1)[0].lstrip("%")


def reduce(path, devices: trace_reduce.Devices = trace_reduce.TPU,
           top: int = 10, paths: Optional[Dict] = None) -> Dict:
    """The reading of one trace (a file, or anything with planes, lines
    and events).  ``paths`` is ``op_paths`` of the trace, read from the
    file when not given."""
    if isinstance(path, str):
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
        if paths is None:
            paths = op_paths(path)
    else:
        pd = path
    paths = paths or {}
    program_spans: List[Event] = []
    bench_spans: List[Event] = []
    dropped: List[float] = []
    modules: Dict[str, List[Event]] = {}
    ops: Dict[str, list] = {}
    for plane in pd.planes:
        is_dev = devices.plane(plane.name)
        if is_dev:
            modules.setdefault(plane.name, [])
            ops.setdefault(plane.name, [])
        for line in plane.lines:
            for ev in line.events:
                s = float(ev.start_ns)
                e = s + float(ev.duration_ns)
                n = ev.name
                if is_dev:
                    if n == trace_reduce.DROPPED:
                        dropped.append(s)
                    if not devices.event(n):
                        continue
                    if devices.busy_line(line.name):
                        modules[plane.name].append((n, s, e))
                    if devices.op_line(line.name):
                        ops[plane.name].append((_op_key(ev), s, e))
                if not is_dev or plane.name.startswith("/host"):
                    if n.startswith(PROGRAM_SPAN_PREFIX):
                        program_spans.append((n, s, e))
                    elif n.startswith(trace_reduce.SPAN_PREFIX):
                        bench_spans.append((n, s, e))
    window = [(s, e) for n, s, e in bench_spans
              if n == trace_reduce.WINDOW_SPAN]
    if not window:
        raise ValueError(f"{path}: no {trace_reduce.WINDOW_SPAN!r} span")
    if not modules:
        raise ValueError(f"{path}: no device plane")
    lo, hi = window[0]
    hi = min([hi] + dropped)
    bench_spans = [sp for sp in bench_spans
                   if sp[0] != trace_reduce.WINDOW_SPAN]
    n_dev = len(modules)
    scopes = {k: 0.0 for k in STAGES + (OTHER,)}
    programs: Dict[str, float] = {}
    idle = []
    stage_memo: Dict[Tuple[Optional[str], str], str] = {}
    for plane, mods in modules.items():
        by_start = sorted((s, e, n) for n, s, e in mods)
        starts = [(s, e) for s, e, _ in by_start]
        staged = []
        for (prog, instr), s, e in ops[plane]:
            if prog is None:
                i = bisect.bisect_right(starts, (s, float("inf"))) - 1
                prog = (by_start[i][2] if i >= 0
                        and by_start[i][0] <= s < by_start[i][1] else "")
            key = (prog, instr)
            if key not in stage_memo:
                path = paths.get(prog, {}).get(instr, "")
                stage_memo[key] = stage_of(path) if path else None
            staged.append((stage_memo[key], s, e))
        for k, ns in self_times(staged, lo, hi).items():
            scopes[k] += ns / 1e9 / n_dev
        by_name: Dict[str, List[Tuple[float, float]]] = {}
        for n, s, e in mods:
            by_name.setdefault(program_name(n), []).append((s, e))
        for n, iv in by_name.items():
            merged = trace_reduce.union(trace_reduce.clip(iv, lo, hi))
            if merged:
                programs[n] = (programs.get(n, 0.0) + sum(
                    e - s for s, e in merged) / 1e9 / n_dev)
        busy = trace_reduce.union(
            trace_reduce.clip([(s, e) for _, s, e in mods], lo, hi))
        idle.extend(trace_reduce.gaps(busy, lo, hi))
    idle.sort(key=lambda g: g[0] - g[1])
    return {
        "scopes": scopes,
        "programs": dict(sorted(programs.items(), key=lambda kv: -kv[1])),
        "span_gaps": [[label_gap(g, program_spans, bench_spans),
                       (g[1] - g[0]) / 1e9] for g in idle[:top]],
    }


def s_per_zmw(ctx, stage: str) -> Optional[float]:
    """A stage's device self-seconds per record of the window: its
    seconds over the traced slices, over their traced seconds, times
    the window's seconds, over the window's records."""
    tr = ctx.trace
    if (tr is None or "scopes" not in tr or not ctx.records
            or not tr["window_s"]):
        return None
    return (tr["scopes"][stage] / tr["window_s"] * ctx.window_s
            / len(ctx.records))


def combine(parts: List[Dict], top: int = 10) -> Dict:
    """The slices' stage and program seconds summed, the longest
    labelled gaps."""
    scopes = {k: 0.0 for k in STAGES + (OTHER,)}
    programs: Dict[str, float] = {}
    for p in parts:
        for k, v in p["scopes"].items():
            scopes[k] = scopes.get(k, 0.0) + v
        for k, v in p["programs"].items():
            programs[k] = programs.get(k, 0.0) + v
    gaps = sorted((g for p in parts for g in p["span_gaps"]),
                  key=lambda g: -g[1])
    return {
        "scopes": scopes,
        "programs": dict(sorted(programs.items(), key=lambda kv: -kv[1])),
        "span_gaps": gaps[:top],
    }
