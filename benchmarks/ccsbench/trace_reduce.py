"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with ``jax.profiler.ProfileData``.  The traced interval is the
harness's ``bench.window`` span; a device is busy wherever one of its
programs runs (the union of the events on its busy line), and idle
elsewhere in the interval.  Each idle gap is labelled with the harness
span (``bench.*``) that overlaps it most, or ``host.other``.

On a TPU the devices are the ``/device:TPU:<n>`` planes, busy is read
from their "XLA Modules" line (one event per program run) and the
operations from "XLA Ops".  ``Devices`` says where to look, so that a
trace recorded on the CPU can check the arithmetic.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Callable, Dict, List, Optional, Tuple

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Devices:
    plane: Callable[[str], bool]
    busy_line: Callable[[str], bool]
    op_line: Callable[[str], bool]
    event: Callable[[str], bool] = lambda name: True


TPU = Devices(plane=lambda p: p.startswith("/device:TPU:"),
              busy_line=lambda ln: ln == "XLA Modules",
              op_line=lambda ln: ln == "XLA Ops")

WINDOW_SPAN = "bench.window"
DROPPED = "Trace Buffers Dropped"   # the device's buffer ran full here
SPAN_PREFIX = "bench."


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] around merged ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def label_gap(gap: Interval, spans: List[Tuple[str, float, float]]) -> str:
    best, name = 0.0, "host.other"
    for n, s, e in spans:
        o = overlap(gap, (s, e))
        if o > best:
            best, name = o, n
    return name


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def reduce(path, devices: Devices = TPU, top: int = 10) -> Dict:
    """busy_s (mean over devices), window_s, idle_share, and the
    breakdown: the ``top`` operations by summed duration and the ``top``
    longest idle gaps, labelled.  Seconds throughout.  The traced
    interval ends where a device's buffer ran full, if it did."""
    if isinstance(path, str):
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
    else:
        pd = path                  # anything with planes/lines/events
    spans: List[Tuple[str, float, float]] = []
    dropped: List[float] = []
    busy: Dict[str, List[Interval]] = {}
    op_events: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        is_dev = devices.plane(plane.name)
        if is_dev:
            busy.setdefault(plane.name, [])
        for line in plane.lines:
            if is_dev:
                dropped.extend(s for n, s, e in _events(line) if n == DROPPED)
            if is_dev and devices.busy_line(line.name):
                busy[plane.name].extend(
                    (s, e) for n, s, e in _events(line) if devices.event(n))
            if is_dev and devices.op_line(line.name):
                op_events.extend(ev for ev in _events(line)
                                 if devices.event(ev[0]))
            if not is_dev or plane.name.startswith("/host"):
                spans.extend(ev for ev in _events(line)
                             if ev[0].startswith(SPAN_PREFIX))
    window = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not window:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span")
    if not busy:
        raise ValueError(f"{path}: no device plane")
    lo, hi = window[0]
    hi = min([hi] + dropped)       # only what the buffer kept counts
    spans = [sp for sp in spans if sp[0] != WINDOW_SPAN]
    ops: Dict[str, float] = {}
    for n, s, e in op_events:
        if e > lo and s < hi:
            # an HLO instruction's text: keep its name
            n = n.split(" = ", 1)[0]
            ops[n] = ops.get(n, 0.0) + min(e, hi) - max(s, lo)
    busy_s, idle = [], []
    for intervals in busy.values():
        merged = union(clip(intervals, lo, hi))
        busy_s.append(sum(e - s for s, e in merged) / 1e9)
        idle.extend(gaps(merged, lo, hi))
    window_s = (hi - lo) / 1e9
    busy_mean = sum(busy_s) / len(busy_s)
    idle.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_mean,
        "window_s": window_s,
        "idle_share": 1.0 - busy_mean / window_s,
        "devices": len(busy),
        "device_ops": [[n, d / 1e9] for n, d in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label_gap(g, spans), (g[1] - g[0]) / 1e9]
                      for g in idle[:top]],
    }


def combine(parts: List[Dict], top: int = 10) -> Dict:
    """One reduction of several traced slices: their busy and traced
    seconds summed, operations summed by name, the longest gaps."""
    busy = sum(p["busy_s"] for p in parts)
    window = sum(p["window_s"] for p in parts)
    ops: Dict[str, float] = {}
    for p in parts:
        for n, d in p["device_ops"]:
            ops[n] = ops.get(n, 0.0) + d
    idle = sorted((g for p in parts for g in p["idle_gaps"]),
                  key=lambda g: -g[1])
    return {
        "busy_s": busy,
        "window_s": window,
        "idle_share": 1.0 - busy / window,
        "devices": parts[0]["devices"],
        "device_ops": [[n, d] for n, d in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": idle[:top],
    }
