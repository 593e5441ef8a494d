"""The trace reduction, checked on a trace recorded on the CPU.

``data/cpu_window.xplane.pb`` was recorded with JAX 0.9 on the CPU:
inside a ``bench.window`` span, a jitted sin-matmul, a 20 ms sleep in
``bench.ingest``, a jitted cos-cumsum, a 10 ms sleep in ``bench.write``,
the sin-matmul again and a bare 5 ms sleep.  On the CPU the XLA
operations run on the ``tf_XLA*`` threads of the ``/host:CPU`` plane,
so those lines stand in for a device's.  The numbers below were worked
out by hand from the trace's event list (start, duration in ns).
"""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "ccsbench"))

import trace_reduce  # noqa: E402

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "cpu_window.xplane.pb")
CPU = trace_reduce.Devices(
    plane=lambda p: p == "/host:CPU",
    busy_line=lambda ln: ln.startswith("tf_XLA"),
    op_line=lambda ln: ln.startswith("tf_XLA"),
    event=lambda n: not (n.startswith("end:") or "Threadpool" in n
                         or "Thunk" in n or "Await" in n))

# bench.window: start 126980, duration 39400405
WINDOW_S = 39400405e-9
# the eleven operations, none overlapping (ns):
# 362310 wrapped_sine + 454913 dot_general.1, 443461 copy_bitcast_fusion
# + 164473 + 89122 + 53359 + 7635 + 1922 + 104702 (the cumsum's
# fusions), 364059 wrapped_sine + 425168 dot_general.1
BUSY_S = 2471124e-9


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE, CPU, top=3)


def test_busy_and_idle(reduced):
    assert reduced["window_s"] == pytest.approx(WINDOW_S, abs=1e-12)
    assert reduced["busy_s"] == pytest.approx(BUSY_S, abs=1e-12)
    assert reduced["idle_share"] == pytest.approx(1 - BUSY_S / WINDOW_S)
    assert reduced["devices"] == 1


def test_top_operations(reduced):
    assert reduced["device_ops"] == [
        ["dot_general.1", pytest.approx((454913 + 425168) * 1e-9)],
        ["wrapped_sine", pytest.approx((362310 + 364059) * 1e-9)],
        ["copy_bitcast_fusion", pytest.approx(443461e-9)]]


def test_idle_gaps_labelled_by_the_harness_span(reduced):
    # 1145417 -> 21781299 overlaps bench.ingest [1211799, 21509984];
    # 22974017 -> 33457951 overlaps bench.write [23092947, 33249646];
    # 34325100 -> the window's end 39527385 overlaps no bench span
    assert reduced["idle_gaps"] == [
        ["bench.ingest", pytest.approx(20635882e-9)],
        ["bench.write", pytest.approx(10483934e-9)],
        ["host.other", pytest.approx(5202285e-9)]]


def test_interval_arithmetic():
    u = trace_reduce.union([(5, 7), (1, 3), (2, 4), (7, 8)])
    assert u == [(1, 4), (5, 8)]
    assert trace_reduce.gaps(u, 0, 10) == [(0, 1), (4, 5), (8, 10)]
    assert trace_reduce.clip(u, 2, 6) == [(2, 4), (5, 6)]
    assert trace_reduce.label_gap((0, 10), [("a", 0, 2), ("b", 3, 9)]) == "b"
    assert trace_reduce.label_gap((0, 1), [("a", 5, 6)]) == "host.other"


def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def test_the_interval_ends_where_the_buffer_ran_full():
    """A TPU trace whose device buffer filled: only the part before the
    drop counts, for busy time, operations and gaps alike."""
    pd = types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/host:CPU", lines=[_line("python", [
            _ev("bench.window", 0, 10_000), _ev("bench.write", 500, 400)])]),
        types.SimpleNamespace(name="/device:TPU:0", lines=[
            _line("XLA Modules", [_ev("jit_step(1)", 100, 300),
                                  _ev("jit_step(1)", 1000, 5000)]),
            _line("XLA Ops", [_ev("%while.1 = (s32[]) while(...)", 100, 300),
                              _ev("%fusion.2 = s32[] fusion(...)", 1000,
                                  5000)]),
            _line("XLA TraceMe", [_ev(trace_reduce.DROPPED, 2000, 8000)])])])
    r = trace_reduce.reduce(pd)
    assert r["window_s"] == pytest.approx(2000e-9)
    assert r["busy_s"] == pytest.approx((300 + 1000) * 1e-9)
    assert r["device_ops"] == [["%fusion.2", pytest.approx(1000e-9)],
                               ["%while.1", pytest.approx(300e-9)]]
    assert r["idle_gaps"][0] == ["bench.write", pytest.approx(600e-9)]


def test_slices_combine_into_one_reading():
    a = {"busy_s": 0.2, "window_s": 0.25, "devices": 1,
         "device_ops": [["%while.1", 0.15], ["%fusion.2", 0.05]],
         "idle_gaps": [["bench.write", 0.04], ["host.other", 0.01]]}
    b = {"busy_s": 0.1, "window_s": 0.25, "devices": 1,
         "device_ops": [["%fusion.2", 0.12]],
         "idle_gaps": [["bench.ingest", 0.15]]}
    r = trace_reduce.combine([a, b], top=2)
    assert r["busy_s"] == pytest.approx(0.3)
    assert r["window_s"] == pytest.approx(0.5)
    assert r["idle_share"] == pytest.approx(0.4)
    assert r["device_ops"] == [["%fusion.2", pytest.approx(0.17)],
                               ["%while.1", pytest.approx(0.15)]]
    assert r["idle_gaps"] == [["bench.ingest", 0.15], ["bench.write", 0.04]]


def test_a_trace_without_the_window_span_is_refused(tmp_path):
    with pytest.raises(ValueError):
        trace_reduce.reduce(TRACE, trace_reduce.TPU)
