"""Device time per stage and per program, and gaps labelled by the
program's spans (benchmarks/ccsbench/stages.py), on synthetic planes
and on a trace recorded on the CPU; and the three stage readers."""

import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "ccsbench"))

import cells  # noqa: E402
import stages  # noqa: E402
import trace_reduce  # noqa: E402

# the CPU stand-in for a device, as in test_ccsbench_trace.py
CPU = trace_reduce.Devices(
    plane=lambda p: p == "/host:CPU",
    busy_line=lambda ln: ln.startswith("tf_XLA"),
    op_line=lambda ln: ln.startswith("tf_XLA"),
    event=lambda n: not (n.startswith("end:") or "Threadpool" in n
                         or "Thunk" in n or "Await" in n))


def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start,
                                 duration_ns=dur, stats=[])


def _plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n.replace("_", " "), events=evs)
        for n, evs in lines.items()])


P = "jit(ccsx_refine_packed)/while/body"
# the programs' HLO op-name paths, as op_paths reads them off a trace
PATHS = {
    "jit_ccsx_refine_packed(17)": {
        "while.1": "jit(ccsx_refine_packed)/while",
        "while.2": P + "/fill/while",
        "dynamic-slice.3": P + "/fill/while/body/dynamic-slice",
        "while.4": P + "/traceback/while",
        "slice.8": "",        # XLA's own: no path
        "fusion.5": P + "/vote/reduce_sum",
        "fusion.6": "jit(ccsx_refine_packed)/breakpoint/cumsum"},
    # the pair fill's ops carry no stage, and its fusion.5 is its own
    "jit_ccsx_pair_fill(3)": {"fusion.5": "jit(ccsx_pair_fill)/while"},
}


def _device(drop=None, more=()):
    ops = [
        _ev("%while.1 = (s32[]) while(...)", 0, 1000),
        _ev("%while.2", 100, 500),
        _ev("%dynamic-slice.3", 200, 100),
        _ev("%while.4", 600, 200),
        _ev("%slice.8", 650, 50),
        _ev("%fusion.5 = s32[8] fusion(...)", 800, 100),
        _ev("%fusion.6", 900, 50),
        _ev("%fusion.5", 1200, 300),
    ]
    lines = {"XLA_Modules": [_ev("jit_ccsx_refine_packed(17)", 0, 1000),
                             _ev("jit_ccsx_pair_fill(3)", 1200, 300),
                             *more],
             "XLA_Ops": ops}
    if drop is not None:
        lines["XLA_TraceMe"] = [_ev(trace_reduce.DROPPED, drop, 10)]
    return _plane("/device:TPU:0", **lines)


def _host(*spans):
    return _plane("/host:CPU", python=[_ev(n, s, d) for n, s, d in spans])


def test_the_stage_names_are_the_programs():
    from ccsx_tpu.utils import trace

    assert stages.STAGES == trace.STAGES
    assert stages.PROGRAM_SPAN_PREFIX == trace.ANNOTATION_PREFIX


def test_self_time_per_stage_and_busy_seconds_per_program():
    """Each instant goes to the innermost operation, each operation to
    the first stage on its path: the fill loop keeps its nested slice,
    the traceback loop its nested path-less slice, and the outer refine
    loop's own time and the pair fill are 'other'."""
    pd = types.SimpleNamespace(planes=[
        _host(("bench.window", 0, 2000)), _device()])
    r = stages.reduce(pd, paths=PATHS)
    assert r["scopes"] == pytest.approx({
        "fill": 500e-9, "traceback": 200e-9, "vote": 100e-9,
        "breakpoint": 50e-9, "other": (100 + 50 + 300) * 1e-9})
    assert r["programs"] == pytest.approx({"ccsx_refine_packed": 1000e-9,
                                           "ccsx_pair_fill": 300e-9})
    assert list(r["programs"]) == ["ccsx_refine_packed", "ccsx_pair_fill"]


def test_stages_end_where_the_buffer_ran_full():
    pd = types.SimpleNamespace(planes=[
        _host(("bench.window", 0, 2000)), _device(drop=650)])
    r = stages.reduce(pd, paths=PATHS)
    assert r["scopes"]["fill"] == pytest.approx(500e-9)
    assert r["scopes"]["traceback"] == pytest.approx(50e-9)
    assert r["scopes"]["vote"] == 0.0
    assert r["programs"] == pytest.approx({"ccsx_refine_packed": 650e-9})


def test_gaps_labelled_by_the_innermost_program_span():
    """A gap inside the driver's emit (itself inside an admit) is
    emit's; one overlapping only the harness's writer is the writer's;
    one overlapping nothing is host.other."""
    pd = types.SimpleNamespace(planes=[
        _host(("bench.window", 0, 3000), ("ccsx.admit", 950, 350),
              ("ccsx.emit", 1000, 250), ("bench.write", 1550, 500)),
        _device(more=[_ev("jit_ccsx_seed(4)", 2100, 100)])])
    r = stages.reduce(pd, paths=PATHS)
    assert r["span_gaps"] == [["host.other", pytest.approx(800e-9)],
                              ["bench.write", pytest.approx(600e-9)],
                              ["ccsx.emit", pytest.approx(200e-9)]]


def test_path_and_program_names():
    assert stages.stage_of("jit(f)/while/body/vote/while") == "vote"
    assert stages.stage_of("jit(ccsx_pair_fill)/jit(fill)/x") == "other"
    assert stages.stage_of(
        "jit(ccsx_refine_packed_fused)/vmap(breakpoint)/le") == "breakpoint"
    assert stages.program_name("jit_ccsx_seed(12)") == "ccsx_seed"
    assert stages.program_name("ccsx_round") == "ccsx_round"


def test_op_paths_read_off_a_recorded_trace():
    """The programs' HLO, kept in the trace's metadata plane, names each
    instruction's op-name path (test_ccsbench_trace.py's CPU trace)."""
    paths = stages.op_paths(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "data",
        "cpu_window.xplane.pb"))
    assert paths["jit__lambda(5)"]["dot_general.1"] == (
        "jit(<lambda>)/dot_general")
    assert paths["jit__lambda(5)"]["wrapped_sine"] == "jit(<lambda>)/sin"


def test_slices_combine_into_one_reading():
    a = {"scopes": {"fill": 0.2, "other": 0.1},
         "programs": {"ccsx_refine_packed": 0.3},
         "span_gaps": [["ccsx.emit", 0.02]]}
    b = {"scopes": {"fill": 0.1, "vote": 0.05},
         "programs": {"ccsx_refine_packed": 0.1, "ccsx_pair_fill": 0.05},
         "span_gaps": [["host.other", 0.03], ["bench.write", 0.01]]}
    r = stages.combine([a, b], top=2)
    assert r["scopes"]["fill"] == pytest.approx(0.3)
    assert r["scopes"]["vote"] == pytest.approx(0.05)
    assert r["scopes"]["traceback"] == 0.0
    assert r["programs"] == pytest.approx({"ccsx_refine_packed": 0.4,
                                           "ccsx_pair_fill": 0.05})
    assert r["span_gaps"] == [["host.other", 0.03], ["ccsx.emit", 0.02]]


def test_a_program_span_on_the_cpu_labels_its_gap(tmp_path):
    """Recorded here: the program's span around a 0.1 s wait between
    two jitted calls inside the harness's window span is the gap's
    label, and the calls' named scopes are their stages."""
    import jax
    import jax.numpy as jnp

    from ccsx_tpu.utils import trace

    def g(x):
        with jax.named_scope("fill"):
            y = jnp.sin(x)
        with jax.named_scope("vote"):
            return y @ x

    f = jax.jit(g)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    d = str(tmp_path / "prof")
    with jax.profiler.trace(d):
        with jax.profiler.TraceAnnotation("bench.window"):
            f(x).block_until_ready()
            with trace.span("emit", cat="write"):
                time.sleep(0.1)
            f(x).block_until_ready()
    r = stages.reduce(trace_reduce.find_xplane(d), CPU)
    assert [g[0] for g in r["span_gaps"] if g[1] >= 0.09] == ["ccsx.emit"]
    # the harness's own reduction of the same trace leaves it unnamed
    assert [g[0] for g in trace_reduce.reduce(
        trace_reduce.find_xplane(d), CPU)["idle_gaps"]
        if g[1] >= 0.09] == ["host.other"]
    assert r["scopes"]["fill"] > 0 and r["scopes"]["vote"] > 0


@pytest.mark.parametrize("stage", ["fill", "traceback", "vote"])
def test_stage_readers(stage):
    """Self-seconds over the traced slices, over their traced seconds,
    times the window's seconds, over its records; None where the trace
    has no stages."""
    tr = {"busy_s": 0.35, "window_s": 0.4,
          "scopes": {"fill": 0.2, "traceback": 0.1, "vote": 0.04,
                     "breakpoint": 0.005, "other": 0.005}}
    ctx = types.SimpleNamespace(window_s=40.0, trace=tr,
                                records=[(0, "m/1/ccs", b"")] * 80)
    read = cells.reader(f"{stage}_s_per_zmw")
    assert read(ctx) == pytest.approx(tr["scopes"][stage] / 0.4 * 40 / 80)
    ctx.trace = {"busy_s": 0.35, "window_s": 0.4}
    assert read(ctx) is None
    ctx.trace = None
    assert read(ctx) is None
