"""Cells and metric readers found by name, on synthetic snapshots."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks", "ccsbench")
sys.path.insert(0, BENCH)

import cells  # noqa: E402
import gen  # noqa: E402


def _ctx(**kw):
    at_open = {"t_ingest": 1.0, "t_prep_blocked": 2.0, "dp_rows_real": 100,
               "dp_rows_dispatched": 128, "device_dispatches": 10}
    at_close = {"t_ingest": 1.6, "t_prep_blocked": 3.0, "dp_rows_real": 460,
                "dp_rows_dispatched": 512, "device_dispatches": 16}
    ctx = types.SimpleNamespace(
        window_s=20.0, setup_s=95.5, records=[(0, "m/1/ccs", b"")] * 4,
        errors=3, bases=3000, at_open=at_open, at_close=at_close,
        delta=lambda k: at_close[k] - at_open[k], compiles=[], trace=None,
        traced_records=None, pool=[], config={})
    for k, v in kw.items():
        setattr(ctx, k, v)
    return ctx


def test_every_named_metric_has_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(cells.reader(m["name"]))


@pytest.mark.parametrize("name,want", [
    ("zmws_per_s", 4 / 20.0),
    ("consensus_qv", 30.0),
    ("setup_s", 95.5),
    ("ingest_share", 0.6 / 20.0),
    ("prep_blocked_share", 1.0 / 20.0),
    ("dp_row_fill", 360 / 384),
    ("dispatches_per_zmw", 6 / 4),
    ("window_compiles", 0),
])
def test_reader_values(name, want):
    assert cells.reader(name)(_ctx()) == pytest.approx(want)


def test_readers_with_nothing_to_read_return_none():
    empty = _ctx(records=[], bases=0,
                 delta=lambda k: 0)
    for name in ("consensus_qv", "dp_row_fill", "dispatches_per_zmw",
                 "device_idle_share", "boundary_idle_share", "dp_gcups"):
        assert cells.reader(name)(empty) is None


@pytest.mark.parametrize("impl", ["scan", "pallas", "rotband"])
def test_dp_gcups_counts_the_input_not_the_fill(impl, monkeypatch):
    """Nominal cells: first max_passes passes x (2 band + 1) x
    (1 + refine_iters), whatever fill the program is told to use; over
    the window's seconds times the traced busy share."""
    monkeypatch.setenv("CCSX_BANDED_IMPL", impl)
    program = {"band": 128, "refine_iters": 2, "max_passes": 3}
    pool = [gen.Hole(0, None, [bytes(100), bytes(200), bytes(300),
                               bytes(400)]),
            gen.Hole(1, None, [bytes(50)])]
    ctx = _ctx(pool=pool, config={"program": program},
               trace={"busy_s": 0.5, "window_s": 1.0},
               records=[(0, "m/0/ccs", b""), (0, "m/3/ccs", b""),
                        (0, "m/2/ccs", b"")])
    cells_0 = (100 + 200 + 300) * 257 * 3
    cells_1 = 50 * 257 * 3
    want = (2 * cells_0 + cells_1) / 1e9 / (20.0 * 0.5)   # 3 -> 1, 2 -> 0
    assert cells.reader("dp_gcups")(ctx) == pytest.approx(want)


def test_device_idle_share_reads_the_trace():
    assert cells.reader("device_idle_share")(
        _ctx(trace={"idle_share": 0.25})) == 0.25


def test_boundary_idle_share_reads_its_own_slice():
    tr = {"idle_share": 0.25, "boundary": {"idle_share": 0.75}}
    assert cells.reader("boundary_idle_share")(_ctx(trace=tr)) == 0.75
    assert cells.reader("boundary_idle_share")(
        _ctx(trace={"idle_share": 0.25})) is None


def test_a_cell_added_as_data_only_is_found(tmp_path):
    """A new configuration file, traffic file, limits file and
    workloads entry: found by name with no code change."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "amplicon_deep.json")))
    cfg["name"] = "cdna"
    (tmp_path / "cdna.json").write_text(json.dumps(cfg))
    bench["configs"].append({"name": "cdna", "source": "x",
                             "file": "cdna.json", "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "cdna.burst", "config": "cdna",
                               "traffic": "burst", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "ingest_share", "unit": "share",
                               "better": "lower",
                               "source": "program_counter", "layer": "x",
                               "moves": "zmws_per_s",
                               "workloads": ["amplicon_deep.stream"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for d in ("traffic", "limits"):
        (tmp_path / d).mkdir()
    (tmp_path / "traffic" / "burst.json").write_text(
        json.dumps({"name": "burst", "pool_holes": 4, "ramp_holes": 2}))
    (tmp_path / "limits" / "cdna.burst.json").write_text(
        json.dumps({"err_rate": {"limit": 0.1}}))
    cell = cells.load("cdna.burst", root=str(tmp_path), here=str(tmp_path))
    assert cell.config["name"] == "cdna"
    assert cell.traffic["pool_holes"] == 4
    assert cell.limits["err_rate"]["limit"] == 0.1
    names = [m["name"] for m in cell.per_layer]
    assert names.count("ingest_share") == 1   # the listed-cells entry is not
    with pytest.raises(KeyError):
        cells.load("nope.stream", root=str(tmp_path), here=str(tmp_path))


def test_run_refuses_the_cpu(tmp_path):
    """No TPU: exit non-zero and print no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", "amplicon_deep.stream", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    assert "not 'tpu'" in r.stderr


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    """Only BENCHMARK.json and the benchmark's files: no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "ccsbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "benchmarks/ccsbench/run.py",
                        "--workload", "amplicon_deep.stream", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
