"""The hifi_wgs configuration: its data files and its pool's sizes.

Its windowed loop at these widths, on the CPU, is
``tests/test_window_loop.py`` (chained 2,048-base windows through the
batched driver against the per-hole path, within this cell's
``err_rate`` limit).
"""

import json
import os
import statistics
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks", "ccsbench")
sys.path.insert(0, BENCH)

import cells  # noqa: E402
import gen  # noqa: E402
import reference  # noqa: E402

CELL = "hifi_wgs.stream"


@pytest.fixture(scope="module")
def cell():
    return cells.load(CELL)


def test_the_cell_loads_with_its_configuration(cell):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "hifi_wgs")
    assert entry["reduced"] == []
    assert cell.config["source"] == entry["source"]
    assert cell.chips == 1 and cell.traffic["name"] == "stream"
    assert {m["name"] for m in cell.end_to_end} == {
        "zmws_per_s", "consensus_qv", "setup_s"}
    assert "rows_per_zmw" in {m["name"] for m in cell.per_layer}
    assert set(cell.limits) == {"err_rate", "worst_hole_err", "missing",
                                "order_faults"}
    for k, lim in cell.limits.items():
        assert lim["lower"] <= lim["limit"]
        assert lim["upper"] is None or lim["limit"] < lim["upper"]


def test_the_program_runs_the_stated_windows(cell, tmp_path):
    """The windowed loop's widths the file states are the ones the
    cell's arguments give the program (the harness checks the
    ``program`` block; these are checked here)."""
    from ccsx_tpu import cli

    args = cli.build_parser().parse_args(
        [*cell.config["cli"], "--batch", "on", str(tmp_path / "in.bam"),
         str(tmp_path / "o.fa")])
    cfg = cli.config_from_args(args)
    got = {k: getattr(cfg, k) for k in cell.config["windowing"]}
    assert got == cell.config["windowing"]
    assert got == {"window_init": 2048, "window_add": 2048,
                   "window_minlen": 1024, "max_window": 8192}


def test_the_pool_is_a_hifi_smrt_cell(cell):
    sizes = gen.size_set(cell.config, cell.traffic["pool_holes"])
    subreads = [n for _, n in sizes]
    assert min(subreads) == 2 and max(subreads) == 25
    assert statistics.median(subreads) == 8
    assert all(15000 <= t <= 20000 for t, _ in sizes)
    # the reader's filter (-c 3: at least 5 subreads) drops 13 holes
    lens = [t * n for t, n in sizes]
    dropped = [not reference.kept(n, b, cell.config["program"])
               for (_, n), b in zip(sizes, lens)]
    assert sum(dropped) == 13
