"""The harness end to end on the CPU, at a size a test run can hold.

A tiny cell, added as data only (configuration, traffic and limits
files in a temporary root), runs through ``run.run`` with the chip check
skipped: the program's batched driver over the streamed pool, the ramp,
a window closed after a count of records, and the judgement.  Then the
same run with the timed path broken underneath must come out not
correct: the control (the program's own ``--max-passes 3``, consensus
from three passes where the configuration states 32), an answer
altered where it is produced (every third hole, or one in six), and
half of the holes left out.

Limits (limits/tiny.stream.json below), from CPU runs of this cell on
seeds 2**31 + 5, 2**31 + 77 and 2**32 + 9 (PR 22): ``err_rate`` 0.03,
between the sound runs' 0.0085-0.0111 and the control's 0.0513-0.0555;
``worst_hole_err`` 0.1, above the sound runs' 0.019-0.027 (the
control's 0.062-0.077 is under three times that: ``err_rate`` is the
number that fails it) and below a shuffled hole's ~0.5; ``missing`` and
``order_faults`` 0.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks", "ccsbench")
sys.path.insert(0, BENCH)

import cells  # noqa: E402
import run  # noqa: E402

SEED = 2**31 + 5


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tmp_path_factory.mktemp("cell")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(BENCH, "configs", "amplicon_deep.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny", template_len={"dist": "uniform", "lo": 700,
                                          "hi": 1000},
               polymerase_len={"dist": "lognormal", "median": 9000,
                               "sigma": 0.2})
    cfg["cli"][cfg["cli"].index("-m") + 1] = "1000"
    cfg["cli"] += ["--inflight", "4"]         # few shapes to compile here
    cfg["program"]["min_len"] = 1000
    (root / "tiny.json").write_text(json.dumps(cfg))
    bench["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                         "file": "tiny.json", "why": "test"}]
    bench["workloads"] = [{"name": "tiny.stream", "config": "tiny",
                           "traffic": "stream", "chips": 1, "why": "test"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for d in ("traffic", "limits"):
        (root / d).mkdir()
    (root / "traffic" / "stream.json").write_text(json.dumps(
        {"name": "stream", "pool_holes": 6, "ramp_holes": 2}))
    (root / "limits" / "tiny.stream.json").write_text(json.dumps(
        {"err_rate": {"limit": 0.03}, "worst_hole_err": {"limit": 0.1},
         "missing": {"limit": 0}, "order_faults": {"limit": 0}}))
    return cells.load("tiny.stream", root=str(root), here=str(root))


@pytest.fixture(autouse=True)
def one_device(monkeypatch):
    """One device, as on a one-chip machine (the tests' CPU has 8)."""
    import jax

    from ccsx_tpu.pipeline import batch

    base = batch.BatchExecutor

    class OneDevice(base):
        def __init__(self, cfg, **kw):
            kw["devices"] = jax.local_devices()[:1]
            super().__init__(cfg, **kw)

    monkeypatch.setattr(batch, "BatchExecutor", OneDevice)
    yield
    while run.DRIVERS:       # each stops at its first record after close
        run.DRIVERS.pop().join(300)


def _run(cell, **kw):
    return run.run(cell, SEED, 0.0, False, require_tpu=False,
                   min_records=6, **kw)


def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 6 and res["failed"] == 0
    assert set(res["metrics"]) == {"zmws_per_s", "consensus_qv", "setup_s"}
    assert list(res)[-1] == "checks"


def test_control_is_not_correct(cell):
    """Consensus from 3 passes where the configuration states 32."""
    res = _run(cell, cli_extra=["--max-passes", "3", "--pass-buckets",
                                "4"])
    assert not res["correct"]
    assert res["checks"]["err_rate"]["value"] > 0.03


@pytest.mark.parametrize("every,check,over", [
    (3, "err_rate", 0.03),           # every third hole shuffled
    (6, "worst_hole_err", 0.1),      # one in six: the worst hole fails
])
def test_altered_answer_is_not_correct(cell, monkeypatch, every, check,
                                       over):
    """Holes shuffled where the driver produces them."""
    from ccsx_tpu.pipeline import batch

    real = batch._finish
    calls = []

    def altered(result):
        rec = real(result)
        calls.append(1)
        if rec is None or len(calls) % every:
            return rec
        seq = np.frombuffer(rec[0], np.uint8).copy()
        np.random.default_rng(len(calls)).shuffle(seq)
        return seq.tobytes(), rec[1]

    monkeypatch.setattr(batch, "_finish", altered)
    res = _run(cell)
    assert not res["correct"]
    assert res["checks"][check]["value"] > over


def test_half_the_holes_left_out_is_not_correct(cell, monkeypatch):
    """Every other hole finishes without a record."""
    from ccsx_tpu.pipeline import batch

    real = batch._finish
    calls = []

    def dropped(result):
        calls.append(1)
        return None if len(calls) % 2 else real(result)

    monkeypatch.setattr(batch, "_finish", dropped)
    res = _run(cell)
    assert not res["correct"]
    assert res["checks"]["order_faults"]["value"] > 0
    assert res["checks"]["missing"]["value"] > 0
