"""Ragged pass-packing (pipeline/pack.py + batch._refine_step_packed):
byte-parity with the host refinement spec and the bucketed control path,
the hole-level OOM-resplit ladder, and the packing occupancy counters.

The packer's own invariants live in the fast unit tier
(tests/test_pack.py); here the packed DEVICE path is differential-tested
— the acceptance pin that lets packing be the batched default."""

import re

import numpy as np
import pytest

from ccsx_tpu import cli
from ccsx_tpu.config import CcsConfig
from ccsx_tpu.consensus import windowed as win_mod
from ccsx_tpu.consensus.star import RefineRequest, StarMsa, refine_host
from ccsx_tpu.pipeline.batch import BatchExecutor
from ccsx_tpu.utils import faultinject, synth
from ccsx_tpu.utils.metrics import Metrics

# mixed pass counts around and past the old {4, 8, 16, 32} bucket edges,
# one shared length bucket so the whole set packs into few slabs (cheap
# compiles); the error-free hole exercises the fixpoint freeze inside a
# shared slab
SPECS = [(3, 500, 0.12), (5, 500, 0.06), (4, 500, 0.0), (9, 500, 0.12),
         (11, 500, 0.1)]


def _requests(rng, cfg, specs=SPECS):
    sm = StarMsa(cfg.align, cfg.max_ins_per_col, cfg.len_bucket_quant)
    reqs = []
    for n, tlen, err in specs:
        tpl = rng.integers(0, 4, tlen).astype(np.uint8)
        if err == 0.0:
            ps = [tpl.copy() for _ in range(n)]
        else:
            ps = [synth.mutate(rng, tpl, err / 3, err / 3, err / 3)
                  for _ in range(n)]
        qs, qlens, row_mask = sm.pack(ps, cfg.pass_buckets, cfg.max_passes)
        reqs.append(RefineRequest(qs, qlens, row_mask, ps[0],
                                  cfg.refine_iters))
    return sm, reqs


def _assert_refine_matches_host(sm, cfg, req, res):
    want = refine_host(sm.round, req.qs, req.qlens, req.row_mask,
                       req.draft, req.iters)
    np.testing.assert_array_equal(res.draft, want.draft)
    rr, wr = res.rr, want.rr
    assert rr.tlen == wr.tlen
    T = rr.tlen
    np.testing.assert_array_equal(rr.cons[:T], wr.cons[:T])
    np.testing.assert_array_equal(rr.ins_base[:T], wr.ins_base[:T])
    np.testing.assert_array_equal(rr.ins_votes[:T], wr.ins_votes[:T])
    np.testing.assert_array_equal(rr.ncov[:T], wr.ncov[:T])
    nseq = int(req.row_mask.sum())
    host_bp = win_mod.find_breakpoint(wr, nseq, cfg)
    if rr.bp is not None:  # host-replayed results carry bp=None
        assert (rr.bp if rr.bp >= 1 else None) == host_bp
        bp_eff = host_bp if host_bp is not None else max(
            T - cfg.bp_window, 1)
        np.testing.assert_array_equal(
            rr.advance, win_mod._advance(wr, bp_eff).astype(np.int32))


def test_packed_refine_matches_host_and_counts(rng):
    """Slab-packed fused dispatches == the host refinement loop,
    bitwise, across pass counts spanning the old bucket edges — with a
    row budget small enough to force multiple slabs, tail shrinking,
    and cross-hole slab sharing.  The packing counters must tell the
    same story the dispatch plan does."""
    cfg = CcsConfig(is_bam=False, slab_rows=16)
    sm, reqs = _requests(rng, cfg)
    metrics = Metrics()
    ex = BatchExecutor(cfg, metrics=metrics)
    assert ex._packing
    results = ex.run(reqs)
    for req, res in zip(reqs, results):
        _assert_refine_matches_host(sm, cfg, req, res)
    assert metrics.refine_overflows == 0
    assert metrics.windows == len(reqs)
    # 32 rows over a 16-row budget: more than one slab, all real rows
    # dispatched exactly once.  Under the test harness's 8 fake devices
    # the slabs stack into ONE fused multi-chip wave (one dispatch);
    # fused_slabs_real still counts every planned slab
    assert metrics.packed_dispatches >= 1
    assert metrics.fused_slabs_real >= 2
    assert metrics.fused_waves == metrics.packed_dispatches
    assert metrics.dp_rows_real == sum(n for n, _, _ in SPECS)
    assert 0 < metrics.dp_rows_real <= metrics.dp_rows_dispatched
    snap = metrics.snapshot()
    assert snap["dp_z_fill"] == 1.0  # a slab IS the dispatch: no Z pad
    assert 0 < snap["dp_row_fill"] <= 1
    assert snap["packed_holes_per_dispatch"] >= 1
    assert 0 < snap["fused_slot_fill"] <= 1
    assert snap["distinct_slab_shapes"] >= 1


def test_packed_slab_rows_knob_output_invariant(rng):
    """The row budget changes only slab tiling, never results: the
    byte-identity that makes --slab-rows a safe tuning knob."""
    cfg_a = CcsConfig(is_bam=False, slab_rows=16)
    cfg_b = CcsConfig(is_bam=False, slab_rows=64)
    _, reqs = _requests(rng, cfg_a)
    ra = BatchExecutor(cfg_a).run(reqs)
    rb = BatchExecutor(cfg_b).run(reqs)
    for a, b in zip(ra, rb):
        assert a.rr.tlen == b.rr.tlen
        assert a.rr.bp == b.rr.bp
        np.testing.assert_array_equal(a.rr.cons, b.rr.cons)
        np.testing.assert_array_equal(a.rr.advance, b.rr.advance)
        np.testing.assert_array_equal(a.draft, b.draft)


def test_packed_oom_bisects_by_hole_then_replays_on_host(rng):
    """The recovery ladder on a packed slab: an OOM bisects the slab BY
    HOLE and re-packs each half at the smaller covering slab (results
    must stay bitwise); a persistent OOM runs the ladder to the
    per-hole host replay — the packed analog of the Z-bucket resplit
    acceptance cases in test_faults.py."""
    cfg = CcsConfig(is_bam=False, slab_rows=16)
    sm, reqs = _requests(rng, cfg)
    try:
        faultinject.arm("device_oom@1")
        m1 = Metrics()
        res = BatchExecutor(cfg, metrics=m1).run(reqs)
        assert m1.oom_resplits >= 1 and m1.host_fallbacks == 0
        for req, r in zip(reqs, res):
            _assert_refine_matches_host(sm, cfg, req, r)

        faultinject.arm("device_oom@1+")
        m2 = Metrics()
        res = BatchExecutor(cfg, metrics=m2).run(reqs)
        assert m2.oom_resplits >= 1 and m2.host_fallbacks >= 1
        for req, r in zip(reqs, res):
            _assert_refine_matches_host(sm, cfg, req, r)
    finally:
        faultinject.disarm()


@pytest.mark.slow  # ~20s three-arm CLI A/B (r15 budget audit); tier-1
# keeps the executor-level packed==bucketed pins in test_batch.py
# (packed_transfer_protocol, executor_matches_per_hole) and the CLI
# batched==per-hole pin (test_cli_batched_equals_per_hole)
def test_cli_packed_equals_bucketed_equals_per_hole(tmp_path, rng):
    """The tentpole acceptance pin on a mixed-pass synth corpus: the
    packed default, the --pass-buckets bucketed control, and the
    per-hole path must produce byte-identical FASTQ, while the
    occupancy counters show which grouping ran."""
    import json

    zs = [synth.make_zmw(rng, template_len=700, n_passes=5 + 2 * h,
                         movie="mv", hole=str(h)) for h in range(4)]
    fa = tmp_path / "in.fa"
    fa.write_text(synth.make_fasta(zs))
    outs, finals = {}, {}
    for tag, extra in (
            ("packed", ["--batch", "on"]),
            ("bucketed", ["--batch", "on", "--pass-buckets", "4,8,16,32"]),
            ("perhole", ["--batch", "off"])):
        o = tmp_path / f"{tag}.fq"
        m = tmp_path / f"{tag}.jsonl"
        assert cli.main(["-A", "-m", "1000", "--fastq", "--metrics",
                         str(m), *extra, str(fa), str(o)]) == 0
        outs[tag] = o.read_text()
        finals[tag] = [json.loads(ln)
                       for ln in m.read_text().splitlines()][-1]
    assert outs["packed"] == outs["bucketed"] == outs["perhole"]
    assert outs["packed"].count("@mv/") == 4
    assert finals["packed"]["dp_row_fill"] is not None
    assert finals["packed"]["packed_holes_per_dispatch"] >= 1
    assert finals["bucketed"]["dp_row_fill"] is None  # control ran bucketed


@pytest.mark.parametrize("fused", [False, True])
def test_packed_refine_step_names_its_program_and_stages(fused):
    """Lowered, not compiled: the packed refine program is named for
    its dispatch site (the profiler trace's "XLA Modules"), and its
    operations carry the four stage scopes (their op-name paths)."""
    import jax
    import jax.numpy as jnp

    from ccsx_tpu.parallel.mesh import build_slab_mesh
    from ccsx_tpu.pipeline import batch as bm
    from ccsx_tpu.utils import trace

    cfg = CcsConfig(is_bam=False)
    R, qmax, tmax, H = 8, 128, 256, 2
    Lbig, Lsmall = bm._slab_wire_sizes(R, qmax, H, tmax,
                                       cfg.max_ins_per_col)
    bp = BatchExecutor(cfg)._bp_consts()
    lead = ()
    if fused:
        step = bm._refine_step_packed_fused(
            cfg.align, cfg.max_ins_per_col, tmax, 2, H, bp, (R, qmax),
            build_slab_mesh(jax.devices()[:2]))
        lead = (2,)
    else:
        step = bm._refine_step_packed(cfg.align, cfg.max_ins_per_col,
                                      tmax, 2, H, bp, pack=(R, qmax))
    text = step.lower(jax.ShapeDtypeStruct(lead + (Lbig,), jnp.uint8),
                      jax.ShapeDtypeStruct(lead + (Lsmall,), jnp.int32)
                      ).as_text(debug_info=True)
    name = "ccsx_refine_packed_fused" if fused else "ccsx_refine_packed"
    assert f"module @jit_{name} " in text
    for stage in trace.STAGES:
        # a part of an op-name path; 'vmap(<scope>)' under the fused vmap
        assert re.search(rf"[/(]{stage}[)/]", text), stage



@pytest.fixture
def clear_packed_steps():
    """Drops the jitted packed steps, and again after the test: a fill
    forced by the environment is chosen when a step is first traced."""
    from ccsx_tpu.pipeline import batch as bm

    def clear():
        bm._refine_step_packed.cache_clear()
        bm._refine_step_packed_fused.cache_clear()

    clear()
    yield clear
    clear()


@pytest.mark.parametrize("one_device", [True, False])
def test_packed_refine_pallas_matches_scan(rng, monkeypatch,
                                           clear_packed_steps, one_device):
    """The v1 kernel (interpret mode here) in the packed refine step, on
    one device and in the fused multi-chip step, gives the scan's
    results byte for byte."""
    import jax

    from ccsx_tpu.ops import banded_pallas

    kernel = banded_pallas.batched_align_global_moves
    traced = []
    monkeypatch.setattr(banded_pallas, "batched_align_global_moves",
                        lambda *a, **k: traced.append(1) or kernel(*a, **k))
    cfg = CcsConfig(is_bam=False, slab_rows=16)
    _, reqs = _requests(rng, cfg)
    devices = jax.devices()[:1] if one_device else None
    out = {}
    for impl in ("scan", "pallas"):
        monkeypatch.setenv("CCSX_BANDED_IMPL", impl)
        clear_packed_steps()
        metrics = Metrics()
        out[impl] = BatchExecutor(cfg, metrics=metrics,
                                  devices=devices).run(reqs)
        assert set(metrics.banded_dispatches) == {impl}
        assert metrics.host_fallbacks == 0
        assert bool(traced) == (impl == "pallas")
    for a, b in zip(out["scan"], out["pallas"]):
        assert a.rr.tlen == b.rr.tlen and a.rr.bp == b.rr.bp
        for f in ("cons", "ins_base", "ins_votes", "ncov", "nwin",
                  "advance"):
            np.testing.assert_array_equal(getattr(a.rr, f),
                                          getattr(b.rr, f), err_msg=f)
        np.testing.assert_array_equal(a.draft, b.draft)
