"""Batched device pipeline (pipeline/batch.py): parity with the per-hole
path, shape-bucketed execution, ordering, quarantine, and resume."""

import numpy as np
import pytest

from ccsx_tpu import cli
from ccsx_tpu.config import CcsConfig
from ccsx_tpu.consensus.star import RoundRequest, StarMsa, run_rounds
from ccsx_tpu.consensus.windowed import windowed_gen
from ccsx_tpu.io import fastx
from ccsx_tpu.ops import encode as enc
from ccsx_tpu.pipeline.batch import BatchExecutor, _z_bucket
from ccsx_tpu.utils import synth


def _passes(rng, n=4, tlen=600):
    tpl = rng.integers(0, 4, tlen).astype(np.uint8)
    return [synth.mutate(rng, tpl, 0.02, 0.04, 0.04) for _ in range(n)]


def test_z_bucket():
    assert _z_bucket(1) == 1
    assert _z_bucket(3) == 4
    assert _z_bucket(64) == 64
    assert _z_bucket(65) == 128  # keeps doubling: bounded retraces


@pytest.mark.slow  # ~23s: fine-grained executor-vs-per-hole A/B; the
# CLI batched==per-hole byte-identity pin below keeps the invariant
# tier-1 (r20 budget audit)
def test_executor_matches_per_hole_rounds(rng):
    """One batched dispatch == N independent per-hole rounds, bitwise."""
    cfg = CcsConfig(is_bam=False)
    sm = StarMsa(cfg.align, cfg.max_ins_per_col, cfg.len_bucket_quant)
    reqs = []
    for i in range(5):
        ps = _passes(rng, n=3 + (i % 3), tlen=500 + 40 * i)
        qs, qlens, row_mask = sm.pack(ps, cfg.pass_buckets, cfg.max_passes)
        reqs.append(RoundRequest(qs, qlens, row_mask, ps[0]))

    batched = BatchExecutor(cfg).run(reqs)
    for req, rb in zip(reqs, batched):
        ra = sm.round(req.qs, req.qlens, req.row_mask, req.draft)
        assert ra.tlen == rb.tlen
        np.testing.assert_array_equal(ra.cons, rb.cons)
        np.testing.assert_array_equal(ra.ins_base, rb.ins_base)
        np.testing.assert_array_equal(ra.ins_votes, rb.ins_votes)
        np.testing.assert_array_equal(ra.ncov, rb.ncov)
        # the batched path leaves the big per-pass tensors on device and
        # returns the device breakpoint + advance instead; they must
        # equal the host spec computed from the per-hole result
        assert rb.aligned is None and rb.match is None
        from ccsx_tpu.consensus import windowed as win_mod

        nseq = int(req.row_mask.sum())
        host_bp = win_mod.find_breakpoint(ra, nseq, cfg)
        assert (rb.bp if rb.bp >= 1 else None) == host_bp
        bp_eff = host_bp if host_bp is not None else max(
            ra.tlen - cfg.bp_window, 1)
        np.testing.assert_array_equal(
            rb.advance, win_mod._advance(ra, bp_eff).astype(np.int32))


@pytest.mark.slow  # ~17s window sweep; the CLI batched==per-hole pin
# keeps the executor tier-1 (r13 audit; r20 moved per-hole-rounds slow)
def test_executor_drives_windowed_gen_to_same_result(rng):
    """Driving the windowed generator with batched results reproduces the
    per-hole windowed consensus exactly."""
    cfg = CcsConfig(is_bam=False, window_init=512, window_add=512,
                    window_minlen=256, max_window=2048)
    sm = StarMsa(cfg.align, cfg.max_ins_per_col, cfg.len_bucket_quant)
    ps = _passes(rng, n=5, tlen=1500)

    want = run_rounds(windowed_gen(ps, cfg), sm)

    ex = BatchExecutor(cfg)
    gen = windowed_gen(ps, cfg)
    req = next(gen)
    try:
        while True:
            rr = ex.run([req])[0]
            req = gen.send(rr)
    except StopIteration as e:
        got = e.value
    np.testing.assert_array_equal(want, got)


def _make_inputs(tmp_path, rng, n_holes, tlen=900):
    # >=5 passes so every hole clears the count filter (min_fulllen_count+2)
    zs = [synth.make_zmw(rng, template_len=tlen, n_passes=5 + (h % 3),
                         movie="mv", hole=str(100 + h))
          for h in range(n_holes)]
    fa = tmp_path / "in.fa"
    fa.write_text(synth.make_fasta(zs))
    return zs, fa


def test_cli_batched_equals_per_hole(tmp_path, rng):
    """--batch on must produce byte-identical output to --batch off."""
    zs, fa = _make_inputs(tmp_path, rng, n_holes=4)
    o_ref = tmp_path / "ref.fa"
    o_bat = tmp_path / "bat.fa"
    assert cli.main(["-A", "-m", "1000", "--batch", "off",
                     str(fa), str(o_ref)]) == 0
    assert cli.main(["-A", "-m", "1000", "--batch", "on",
                     str(fa), str(o_bat)]) == 0
    assert o_ref.read_text() == o_bat.read_text()
    assert o_ref.read_text().count(">") == 4


@pytest.mark.slow  # ~20s: projector A/B; per-hole equality tests stay tier-1 (r11 audit)
def test_cli_batched_scan_projector_equals_walk(tmp_path, rng, monkeypatch):
    """CCSX_PROJECTOR=scan (the TPU-default row-scan traceback,
    ops/traceback.make_projector_scan) through the FULL fused batched
    pipeline must be byte-identical to the walk default — integration
    coverage for the composition (vmap inside _refine_step's while_loop)
    that unit differential tests can't see."""
    from ccsx_tpu.consensus import star
    from ccsx_tpu.pipeline import batch as batch_mod

    zs, fa = _make_inputs(tmp_path, rng, n_holes=3, tlen=1100)
    o_ref = tmp_path / "ref.fq"
    o_scan = tmp_path / "scan.fq"
    args = ["-A", "-m", "1000", "--fastq", "--batch", "on"]

    def clear():
        for fn in (star._projector, batch_mod._round_body,
                   batch_mod._round_step, batch_mod._refine_step):
            fn.cache_clear()

    # pin BOTH runs explicitly: the unset-env default is the walk on
    # every backend (until the TPU A/B flips it), but a pre-set
    # CCSX_PROJECTOR in the environment would pollute the baseline
    clear()  # projector impl is read when the builders run
    monkeypatch.setenv("CCSX_PROJECTOR", "walk")
    try:
        assert cli.main(args + [str(fa), str(o_ref)]) == 0
        clear()
        monkeypatch.setenv("CCSX_PROJECTOR", "scan")
        assert cli.main(args + [str(fa), str(o_scan)]) == 0
    finally:
        monkeypatch.undo()
        clear()
    assert o_ref.read_text() == o_scan.read_text()
    assert o_ref.read_text().count("@") >= 3


def test_cli_batched_whole_read_equals_per_hole(tmp_path, rng):
    zs, fa = _make_inputs(tmp_path, rng, n_holes=3)
    o_ref = tmp_path / "ref.fa"
    o_bat = tmp_path / "bat.fa"
    assert cli.main(["-A", "-P", "-m", "1000", "--batch", "off",
                     str(fa), str(o_ref)]) == 0
    assert cli.main(["-A", "-P", "-m", "1000", "--batch", "on",
                     str(fa), str(o_bat)]) == 0
    assert o_ref.read_text() == o_bat.read_text()


@pytest.mark.slow  # ~10s: a third batch-grid point (r20 budget audit,
# same family as the two r16 demotions); the CLI batched==per-hole
# byte-identity pin keeps ordering tier-1 at the default window
def test_cli_batched_small_inflight_preserves_order(tmp_path, rng):
    """A tiny in-flight window forces staggered admission; output order
    must stay input order."""
    zs, fa = _make_inputs(tmp_path, rng, n_holes=5, tlen=700)
    out = tmp_path / "o.fa"
    assert cli.main(["-A", "-m", "1000", "--batch", "on",
                     "--inflight", "2", str(fa), str(out)]) == 0
    names = [r.name for r in fastx.read_fastx(str(out))]
    assert names == [f"mv/{100 + h}/ccs" for h in range(5)]


def test_cli_batched_journal_resume(tmp_path, rng):
    import json

    zs, fa = _make_inputs(tmp_path, rng, n_holes=3, tlen=700)
    full = tmp_path / "full.fa"
    assert cli.main(["-A", "-m", "1000", "--batch", "on",
                     str(fa), str(full)]) == 0
    out = tmp_path / "o.fa"
    jp = tmp_path / "j.json"
    jp.write_text(json.dumps({"input_id": str(fa), "holes_done": 2}))
    recs = list(fastx.read_fastx(str(full)))
    out.write_text("".join(f">{r.name}\n{r.seq.decode()}\n"
                           for r in recs[:2]))
    assert cli.main(["-A", "-m", "1000", "--batch", "on",
                     "--journal", str(jp), str(fa), str(out)]) == 0
    assert out.read_text() == full.read_text()
    assert json.loads(jp.read_text())["holes_done"] == 3


def test_executor_deep_pass_vote_compaction(rng):
    """uint8 vote/coverage transfer must stay exact at the deepest pass
    bucket (64): votes*2 reaches 128 — the compaction headroom case."""
    cfg = CcsConfig(is_bam=False, max_passes=64,
                    pass_buckets=(4, 8, 16, 32, 64))
    sm = StarMsa(cfg.align, cfg.max_ins_per_col, cfg.len_bucket_quant)
    tpl = rng.integers(0, 4, 300).astype(np.uint8)
    from ccsx_tpu.utils import synth as synth_mod

    ps = [synth_mod.mutate(rng, tpl, 0.02, 0.04, 0.04) for _ in range(40)]
    qs, qlens, row_mask = sm.pack(ps, cfg.pass_buckets, cfg.max_passes)
    req = RoundRequest(qs, qlens, row_mask, ps[0])
    rb = BatchExecutor(cfg).run([req])[0]
    ra = sm.round(req.qs, req.qlens, req.row_mask, req.draft)
    np.testing.assert_array_equal(ra.cons, rb.cons)
    np.testing.assert_array_equal(ra.ins_votes, rb.ins_votes)
    np.testing.assert_array_equal(ra.ncov, rb.ncov)
    assert int(np.asarray(rb.ncov).max()) == 40
    # materialization arithmetic (votes*2 > ncov) must agree too
    np.testing.assert_array_equal(ra.materialize(), rb.materialize())


@pytest.mark.parametrize("mesh", [
    (4, 2),
    # extra mesh shapes ride slow; (4,2) + test_sharded_round's
    # split-invariant pin the 'pass' collectives tier-1 (r16 budget audit)
    pytest.param((2, 4), marks=pytest.mark.slow),
    pytest.param((8, 1), marks=pytest.mark.slow),
])
def test_executor_pass_axis_mesh_matches_per_hole(rng, mesh):
    """The production batched round under a (data, pass) mesh must equal
    the per-hole rounds exactly — GSPMD's psums over 'pass' are the same
    collectives tests/test_sharded_round.py pins."""
    cfg = CcsConfig(is_bam=False, mesh_shape=mesh)
    sm = StarMsa(cfg.align, cfg.max_ins_per_col, cfg.len_bucket_quant)
    reqs = []
    for i in range(5):
        ps = _passes(rng, n=5 + (i % 4), tlen=500 + 40 * i)  # P bucket 8
        qs, qlens, row_mask = sm.pack(ps, cfg.pass_buckets, cfg.max_passes)
        reqs.append(RoundRequest(qs, qlens, row_mask, ps[0]))
    batched = BatchExecutor(cfg).run(reqs)
    from ccsx_tpu.consensus import windowed as win_mod

    for req, rb in zip(reqs, batched):
        ra = sm.round(req.qs, req.qlens, req.row_mask, req.draft)
        np.testing.assert_array_equal(ra.cons, rb.cons)
        np.testing.assert_array_equal(ra.ins_base, rb.ins_base)
        np.testing.assert_array_equal(ra.ins_votes, rb.ins_votes)
        np.testing.assert_array_equal(ra.ncov, rb.ncov)
        # the on-device breakpoint/advance must survive the pass axis too
        nseq = int(req.row_mask.sum())
        host_bp = win_mod.find_breakpoint(ra, nseq, cfg)
        assert (rb.bp if rb.bp >= 1 else None) == host_bp
        bp_eff = host_bp if host_bp is not None else max(
            ra.tlen - cfg.bp_window, 1)
        np.testing.assert_array_equal(
            rb.advance, win_mod._advance(ra, bp_eff).astype(np.int32))


def test_cli_mesh_flag_output_identical(tmp_path, rng):
    """--mesh 4,2 (pass-parallel production path) == --batch off output."""
    zs, fa = _make_inputs(tmp_path, rng, n_holes=3)
    o_ref = tmp_path / "ref.fa"
    o_mesh = tmp_path / "mesh.fa"
    assert cli.main(["-A", "-m", "1000", "--batch", "off",
                     str(fa), str(o_ref)]) == 0
    assert cli.main(["-A", "-m", "1000", "--batch", "on", "--mesh", "4,2",
                     str(fa), str(o_mesh)]) == 0
    assert o_ref.read_text() == o_mesh.read_text()


def test_cli_mesh_flag_invalid(tmp_path, capsys):
    rc = cli.main(["--mesh", "nope", "x.fa", str(tmp_path / "y.fa")])
    assert rc == 1
    assert "--mesh" in capsys.readouterr().err


def test_cli_mesh_too_large_clean_error(tmp_path, rng, capsys):
    """An infeasible --mesh fails rc 1 with a clean message and must NOT
    truncate an existing output file."""
    zs, fa = _make_inputs(tmp_path, rng, n_holes=1)
    out = tmp_path / "o.fa"
    out.write_text("precious\n")
    rc = cli.main(["-A", "-m", "1000", "--batch", "on", "--mesh", "16,2",
                   str(fa), str(out)])
    assert rc == 1
    assert "invalid --mesh" in capsys.readouterr().err
    assert out.read_text() == "precious\n"


@pytest.mark.slow  # ~12s: transfer-protocol A/B; the single-device ==
# multi-device dispatch pin (test_dispatch.py::test_fused_multichip_
# byte_identical_to_single_device) keeps the divergence seam tier-1
# (r20 budget audit)
def test_packed_transfer_protocol_matches_unpacked(rng):
    """The packed single-device transfer protocol (one uint8 + one int32
    buffer each way, pipeline/batch._pack_args/_unpack_round/_unpack_
    refine) must be bit-identical to the separate-array protocol the
    multi-device path ships — if they drift, single-chip and sharded
    runs diverge silently."""
    from ccsx_tpu.pipeline import batch as bm

    cfg = CcsConfig(is_bam=False)
    sm = StarMsa(cfg.align, cfg.max_ins_per_col, cfg.len_bucket_quant)
    ps = _passes(rng, n=4, tlen=700)
    qs, qlens, row_mask = sm.pack(ps, cfg.pass_buckets, cfg.max_passes)
    P, qmax = qs.shape
    ex = BatchExecutor(cfg)
    tmax = bm.bucket_len(len(ps[0]), cfg.len_bucket_quant)
    args = ex._stack_group(
        [RoundRequest(qs, qlens, row_mask, ps[0])], [0], P, qmax, tmax)
    bp_consts = ex._bp_consts()

    plain = bm._round_step(cfg.align, cfg.max_ins_per_col, tmax,
                           bp_consts)(*args)
    packed = bm._round_step(cfg.align, cfg.max_ins_per_col, tmax,
                            bp_consts, pack=(P, qmax))(
                                *bm._pack_args(args))
    un = bm._unpack_round(np.asarray(packed[0]), np.asarray(packed[1]),
                          cfg.max_ins_per_col, tmax)
    for a, b in zip(plain, un):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    rplain = bm._refine_step(cfg.align, cfg.max_ins_per_col, tmax,
                             cfg.refine_iters, bp_consts)(*args)
    rpacked = bm._refine_step(cfg.align, cfg.max_ins_per_col, tmax,
                              cfg.refine_iters, bp_consts,
                              pack=(P, qmax))(*bm._pack_args(args))
    run = bm._unpack_refine(np.asarray(rpacked[0]),
                            np.asarray(rpacked[1]),
                            cfg.max_ins_per_col, tmax)
    for a, b in zip(rplain, run):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow  # ~13s knob A/B; test_packing's packed==bucketed==
# per-hole CLI pin keeps the invariant tier-1 (r13 audit)
def test_pass_buckets_knob_output_invariant(tmp_path, rng):
    """--pass-buckets changes only device padding (masked rows), never
    output bytes — the invariance that makes it a safe tuning knob —
    while the occupancy counters show the repacking happened."""
    import json

    zs = [synth.make_zmw(rng, template_len=900, n_passes=5 + (h % 6),
                         movie="mv", hole=str(h)) for h in range(4)]
    fa = tmp_path / "in.fa"
    fa.write_text(synth.make_fasta(zs))
    outs, fills = [], []
    for i, extra in enumerate(([], ["--pass-buckets", "6,12,32"])):
        o = tmp_path / f"o{i}.fq"
        m = tmp_path / f"m{i}.jsonl"
        assert cli.main(["-A", "-m", "1000", "--fastq", "--batch", "on",
                         "--metrics", str(m), *extra, str(fa),
                         str(o)]) == 0
        outs.append(o.read_text())
        fin = [json.loads(ln) for ln in m.read_text().splitlines()][-1]
        fills.append(fin["dp_pass_fill"])
    assert outs[0] == outs[1]
    # the repacking is real (which direction depends on the pass
    # distribution — that is exactly what the knob is for)
    assert fills[0] != fills[1], fills


def test_pass_buckets_bad_value_rejected(capsys):
    assert cli.main(["--pass-buckets", "8,4", "in.fa", "out.fa"]) == 1
    assert "--pass-buckets" in capsys.readouterr().err


@pytest.mark.parametrize("refine", [False, True])
@pytest.mark.parametrize("pack", [False, True])
def test_bucketed_steps_name_their_program_and_stages(refine, pack):
    """Lowered, not compiled: the bucketed round and refine programs,
    packed (one device) or not (a mesh), are named for their dispatch
    site and carry the four stage scopes."""
    import jax
    import jax.numpy as jnp

    from ccsx_tpu.pipeline import batch as bm
    from ccsx_tpu.utils import trace

    cfg = CcsConfig(is_bam=False)
    Z, P, qmax, tmax = 2, 4, 128, 256
    bp = BatchExecutor(cfg)._bp_consts()
    packing = (P, qmax) if pack else None
    if refine:
        step = bm._refine_step(cfg.align, cfg.max_ins_per_col, tmax, 2, bp,
                               pack=packing)
    else:
        step = bm._round_step(cfg.align, cfg.max_ins_per_col, tmax, bp,
                              pack=packing)
    S = jax.ShapeDtypeStruct
    if pack:
        args = (S((Z, P * qmax + tmax), jnp.uint8),
                S((Z, 2 * P + 1), jnp.int32))
    else:
        args = (S((Z, P, qmax), jnp.uint8), S((Z, P), jnp.int32),
                S((Z, tmax), jnp.uint8), S((Z,), jnp.int32),
                S((Z, P), jnp.bool_))
    text = step.lower(*args).as_text(debug_info=True)
    name = "ccsx_refine" if refine else "ccsx_round"
    assert f"module @jit_{name} " in text
    for stage in trace.STAGES:
        assert f"/{stage}/" in text, stage


@pytest.mark.parametrize("partitioned", [False, True])
@pytest.mark.parametrize("refine", [False, True])
def test_mesh_steps_keep_the_scan_on_tpu(monkeypatch, refine, partitioned):
    """Traced as for a TPU: the bucketed steps take the v1 kernel, but
    not when the --mesh call sites mark them GSPMD-partitioned (XLA
    cannot partition a Mosaic call)."""
    import jax
    import jax.numpy as jnp

    from ccsx_tpu.consensus import star
    from ccsx_tpu.pipeline import batch as bm

    monkeypatch.setattr(star, "_backend", lambda: "tpu")
    monkeypatch.delenv("CCSX_BANDED_IMPL", raising=False)
    cfg = CcsConfig(is_bam=False)
    Z, P, qmax, tmax = 2, 4, 128, 256
    bp = BatchExecutor(cfg)._bp_consts()
    # unwrapped: a fresh jit, so no trace cached under the CPU's choice
    if refine:
        step = bm._refine_step.__wrapped__(
            cfg.align, cfg.max_ins_per_col, tmax, 2, bp,
            partitioned=partitioned)
    else:
        step = bm._round_step.__wrapped__(
            cfg.align, cfg.max_ins_per_col, tmax, bp,
            partitioned=partitioned)
    S = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(step)(
        S((Z, P, qmax), jnp.uint8), S((Z, P), jnp.int32),
        S((Z, tmax), jnp.uint8), S((Z,), jnp.int32), S((Z, P), jnp.bool_))
    assert ("pallas_call" in str(jaxpr)) is not partitioned
