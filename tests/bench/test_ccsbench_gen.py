"""The benchmark's seeded input and its plain reference (CPU, fast)."""

import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "benchmarks", "ccsbench")
sys.path.insert(0, BENCH)

import gen  # noqa: E402
import reference  # noqa: E402


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _small(cfg, lo, hi):
    cfg = dict(cfg)
    cfg["template_len"] = dict(cfg["template_len"], lo=lo, hi=hi)
    return cfg


@pytest.mark.parametrize("seed", [2**31 + 7, 2**33 + 1])
def test_pool_is_deterministic_per_seed(seed):
    cfg = _small(_config("amplicon_deep"), 300, 400)
    a = gen.make_pool(cfg, 6, seed)
    b = gen.make_pool(cfg, 6, seed)
    c = gen.make_pool(cfg, 6, seed + 1)
    for x, y in zip(a, b):
        assert np.array_equal(x.template, y.template)
        assert len(x.passes) == len(y.passes)
        assert all(np.array_equal(p, q) for p, q in zip(x.passes, y.passes))
    assert any(not np.array_equal(x.template[:50], y.template[:50])
               for x, y in zip(a, c))


@pytest.mark.parametrize("n", [64, 128])
def test_every_seed_gets_the_same_sizes(n):
    """Every seed gets the same (template length, passes) in the same
    order: templates read off the configuration's distribution, passes
    as many as each hole's polymerase read covers."""
    cfg = _config("amplicon_deep")
    sizes = gen.size_set(cfg, n)
    t, pol = cfg["template_len"], cfg["polymerase_len"]
    tl = sorted(s[0] for s in sizes)
    assert t["lo"] <= tl[0] and tl[-1] <= t["hi"]
    assert abs(np.median(tl) - (t["lo"] + t["hi"]) / 2) <= 1
    # polymerase read of each hole: between its passes and one more
    lo = [p * (tl + cfg["adapter_len"]) for tl, p in sizes]
    hi = [(p + 1) * (tl + cfg["adapter_len"]) for tl, p in sizes]
    med = np.median([(a + b) / 2 for a, b in zip(lo, hi)])
    assert abs(med / pol["median"] - 1) < 0.05
    assert min(p for _, p in sizes) >= cfg["program"]["min_count"] + 2
    small = _small(cfg, 200, 260)
    got = [[(len(h.template), len(h.passes))
            for h in gen.make_pool(small, 8, s)] for s in (1, 2**31 + 99)]
    assert got[0] == got[1] == gen.size_set(small, 8)


def test_passes_fall_as_inserts_grow():
    """One polymerase read covers fewer passes of a longer insert."""
    cfg = dict(_config("amplicon_deep"), polymerase_len={
        "dist": "lognormal", "median": 30000, "sigma": 0.0})
    short = _small(cfg, 1000, 1000)
    long_ = _small(cfg, 3000, 3000)
    assert {p for _, p in gen.size_set(short, 16)} == {30000 // 1045}
    assert {p for _, p in gen.size_set(long_, 16)} == {30000 // 3045}


def test_reads_follow_the_error_model():
    rng = np.random.default_rng(3)
    t = rng.integers(0, 4, 4000).astype(np.uint8)
    reads = [gen.mutate(rng, t, 0.02, 0.05, 0.05) for _ in range(3)]
    # E[len] / len = (1 - del) / (1 - ins)
    ratio = np.mean([len(r) for r in reads]) / len(t)
    assert abs(ratio - 0.95 / 0.95) < 0.02
    err = np.mean([reference.edit_distance(r, t) for r in reads]) / len(t)
    assert 0.09 < err < 0.13


def test_partial_ends_and_strands():
    cfg = _small(_config("amplicon_deep"), 1000, 1000)
    pool = gen.make_pool(cfg, 4, 11)
    for h in pool:
        full = [len(p) for p in h.passes[1:-1]]
        for end in (h.passes[0], h.passes[-1]):
            assert 0.25 * np.mean(full) < len(end) < 0.65 * np.mean(full)
        # passes alternate strands: neighbours align far better after
        # reverse-complementing one of them
        a, b = h.passes[1], h.passes[2]
        assert (reference.edit_distance(gen.revcomp(a), b)
                < 0.5 * reference.edit_distance(a, b))


def test_bam_reads_back_through_the_program(tmp_path):
    from ccsx_tpu.io import bam as bam_mod
    from ccsx_tpu.ops import encode as enc

    cfg = _small(_config("amplicon_deep"), 301, 333)
    pool = gen.make_pool(cfg, 3, 5)
    path = str(tmp_path / "pool.bam")
    gen.write_bam(path, "mv", pool)
    recs = list(bam_mod.read_bam_records(path))
    want = [(n, enc.decode(p)) for h in pool
            for n, p in zip(gen.subread_names("mv", h), h.passes)]
    assert [(r.name, r.seq.decode()) for r in recs] == want


def test_edit_distance_matches_the_full_table():
    rng = np.random.default_rng(0)

    def full(a, b):
        d = np.arange(len(b) + 1)
        for i in range(1, len(a) + 1):
            prev, d = d, np.empty_like(d)
            d[0] = i
            for j in range(1, len(b) + 1):
                d[j] = min(prev[j] + 1, d[j - 1] + 1,
                           prev[j - 1] + (a[i - 1] != b[j - 1]))
        return int(d[-1])

    for n in (0, 1, 37, 120):
        a = rng.integers(0, 4, n).astype(np.uint8)
        b = gen.mutate(rng, a, 0.1, 0.1, 0.1) if n else a
        assert reference.edit_distance(a, b) == full(a, b)
    a = rng.integers(0, 4, 90).astype(np.uint8)
    b = rng.integers(0, 4, 150).astype(np.uint8)
    assert reference.edit_distance(a, b) == full(a, b)


def test_consensus_qv_on_known_edits():
    rng = np.random.default_rng(1)
    t = rng.integers(0, 4, 2000).astype(np.uint8)
    q = t.copy()
    q[100] = (q[100] + 1) % 4                       # substitution
    q = np.delete(q, 700)                           # deletion
    q = np.insert(q, 1500, (q[1500] + 2) % 4)       # insertion
    cns = bytes(np.frombuffer(b"ACGT", np.uint8)[q])
    assert reference.hole_errors(cns, t) == 3
    rc = bytes(np.frombuffer(b"ACGT", np.uint8)[gen.revcomp(q)])
    assert reference.hole_errors(rc, t) == 3        # either strand
    assert reference.qv(3, 2000) == pytest.approx(-10 * np.log10(3 / 2000))
    assert reference.qv(0, 2000) == 60.0


@pytest.mark.parametrize("shift", range(7))
def test_orientation_is_found_at_any_offset(shift):
    """A consensus that starts a few bases off the template (extra
    leading bases) is still judged in its own orientation."""
    rng = np.random.default_rng(shift)
    t = rng.integers(0, 4, 3000).astype(np.uint8)
    q = np.concatenate([rng.integers(0, 4, shift).astype(np.uint8), t])
    acgt = np.frombuffer(b"ACGT", np.uint8)
    assert reference.hole_errors(bytes(acgt[q]), t) == shift
    assert reference.hole_errors(bytes(acgt[gen.revcomp(q)]), t) == shift


def test_reference_read_step_filter():
    cli = {"min_count": 3, "min_len": 5000, "max_len": 500000}
    assert reference.kept(5, 5000, cli)
    assert not reference.kept(4, 90000, cli)
    assert not reference.kept(9, 4999, cli)
    assert not reference.kept(30, 500001, cli)
