"""Differential test: native C++ scalar aligner vs the NumPy oracle.

Exact equality required — same DP, same tie-breaking, same traceback —
so either implementation can serve as the spec for the device kernels.
"""

import numpy as np
import pytest

from ccsx_tpu import native
from ccsx_tpu.ops import oracle
from ccsx_tpu.utils import synth

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native toolchain unavailable")


def _check(q, t, mode, **scores):
    from ccsx_tpu.native.align import align_scalar_native
    want = oracle.align(q, t, mode=mode, **scores)
    got = align_scalar_native(q, t, mode=mode, **scores)
    assert got is not None
    assert got.score == want.score
    assert (got.qb, got.qe, got.tb, got.te) == (
        want.qb, want.qe, want.tb, want.te), mode
    assert (got.aln, got.mat, got.mis, got.ins, got.del_) == (
        want.aln, want.mat, want.mis, want.ins, want.del_)
    assert got.cigar == want.cigar


@pytest.mark.parametrize("mode", ["global", "qfree", "local"])
def test_random_pairs(mode, rng):
    for trial in range(8):
        tlen = int(rng.integers(5, 120))
        t = rng.integers(0, 4, tlen).astype(np.uint8)
        q = synth.mutate(rng, t, 0.05, 0.08, 0.08)
        _check(q, t, mode)


@pytest.mark.parametrize("mode", ["global", "qfree", "local"])
def test_unrelated_and_edge(mode, rng):
    q = rng.integers(0, 4, 40).astype(np.uint8)
    t = rng.integers(0, 4, 55).astype(np.uint8)
    _check(q, t, mode)
    _check(np.array([0], np.uint8), np.array([3], np.uint8), mode)
    # N bases never match
    _check(np.full(10, 4, np.uint8), np.full(10, 4, np.uint8), mode)


def test_clipping_qfree(rng):
    t = rng.integers(0, 4, 60).astype(np.uint8)
    junk = rng.integers(0, 4, 25).astype(np.uint8)
    q = np.concatenate([junk, synth.mutate(rng, t, 0.02, 0.02, 0.02), junk])
    _check(q, t, "qfree")


def test_alt_scores(rng):
    t = rng.integers(0, 4, 80).astype(np.uint8)
    q = synth.mutate(rng, t, 0.1, 0.05, 0.05)
    _check(q, t, "global", match=1, mismatch=-4, gap_open=-6, gap_extend=-1)


def test_size_cap_returns_none():
    from ccsx_tpu.native.align import align_scalar_native
    q = np.zeros(1 << 15, np.uint8)
    t = np.zeros(1 << 14, np.uint8)
    assert align_scalar_native(q, t) is None


def test_banded_fill_vec_equals_scalar(rng):
    """The two builds of native/baseline_simd.cpp (vectorized vs
    -fno-tree-vectorize, identical source) must agree bit-for-bit on the
    final band row — the precondition for reading their speed ratio as
    a SIMD factor (bench_baseline.json, VERDICT r4 item 4)."""
    import ctypes

    from ccsx_tpu import native

    L = native.lib()
    if L is None:
        import pytest

        pytest.skip("native library unavailable")

    def run(fn, q, t):
        h = np.zeros(128, np.int16)
        rc = fn(q.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), len(q),
                t.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), len(t),
                2, -6, -3, -2,
                h.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)))
        assert rc == 0
        return h

    for _ in range(8):
        ql = int(rng.integers(50, 2500))
        tl = int(rng.integers(50, 2500))
        q = rng.integers(0, 4, ql).astype(np.uint8)
        t = rng.integers(0, 4, tl).astype(np.uint8)
        hv = run(L.ccsx_banded_fill_vec, q, t)
        hs = run(L.ccsx_banded_fill_scalar, q, t)
        np.testing.assert_array_equal(hv, hs)
    # identity alignment: the band covers the main diagonal end-to-end,
    # so the best final-row cell is the perfect-match global score
    q = rng.integers(0, 4, 1000).astype(np.uint8)
    assert run(L.ccsx_banded_fill_vec, q, q).max() == 2 * 1000
