"""Compile the main path's device programs for a described TPU v5e.

No chip is attached and nothing runs: the TPU compiler, installed here,
compiles for one chip of a described ``v5e:2x2`` (on-chip-measurement
guide §2).  It refuses what the chip would refuse — unaligned tiling,
too much VMEM, a program that does not fit HBM — before chip time is
spent.  The topology is described only inside the module fixture: only
one process may load libtpu, and only the xdist worker that runs this
file does.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ccsx_tpu.config import AlignParams, CcsConfig
from ccsx_tpu.consensus.star import bucket_len
from ccsx_tpu.ops import banded, banded_pallas, banded_rotband

HBM_BYTES = 16 * 10 ** 9      # one v5e chip
N = 128                       # pairs per fill dispatch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One chip of the described host, with the persistent compilation
    cache off (an entry compiled for a described chip cannot be read
    back without one)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fill(impl: str):
    params = AlignParams()
    if impl == "scan":
        return banded.make_batched("global", params, with_moves=True,
                                   with_stats=False)
    mod = banded_pallas if impl == "pallas" else banded_rotband
    return jax.jit(lambda qs, ql, ts, tl: mod.batched_align_global_moves(
        qs, ql, ts, tl, params, with_stats=False, interpret=False))


@pytest.mark.parametrize("qmax", [1024, banded_pallas.PALLAS_MAX_QMAX])
@pytest.mark.parametrize("impl", ["scan", "pallas", "rotband"])
def test_fill_compiles_for_v5e(one_chip, impl, qmax):
    args = (_spec((N, qmax), jnp.uint8, one_chip),
            _spec((N,), jnp.int32, one_chip),
            _spec((N, qmax), jnp.uint8, one_chip),
            _spec((N,), jnp.int32, one_chip))
    compiled = _fill(impl).lower(*args).compile()
    if impl != "scan":
        assert "tpu_custom_call" in compiled.as_text()


def test_packed_refine_step_fits_one_v5e(one_chip):
    """The packed fused refine step at the default slab_rows=128, sized
    for the largest window the default max_window lets through."""
    from ccsx_tpu.pipeline import batch

    cfg = CcsConfig()
    R = cfg.slab_rows
    H = R // 4                                        # pack.SEG_DIV
    qmax = bucket_len(cfg.max_window + 1, cfg.len_bucket_quant)
    tmax = batch._fused_tmax(cfg.max_window, cfg.len_bucket_quant)
    bp = (cfg.bp_window, cfg.bp_minwin, cfg.bp_rowrate, cfg.bp_colrate,
          cfg.bp_colrate_lowpass)
    step = batch._refine_step_packed(cfg.align, cfg.max_ins_per_col, tmax,
                                     cfg.refine_iters, H, bp, (R, qmax))
    lbig, lsmall = batch._slab_wire_sizes(R, qmax, H, tmax,
                                          cfg.max_ins_per_col)
    compiled = step.lower(_spec((lbig,), jnp.uint8, one_chip),
                          _spec((lsmall,), jnp.int32, one_chip)).compile()
    mem = compiled.memory_analysis()
    print(f"packed refine R={R} qmax={qmax} tmax={tmax}: {mem}")
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES


def test_packed_refine_step_takes_the_kernel_on_v5e(one_chip, monkeypatch):
    """The packed refine step at a whole-read amplicon shape (128-row
    slab, qmax 2048, the fused draft capacity above it) with nothing
    forced: traced for a TPU it selects the v1 kernel, which Mosaic
    compiles into the program, and the program fits one chip."""
    from ccsx_tpu.consensus import star
    from ccsx_tpu.pipeline import batch

    monkeypatch.setattr(star, "_backend", lambda: "tpu")
    monkeypatch.delenv("CCSX_BANDED_IMPL", raising=False)
    cfg = CcsConfig()
    R = cfg.slab_rows
    H = R // 4                                        # pack.SEG_DIV
    qmax = 2048
    tmax = batch._fused_tmax(qmax, cfg.len_bucket_quant)
    assert star.banded_impl_effective(qmax) == "pallas"
    bp = (cfg.bp_window, cfg.bp_minwin, cfg.bp_rowrate, cfg.bp_colrate,
          cfg.bp_colrate_lowpass)
    # unwrapped: a fresh jit, so no trace cached under the CPU's choice
    step = batch._refine_step_packed.__wrapped__(
        cfg.align, cfg.max_ins_per_col, tmax, cfg.refine_iters, H, bp,
        (R, qmax))
    lbig, lsmall = batch._slab_wire_sizes(R, qmax, H, tmax,
                                          cfg.max_ins_per_col)
    compiled = step.lower(_spec((lbig,), jnp.uint8, one_chip),
                          _spec((lsmall,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    print(f"packed refine (v1 fill) R={R} qmax={qmax} tmax={tmax}: {mem}")
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES


def _kernel_bodies(text):
    return re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', text)


def test_kernel_body_does_not_name_its_callers(one_chip):
    """At the one-frame location limit that enable_compile_cache sets,
    the v1 kernel's serialized body, on which the persistent compile
    cache keys, is the same whichever call stack traced it."""
    from ccsx_tpu.utils import device

    def first_caller(n):
        return _fill("pallas").lower(
            _spec((n, 256), jnp.uint8, one_chip),
            _spec((n,), jnp.int32, one_chip),
            _spec((n, 256), jnp.uint8, one_chip),
            _spec((n,), jnp.int32, one_chip)).as_text()

    def second_caller(n):
        return first_caller(n)

    was = jax.config.jax_traceback_in_locations_limit
    try:
        device.stable_kernel_locations()
        a = _kernel_bodies(first_caller(8))
        b = _kernel_bodies(second_caller(16))
    finally:
        jax.config.update("jax_traceback_in_locations_limit", was)
    # one body per kernel call: one call for 8 problems, two for 16
    assert len(a) == 1 and set(b) == set(a)
