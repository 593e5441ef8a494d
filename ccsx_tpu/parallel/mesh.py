"""Device mesh construction and the sharded consensus step.

The reference's only parallelism is host threads over independent ZMWs
(kt_for, kthread.c:34-65).  The TPU design shards two axes:

  data axis — ZMW batches (each hole independent: pure data parallelism,
      no cross-device traffic in the hot loop);
  pass axis — MSA rows (passes) of each hole: each device aligns its rows
      against the shared draft and the column vote is a psum over the pass
      axis — the tensor/sequence-parallel analog for this workload, riding
      ICI.

The sharded step below is exercised by __graft_entry__.dryrun_multichip
and tests/test_sharded_round.py, both of which assert its four outputs
equal the unsharded per-hole star round BIT-EXACTLY (the vote is a pure
pass-axis reduction, so sharding must change nothing).  The production
batched runner (pipeline/batch.py) lays its rounds over the same
(data, pass) mesh via input NamedShardings (--mesh D,P; default pure
data) — GSPMD inserts the identical psums; its mesh path is pinned
bit-equal to the per-hole rounds in tests/test_batch.py.  This module's
explicit shard_map version remains the reference formulation and the
dryrun target.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ccsx_tpu.config import AlignParams
from ccsx_tpu.ops import banded, traceback


def shard_map_nocheck(f, mesh: Mesh, in_specs, out_specs):
    """jax.shard_map with the replication check off.  Both the (data,
    pass) sharded round below and the fused multi-chip packed dispatch
    (pipeline/batch.py) go through here: DP scan carries mix replicated
    init constants with varying values, and pcasting every carry
    component buys nothing."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def build_slab_mesh(devices) -> Mesh:
    """A 1-D ('slab',) mesh over the given local devices — the fused
    multi-chip packed dispatch stacks same-shape slabs into a leading
    device dimension and shard_maps one executable over this mesh (one
    transfer + one dispatch per group per wave, vs one of each per slab
    per chip under the r7 round-robin)."""
    return Mesh(np.array(devices), axis_names=("slab",))


def build_mesh(shape: Optional[Tuple[int, ...]] = None,
               axis_names: Tuple[str, ...] = ("data", "pass"),
               devices=None) -> Mesh:
    """A (data, pass) mesh over `devices` (default: all available).

    Default split: the pass axis gets 2 devices when there are >= 4 devices,
    otherwise 1 (pure data parallelism).
    """
    devs = np.array(devices if devices is not None else jax.devices())
    n = len(devs)
    if shape is None:
        p = 2 if n >= 4 and n % 2 == 0 else 1
        shape = (n // p, p)
    return Mesh(devs.reshape(shape), axis_names=axis_names)


def make_sharded_round(mesh: Mesh, params: AlignParams, tmax: int,
                       max_ins: int = 4):
    """Jitted, mesh-sharded star-MSA round.

    Inputs (global shapes):
      qs       (Z, Pp, W) uint8 — Z ZMWs x Pp passes, padded
      qlens    (Z, Pp) int32
      ts       (Z, tmax) uint8 — per-ZMW draft (replicated over 'pass')
      tlens    (Z,) int32
      row_mask (Z, Pp) bool

    Output: cons (Z, tmax) uint8, ins_base (Z, tmax, R) uint8,
      ins_votes (Z, tmax, R) int32, ncov (Z, tmax) int32,
      nwin (Z, tmax) int32 — all sharded over 'data' only (vote results
      are replicated over 'pass' after the psum).
    """
    projector = traceback.make_projector(tmax, max_ins)

    align_one = functools.partial(
        banded.banded_align, mode="global", params=params, with_moves=True,
        with_stats=False)

    def local_round(qs, qlens, ts, tlens, row_mask):
        # vmap over local ZMWs and local passes
        f = jax.vmap(jax.vmap(align_one, in_axes=(0, 0, None, None)),
                     in_axes=(0, 0, 0, 0))
        _, moves, offs = f(qs, qlens, ts, tlens)
        proj = jax.vmap(jax.vmap(projector, in_axes=(0, 0, 0, 0, None)),
                        in_axes=(0, 0, 0, 0, 0))
        aligned, ins_cnt, ins_b, _lead = proj(moves, offs, qs, qlens, tlens)

        mask = row_mask[:, :, None]
        cnts = jnp.stack(
            [((aligned == c) & mask).sum(1) for c in range(5)], axis=1
        )  # (Zl, 5, T)
        cnts = jax.lax.psum(cnts, "pass")
        ncov = cnts.sum(1)
        nwin = cnts.max(1)
        cons = jnp.argmax(cnts, axis=1).astype(jnp.uint8)
        cons = jnp.where(ncov == 0, jnp.uint8(4), cons)

        bases, votes = [], []
        for r in range(max_ins):
            has = mask[:, :, 0][:, :, None] * 0  # placate linters
            has = (ins_cnt > r) & row_mask[:, :, None]
            votes_r = jax.lax.psum(has.sum(1), "pass")
            bc = jnp.stack(
                [((ins_b[:, :, :, r] == c) & has).sum(1) for c in range(4)],
                axis=1)
            bc = jax.lax.psum(bc, "pass")
            bases.append(jnp.argmax(bc, axis=1).astype(jnp.uint8))
            votes.append(votes_r)
        ins_base = jnp.stack(bases, axis=2)
        ins_votes = jnp.stack(votes, axis=2)
        return cons, ins_base, ins_votes, ncov, nwin

    in_specs = (P("data", "pass", None), P("data", "pass"),
                P("data", None), P("data"), P("data", "pass"))
    out_specs = (P("data", None), P("data", None, None),
                 P("data", None, None), P("data", None),
                 P("data", None))
    shard = shard_map_nocheck(local_round, mesh, in_specs, out_specs)
    return jax.jit(shard)


def shard_batch(mesh: Mesh, arrays, specs):
    """Device-put host arrays with NamedShardings."""
    return [
        jax.device_put(a, NamedSharding(mesh, s))
        for a, s in zip(arrays, specs)
    ]
