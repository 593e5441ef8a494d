"""Batched device k-mer seeding: the exact twin of ops/seed.seed_diagonal.

Host seeding is per-pair NumPy (a sort-join per template plus an
O(Q log T) vote per pair) serialized on the prep plane's pump thread;
in the long-template regime (ROADMAP item 4: 100kb+ molecules) that
serialization and the host CPU footprint become the per-node ceiling
the future serve plane pays per tenant.  This op moves the whole vote
to the device as ONE fixed-shape dispatch per (qmax, tmax) bucket —
sort, capped join, diagonal histogram, windowed argmax, and the median
line — batched over every pair of a wave.

Bit-exactness is the contract (differentially fuzz-pinned against
seed_diagonal by tests/test_sketch.py, random + adversarial
repeat-heavy/N-laden corpora): the device path reproduces the host's
stable sort order, its first-MAX_HITS_PER_KMER-in-sorted-order cap,
np.argmax's first-max tie break, and int(np.median(...))'s
truncate-toward-zero on the even-count midpoint average.  The padded
tail is inert by construction (PAD >= 4 makes every window touching it
a bad k-mer, and pad template positions sort into the sentinel tail the
join never reaches).

``--seed-device-min-t`` (config.seed_device_min_t) is the crossover:
templates at least that long seed here, shorter ones keep the host
path with its per-template sorted-index cache (the short regime is
latency-bound and cache-friendly; the long regime is bandwidth-bound
and batch-friendly).  0 disables the device path entirely.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

from ccsx_tpu.ops import seed as seed_mod
from ccsx_tpu.ops import sketch as sketch_mod

MIN_VOTES = 3   # seed_diagonal's default, pinned


@functools.lru_cache(maxsize=32)
def seed_step(qmax: int, tmax: int):
    """Jitted batched seeder: (N, qmax+tmax) uint8 codes + (N, 2) int32
    lengths -> (N, 8) int32 rows
    (found, diag, votes, i0, j0, i1, j1, total).
    ``found`` == 0 exactly when seed_diagonal would return None."""
    import jax
    import jax.numpy as jnp

    nb = (qmax + tmax) // sketch_mod.DIAG_BIN + 2
    # median sentinel: larger than any real diagonal of these shapes
    big = jnp.int32(qmax + tmax + 2 * sketch_mod.DIAG_BIN)

    def ccsx_seed(row, lens):
        q = row[:qmax]
        t = row[qmax:]
        qlen, tlen = lens[0], lens[1]
        cnt, left, order, qpos = sketch_mod._hits_dev(q, t, qlen, tlen)
        total = cnt.sum()
        hist, diags, inhit, lo = sketch_mod._diag_hist_dev(
            cnt, left, order, qpos, qlen, tlen, nb)
        paired = hist[:-1] + hist[1:]
        best = jnp.argmax(paired).astype(jnp.int32)
        votes = paired[best]
        # median of the hit diagonals inside the best 2-bin window,
        # truncated toward zero like int(np.median(...))
        binned = (diags - lo) // sketch_mod.DIAG_BIN
        inb = inhit & ((binned == best) | (binned == best + 1))
        m = inb.sum()
        sorted_d = jnp.sort(jnp.where(inb, diags, big).ravel())
        a = sorted_d[jnp.maximum(m - 1, 0) // 2]
        b = sorted_d[m // 2]
        med2 = a + b
        diag = jnp.where(med2 >= 0, med2 // 2, -((-med2) // 2))
        i0 = jnp.maximum(diag, 0)
        j0 = i0 - diag
        i1 = jnp.minimum(qlen, tlen + diag)
        j1 = i1 - diag
        found = (total > 0) & (votes >= MIN_VOTES)
        z = jnp.int32(0)
        out = jnp.stack([jnp.where(found, 1, 0),
                         jnp.where(found, diag, z),
                         jnp.where(found, votes, z),
                         jnp.where(found, i0, z),
                         jnp.where(found, j0, z),
                         jnp.where(found, i1, z),
                         jnp.where(found, j1, z),
                         total])
        return out.astype(jnp.int32)

    # jit names the program after the function vmap wraps
    return jax.jit(jax.vmap(ccsx_seed))


def hit_from_row(row) -> Optional[seed_mod.SeedHit]:
    """One device output row -> the host-contract SeedHit (or None),
    so the executor consumes either seeding path identically."""
    row = [int(v) for v in row]
    if not row[0]:
        return None
    return seed_mod.SeedHit(
        diag=row[1], votes=row[2],
        line=np.array(row[3:7], dtype=np.int32))
