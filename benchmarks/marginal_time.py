"""THE forced-execution marginal timing helper — the one implementation
all benches share (bench.py, round_profile.py, pallas_ab.py), so a fix
to the method lands everywhere at once.

Why this exists: the timing must not depend on whether
``jax.block_until_ready`` waits on the runtime in use (chip_smoke.py
prints whether it does), so it synchronizes by materializing output
bytes, which always waits.  Method: run ``iters`` calls of
``fn`` inside ONE jitted ``lax.fori_loop`` whose body (a) perturbs the
first argument with the loop index — defeats loop-invariant hoisting —
and (b) folds every output leaf into an int32 checksum — defeats DCE;
fetch the scalar checksum, and report the MARGINAL time between an
``iters``-loop and a 1-loop fetch, which cancels the fixed d2h
latency.  The trip count is a TRACED argument: one compiled program
serves both loops (one compile, and XLA cannot unroll/specialize).
Validated on CPU (agrees with synchronous timing).

Nonpositive marginals (baseline fetch noise exceeding the iters run)
are DISCARDED, never clamped — a clamped sample becomes an absurdly
fast reading that can settle an A/B by noise.
"""

from __future__ import annotations

import time


def marginal_time(fn, *args, iters: int = 100, repeats: int = 3,
                  settle: float = 0.1):
    """List of up to ``repeats`` positive marginal seconds-per-call of
    ``fn(*args)``.  May return fewer (noisy windows are discarded, with
    up to 2x``repeats`` attempts); raises RuntimeError if every attempt
    was nonpositive — a sign the runtime/clock is broken, not the chip.

    FIRST-ARGUMENT CONTRACT: the anti-hoisting perturbation writes
    ``i % 4`` into element [0, 0, ...] of ``args[0]`` each loop
    iteration, so args[0] must tolerate arbitrary values in {0, 1, 2, 3}
    at that position — same dtype, same output shapes, no control-flow
    change.  True of the code tensors every ccsx bench passes first
    (0..3 are the valid bases; lengths/masks ride in later arguments).
    Callers whose natural first argument cannot absorb that (a length,
    a scalar, a one-hot) must reorder arguments so a value-tolerant
    tensor comes first — the perturbed value feeds ``fn``, so a
    corrupted length would time a DIFFERENT workload, not just add
    noise.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    args = tuple(jnp.asarray(a) for a in args)

    @jax.jit
    def run(n, *a):
        def body(i, acc):
            a0 = a[0].at[(0,) * a[0].ndim].set(
                jnp.mod(i, 4).astype(a[0].dtype))
            out = fn(a0, *a[1:])
            return acc + sum(
                jnp.sum(leaf.astype(jnp.int32))
                for leaf in jax.tree_util.tree_leaves(out))
        return jax.lax.fori_loop(0, n, body, jnp.int32(0))

    np.asarray(run(np.int32(1), *args))     # compile before timing
    out = []
    for _ in range(2 * repeats):
        if len(out) >= repeats:
            break
        t0 = time.perf_counter()
        np.asarray(run(np.int32(1), *args))
        base = time.perf_counter() - t0
        t0 = time.perf_counter()
        np.asarray(run(np.int32(iters), *args))
        d = (time.perf_counter() - t0 - base) / (iters - 1)
        if d > 0:
            out.append(d)
        time.sleep(settle)
    if not out:
        raise RuntimeError(
            "every marginal-timing window was nonpositive: the runtime "
            "or clock is lying; no honest sample to report")
    return out
