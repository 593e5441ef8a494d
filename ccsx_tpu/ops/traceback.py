"""Device traceback: move matrix -> star-MSA projection.

Converts the packed move bytes emitted by ``banded_align(mode='global',
with_moves=True)`` into the template-anchored projection used by the
consensus vote (the same representation oracle.project_to_template builds):

  aligned[j]   query code aligned to template column j (0-3), 4 = deletion
  ins_cnt[j]   number of query bases inserted after template column j
  ins_b[j, r]  the last ``max_ins`` inserted bases after column j, in
               forward order, left-justified (PAD=5 elsewhere)
  lead_ins     query bases consumed before template column 0 (counted for
               cursor bookkeeping; not voted)

Two implementations, bit-identical (tests/test_traceback.py).  The cell
walk is the unconditional default on every backend until the TPU A/B
(benchmarks/round_profile.py with CCSX_PROJECTOR=scan) flips it; the
scan is opt-in via ``CCSX_PROJECTOR=scan``:

* ``make_projector_scan`` (opt-in) — a ``lax.scan`` over query ROWS.  The
  key observation: a global affine traceback consumes exactly one query
  row per DIAG/UP move, and the only multi-cell-per-row events are
  horizontal (F) gap runs — whose lengths are a pure function of the
  move bytes and are precomputed VECTORIZED as per-row run-lengths of
  the F-extend bit.  With gap_open < 0, at most one F run precedes each
  row-consuming move (an open that beats an extension implies the source
  cell's H strictly beats its F, so the next choice cannot be LEFT
  again); the scan still resolves twice per row as insurance.  The scan
  carries only three scalars and emits per-row records; the projection
  arrays are built AFTER the scan by vectorized scatters.  vs the cell
  walk this halves the sequential depth (qlen steps instead of
  qlen+tlen) and removes all in-loop scatters.
* ``make_projector_reference`` (default) — the original cell-by-cell
  ``lax.while_loop`` from (qlen, tlen) back to (0, 0); one move byte
  gather + masked scatters per step.  Kept as the executable spec.

This replaces the role of bsalign's MSA materialization
(tidy_msa_bspoa, main.c:572) — our "MSA" is the stack of these
projections.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ccsx_tpu.ops.banded import EBIT_EXT, FBIT_EXT, MOVE_LEFT, MOVE_UP

GAP = 4
PAD = 5

_H, _E, _F = 0, 1, 2


def make_projector(tmax: int, max_ins: int = 4):
    """Build a jitted projector for templates padded to ``tmax`` columns.

    Dispatches between the two bit-identical implementations:
    ``CCSX_PROJECTOR=scan|walk`` forces one; default is the cell walk.
    Measured on XLA:CPU the walk's in-loop scatters are cheap and the
    scan's extra gathers lose (0.31s vs 0.48s at the bench shapes).
    No chip measurement decides between them yet
    (benchmarks/round_profile.py times both); until one favors the
    scan the walk stays the default on every backend."""
    import os

    impl = os.environ.get("CCSX_PROJECTOR", "")
    if impl not in ("", "scan", "walk"):
        raise ValueError(
            f"CCSX_PROJECTOR={impl!r}: expected 'scan' or 'walk'")
    if impl == "scan":
        return make_projector_scan(tmax, max_ins)
    return make_projector_reference(tmax, max_ins)


def make_projector_scan(tmax: int, max_ins: int = 4):
    """The row-scan projector (see module docstring; bit-identical to
    make_projector_reference)."""

    @jax.jit
    def project(moves, offs, q, qlen, tlen):
        qmax = q.shape[0]
        B = moves.shape[1]
        mv = moves.astype(jnp.int32)
        choice = mv & 3
        ebit = (mv & EBIT_EXT) != 0
        fbit = (mv & FBIT_EXT) != 0
        # per-row consecutive F-extend run count ENDING at each lane
        # (including the lane itself): runc[i, l] = l - (last lane <= l
        # with fbit clear), 0 where fbit is clear
        lanes = jnp.arange(B, dtype=jnp.int32)
        clear_pos = jnp.where(fbit, jnp.int32(-1), lanes[None, :])
        last_clear = jax.lax.associative_scan(jnp.maximum, clear_pos,
                                              axis=1)
        runc = jnp.where(fbit, lanes[None, :] - last_clear, 0)

        qlen_i = qlen.astype(jnp.int32)
        tlen_i = tlen.astype(jnp.int32)

        def step(carry, xs):
            j, state, r = carry
            i, ch_row, eb_row, rc_row, off_row = xs
            live = i <= qlen_i

            def lane_of(jj):
                return jnp.clip(jj - off_row, 0, B - 1)

            # resolve a pending horizontal gap run (state H, choice
            # LEFT): consume 1 + runc cells at once.  Applied twice —
            # the second application is a no-op for gap_open < 0.
            def resolve(jj):
                l = lane_of(jj)
                is_left = (state == _H) & (ch_row[l] == MOVE_LEFT) \
                    & (jj > 0)
                return jnp.where(is_left, jj - (1 + rc_row[l]), jj)

            j1 = resolve(resolve(j))
            l1 = lane_of(j1)
            is_up = live & ((j1 == 0) | (state == _E)
                            | (ch_row[l1] == MOVE_UP))
            is_diag = live & ~is_up
            r_emit = jnp.where(state == _E, r + 1, jnp.int32(0))
            state_n = jnp.where(
                is_up,
                jnp.where(eb_row[l1] | (j1 == 0), jnp.int32(_E),
                          jnp.int32(_H)),
                jnp.int32(_H))
            j_n = jnp.where(is_diag, j1 - 1, j1)
            carry_n = (jnp.where(live, j_n, j),
                       jnp.where(live, state_n, state),
                       jnp.where(live, jnp.where(is_up, r_emit, 0), r))
            return carry_n, (is_diag, is_up, j1, r_emit)

        xs = (jnp.arange(1, qmax + 1, dtype=jnp.int32),
              choice, ebit, runc, offs.astype(jnp.int32))
        _, (is_diag, is_up, jcol, r_emit) = jax.lax.scan(
            step, (tlen_i, jnp.int32(_H), jnp.int32(0)), xs,
            reverse=True)

        qv = q.astype(jnp.uint8)
        # aligned: every column < tlen is either diag-written or a
        # deletion (GAP); scatter conflicts are impossible (each diag
        # consumes a distinct column); dead rows write a dump slot
        cols = jnp.arange(tmax, dtype=jnp.int32)
        aligned0 = jnp.where(cols < tlen_i, jnp.uint8(GAP),
                             jnp.uint8(PAD))
        aligned = jnp.concatenate([aligned0, jnp.zeros((1,), jnp.uint8)])
        a_idx = jnp.where(is_diag, jcol - 1, tmax)
        aligned = aligned.at[a_idx].set(qv)[:tmax]

        # insertions: slot j holds bases inserted after template column
        # j-1 (slot 0 = leading); one vertical run per slot, so a row's
        # stored position is min(k, max_ins)-1-r with k the run length
        s_idx = jnp.where(is_up, jcol, tmax + 1)
        ins_cnt_full = jnp.zeros((tmax + 2,), jnp.int32).at[s_idx].add(
            is_up.astype(jnp.int32))
        k_row = ins_cnt_full[s_idx]
        kept = is_up & (r_emit < max_ins)
        pos = jnp.clip(jnp.minimum(k_row, max_ins) - 1 - r_emit,
                       0, max_ins - 1)
        b_slot = jnp.where(kept, s_idx, tmax + 1)
        ins_b_full = jnp.full((tmax + 2, max_ins), PAD, jnp.uint8)
        ins_b_full = ins_b_full.at[b_slot, pos].set(qv)
        return (aligned, ins_cnt_full[1:tmax + 1],
                ins_b_full[1:tmax + 1], ins_cnt_full[0])

    return project


def make_projector_reference(tmax: int, max_ins: int = 4):
    """The original cell-by-cell walk (executable spec for the scan
    projector; one move-byte gather + masked scatters per step)."""

    @jax.jit
    def project(moves, offs, q, qlen, tlen):
        qmax = q.shape[0]
        B = moves.shape[1]
        aligned = jnp.full((tmax,), PAD, jnp.uint8)
        # slot s+1 holds insertions after template column s; slot 0 holds
        # the leading insertions (query bases before template column 0),
        # which cursor bookkeeping must still count (main.c:622-638 walks
        # every MSA cell)
        ins_cnt = jnp.zeros((tmax + 1,), jnp.int32)
        ins_b = jnp.full((tmax + 1, max_ins), PAD, jnp.uint8)

        def cond(st):
            i, j, state, *_ = st
            return (i > 0) | (j > 0)

        def body(st):
            i, j, state, aligned, ins_cnt, ins_b = st
            # move byte of cell (i, j); rows are 1-indexed: row i at moves[i-1]
            row = jnp.clip(i - 1, 0, qmax - 1)
            lane = jnp.clip(j - offs[row], 0, B - 1)
            m = moves[row, lane].astype(jnp.int32)
            choice = m & 3

            def do_diag(st):
                i, j, state, aligned, ins_cnt, ins_b = st
                aligned = aligned.at[j - 1].set(q[i - 1])
                return (i - 1, j - 1, jnp.int32(_H), aligned, ins_cnt, ins_b)

            def do_up(st):
                # consume one query base as an insertion after column j-1
                # (slot j in the shifted ins arrays; j == 0 -> leading slot)
                i, j, state, aligned, ins_cnt, ins_b = st
                slot = j
                cnt = ins_cnt[slot]
                pos = max_ins - 1 - cnt
                ins_b = jax.lax.cond(
                    pos >= 0,
                    lambda b: b.at[slot, jnp.maximum(pos, 0)].set(q[i - 1]),
                    lambda b: b,
                    ins_b,
                )
                ins_cnt = ins_cnt.at[slot].add(1)
                nxt = jnp.where((m & EBIT_EXT) != 0, _E, _H)
                # boundary: column 0 of the DP is a forced vertical run
                nxt = jnp.where(j == 0, _E, nxt).astype(jnp.int32)
                return (i - 1, j, nxt, aligned, ins_cnt, ins_b)

            def do_left(st):
                i, j, state, aligned, ins_cnt, ins_b = st
                aligned = aligned.at[j - 1].set(GAP)
                nxt = jnp.where((m & FBIT_EXT) != 0, _F, _H)
                nxt = jnp.where(i == 0, _F, nxt).astype(jnp.int32)
                return (i, j - 1, nxt, aligned, ins_cnt, ins_b)

            # boundary overrides: off the matrix edges the op is forced
            forced_up = (j == 0) & (i > 0)
            forced_left = (i == 0) & (j > 0)
            op = jnp.where(
                forced_up, 1,
                jnp.where(
                    forced_left, 2,
                    jnp.where(
                        state == _E, 1,
                        jnp.where(
                            state == _F, 2,
                            jnp.where(choice == 0, 0,
                                      jnp.where(choice == MOVE_UP, 1, 2)),
                        ),
                    ),
                ),
            )
            return jax.lax.switch(op, [do_diag, do_up, do_left], st)

        i0 = qlen.astype(jnp.int32)
        j0 = tlen.astype(jnp.int32)
        st = (i0, j0, jnp.int32(_H), aligned, ins_cnt, ins_b)
        _, _, _, aligned, ins_cnt, ins_b = jax.lax.while_loop(cond, body, st)

        # left-justify the right-aligned insertion cells
        used = jnp.minimum(ins_cnt, max_ins)
        shift = (max_ins - used)[:, None]
        cols = jnp.arange(max_ins)[None, :] + shift
        ins_b = jnp.take_along_axis(
            ins_b, jnp.clip(cols, 0, max_ins - 1), axis=1
        )
        ins_b = jnp.where(jnp.arange(max_ins)[None, :] < used[:, None],
                          ins_b, PAD)
        # split the leading slot back out: index j = insertions after
        # template column j; lead_ins = query bases before column 0
        return aligned, ins_cnt[1:], ins_b[1:], ins_cnt[0]

    return project
