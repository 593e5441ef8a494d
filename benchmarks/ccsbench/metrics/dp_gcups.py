"""dp_gcups: nominal banded-DP cells of the window's holes, in units of
1e9, over the device's busy seconds in the window, taken as the window's
length times the busy share of the traced part of it (the profiler's
buffer holds about a second of this program's operations).

A hole's nominal cells are the lengths of its first ``max_passes``
passes, times the band's width 2 * band + 1, times 1 + refine_iters
fills (the draft and each refinement), all as the configuration file
states them.  The count depends on the input alone, never on the
program's padded arrays or counters, so it reads the same work whatever
fill implements it."""


def nominal_cells(hole, program: dict) -> int:
    passes = hole.passes[:program["max_passes"]]
    return (sum(len(p) for p in passes) * (2 * program["band"] + 1)
            * (1 + program["refine_iters"]))


def read(ctx):
    if ctx.trace is None or not ctx.records or not ctx.trace["busy_s"]:
        return None
    pool = {h.hole: h for h in ctx.pool}
    cells = sum(nominal_cells(pool[int(r[1].split("/")[1]) % len(pool)],
                              ctx.config["program"])
                for r in ctx.records)
    busy_share = ctx.trace["busy_s"] / ctx.trace["window_s"]
    return cells / 1e9 / (ctx.window_s * busy_share)
