#!/usr/bin/env python3
"""Chip smoke: run the batched CLI consensus path once on a TPU.

BAM subreads -> ``ccsx_tpu.cli.main`` (``--batch auto``: on for TPU) ->
packed star-MSA refine on the device -> FASTA, at the default config
(band 128, max_window 8192, slab_rows 128, refine_iters 2, -c 3 -m 5000
-M 500000) on seeded HiFi-like ZMWs (the first 16 of 32), in this one
process.  Every
check that fails exits non-zero; the last stdout line is the JSON
result only when every phase passed.

    python chip_smoke.py             # one chip: the main path + checks
    python chip_smoke.py --chips 4   # only the multi-chip layouts

Earlier stdout lines carry smoke timings (not benchmark numbers), the
``block_until_ready`` finding and the compile-cache directory.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SEEDED_HOLES = 32          # ZMWs the seed generates
# the cuts that keep a cold run near 10 minutes on one chip: each packed
# refine program takes ~30-90 s of host compile, and a cold run
# compiles ~7 of them for 16 holes (9 for 32); the per-hole path and
# both kernels compile their own on the cross-check subset
HOLES = 16                 # ZMWs in the main run
CROSS_HOLES = 4            # ZMWs in the cross-checks and on four chips
TLEN = (10_000, 20_000)    # HiFi SMRT-cell insert sizes
ERR = dict(sub_rate=0.02, ins_rate=0.05, del_rate=0.05)
MIN_IDENTITY = 0.97
ZERO_COUNTERS = ("host_fallbacks", "compile_fallbacks", "device_hangs",
                 "breaker_trips", "holes_failed")


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(phase: str, **kv) -> None:
    print(f"smoke {phase}: {json.dumps(kv)}", flush=True)


def device_check(count: int) -> dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    say("device", **dev)
    check(dev["platform"] == "tpu", f"platform is {dev['platform']!r}, "
          "not 'tpu'")
    check(dev["count"] >= count, f"{dev['count']} devices, need {count}")
    return dev


def build_native() -> None:
    ndir = os.path.join(HERE, "ccsx_tpu", "native")
    r = subprocess.run(["make", "-s", "-C", ndir, "clean", "all"],
                       capture_output=True, text=True)
    check(r.returncode == 0, f"native build failed: {r.stderr[-2000:]}")
    import ccsx_tpu
    from ccsx_tpu import native

    check(os.path.dirname(os.path.abspath(ccsx_tpu.__file__))
          == os.path.join(HERE, "ccsx_tpu"),
          f"ccsx_tpu imported from {ccsx_tpu.__file__}, not this checkout")
    check(native.available(), f"native library unavailable: "
          f"{native.build_error()}")


def make_zmws(seed: int, holes: int, tlen=TLEN):
    """``holes`` seeded ZMWs: templates uniform in ``tlen``, pass counts
    log-normal (median 9, clipped 5-30) as benchmarks/quality.py."""
    import numpy as np

    from ccsx_tpu.utils import synth

    sys.path.append(os.path.join(HERE, "benchmarks"))
    from quality import sample_pass_counts

    rng = np.random.default_rng(seed)
    counts = sample_pass_counts(rng, holes)
    tlens = rng.integers(tlen[0], tlen[1] + 1, holes)
    return [synth.make_zmw(rng, int(tlens[h]), int(counts[h]),
                           movie="smoke", hole=str(h), **ERR)
            for h in range(holes)]


def write_bam(path: str, zs) -> None:
    from ccsx_tpu.io import bam as bam_mod
    from ccsx_tpu.ops import encode as enc

    bam_mod.write_bam(path, [(n, enc.decode(s).encode(), None)
                             for z in zs for n, s in zip(z.names, z.passes)],
                      bgzf=True)


def run_cli(args, bam: str, out: str, metrics: str) -> dict:
    """One in-process CLI run; returns its final metrics event."""
    from ccsx_tpu import cli

    rc = cli.main([*args, "--metrics", metrics, bam, out])
    check(rc == 0, f"cli {args} exited {rc}")
    with open(metrics) as f:
        final = [json.loads(line) for line in f][-1]
    check(final.get("event") == "final", "no final metrics event")
    return final


def check_clean(final: dict, holes: int, what: str) -> None:
    check(final["holes_out"] == holes,
          f"{what}: {final['holes_out']}/{holes} holes emitted")
    check(final["device_dispatches"] > 0, f"{what}: no device dispatch")
    for k in ZERO_COUNTERS:
        check(not final.get(k), f"{what}: {k}={final.get(k)}")
    check(not final.get("degraded"),
          f"{what}: degraded {final.get('degraded')!r}")


def read_fasta(path: str) -> dict:
    from ccsx_tpu.io import fastx

    return {r.name: r.seq for r in fastx.read_fastx(path)}


def identities(zs, out: str):
    """identity_either of every hole against its template, in threads
    (the native aligner releases the GIL)."""
    from ccsx_tpu.ops import encode as enc
    from ccsx_tpu.utils import synth

    got = read_fasta(out)
    names = [f"{z.movie}/{z.hole}/ccs" for z in zs]
    missing = [n for n in names if n not in got]
    check(not missing, f"holes not emitted: {missing}")
    with concurrent.futures.ThreadPoolExecutor(
            min(8, os.cpu_count() or 1)) as pool:
        return list(pool.map(
            lambda zn: synth.identity_either(enc.encode(got[zn[1]]),
                                             zn[0].template),
            zip(zs, names)))


class CompileClock:
    """Sums JAX's compile-phase durations (trace, lowering, backend)."""

    def __init__(self):
        import jax

        self.secs = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, *a, **k):
        if event.startswith("/jax/core/compile/"):
            name = event.rsplit("/", 1)[1]
            self.secs[name] = self.secs.get(name, 0.0) + duration

    def total(self) -> float:
        return sum(self.secs.values())


def block_until_ready_probe() -> dict:
    """Whether block_until_ready waits on this runtime: one real-width
    jitted scan fill (N=128 pairs, qmax=tmax=4096, band 128), timed to
    block_until_ready, then the host fetch that follows it, then a fresh
    call timed straight to a host fetch.  On a TPU the same fill through
    the two Pallas kernels is timed call-to-fetch too."""
    import jax
    import numpy as np

    from ccsx_tpu.config import AlignParams
    from ccsx_tpu.ops import banded

    fill = banded.make_batched("global", AlignParams(), with_moves=True,
                               with_stats=False)
    rng = np.random.default_rng(1)
    n, w = 128, 4096
    qs = rng.integers(0, 4, (n, w)).astype(np.uint8)
    ts = qs.copy()
    lens = np.full(n, w, np.int32)
    args = [jax.device_put(a) for a in (qs, lens, ts, lens)]
    np.asarray(fill(*args)[0].score)                # compile + warm
    block, after, fetch = [], [], []
    for _ in range(3):
        t0 = time.perf_counter()
        out = fill(*args)
        jax.block_until_ready(out)
        t1 = time.perf_counter()
        np.asarray(out[0].score)
        t2 = time.perf_counter()
        out = fill(*args)
        np.asarray(out[0].score)
        t3 = time.perf_counter()
        block.append(t1 - t0)
        after.append(t2 - t1)
        fetch.append(t3 - t2)
    res = {"block_s": sorted(block)[1], "fetch_after_block_s":
           sorted(after)[1], "call_to_fetch_s": sorted(fetch)[1]}
    # waits: the block covered the execution, so fetching afterwards
    # costs only the copy, and the block alone is most of call-to-fetch
    res["waits"] = bool(res["block_s"] >= 0.5 * res["call_to_fetch_s"])
    if jax.default_backend() == "tpu":
        # the same fill through both kernels (smoke, not a benchmark)
        from ccsx_tpu.ops import banded_pallas, banded_rotband

        for name, mod in (("pallas", banded_pallas),
                          ("rotband", banded_rotband)):
            kfill = jax.jit(lambda q, ql, t, tl, _m=mod:
                            _m.batched_align_global_moves(
                                q, ql, t, tl, AlignParams(),
                                with_stats=False, interpret=False))
            np.asarray(kfill(*args)[0].score)       # compile + warm
            runs = []
            for _ in range(3):
                t0 = time.perf_counter()
                np.asarray(kfill(*args)[0].score)
                runs.append(time.perf_counter() - t0)
            res[f"{name}_call_to_fetch_s"] = sorted(runs)[1]
    return res


def scan_vs_oracle(pairs: int = 6) -> int:
    """Real-width (band 128, qmax 1024) pairs through the scan fill;
    scores must equal ops/oracle.align (the band covers these paths)."""
    import numpy as np

    from ccsx_tpu.config import AlignParams
    from ccsx_tpu.ops import banded, oracle
    from ccsx_tpu.utils import synth

    p = AlignParams()
    fill = banded.make_batched("global", p)
    rng = np.random.default_rng(2)
    w = 1024
    qs = np.full((pairs, w), banded.PAD, np.uint8)
    ts = np.full((pairs, w), banded.PAD, np.uint8)
    ql = np.zeros(pairs, np.int32)
    tl = np.zeros(pairs, np.int32)
    want = []
    for i in range(pairs):
        t = rng.integers(0, 4, int(rng.integers(700, 900))).astype(np.uint8)
        q = synth.mutate(rng, t, **ERR)[:w]
        qs[i, :len(q)], ts[i, :len(t)] = q, t
        ql[i], tl[i] = len(q), len(t)
        want.append(oracle.align(q, t, mode="global", match=p.match,
                                 mismatch=p.mismatch, gap_open=p.gap_open,
                                 gap_extend=p.gap_extend).score)
    got = [int(s) for s in np.asarray(fill(qs, ql, ts, tl).score)]
    check(got == want, f"scan scores {got} != oracle {want}")
    return pairs


class KernelCalls:
    """Records every (impl, interpret) the star aligner traces a Pallas
    kernel with, so a kernel arm is proven to have run on the chip."""

    def __init__(self):
        from ccsx_tpu.ops import banded_pallas, banded_rotband

        self.calls = []
        for impl, mod in (("pallas", banded_pallas),
                          ("rotband", banded_rotband)):
            orig = mod.batched_align_global_moves

            def wrapped(*a, _orig=orig, _impl=impl, **k):
                self.calls.append((_impl, bool(k.get("interpret"))))
                return _orig(*a, **k)

            mod.batched_align_global_moves = wrapped


def fasta_records(path: str, names) -> bytes:
    """The raw bytes of the named records of a FASTA file, in order."""
    with open(path, "rb") as f:
        recs = [b">" + r for r in f.read().split(b"\n>")]
    recs[0] = recs[0][1:]
    by_name = {r[1:].split(b"\n", 1)[0].split()[0].decode():
               r.rstrip(b"\n") + b"\n"
               for r in recs}
    return b"".join(by_name[n] for n in names)


def cross_checks(zs, work: str, main_out: str) -> dict:
    """On the first CROSS_HOLES holes: the per-hole path (--batch off)
    with the scan, Pallas and rotband fills, each byte-identical to
    those holes' records in the main batched run (its fill chosen by
    star.banded_impl_effective: v1 at qmax <= 4096) — which also
    pins that a hole's consensus does not depend on the slabs it shared.
    The kernels run in the per-hole rounds, which call the fill eagerly
    and read CCSX_BANDED_IMPL per call: the batched path would recompile
    every packed refine program for each kernel (~4 min a kernel)."""
    sub = os.path.join(work, "sub.bam")
    write_bam(sub, zs[:CROSS_HOLES])
    ref = fasta_records(main_out, [f"{z.movie}/{z.hole}/ccs"
                                   for z in zs[:CROSS_HOLES]])
    kernels = KernelCalls()
    res = {}
    try:
        for impl in ("scan", "pallas", "rotband"):
            t0 = time.perf_counter()
            os.environ["CCSX_BANDED_IMPL"] = impl
            out = os.path.join(work, f"sub.{impl}.fa")
            final = run_cli(["--batch", "off"], sub, out,
                            os.path.join(work, f"sub.{impl}.jsonl"))
            check_clean(final, CROSS_HOLES, f"--batch off, {impl}")
            with open(out, "rb") as f:
                check(f.read() == ref, f"--batch off, {impl}: FASTA "
                      "differs from the batched run")
            if impl != "scan":
                check(any(i == impl for i, _ in kernels.calls),
                      f"{impl}: the kernel never ran")
            res[f"batch_off_{impl}"] = {
                "identical": True, "seconds": time.perf_counter() - t0}
        interp = [c for c in kernels.calls if c[1]]
        check(not interp, f"kernels ran in interpret mode: {interp[:3]}")
        res["kernel_calls"] = len(kernels.calls)
        return res
    finally:
        os.environ.pop("CCSX_BANDED_IMPL", None)


def one_chip(work: str, seed: int) -> None:
    from ccsx_tpu.utils.device import compile_cache_dir

    say("compile-cache", dir=compile_cache_dir(),
        from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")))
    clock = CompileClock()
    t0 = time.perf_counter()
    zs = make_zmws(seed, SEEDED_HOLES)[:HOLES]
    say("cut", holes=HOLES, of=SEEDED_HOLES, cross_check_holes=CROSS_HOLES,
        why="cold compiles: ~7 packed programs at ~30-90 s each")
    bam = os.path.join(work, "in.bam")
    write_bam(bam, zs)
    say("input", holes=HOLES, passes=sum(len(z.passes) for z in zs),
        bases=sum(len(p) for z in zs for p in z.passes),
        seconds=time.perf_counter() - t0)

    out = os.path.join(work, "out.fa")
    t0 = time.perf_counter()
    final = run_cli([], bam, out, os.path.join(work, "m.jsonl"))
    cold = time.perf_counter() - t0
    cold_compile = clock.total()
    check_clean(final, HOLES, "main run")
    t0 = time.perf_counter()
    idys = identities(zs, out)
    low = [(z.hole, i) for z, i in zip(zs, idys) if i <= MIN_IDENTITY]
    check(not low, f"holes at identity <= {MIN_IDENTITY}: {low}")
    say("identity", mean=sum(idys) / len(idys), min=min(idys),
        q20_share=sum(i >= 0.99 for i in idys) / len(idys),
        seconds=time.perf_counter() - t0)

    out2 = os.path.join(work, "out.warm.fa")
    t0 = time.perf_counter()
    final2 = run_cli([], bam, out2, os.path.join(work, "m.warm.jsonl"))
    warm = time.perf_counter() - t0
    check_clean(final2, HOLES, "warm run")
    with open(out, "rb") as a, open(out2, "rb") as b:
        check(a.read() == b.read(), "warm run FASTA differs from cold")
    say("timing (smoke, not a benchmark)", cold_wall_s=cold,
        cold_compile_s=cold_compile, compile_phases_s=clock.secs,
        warm_wall_s=warm, warm_compile_s=clock.total() - cold_compile,
        zmws_per_s_warm=HOLES / warm,
        device_dispatches=final2["device_dispatches"],
        banded_dispatches=final2.get("banded_dispatches"))

    t0 = time.perf_counter()
    say("block_until_ready", **block_until_ready_probe(),
        seconds=time.perf_counter() - t0)
    t0 = time.perf_counter()
    say("scan-vs-oracle", pairs=scan_vs_oracle(),
        seconds=time.perf_counter() - t0)
    say("cross-checks", holes=CROSS_HOLES, **cross_checks(zs, work, out))


def four_chips(work: str, seed: int) -> None:
    """One device, the default slab layout over all four, and --mesh
    2,2: byte-identical FASTA on the same seeded input."""
    import jax

    from ccsx_tpu.pipeline import batch as batch_mod

    zs = make_zmws(seed, SEEDED_HOLES)[:CROSS_HOLES]
    say("cut", holes=CROSS_HOLES, of=SEEDED_HOLES,
        why="three layouts each compile their own programs")
    bam = os.path.join(work, "in.bam")
    write_bam(bam, zs)
    made = []
    base = batch_mod.BatchExecutor

    class Recorded(base):
        devices = None

        def __init__(self, cfg, **kw):
            if Recorded.devices is not None:
                kw["devices"] = Recorded.devices
            super().__init__(cfg, **kw)
            made.append(self)

    batch_mod.BatchExecutor = Recorded
    try:
        outs = {}
        for name, args, devs in (
                ("one_device", [], jax.local_devices()[:1]),
                ("slab_layout", [], None),
                ("mesh_2x2", ["--mesh", "2,2"], None)):
            Recorded.devices = devs
            made.clear()
            out = os.path.join(work, f"{name}.fa")
            t0 = time.perf_counter()
            final = run_cli(args, bam, out,
                            os.path.join(work, f"{name}.jsonl"))
            check_clean(final, CROSS_HOLES, name)
            ex = made[-1]
            layout = {"devices": len(ex._devices),
                      "slab_mesh": ex._slab_mesh is not None,
                      "mesh": (None if ex._mesh is None
                               else dict(ex._mesh.shape))}
            say(name, wall_s=time.perf_counter() - t0,
                device_dispatches=final["device_dispatches"],
                fused_waves=final.get("fused_waves"), **layout)
            with open(out, "rb") as f:
                outs[name] = f.read()
        check(made[-1]._mesh is not None, "--mesh 2,2 built no mesh")
        check(outs["slab_layout"] == outs["one_device"],
              "slab layout over 4 chips differs from one device")
        check(outs["mesh_2x2"] == outs["one_device"],
              "--mesh 2,2 differs from one device")
        say("multi-chip", result="byte-identical", holes=CROSS_HOLES)
    finally:
        batch_mod.BatchExecutor = base


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip layouts")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args(argv)
    try:
        dev = device_check(a.chips)
        build_native()
        with tempfile.TemporaryDirectory(prefix="chip_smoke.") as work:
            (four_chips if a.chips == 4 else one_chip)(work, a.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
