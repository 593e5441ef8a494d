"""Chaos soak harness: randomized fault schedules, byte-identity oracle.

The resilience layer's whole claim is "a fault changes WHERE a request
computes, never WHAT it computes" — every recovery rung lands on the
bit-exact host spec, kills resume from the journal, and a shepherded
rank restart merges to the unsharded bytes.  That claim is only worth
anything under composition, so this harness drives RANDOMIZED fault
schedules end-to-end and asserts byte-identity against the fault-free
run for every trial:

* **In-process faults** (`device_oom`, `device_oom` storms, `stall`,
  `device_hang` — the latter under ``--dispatch-deadline``): armed via
  utils/faultinject.py at a seeded random call index, run through the
  full CLI, output compared byte-for-byte.
* **Kill/resume faults** (`write`, `journal`): the CLI runs in a
  subprocess, dies at the injected os._exit(57), and a clean resume
  must complete byte-identical with no duplicated or dropped holes.
* **Shepherd trials** (`rank_death`): a sharded run under
  `ccsx-tpu shepherd` with one rank SIGKILLed at a seeded retirement;
  the supervisor restarts it and the merged output must equal the
  unsharded run's bytes.

* **Input-plane faults** (`disk_full`, `input_corrupt`): an injected
  ENOSPC must exit through the clean rc-1 path with the journal
  consistent and resume byte-identical; an injected classified
  corruption under ``--salvage`` must complete rc 0 degraded with the
  byte-identity oracle restricted to UNDAMAGED holes (the salvage
  contract; real crafted-byte corruption is the corruption fuzzer's
  domain, benchmarks/corrupt.py).

Schedules are pure functions of ``--seed``, so any red trial is
replayable exactly.  Deliberately NOT injected here: ``compute`` and
``ingest`` faults — they are *designed* to change the output
(quarantine a hole / abort the run), so byte-identity is the wrong
oracle for them; tests/test_faults.py pins their contracts instead.

The fast deterministic slice of this harness runs in tier-1
(tests/test_chaos.py, `make chaos`); the full soak is the `slow` mark
and this CLI:

    python benchmarks/chaos.py --seed 0 --trials 12 --holes 6 \
        --json benchmarks/chaos_rNN.json

Fleet-membership churn (rank SIGKILL under the ELASTIC scheduler,
mid-run --join, SIGTERM drain, stragglers) is the fleet soak's domain
— benchmarks/fleet.py reuses this harness's corpus builder, reference
runner, and byte-identity oracle (`make fleet-chaos`).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from ccsx_tpu import cli                                     # noqa: E402
from ccsx_tpu.utils import faultinject, synth                # noqa: E402

# the same backend-pinning runner idiom as tests/test_faults.py — the
# kill trials must die in their OWN process
_RUNNER = ("import sys, jax; jax.config.update('jax_platforms', 'cpu'); "
           "from ccsx_tpu.cli import main; sys.exit(main(sys.argv[1:]))")

# in-process fault menu: (name, spec-template, extra CLI args).  The
# call index N is drawn per trial from the seeded rng.
INPROC_FAULTS = (
    ("device_oom", "device_oom@{n}", ()),
    ("device_oom_storm", "device_oom@{n}+", ()),
    ("stall", "stall@{n}", ("--stall-timeout", "0.2")),
    ("device_hang", "device_hang@{n}", ("--dispatch-deadline", "2")),
)
KILL_FAULTS = ("write", "journal")


def make_corpus(tmp: str, rng, holes: int, tlen: int = 700,
                n_passes: int = 5) -> str:
    zs = [synth.make_zmw(rng, template_len=tlen, n_passes=n_passes,
                         movie="mv", hole=str(100 + h))
          for h in range(holes)]
    p = os.path.join(tmp, "in.fa")
    with open(p, "w") as f:
        f.write(synth.make_fasta(zs))
    return p


def _base_args(in_fa: str, out: str, extra=()) -> list:
    return ["-A", "-m", "1000", "--batch", "on", *extra, in_fa, out]


def run_reference(in_fa: str, tmp: str) -> bytes:
    ref = os.path.join(tmp, "ref.fa")
    rc = cli.main(_base_args(in_fa, ref))
    assert rc == 0, f"fault-free reference run failed rc={rc}"
    return open(ref, "rb").read()


def trial_inproc(in_fa: str, tmp: str, ref: bytes, name: str,
                 spec: str, extra) -> dict:
    out = os.path.join(tmp, f"o_{name}.fa")
    m = os.path.join(tmp, f"m_{name}.jsonl")
    faultinject.arm(spec)
    try:
        rc = cli.main(_base_args(in_fa, out,
                                 (*extra, "--metrics", m)))
    finally:
        faultinject.disarm()
    got = open(out, "rb").read() if os.path.exists(out) else b""
    final = {}
    try:
        final = [json.loads(line) for line in open(m)][-1]
    except (OSError, IndexError, ValueError):
        pass
    return {"kind": name, "spec": spec, "rc": rc,
            "identical": got == ref,
            "ok": rc == 0 and got == ref,
            "counters": {k: final.get(k) for k in
                         ("device_hangs", "oom_resplits",
                          "host_fallbacks", "breaker_trips", "stalls")},
            "degraded": bool(final.get("degraded"))}


def trial_kill_resume(in_fa: str, tmp: str, ref: bytes, point: str,
                      n: int) -> dict:
    """Subprocess dies at the injected os._exit; the resume must finish
    byte-identical (journal v2 torn-tail contract)."""
    out = os.path.join(tmp, f"o_kill_{point}.fa")
    jp = os.path.join(tmp, f"j_{point}.json")
    args = _base_args(in_fa, out, ("--journal", jp))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="", CCSX_FAULTS=f"{point}@{n}",
               CCSX_JOURNAL_FSYNC_S="0")
    r = subprocess.run([sys.executable, "-c", _RUNNER, *args], env=env,
                       cwd=_REPO, capture_output=True, text=True,
                       timeout=600)
    killed = r.returncode == faultinject.EXIT_CODE
    rc = cli.main(args)   # resume, no faults
    got = open(out, "rb").read() if os.path.exists(out) else b""
    return {"kind": f"kill_{point}", "spec": f"{point}@{n}",
            "killed_rc": r.returncode, "resume_rc": rc,
            "identical": got == ref,
            "ok": killed and rc == 0 and got == ref}


def trial_disk_full_resume(in_fa: str, tmp: str, ref: bytes,
                           n: int) -> dict:
    """ENOSPC (injected OSError in the synchronous writer) must exit
    through the clean rc-1 path with the journal consistent; the
    resume must complete byte-identical — the disk-full reality of
    long runs on shared scratch."""
    out = os.path.join(tmp, "o_diskfull.fa")
    jp = os.path.join(tmp, "j_diskfull.json")
    args = _base_args(in_fa, out, ("--journal", jp))
    os.environ["CCSX_JOURNAL_FSYNC_S"] = "0"
    try:
        faultinject.arm(f"disk_full@{n}")
        rc1 = cli.main(args)
        faultinject.disarm()
        rc2 = cli.main(args)   # disk "freed": resume, no faults
    finally:
        faultinject.disarm()
        os.environ.pop("CCSX_JOURNAL_FSYNC_S", None)
    got = open(out, "rb").read() if os.path.exists(out) else b""
    return {"kind": "disk_full_resume", "spec": f"disk_full@{n}",
            "enospc_rc": rc1, "resume_rc": rc2,
            "identical": got == ref,
            "ok": rc1 == 1 and rc2 == 0 and got == ref}


def trial_input_corrupt(in_fa: str, tmp: str, ref: bytes,
                        n: int) -> dict:
    """An injected classified corruption at the Nth ingested hole with
    --salvage: the run must complete rc 0 degraded with exactly that
    hole dropped — the byte-identity oracle restricted to UNDAMAGED
    holes (the salvage contract, io/corruption.py)."""
    out = os.path.join(tmp, "o_incorrupt.fa")
    m = os.path.join(tmp, "m_incorrupt.jsonl")
    faultinject.arm(f"input_corrupt@{n}")
    try:
        rc = cli.main(_base_args(in_fa, out,
                                 ("--salvage", "--metrics", m)))
    finally:
        faultinject.disarm()
    got = open(out, "rb").read() if os.path.exists(out) else b""
    # undamaged-holes oracle: every emitted record must be byte-equal
    # to its clean-run twin, and exactly one hole (the injected one)
    # may be missing
    def _by_hole(b):
        return {c.split("\n", 1)[0]: c
                for c in b.decode(errors="replace").split(">")[1:]}
    r, s = _by_hole(ref), _by_hole(got)
    sub_ok = all(s.get(k) == v for k, v in r.items() if k in s)
    final = {}
    try:
        final = [json.loads(line) for line in open(m)][-1]
    except (OSError, IndexError, ValueError):
        pass
    return {"kind": "input_corrupt", "spec": f"input_corrupt@{n}",
            "rc": rc, "holes_corrupt": final.get("holes_corrupt"),
            "degraded": bool(final.get("degraded")),
            "ok": (rc == 0 and len(s) == len(r) - 1 and sub_ok
                   and final.get("holes_corrupt") == 1
                   and bool(final.get("degraded")))}


def trial_shepherd_rank_death(in_fa: str, tmp: str, ref: bytes,
                              hosts: int, dead_rank: int,
                              n: int) -> dict:
    """A shepherded sharded run with one rank SIGKILLed at its Nth
    retirement: the supervisor restarts it (journal resume) and the
    merged output must equal the unsharded reference bytes."""
    from ccsx_tpu.pipeline.supervisor import shepherd_run

    out = os.path.join(tmp, "shep.fa")
    fwd = ["-A", "-m", "1000", "--hosts", str(hosts), in_fa, out]
    rc = shepherd_run(
        in_fa, out, hosts, fwd,
        max_restarts=2, backoff_s=0.1, poll_s=0.1,
        env=dict(os.environ, CCSX_JOURNAL_FSYNC_S="0"),
        first_launch_env={dead_rank: {
            "CCSX_FAULTS": f"rank_death@{n}"}})
    got = open(out, "rb").read() if os.path.exists(out) else b""
    return {"kind": "shepherd_rank_death",
            "spec": f"rank{dead_rank}:rank_death@{n}",
            "rc": rc, "identical": got == ref,
            "ok": rc == 0 and got == ref}


def run_trials(seed: int, trials: int, holes: int,
               include_kills: bool = True,
               include_shepherd: bool = True,
               include_input: bool = True,
               max_call: int = 4, tmp: str = None) -> dict:
    """The soak driver: ``trials`` seeded in-process fault trials plus
    (optionally) one kill/resume trial per kill point, one shepherd
    rank-death trial, and the input-plane trials (disk_full ENOSPC +
    resume; input_corrupt under --salvage with the undamaged-holes
    oracle).  Returns the summary dict; ``summary["ok"]`` is the
    one-bit verdict (every trial byte-identical / contract-clean)."""
    # unit-scale hang budgets unless the caller already chose: grace x1
    # (the chaos corpus compiles in seconds on CPU — 10x grace would
    # make every first-of-shape device_hang trial a ~20 s wait) and a
    # bounded hang sleep so abandoned daemon threads don't hold the
    # dispatch closures for an hour of soak
    os.environ.setdefault("CCSX_DEADLINE_GRACE", "1")
    os.environ.setdefault("CCSX_FAULT_HANG_S", "60")
    os.environ.setdefault("CCSX_FAULT_STALL_S", "0.3")
    rng = np.random.default_rng(seed)
    own_tmp = tmp is None
    tmp = tmp or tempfile.mkdtemp(prefix="ccsx_chaos_")
    t0 = time.monotonic()
    results = []
    try:
        in_fa = make_corpus(tmp, rng, holes)
        ref = run_reference(in_fa, tmp)
        for t in range(trials):
            name, spec_t, extra = INPROC_FAULTS[
                int(rng.integers(len(INPROC_FAULTS)))]
            n = int(rng.integers(1, max_call + 1))
            results.append(trial_inproc(in_fa, tmp, ref, name,
                                        spec_t.format(n=n), extra))
        if include_kills:
            for point in KILL_FAULTS:
                results.append(trial_kill_resume(
                    in_fa, tmp, ref, point,
                    int(rng.integers(1, max(holes, 2)))))
        if include_input:
            # the input failure domain mixed into the same soak: a
            # disk-full abort + resume, and an injected classified
            # corruption salvaged mid-run
            results.append(trial_disk_full_resume(
                in_fa, tmp, ref, int(rng.integers(1, max(holes, 2)))))
            results.append(trial_input_corrupt(
                in_fa, tmp, ref, int(rng.integers(1, holes + 1))))
        if include_shepherd:
            results.append(trial_shepherd_rank_death(
                in_fa, tmp, ref, hosts=2, dead_rank=1,
                n=int(rng.integers(1, max(holes // 2, 2)))))
    finally:
        if own_tmp:
            import shutil

            shutil.rmtree(tmp, ignore_errors=True)
    bad = [r for r in results if not r["ok"]]
    return {"seed": seed, "holes": holes, "trials": results,
            "n_trials": len(results), "n_failed": len(bad),
            "ok": not bad,
            "elapsed_s": round(time.monotonic() - t0, 1)}


def main():
    ap = argparse.ArgumentParser(
        description="Chaos soak: randomized fault schedules, "
                    "byte-identity oracle (seeded, replayable)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trials", type=int, default=12,
                    help="in-process fault trials [12]")
    ap.add_argument("--holes", type=int, default=6)
    ap.add_argument("--no-kills", action="store_true",
                    help="skip the subprocess kill/resume trials")
    ap.add_argument("--no-shepherd", action="store_true",
                    help="skip the shepherd rank-death trial")
    ap.add_argument("--no-input", action="store_true",
                    help="skip the input-plane trials (disk_full, "
                         "input_corrupt)")
    ap.add_argument("--json", default=None)
    a = ap.parse_args()
    summary = run_trials(a.seed, a.trials, a.holes,
                         include_kills=not a.no_kills,
                         include_shepherd=not a.no_shepherd,
                         include_input=not a.no_input)
    print(json.dumps(summary, indent=1))
    if a.json:
        with open(a.json, "w") as f:
            json.dump(summary, f, indent=1)
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
