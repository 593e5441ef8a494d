"""AOT warmup precompiler: overlap XLA compiles with ingest/prep.

The r7 flight recorder showed cold compiles serializing IN FRONT of the
stream: the first dispatch of every (group, shape) blocks the driver
thread for the whole XLA compile (seconds to tens of seconds) while
the chip and the ingest pipe both idle.  With canonical slab shapes (pipeline/pack.py, r8) a group's
executables are PREDICTABLE the moment prep yields its first
RefineRequest — qmax/tmax/iters from the request, R from the
(<= ladder)-entry canonical height set — so this module compiles them
on a background thread concurrently with ingest/prep, and the first
real dispatch of a warmed shape runs at steady-state speed.

Mechanism: the builder executes the REAL jitted step (the same object
the dispatch path gets from the lru-cached factory) on an all-zero
slab and blocks until ready.  A zero slab has an all-False row mask,
so every segment starts frozen and the fused while_loop exits without
one iteration — the execution costs ~a breakpoint scan on zeros.
``fn.lower(...).compile()`` would share the XLA compile but NOT the
jit dispatch cache (measured on jax 0.4: the first real call still
pays a retrace + cache population on the dispatch path); the
zero-slab call primes the exact fast path.

Attribution (utils/trace.py): each builder runs inside a
``device_span(..., warmup=True)`` carrying the SAME group and shape
keys the dispatch span will use, so the warmup books the (group,
shape)'s one compile — and the first real dispatch books no compile,
which is the trace-visible proof the overlap worked.  A warmup span
for an already-seen shape books nothing.

Coordination with the dispatch path: before dispatching a shape, the
executor calls ``claim(key)`` — a still-queued warmup is cancelled
(the dispatch compiles inline, exactly as without warmup), an
in-flight one returns an Event to wait on (the compile is already
running on the other thread; waiting costs no more than compiling and
avoids a duplicate), a finished or unknown one returns None.

``--no-warmup`` (cfg.warmup_compile = False) disables the whole layer:
the drivers then construct no WarmupCompiler and every call site
degrades to r7 behavior.  A compile failure in a builder is kept and
re-raised by ``settle(key)`` at the dispatch that claims the key, so
it fails that dispatch exactly as an inline compile failure would.
"""

from __future__ import annotations

import sys
import time
import threading
from typing import Callable, Dict, List, Optional, Tuple


class WarmupCompiler:
    """One background thread draining a FIFO of (key, builder) compile
    jobs.  Keys are arbitrary hashables (the executors use executable-
    identity tuples); a key is only ever built once.

    ``debounce_s``: a job only STARTS once it has sat queued this long.
    Executors refine their shape predictions as admission accumulates
    holes (warm_refine's row accumulator) and cancel superseded keys
    via claim() — but a build that already started cannot be cancelled,
    and XLA compiles cost tens of seconds, so racing the first
    prediction into the compiler would build a program the refined
    prediction obsoletes milliseconds later.  Half a second of settle
    time is noise against the compile it saves.

    ``workers``: build threads.  More than one matters at the sweep
    where the run's groups cross a shape boundary TOGETHER (lockstep
    windows: the whole admission batch dribbles below the slab budget
    in the same sweep, so several groups need their tail-height
    executable at once) — XLA compiles release the GIL, so a small
    pool turns that serial compile train into concurrent builds.  The
    default scales to the host but stays small: compile threads
    compete with the dispatch stream for cores."""

    def __init__(self, debounce_s: float = 0.5,
                 workers: Optional[int] = None):
        import os

        self.debounce_s = max(0.0, float(debounce_s))
        if workers is None:
            workers = min(4, max(1, (os.cpu_count() or 4) // 4))
        self._cv = threading.Condition()
        self._queue: List[Tuple[object, Callable[[], None], float]] = []
        self._state: Dict[object, str] = {}  # queued|running|claimed|done
        self._events: Dict[object, threading.Event] = {}
        self._errors: Dict[object, BaseException] = {}
        self._stop = False
        self._threads = [
            threading.Thread(target=self._run, daemon=True,
                             name=f"ccsx-warmup-{i}")
            for i in range(max(1, int(workers)))]
        for t in self._threads:
            t.start()

    def submit(self, key, builder: Callable[[], None],
               urgent: bool = False) -> bool:
        """Enqueue ``builder`` under ``key`` unless the key was ever
        submitted before (or the compiler is closed).  Returns whether
        the job was accepted.  ``urgent`` skips the debounce — for
        sweep-time EXACT shapes (no refinement can supersede them, and
        their dispatch is imminent).

        A CLAIMED (cancelled) key is resubmittable: prediction
        refinement cancels a superseded height, but the same height
        can become wanted again later (the dribble-tail warm after the
        group's prediction walked past it) — a permanent tombstone
        would silently drop exactly that resubmission.  If the claim
        came from a dispatch that compiled inline, the re-build is a
        jit-cache hit costing milliseconds."""
        with self._cv:
            if self._stop or self._state.get(key) in ("queued",
                                                      "running", "done"):
                return False
            self._state[key] = "queued"
            t = time.monotonic() - (self.debounce_s if urgent else 0.0)
            self._queue.append((key, builder, t))
            self._cv.notify()
            return True

    def claim(self, key) -> Optional[threading.Event]:
        """Dispatch-path synchronization for ``key``:

        * queued  -> cancelled; returns None (caller compiles inline —
                     no duplicated work, attribution lands on the
                     dispatch span as without warmup)
        * running -> returns the completion Event (caller should wait:
                     the compile is already happening concurrently)
        * done / claimed / never submitted -> None
        """
        with self._cv:
            st = self._state.get(key)
            if st == "queued":
                self._queue = [e for e in self._queue if e[0] != key]
                self._state[key] = "claimed"
                return None
            if st == "running":
                return self._events[key]
            return None

    def settle(self, key) -> None:
        """The dispatch path's claim: ``claim(key)``, wait out an
        in-flight build, then re-raise the build's failure if it had
        one — a warmup that failed to compile fails the dispatch that
        needed it."""
        ev = self.claim(key)
        if ev is not None:
            ev.wait()
        with self._cv:
            err = self._errors.get(key)
        if err is not None:
            raise err

    def busy(self) -> bool:
        """True while any accepted job is queued or building — the
        serving plane's readiness probe (a cold server still compiling
        its first tenant's executables reports ``ready: false`` so a
        load balancer does not route a job storm into a compile
        storm)."""
        with self._cv:
            return bool(self._queue) or ("running"
                                         in self._state.values())

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Block until every accepted job has finished (benchmarks use
        this to warm synchronously before timing).  Returns False on
        timeout."""
        with self._cv:
            return self._cv.wait_for(
                lambda: not self._queue and "running" not in
                self._state.values(), timeout=timeout)

    def close(self, timeout: float = 10.0) -> None:
        """Drop queued jobs, let in-flight builds finish, stop the
        threads.  Idempotent; safe from a driver finally block."""
        with self._cv:
            self._stop = True
            self._queue.clear()
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=timeout)

    def _run(self) -> None:
        while True:
            with self._cv:
                while True:
                    if self._stop:
                        return
                    if self._queue:
                        # debounce: give prediction refinement its
                        # cancellation window before committing.  Pick
                        # the EARLIEST-READY job, not the FIFO head: an
                        # urgent (pre-aged) sweep-time job must not sit
                        # behind a still-debouncing prediction, or its
                        # own dispatch claims it back and compiles
                        # inline — the exact stall it exists to avoid.
                        now = time.monotonic()
                        i = min(range(len(self._queue)),
                                key=lambda j: self._queue[j][2])
                        wait = (self._queue[i][2] + self.debounce_s
                                - now)
                        if wait <= 0:
                            break
                        self._cv.wait(wait)
                    else:
                        self._cv.wait()
                key, builder, _ = self._queue.pop(i)
                self._state[key] = "running"
                ev = self._events[key] = threading.Event()
            try:
                builder()
            except Exception as e:  # re-raised by settle(key)
                print(f"[ccsx-tpu] warmup compile failed for {key!r}: "
                      f"{e}", file=sys.stderr)
                with self._cv:
                    self._errors[key] = e
            finally:
                with self._cv:
                    self._state[key] = "done"
                    ev.set()
                    self._cv.notify_all()
