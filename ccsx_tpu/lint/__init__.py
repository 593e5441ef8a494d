"""ccsx-lint: the repo-native static-analysis plane.

Pure ``ast``/``tokenize`` — importing this package MUST NOT import jax
(or anything that transitively does): the linter is a tier-1 test and
a pre-review gate on the 1-core box, so it has to run in seconds.

The checkers pin the defect families this codebase has actually
shipped and hand-reviewed out, one checker per family:

- ``int32-overflow``   the silent traced-int32 wrap in index
                       interpolation (the pre-r11 ``_line_interp`` and
                       pre-r14 ``compute_offsets`` expressions)
- ``bare-write``       crash-safety writes in lease/journal/spool/fleet
                       domains that bypass ``write_json_atomic`` /
                       ``write_json_exclusive`` / ``O_EXCL``
- ``metrics-lock``     read-modify-write on Metrics counters outside
                       ``bump()``/``add_stage()``
- ``contextvar-restore`` ``ContextVar.set()`` with no token restore in
                       a ``finally`` (the r17 cid cross-stamp shape)
- ``schema-drift``     the static complement of the runtime telemetry
                       schema guard: consumed keys exist in
                       ``Metrics.snapshot()`` and snapshot keys reach
                       /metrics or the structured allowlist

See ``ccsx_tpu/lint/core.py`` for the findings format, the inline
pragma (``# lint: ok[<check>] <reason>``), and the committed baseline
(``lint_baseline.json``) that records deliberate suppressions.
"""

from ccsx_tpu.lint.core import Finding, LintResult, lint_main, run_lint

__all__ = ["Finding", "LintResult", "lint_main", "run_lint"]
