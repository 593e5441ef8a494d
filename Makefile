# Repo-level convenience targets (the native layer has its own
# Makefile at ccsx_tpu/native/Makefile, auto-invoked on import).

PY ?= python
PYTEST_FLAGS = -q -p no:cacheprovider -p no:xdist -p no:randomly

.PHONY: chaos chaos-soak fleet-chaos serve-chaos serve-fleet-chaos fuzz fuzz-sweep tier1 tier1-shard native long-molecule pallas-ab lint

# the static-analysis plane (ccsx_tpu/lint/): the repo-native checkers
# over the tree against the committed baseline (lint_baseline.json),
# then ruff with the pinned config in pyproject.toml when available
# (the container doesn't ship it; the gate is the repo-native pass,
# which tests/test_lint.py also runs as a tier-1 test).  Exit 0 iff
# zero unsuppressed findings.
lint:
	$(PY) -m ccsx_tpu.cli lint
	@if command -v ruff >/dev/null 2>&1; then \
	  ruff check ccsx_tpu tests benchmarks; \
	else \
	  echo "ruff not installed; skipping (config pinned in pyproject.toml)"; \
	fi

# the long-template (ultra-long-read) A/B: prefilter + device seeding
# vs the legacy host path, interleaved arms, bytes asserted identical
# (also directly: python benchmarks/long_molecule.py --scenarios ...)
long-molecule:
	JAX_PLATFORMS=cpu $(PY) benchmarks/long_molecule.py \
	  --scenarios 4x50000,4x50000d4,1x100000d4 --passes 8 \
	  --json benchmarks/long_molecule_r11.json

# the deterministic tier-1 chaos slice (tests/test_chaos.py fast
# tests): seeded fault schedules through the full CLI with the
# byte-identity oracle — the recovery ladder, dispatch deadline,
# circuit breaker, and shepherd restart in one command
chaos:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_chaos.py -m 'not slow' $(PYTEST_FLAGS)

# the deterministic tier-1 corruption-fuzz slice (tests/
# test_corrupt_fuzz.py fast tests): seeded hostile-input mutants
# through the full CLI with the salvage invariant as oracle
fuzz:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_corrupt_fuzz.py -m 'not slow' $(PYTEST_FLAGS)

# the full >= 50-mutants-per-format sweep (also directly:
# python benchmarks/corrupt.py --seed N --mutants 50)
fuzz-sweep:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_corrupt_fuzz.py $(PYTEST_FLAGS)
	JAX_PLATFORMS=cpu $(PY) benchmarks/corrupt.py --seed 0 --mutants 50

# elastic fleet churn: the deterministic tier-1 slice (tests/
# test_fleet.py fast tests: lease crash-consistency + SIGKILL/drain/
# join byte-identity) then the seeded soak mixing rank SIGKILL,
# mid-run --join, SIGTERM drain, and a straggler against the
# byte-identity oracle (also directly:
# python benchmarks/fleet.py --seed N [--scale64])
fleet-chaos:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_fleet.py $(PYTEST_FLAGS)
	JAX_PLATFORMS=cpu $(PY) benchmarks/fleet.py --seed 0 --holes 6

# the serving plane: the deterministic tier-1 slice (tests/
# test_serve.py: concurrent byte identity + zero steady-state
# recompiles, 429/cancel/drain-resume, per-tenant hang isolation)
# then the seeded multi-tenant soak — cancel, device hang, salvage,
# ENOSPC retry, drain/restart — against the blast-radius oracle
# (also directly: python benchmarks/serve_chaos.py --seed N)
serve-chaos:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_serve.py $(PYTEST_FLAGS)
	JAX_PLATFORMS=cpu $(PY) benchmarks/serve_chaos.py --seed 0 --holes 6

# the replica-fleet plane: the deterministic tier-1 slice (tests/
# test_lease.py crash-consistency + tests/test_serve_fleet.py:
# cross-replica handoff, dead-replica requeue, exclusive retirement,
# gateway routing, fan-out) then the seeded 3-replica subprocess soak —
# SIGKILL mid-wave, mid-run join, SIGTERM drain — against the
# zero-lost/zero-duplicate/byte-identity oracle (also directly:
# python benchmarks/serve_fleet_chaos.py --seed N)
serve-fleet-chaos:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_lease.py tests/test_serve_fleet.py -m 'not slow' $(PYTEST_FLAGS)
	JAX_PLATFORMS=cpu $(PY) benchmarks/serve_fleet_chaos.py --seed 0

# the full randomized soak (also available directly:
# python benchmarks/chaos.py --seed N --trials T)
chaos-soak:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_chaos.py $(PYTEST_FLAGS)
	JAX_PLATFORMS=cpu $(PY) benchmarks/chaos.py --seed 0 --trials 8 --holes 4

# the DP-kernel promotion harness, check mode (scan vs Pallas v1 vs
# rotband v2 bit-identity, interpret mode on CPU).  On the chip, run
# `python chip_smoke.py` (the main path, including the kernels'
# byte-identity); the timed three-arm run needs the chip too.
pallas-ab:
	JAX_PLATFORMS=cpu $(PY) benchmarks/pallas_ab.py --mode check

# the ROADMAP tier-1 suite (same flags as the verify command)
tier1:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -m 'not slow' --continue-on-collection-errors $(PYTEST_FLAGS)

# tier-1 split across N workers pulling per-file leases through the
# r16 lease domain (utils/lease.py + exclusive done markers): same
# suite, 1/N-ish the wall clock, crash-safe work handoff
N ?= 2
tier1-shard:
	$(PY) benchmarks/tier1_shard.py --workers $(N)

native:
	$(MAKE) -C ccsx_tpu/native
