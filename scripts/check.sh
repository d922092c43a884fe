#!/usr/bin/env bash
# Repo check: tier-1 tests + seeded property pass + smoke benchmarks.
#
#   scripts/check.sh            # full tier-1 pytest + property pass + smoke
#   scripts/check.sh --fast     # skip the slow SPMD subprocess tests
#
# The tier-1 run fails on any regression below the pinned passed-count
# baseline (so silently lost/skipped tests fail CI, not just failures).
# The property pass re-runs the property-based coding tests at 3x
# example depth — a deeper deterministic search than tier-1's defaults
# (hypothesis is derandomized by tests/conftest.py; the fallback stub
# is deterministic by construction).  The smoke benchmarks re-validate
# the paper's Fig. 3 / 4(a) / 4(b) claims and the sim_cluster
# MC-vs-eq.(5) cross-check on reduced settings.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# tests run on the CPU; tests/test_tpu_compile.py compiles for a
# described TPU v5e through libtpu's compiler without needing a chip
export JAX_PLATFORMS=cpu

echo "== repo hygiene (repro.lint RH001-RH005) =="
# tracked .pyc, stray bench/smoke JSON outside BENCH_*.json, the
# BENCH_async.json headline floor, the BENCH_ckpt.json coded-
# checkpoint storage-overhead floor, and the BENCH_autotune.json
# tuned-vs-default floor — formerly inline bash/grep here, now rules
# in src/repro/lint/hygiene.py (stdlib-only, no jax import).
python -m repro.lint --hygiene

echo
echo "== contract lint (repro.lint RL001-RL007) =="
# retrace / PRNG / side-effect / collective-axis / tiling / deprecation
# / env-coercion contracts, AST-checked against lint-baseline.json
# (docs/LINT.md).
python -m repro.lint src tests benchmarks

# tier-1 passed-count baseline.  Bump this when tests are added — it is
# what catches silently lost/uncollected files, not just failures.  The
# 54 v5e compile tests (tests/test_tpu_compile.py) skip without libtpu,
# which requirements.txt brings through jax[tpu].
BASELINE=537
# tests carrying @pytest.mark.spmd (registered in pytest.ini): the
# multi-device subprocess tests the fast lane deselects.
SPMD_COUNT=9

PYTEST_ARGS=(-x -q --durations=10)
if [[ "${1:-}" == "--fast" ]]; then
  PYTEST_ARGS+=(-m "not spmd")
  BASELINE=$((BASELINE - SPMD_COUNT))
fi

echo "== tier-1 pytest =="
pytest_log="$(mktemp)"
trap 'rm -f "$pytest_log"' EXIT
python -m pytest "${PYTEST_ARGS[@]}" | tee "$pytest_log"
passed="$(grep -oE '[0-9]+ passed' "$pytest_log" | tail -1 | grep -oE '[0-9]+' || echo 0)"
if (( passed < BASELINE )); then
  echo "check.sh: REGRESSION — $passed passed < baseline $BASELINE" >&2
  exit 1
fi
echo "check.sh: $passed passed (baseline $BASELINE)"

echo
echo "== seeded property pass (3x examples) =="
# deeper deterministic search than the tier-1 defaults: the property
# tests scale their example counts by REPRO_PROPERTY_EXAMPLES.  The
# wave selection is the sim-layer differential pair (staleness-0 event
# identity + trace invariants) — the jit-compiled trainer tests above
# them don't gain from extra examples and would triple the wall time.
REPRO_PROPERTY_EXAMPLES=3 python -m pytest -q \
  tests/test_property_coding.py \
  tests/test_arrivals.py \
  "tests/test_wave_loop.py::test_wave_staleness0_event_identical_to_barrier" \
  "tests/test_wave_loop.py::test_wave_trace_invariants"

echo
echo "== smoke benchmarks =="
# includes the coded_step bench-regression guard: the flat fused combine
# must never fall behind the tree baseline by >1.15x at the smoke shape
# (assertion inside benchmarks/coded_step.py) — the serve_load
# tail-latency guard: the coded decode tier must beat the uncoded R=1
# baseline on p99 step latency by >=1.5x and agree with the Env
# order-statistics closed form (assertions inside
# benchmarks/serve_load.py) — and the wave_step async guard: the
# wave-pipelined loop at staleness 1 must beat the barrier by >=1.15x
# at the smoke horizon, with k=0 pricing exactly at the barrier
# (assertions inside benchmarks/wave_step.py) — and the ckpt_recovery
# robustness guard: every <=s loss pattern restores bit-exactly, the
# e2e worker-death recovery completes, and the coded storage overhead
# stays under 1.5*(s/N + 1) (assertions inside
# benchmarks/ckpt_recovery.py) — and the autotune correctness guard:
# the tuner's pick must equal an independent brute-force argmin on the
# exhaustive N=4 space, admit nothing over the memory budget, and beat
# the hand-picked default (assertions inside benchmarks/autotune.py).
# bench_smoke.json is the machine-readable row dump (uploaded as a CI
# artifact).
python -m benchmarks.run --smoke --json bench_smoke.json

echo
echo "check.sh: ALL OK"
