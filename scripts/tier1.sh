#!/usr/bin/env bash
# Tier-1 verification — the ROADMAP command, verbatim.
# Run from the repo root:  ./scripts/tier1.sh
# The full (slow-included) sweep:  ./scripts/tier1.sh -m slow
# With the serving-allocator smoke:  ./scripts/tier1.sh --bench-smoke
#   (runs bench_serving.py at toy sizes — 2 slots, tiny pool, long-tail
#   trace at 50% of the eager reservation, the chunked-vs-monolithic
#   prefill A/B, the flat-step section (flat/chunked/monolithic outputs
#   must be token-identical — a flat-vs-chunked mismatch fails the run),
#   the speculative-decoding section, and the prefix-cache
#   section (shared-system-prompt trace: cache-on must be token-identical
#   to cache-off at <= 0.5x the prefill tokens, and a tight-pool
#   preempt-resume must recompute only the uncached suffix) —
#   lazy-allocation/preemption regressions and any chunked-vs-monolithic,
#   spec-vs-baseline, or cache-on-vs-cache-off output mismatch (greedy or
#   sampled) fail the run without the full bench; afterwards
#   scripts/bench_check.py gates the fresh BENCH_serving.json entry
#   against its history medians — >15% regression of a key ratio fails)
# With the layout-contract analyzer:  ./scripts/tier1.sh --analyze
#   (runs all four analysis passes — shape-ladder linter, KV-write
#   aliasing pass, recompile-hazard detector, AST invariant lint — plus
#   a sanitized drain over every engine configuration via
#   scripts/analyze.py; any finding fails the run)
# With the seeded fault-plan smoke:  ./scripts/tier1.sh --chaos
#   (runs scripts/chaos_smoke.py — drains a deterministic request mix
#   clean and under seeded FaultPlans (OutOfPages spike, drafter failure
#   burst, NaN-logit injection, page-copier failure) per engine config;
#   any surviving request diverging from the clean run, an unbalanced
#   allocator, or a post-warmup XLA trace fails the run)
set -euo pipefail
cd "$(dirname "$0")/.."

BENCH_SMOKE=0
ANALYZE=0
CHAOS=0
ARGS=()
for a in "$@"; do
  if [[ "$a" == "--bench-smoke" ]]; then BENCH_SMOKE=1;
  elif [[ "$a" == "--analyze" ]]; then ANALYZE=1;
  elif [[ "$a" == "--chaos" ]]; then CHAOS=1;
  else ARGS+=("$a"); fi
done

PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python -m pytest -x -q --durations=15 ${ARGS[@]+"${ARGS[@]}"}

if [[ "$BENCH_SMOKE" == 1 ]]; then
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python benchmarks/bench_serving.py --smoke --skip-throughput
  python scripts/bench_check.py
fi

if [[ "$ANALYZE" == 1 ]]; then
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python scripts/analyze.py
fi

if [[ "$CHAOS" == 1 ]]; then
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python scripts/chaos_smoke.py
fi
