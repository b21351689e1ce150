#!/usr/bin/env bash
# Tier-1 verification — the ROADMAP command, verbatim.
# Run from the repo root:  ./scripts/tier1.sh
# The full (slow-included) sweep:  ./scripts/tier1.sh -m slow
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

ANALYZE=0
CHAOS=0
ARGS=()
for a in "$@"; do
  if [[ "$a" == "--analyze" ]]; then ANALYZE=1;
  elif [[ "$a" == "--chaos" ]]; then CHAOS=1;
  else ARGS+=("$a"); fi
done

PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python -m pytest -x -q --durations=15 ${ARGS[@]+"${ARGS[@]}"}

if [[ "$ANALYZE" == 1 ]]; then
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python scripts/analyze.py
fi

if [[ "$CHAOS" == 1 ]]; then
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python scripts/chaos_smoke.py
fi
