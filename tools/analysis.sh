#!/usr/bin/env bash
# CI wiring for the static analysis suite (docs/STATIC_ANALYSIS.md):
# trace-safety lint, serving concurrency lint, jaxpr invariant audits,
# the XLA cost/memory + collective wire-bytes audits, the
# BENCH-trajectory regression gate, and the SPMD scaling-contract
# auditor (Pass 7, scale_budget.json) over the FULL D in {1,2,4,8}
# mesh ladder — every pass registered in analysis/passes.py. (Tier-1
# tests only run the tiny D in {1,2} subset; this script is where the
# 4/8 rungs get exercised.) Strict mode: any unsuppressed finding or
# failed contract/budget/trajectory pin exits nonzero.
#
# Budget maintenance (run + review + commit the diff):
#   tools/analysis.sh --update-budget     # jaxpr_budget.json
#   tools/analysis.sh --refresh-budgets   # cost_budget.json + scale_budget.json
#                                         #   + scale_budget.json (+ diffs)
#
# The python entry point forces jax onto a cpu 8-device mesh itself, so
# this is safe on hosts whose ambient JAX_PLATFORMS points at real
# accelerators.
set -euo pipefail
cd "$(dirname "$0")/.."
if [[ "$*" == *--update-budget* || "$*" == *--refresh-budgets* ]]; then
  exec python -m lightgbm_tpu.analysis "$@"
fi
exec python -m lightgbm_tpu.analysis --strict "$@"
