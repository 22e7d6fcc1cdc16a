#!/usr/bin/env bash
# Framework-aware static analysis gate (paddle_tpu.analysis, PTA001-006).
#
# Exits nonzero on any NEW finding (not in tools/analysis_baseline.json)
# or any STALE baseline entry (grandfathered code that no longer exists —
# the baseline must shrink with the tree).  Run with --write-baseline to
# refresh the baseline after intentionally grandfathering something; add
# the justification to the new entry before committing.
#
# Usage:
#   tools/lint.sh                # gate the live tree (CI / preflight)
#   tools/lint.sh --format json  # machine-readable report
#   tools/lint.sh --select PTA003,PTA004
#   tools/lint.sh --write-baseline
set -uo pipefail
cd "$(dirname "$0")/.."

BASELINE="tools/analysis_baseline.json"
# the linter is pure-AST but lives inside the package: keep jax quiet/CPU
# in case the package import pulls it in
exec env JAX_PLATFORMS=cpu \
    python -m paddle_tpu.analysis paddle_tpu \
    --root . --baseline "$BASELINE" "$@"
