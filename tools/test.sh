#!/bin/bash
# CPU-only test runner: the suite never needs a chip, and a chip belongs to
# one process at a time, so keep pytest off it.
cd "$(dirname "$0")/.." || exit 1
# static-analysis preflight: a PTA violation fails the run before pytest
# starts (skip with PADDLE_SKIP_LINT=1 when iterating on a known-dirty tree)
if [ "${PADDLE_SKIP_LINT:-0}" != "1" ]; then
    tools/lint.sh > /tmp/paddle_lint.$$ 2>&1 || {
        cat /tmp/paddle_lint.$$; rm -f /tmp/paddle_lint.$$
        echo "tools/test.sh: static analysis failed (tools/lint.sh)"; exit 1
    }
    rm -f /tmp/paddle_lint.$$
fi
if [ $# -eq 0 ]; then set -- tests/ -q; fi
exec env JAX_PLATFORMS=cpu python -m pytest "$@"
