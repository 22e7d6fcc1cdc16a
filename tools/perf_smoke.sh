#!/usr/bin/env bash
# Perf smoke: proves the persistent XLA compilation cache, placed from
# outside with JAX_COMPILATION_CACHE_DIR, works process-over-process, then
# runs the perf-marked pytest suite.
#
# Runs the bert and ernie CPU smoke benches TWICE each in fresh
# processes against a fresh cache directory and asserts the second
# process's compile time drops (the first process pays XLA, the second
# reads the executable from disk).  Exits non-zero on any regression.
# Extra args are passed through to pytest.
set -euo pipefail
cd "$(dirname "$0")/.."

# static-analysis preflight (tools/lint.sh): fail fast on PTA violations
if [ "${PADDLE_SKIP_LINT:-0}" != "1" ]; then
    tools/lint.sh || { echo "$(basename "$0"): lint preflight failed"; exit 1; }
fi

export JAX_PLATFORMS=cpu
# one fixed directory inside the checkout (.gitignore lists .jax_cache/),
# emptied so that each first run below compiles
CACHE_DIR="$PWD/.jax_cache/perf_smoke"
rm -rf "$CACHE_DIR"
OUT_DIR="$(mktemp -d /tmp/paddle_perf_out.XXXXXX)"
trap 'rm -rf "$CACHE_DIR" "$OUT_DIR"' EXIT
export JAX_COMPILATION_CACHE_DIR="$CACHE_DIR"
export FLAGS_JIT_CACHE_MIN_COMPILE_SECS=0     # cache every executable

compile_seconds() {  # run one bench config, print its compile_seconds
    local out="$OUT_DIR/bench_$1_$RANDOM.out"
    python bench.py --config "$1" > "$out"
    python - "$out" <<'EOF'
import json, sys
last = None
for line in open(sys.argv[1]):
    line = line.strip()
    if line.startswith("{") and '"compile_seconds"' in line:
        last = json.loads(line)
if last is None:
    sys.exit("no compile_seconds in bench output")
print(last["compile_seconds"])
EOF
}

fail=0
for cfg in bert ernie; do
    c1=$(compile_seconds "$cfg")
    c2=$(compile_seconds "$cfg")
    echo "[perf_smoke] $cfg compile: first=${c1}s second=${c2}s"
    python - "$cfg" "$c1" "$c2" <<'EOF' || fail=1
import sys
cfg, c1, c2 = sys.argv[1], float(sys.argv[2]), float(sys.argv[3])
# the second process must at least not pay the full compile again; the
# 0.8 factor absorbs trace/dispatch noise on tiny CPU smoke graphs
if not (c2 < c1 and c2 < c1 * 0.8):
    sys.exit(f"{cfg}: persistent compile cache did not help "
             f"({c1:.2f}s -> {c2:.2f}s)")
print(f"{cfg}: cache hit OK ({c1:.2f}s -> {c2:.2f}s)")
EOF
done
[ "$(ls -A "$CACHE_DIR")" ] || { echo "cache dir is empty"; fail=1; }
[ "$fail" -eq 0 ] || { echo "[perf_smoke] FAILED"; exit 1; }

# perf-regression gate over the four bench runs above (min-of-N per
# metric) vs the committed baseline; PADDLE_SKIP_PERF_GATE=1 skips
if [ "${PADDLE_SKIP_PERF_GATE:-0}" != "1" ]; then
    gate_args=()
    for out in "$OUT_DIR"/bench_*.out; do gate_args+=(--run "$out"); done
    python tools/perf_gate.py "${gate_args[@]}" \
        || { echo "[perf_smoke] perf gate FAILED"; exit 1; }
fi

exec python -m pytest tests/ -q -m perf \
    -p no:cacheprovider -p no:randomly "$@"
