#!/usr/bin/env bash
# Kernels smoke: proves the Pallas hot path (masked flash attention,
# paged decode attention, softmax-xent) in CPU interpret mode end to
# end, and the fused bias-gelu beside it (no kernel since PR 42: a jnp
# composite that the products' fusions hold, checked against
# jax.nn.gelu like the kernels):
#
#   1. bench.py --config kernels — per-kernel fwd/bwd parity vs XLA
#      (references cast to the kernel compute dtype, per-kernel
#      tolerances) plus a flag-on/off masked training step through the
#      ops/fused dispatch with per-op attribution.
#   2. bench.py --config genserve — the continuous-batching engine,
#      whose decode_tokens_per_sec now sits in the perf baseline.
#   3. tools/perf_gate.py over both runs (PADDLE_SKIP_PERF_GATE=1 skips).
#   4. the kernels-marked pytest suite (parity, sharding, remat,
#      dispatch, fallback-counter pins).  Extra args pass to pytest.
#
# On a TPU host the same bench config validates against Mosaic instead
# of interpret mode; this smoke is the CPU tier.
set -euo pipefail
cd "$(dirname "$0")/.."

# static-analysis preflight (tools/lint.sh): fail fast on PTA violations
if [ "${PADDLE_SKIP_LINT:-0}" != "1" ]; then
    tools/lint.sh || { echo "$(basename "$0"): lint preflight failed"; exit 1; }
fi

export JAX_PLATFORMS=cpu
OUT_DIR="$(mktemp -d /tmp/paddle_kernels_out.XXXXXX)"
trap 'rm -rf "$OUT_DIR"' EXIT

for cfg in kernels genserve; do
    out="$OUT_DIR/bench_$cfg.out"
    echo "[kernels_smoke] bench --config $cfg"
    python bench.py --config "$cfg" > "$out" \
        || { echo "[kernels_smoke] bench $cfg FAILED"; exit 1; }
    tail -n 1 "$out"
done

# the kernels config reports value=1.0 only when every kernel is inside
# its tolerance AND the flag-on step recorded zero Pallas fallbacks
python - "$OUT_DIR/bench_kernels.out" <<'EOF'
import json, sys
last = None
for line in open(sys.argv[1]):
    line = line.strip()
    if line.startswith("{") and '"metric"' in line:
        last = json.loads(line)
if last is None:
    sys.exit("no result line in kernels bench output")
if last["value"] != 1.0:
    sys.exit(f"kernel parity failed: {json.dumps(last['kernel_max_errs'])} "
             f"fallbacks={last['pallas_fallbacks_during_flag_on']}")
print("[kernels_smoke] parity OK:", json.dumps(last["kernel_max_errs"]))
EOF

if [ "${PADDLE_SKIP_PERF_GATE:-0}" != "1" ]; then
    python tools/perf_gate.py --subset \
        --run "$OUT_DIR/bench_kernels.out" \
        --run "$OUT_DIR/bench_genserve.out" \
        || { echo "[kernels_smoke] perf gate FAILED"; exit 1; }
fi

exec python -m pytest tests/ -q -m kernels \
    -p no:cacheprovider -p no:randomly "$@"
