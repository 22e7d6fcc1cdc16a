#!/usr/bin/env bash
# Pod smoke: proves the elastic pod runtime shrinks-and-continues through
# a REAL rank loss (distributed/elastic.py + podcoord.py).
#
# Launches a 2-rank local pod under the shrink-and-continue supervisor,
# SIGKILLs rank 1 mid-fit via chaos (PADDLE_CHAOS_RANK_KILL), and asserts
#   * the survivor detects the death, rolls back to its in-memory
#     snapshot, re-strides the batch, replays, and FINISHES (rc 0),
#   * the death is classified rank_lost_shrunk (not crash) in
#     paddle_launch_trainer_failures_total,
#   * the goodput ledger's badput{down} for the in-memory continue beats
#     a restart-from-checkpoint equivalent measured in this same script
#     (the restart path's FLOOR: fresh interpreter + framework import,
#     before any restore/fast-forward even starts), and
#   * the SIGKILLed rank still left attributable JSONL telemetry.
# Then runs the pod-marked pytest suite (units + every multi-process
# drill).  Extra args pass through to pytest.
set -euo pipefail
cd "$(dirname "$0")/.."

# static-analysis preflight (tools/lint.sh): fail fast on PTA violations
if [ "${PADDLE_SKIP_LINT:-0}" != "1" ]; then
    tools/lint.sh || { echo "$(basename "$0"): lint preflight failed"; exit 1; }
fi

export JAX_PLATFORMS=cpu

python - <<'EOF'
import json
import os
import subprocess
import sys
import tempfile
import time

from paddle_tpu.distributed.podcoord import DEAD_EXIT
from paddle_tpu.distributed.podtest import run_elastic_pod
from paddle_tpu.utils.metrics import default_registry

SRC = """
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.distributed.elastic import PodRuntime
from paddle_tpu.io import TensorDataset

paddle.seed(0)
net = paddle.nn.Linear(16, 8)
model = paddle.Model(net)
model.prepare(paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters()),
              paddle.nn.MSELoss())
rs = np.random.RandomState(0)
x = rs.randn(96, 16).astype("float32")
y = rs.randn(96, 8).astype("float32")
pod = PodRuntime.from_env()
model.fit(TensorDataset([x, y]), batch_size=8, epochs=1, shuffle=False,
          verbose=0, pod=pod, log_freq=1)
emit(shrinks=pod.shrink_events, live=pod.live)
pod.close()
"""

with tempfile.TemporaryDirectory(prefix="pod-smoke-") as td:
    res, pr = run_elastic_pod(
        SRC, world=2, env={"PADDLE_CHAOS_RANK_KILL": "1@3"},
        telemetry_dir=td, timeout=300)

    # rank 1 really died by SIGKILL; the survivor finished from memory
    assert res.returncodes == [0, -9], res.returncodes
    assert res.survivors_ok, (res.returncodes, res.deaths)
    assert res.deaths[1][0] == DEAD_EXIT, res.deaths
    shrinks = pr.record(0, "shrinks")
    assert shrinks and shrinks[-1]["live"] == [0], shrinks
    print(f"[pod_smoke] rank 1 SIGKILLed mid-fit; rank 0 shrank "
          f"{shrinks[-1]['old']} -> {shrinks[-1]['live']} and finished "
          f"(recovery {shrinks[-1]['recovery_s']:.3f}s)")

    # the death was accounted as rank_lost_shrunk, not a pod crash
    c = default_registry().get("paddle_launch_trainer_failures_total")
    assert c is not None and c.get("rank_lost_shrunk") >= 1, (
        c and c.collect())

    # the SIGKILLed rank still left JSONL telemetry for attribution
    ev1 = os.path.join(td, "rank1", "events.jsonl")
    assert os.path.exists(ev1), os.listdir(td)

    # goodput: in-memory continue's badput{down} vs the restart path's
    # FLOOR (fresh interpreter + framework import, measured here; a real
    # restart also pays checkpoint restore + step fast-forward on top)
    assert res.report is not None
    down_s = res.report["seconds"].get("down", 0.0)
    assert down_s > 0, res.report
    t0 = time.perf_counter()
    subprocess.run([sys.executable, "-c", "import jax, paddle_tpu"],
                   timeout=300, check=True,
                   capture_output=True)
    restart_floor_s = time.perf_counter() - t0
    assert down_s < restart_floor_s, (down_s, restart_floor_s)
    print(f"[pod_smoke] badput down={down_s:.3f}s beats the "
          f"restart-equivalent floor {restart_floor_s:.2f}s "
          f"(goodput_ratio={res.report['goodput_ratio']})")
    print("[pod_smoke] " + json.dumps(
        {"elastic_shrink_recovery_s": res.recovery_s(),
         "badput_down_s": round(down_s, 4),
         "restart_equivalent_s": round(restart_floor_s, 2),
         "goodput_ratio": res.report["goodput_ratio"]}))
EOF
echo "[pod_smoke] elastic shrink-and-continue drill OK"

exec python -m pytest tests/ -q -m pod \
    -p no:cacheprovider -p no:randomly "$@"
