#!/usr/bin/env bash
# Observability smoke (ISSUE 6 acceptance): run a short bert-style fit
# with the monitor enabled and prove the whole telemetry surface end to
# end —
#   * a live /metrics endpoint reporting nonzero, sane paddle_train_mfu
#     and paddle_train_step_ms histograms scraped MID-FIT,
#   * /debug/trace?steps=3 armed over HTTP against the running job
#     produces jax.profiler trace artifacts,
#   * SIGUSR1 mid-fit arms a second bounded capture that completes,
#   * checkpoint stall timings land in the registry,
#   * the JSONL event log exists and parses,
#   * a traced concurrent-generate burst yields complete span trees on
#     /debug/spans (queue + prefill + decode covering the request wall
#     time) and a perfetto-loadable chrome export,
#   * a chaos-stalled trainer killed by the watchdog (exit 86) leaves a
#     valid flight-recorder dump that the goodput ledger ingests,
#   * monitor overhead on the smoke step time stays within budget
#     (OBS_OVERHEAD_PCT, default 2%) with tracing on at the default
#     sample rate, measured as alternating monitor-off/monitor-on
#     steady-state fits in one process,
# then runs the `monitor` + `trace` pytest suites.  Extra args pass to
# pytest.
set -euo pipefail
cd "$(dirname "$0")/.."

# static-analysis preflight (tools/lint.sh): fail fast on PTA violations
if [ "${PADDLE_SKIP_LINT:-0}" != "1" ]; then
    tools/lint.sh || { echo "$(basename "$0"): lint preflight failed"; exit 1; }
fi

export JAX_PLATFORMS=cpu
WORK="$(mktemp -d /tmp/paddle_obs_smoke.XXXXXX)"
trap 'rm -rf "$WORK"' EXIT
export OBS_WORK="$WORK"
export OBS_OVERHEAD_PCT="${OBS_OVERHEAD_PCT:-2}"

echo "== obs_smoke: live fit + scrape + trace + SIGUSR1 =="
python - <<'EOF'
import json, os, signal, threading, time, urllib.request

work = os.environ["OBS_WORK"]
tdir = os.path.join(work, "telemetry")

import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.framework import flags
from paddle_tpu import monitor

flags.set_flags({"FLAGS_telemetry_dir": tdir, "FLAGS_monitor_port": 0})

# bert-smoke-shaped model (the bench smoke encoder, scaled to seconds)
L, H, A, I, S, B, V = 2, 64, 4, 128, 32, 8, 500
paddle.seed(0)

class Bert(nn.Layer):
    def __init__(self):
        super().__init__()
        self.embed = nn.Embedding(V, H)
        layer = nn.TransformerEncoderLayer(H, A, I, dropout=0.0,
                                           activation="gelu")
        self.encoder = nn.TransformerEncoder(layer, L)
        self.head = nn.Linear(H, V)

    def forward(self, ids):
        return self.head(self.encoder(self.embed(ids)))

rs = np.random.RandomState(0)
N = 320  # 40 steps of batch 8 per epoch (epochs below give the prober
         # enough runway to act on the RUNNING job)
x = rs.randint(0, V, (N, S)).astype("int64")
y = rs.randint(0, V, (N, S)).astype("int64")
ds = paddle.io.TensorDataset([x, y])

net = Bert()
model = paddle.Model(net)
model.prepare(paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=net.parameters()),
              nn.CrossEntropyLoss())

results = {}
def prober():
    # wait for the monitor endpoint, then act on the RUNNING job
    srv = None
    for _ in range(300):
        srv = monitor.get_monitor_server()
        if srv is not None:
            break
        time.sleep(0.05)
    assert srv is not None, "monitor server never came up"
    url = srv.url
    # poll mid-fit until the MFU gauge goes live (the first window can
    # land only after the first-step compile finishes)
    body = ""
    for _ in range(300):
        body = urllib.request.urlopen(url + "/metrics",
                                      timeout=5).read().decode()
        for line in body.splitlines():
            if line.startswith("paddle_train_mfu ") \
                    and float(line.split()[1]) > 0:
                break
        else:
            time.sleep(0.2)
            continue
        break
    results["midfit_metrics"] = body

    def traces_done():
        b = urllib.request.urlopen(url + "/metrics",
                                   timeout=5).read().decode()
        for line in b.splitlines():
            if line.startswith("paddle_train_traces_total "):
                return float(line.split()[1])
        return 0.0

    results["trace"] = json.loads(urllib.request.urlopen(
        url + "/debug/trace?steps=3", timeout=5).read())
    # wait for the HTTP-armed capture to COMPLETE before sending the
    # signal (a SIGUSR1 during an active capture extends it instead of
    # starting a second one)
    for _ in range(300):
        if traces_done() >= 1:
            break
        time.sleep(0.1)
    os.kill(os.getpid(), signal.SIGUSR1)  # headless equivalent

t = threading.Thread(target=prober, daemon=True)
t.start()
model.fit(ds, batch_size=B, epochs=4, log_freq=5, verbose=0,
          resume=os.path.join(work, "ckpt"),
          save_dir=os.path.join(work, "ckpt"), checkpoint_interval=10)
t.join(30)
assert not t.is_alive(), "prober never finished"

body = results["midfit_metrics"]
def metric_value(name, text):
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    raise AssertionError(f"{name} not in /metrics")

mfu = metric_value("paddle_train_mfu", body)
assert 0.0 < mfu <= 1.5, f"paddle_train_mfu insane: {mfu}"
assert "paddle_train_step_ms_bucket" in body, "step-time histogram missing"
assert metric_value("paddle_train_step_ms_count", body) > 0
print(f"  mid-fit scrape ok: mfu={mfu}, "
      f"steps={metric_value('paddle_train_step_ms_count', body):.0f}")

# final state: both captures completed, artifacts on disk
telem, srv = monitor.fit_monitor()
final = urllib.request.urlopen(srv.url + "/metrics", timeout=5).read().decode()
assert metric_value("paddle_train_traces_total", final) >= 2, \
    "HTTP-armed + SIGUSR1 captures did not both complete"
assert metric_value("paddle_ckpt_step_stall_ms_count", final) >= 1, \
    "checkpoint stall timings missing"

def files_under(root):
    return [os.path.join(b, f) for b, _d, fs in os.walk(root) for f in fs]

assert files_under(results["trace"]["trace_dir"]), \
    f"/debug/trace produced no artifacts in {results['trace']['trace_dir']}"
print(f"  trace artifacts: {len(files_under(results['trace']['trace_dir']))} "
      f"file(s) in {results['trace']['trace_dir']}")

events = [json.loads(l) for l in open(os.path.join(tdir, "events.jsonl"))]
kinds = {e["event"] for e in events}
assert {"fit_begin", "window", "trace_begin", "trace_end", "ckpt",
        "fit_end"} <= kinds, f"event log incomplete: {kinds}"
windows = [e for e in events if e["event"] == "window"]
assert all(w["samples_per_sec"] > 0 for w in windows)
print(f"  event log ok: {len(events)} events, {len(windows)} windows")
monitor.reset()
print("LIVE-FIT OK")
EOF

echo "== obs_smoke: traced generate burst + flight recorder + goodput =="
python - <<'EOF'
import json, os, subprocess, sys, threading, urllib.request

work = os.environ["OBS_WORK"]

import paddle_tpu as paddle
from paddle_tpu.framework import flags
from paddle_tpu import monitor
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.monitor import MonitorServer
from paddle_tpu.serving.client import ServingClient
from paddle_tpu.serving.generation import GenerationEngine
from paddle_tpu.serving.server import ServingServer

# -- 1. traced concurrent-generate burst -> /debug/spans ----------------
flags.set_flags({"FLAGS_trace_sample_rate": 1.0})
monitor.reset()
paddle.seed(0)
cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
                max_position_embeddings=64, dropout=0.0, attn_dropout=0.0)
model = GPTForCausalLM(cfg)
model.eval()
eng = GenerationEngine(model, max_slots=2, max_seq_len=32,
                       prompt_buckets="8")
srv = ServingServer(None, gen_engine=eng,
                    install_signal_handlers=False).start()
try:
    client = ServingClient(srv.url)
    outs = []
    def burst(i):
        outs.append(client.generate([1 + i, 2, 3], max_new_tokens=4))
    threads = [threading.Thread(target=burst, args=(i,)) for i in range(4)]
    for t in threads: t.start()
    for t in threads: t.join()
    assert len(outs) == 4 and all(len(o["tokens"]) >= 1 for o in outs)

    with MonitorServer(port=0) as mon:
        doc = json.loads(urllib.request.urlopen(
            mon.url + "/debug/spans", timeout=5).read())
        chrome = json.loads(urllib.request.urlopen(
            mon.url + "/debug/spans?format=chrome", timeout=5).read())
    assert chrome["traceEvents"], "chrome export empty"
    by_trace = {}
    for s in doc["spans"]:
        by_trace.setdefault(s["trace_id"], {})[s["name"]] = s
    complete = 0
    for tree in by_trace.values():
        need = {"server.generate", "gen.queued", "gen.prefill", "gen.decode"}
        if not need <= set(tree):
            continue
        total = sum(tree[n]["dur_ms"] for n in
                    ("gen.queued", "gen.prefill", "gen.decode"))
        wall = tree["server.generate"]["dur_ms"]
        assert 0.5 * wall <= total <= 1.1 * wall, \
            f"queue+prefill+decode={total:.1f}ms vs request {wall:.1f}ms"
        complete += 1
    assert complete >= 1, f"no complete span tree in {len(by_trace)} traces"
    print(f"  span trees ok: {complete}/{len(by_trace)} complete, "
          f"{len(chrome['traceEvents'])} chrome events")
finally:
    srv.shutdown()
    monitor.reset()
    flags.set_flags({"FLAGS_trace_sample_rate": 0.01})

# -- 2. chaos watchdog exit 86 -> flight-recorder dump ------------------
fdir = os.path.join(work, "flightrec")
script = f"""
import time
from paddle_tpu.monitor import flightrec
from paddle_tpu.utils.metrics import default_registry
from paddle_tpu.distributed.resilience import ResilientRunner
flightrec.configure({fdir!r}); flightrec.install_hooks()
h_step = default_registry().histogram(
    "paddle_train_step_ms", "per-step wall time",
    [1, 2, 5, 10, 20, 50, 100, 250, 500, 1000, 5000, 30000])
def step(i, s):
    t0 = time.perf_counter()
    flightrec.record("step", step=i)
    time.sleep(0.02)
    h_step.observe((time.perf_counter() - t0) * 1e3)
    return s, 0.1
ResilientRunner(watchdog_timeout=0.5).run(step, {{}}, num_steps=10)
"""
env = dict(os.environ)
env.update({"JAX_PLATFORMS": "cpu", "PADDLE_CHAOS_SLOW_STEP": "3",
            "PADDLE_CHAOS_SLOW_SECONDS": "30"})
r = subprocess.run([sys.executable, "-c", script], env=env,
                   capture_output=True, text=True, timeout=120)
assert r.returncode == 86, f"expected exit 86, got {r.returncode}:\n{r.stderr[-2000:]}"
dumps = [f for f in os.listdir(fdir) if f.startswith("flightrec-")]
assert len(dumps) == 1, dumps
doc = json.load(open(os.path.join(fdir, dumps[0])))
assert doc["reason"] == "watchdog" and doc["records"], doc.get("reason")
print(f"  watchdog dump ok: {dumps[0]} reason={doc['reason']} "
      f"records={len(doc['records'])}")

# -- 3. the goodput ledger ingests the dump -----------------------------
from paddle_tpu.distributed.goodput import GoodputLedger
led = GoodputLedger(fdir)
totals = led.publish()
assert sum(totals.values()) > 0, totals
assert 0.0 <= led.ratio() <= 1.0
print(f"  goodput ledger ok: ratio={led.ratio():.3f} "
      f"seconds={ {k: round(v, 2) for k, v in totals.items()} }")
print("TRACING+FLIGHTREC OK")
EOF

echo "== obs_smoke: monitor overhead budget (<= ${OBS_OVERHEAD_PCT}%) =="
python - <<'EOF'
import os, time
work = os.environ["OBS_WORK"]
budget = float(os.environ["OBS_OVERHEAD_PCT"])

import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.framework import flags
from paddle_tpu import monitor

L, H, A, I, S, B, V = 2, 64, 4, 128, 32, 8, 500
paddle.seed(0)

class Bert(nn.Layer):
    def __init__(self):
        super().__init__()
        self.embed = nn.Embedding(V, H)
        layer = nn.TransformerEncoderLayer(H, A, I, dropout=0.0,
                                           activation="gelu")
        self.encoder = nn.TransformerEncoder(layer, L)
        self.head = nn.Linear(H, V)

    def forward(self, ids):
        return self.head(self.encoder(self.embed(ids)))

rs = np.random.RandomState(0)
N = 1280  # 160 steps: per-fit fixed costs (telemetry singleton, JSONL
          # open, engine begin) amortize out of the per-STEP number the
          # acceptance pins
x = rs.randint(0, V, (N, S)).astype("int64")
y = rs.randint(0, V, (N, S)).astype("int64")
ds = paddle.io.TensorDataset([x, y])
net = Bert()
model = paddle.Model(net)
model.prepare(paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=net.parameters()),
              nn.CrossEntropyLoss())

OFF = {"FLAGS_telemetry_dir": "", "FLAGS_monitor_port": -1,
       "FLAGS_trace_sample_rate": 0.0}
ON = {"FLAGS_telemetry_dir": os.path.join(work, "telem_overhead"),
      "FLAGS_monitor_port": -1,  # JSONL+metrics on; HTTP not the hot path
      "FLAGS_trace_sample_rate": 0.01}  # tracing at its DEFAULT rate —
#     the overhead pin covers the span tracer + flight recorder too

def timed_fit():
    t0 = time.perf_counter()
    model.fit(ds, batch_size=B, epochs=1, shuffle=False, verbose=0)
    return time.perf_counter() - t0

flags.set_flags(OFF)
timed_fit()  # compile + warmup, excluded
# telemetry warmup too (creates the singleton + one ensure_flops compile)
flags.set_flags(ON); timed_fit()
off, on = [], []
for _ in range(5):  # alternate to cancel machine drift; 5 rounds so a
    # single quiet-machine outlier on ONE side can't fake an overhead
    # (min-of-3 lost to a lone fast OFF fit on a noisy box)
    flags.set_flags(OFF); off.append(timed_fit())
    flags.set_flags(ON);  on.append(timed_fit())
flags.set_flags(OFF)
monitor.reset()
overhead = (min(on) - min(off)) / min(off) * 100.0
print(f"  steady-state fit: off={min(off)*1e3:.1f}ms "
      f"on={min(on)*1e3:.1f}ms overhead={overhead:+.2f}%")
assert overhead <= budget, \
    f"monitor overhead {overhead:.2f}% exceeds {budget}% budget"
print("OVERHEAD OK")
EOF

echo "== obs_smoke: monitor + trace + perf pytest suites =="
python -m pytest tests/test_monitor.py tests/test_profiler.py \
    tests/test_tracing.py tests/test_perf.py -q -m "not slow" \
    -p no:cacheprovider "$@"

echo "obs_smoke: ALL OK"
