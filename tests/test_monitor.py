"""Runtime telemetry suite (`monitor` marker — tools/obs_smoke.sh):

  * utils/metrics.py registry: counter/gauge/histogram/reservoir +
    golden exposition text;
  * serving /metrics BYTE-IDENTICAL regression pin across the registry
    migration;
  * MFU math against a hand-computed flops case;
  * JSONL event-log schema + rotation;
  * MonitorServer /metrics, /healthz, federation;
  * /debug/trace?steps=N and SIGUSR1 arm → bounded jax.profiler capture
    on a RUNNING fit (non-empty trace dir, job keeps training);
  * checkpoint durability counters landing in the shared registry.
"""
import json
import os
import signal
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.hapi.model import Model
from paddle_tpu.io import Dataset
from paddle_tpu.utils.metrics import (MetricsRegistry, Reservoir,
                                      default_registry)

pytestmark = pytest.mark.monitor


# -- helpers ----------------------------------------------------------------
class _DS(Dataset):
    def __init__(self, n=48, d=8):
        self.n, self.d = n, d

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        rs = np.random.RandomState(i)
        return (rs.randn(self.d).astype("float32"),
                rs.randn(1).astype("float32"))


def _model():
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
    m = Model(net)
    m.prepare(paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=net.parameters()),
              nn.MSELoss())
    return m


@pytest.fixture
def monitored(tmp_path):
    """A fresh monitor singleton bound to a tmp telemetry dir + an
    ephemeral port; restores the flags and tears the singleton down."""
    from paddle_tpu import monitor
    from paddle_tpu.framework import flags

    prev = flags.get_flags(["FLAGS_telemetry_dir", "FLAGS_monitor_port"])
    monitor.reset()
    flags.set_flags({"FLAGS_telemetry_dir": str(tmp_path / "telemetry"),
                     "FLAGS_monitor_port": 0})
    try:
        yield tmp_path / "telemetry"
    finally:
        monitor.reset()
        flags.set_flags(prev)


def _scrape(url):
    return urllib.request.urlopen(url, timeout=5).read().decode()


# -- registry ---------------------------------------------------------------
class TestRegistry:
    def test_counter_gauge_histogram_render_golden(self):
        reg = MetricsRegistry()
        c = reg.counter("t_total", "outcomes", label="kind",
                        preset=("a", "b"))
        g = reg.gauge("t_gauge", "a gauge")
        h = reg.histogram("t_ms", "a histogram", [1, 10])
        c.inc("a", 2)
        g.set(2.5)
        for v in (0.5, 5.0, 50.0):
            h.observe(v)
        assert reg.prometheus_text() == (
            "# HELP t_total outcomes\n"
            "# TYPE t_total counter\n"
            't_total{kind="a"} 2\n'
            't_total{kind="b"} 0\n'
            "# HELP t_gauge a gauge\n"
            "# TYPE t_gauge gauge\n"
            "t_gauge 2.5\n"
            "# HELP t_ms a histogram\n"
            "# TYPE t_ms histogram\n"
            't_ms_bucket{le="1"} 1\n'
            't_ms_bucket{le="10"} 2\n'
            't_ms_bucket{le="+Inf"} 3\n'
            "t_ms_sum 55.5\n"
            "t_ms_count 3\n")

    def test_get_or_create_returns_same_metric(self):
        reg = MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")
        assert reg.gauge("y") is reg.gauge("y")
        assert reg.histogram("z", buckets=[1]) is reg.histogram("z")

    def test_unlabeled_counter_and_computed_gauge(self):
        reg = MetricsRegistry()
        c = reg.counter("n_total", "n")
        c.inc()
        c.inc(4)
        assert c.get() == 5
        reg.gauge("computed", "fn-backed", fn=lambda: 7)
        assert "computed 7" in reg.prometheus_text()

    def test_fixed_counter_hides_extra_series_but_tracks_them(self):
        reg = MetricsRegistry()
        c = reg.counter("f_total", "f", label="r", preset=("a",),
                        fixed=True)
        c.inc("a")
        c.inc("surprise")
        text = reg.prometheus_text()
        assert 'f_total{r="a"} 1' in text
        assert "surprise" not in text
        assert c.get("surprise") == 1

    def test_reservoir_quantiles_are_exact_order_stats(self):
        r = Reservoir(size=100)
        for v in range(1, 101):
            r.observe(float(v))
        assert r.quantile(0.0) == 1.0
        assert r.quantile(0.50) == pytest.approx(50.0, abs=1.0)
        assert r.quantile(1.0) == 100.0
        # bounded window: old observations age out
        for v in range(1000, 1100):
            r.observe(float(v))
        assert r.quantile(0.0) >= 1000.0

    def test_snapshot(self):
        reg = MetricsRegistry()
        reg.counter("a_total", label="k", preset=("x",)).inc("x", 3)
        reg.gauge("b").set(1.5)
        reg.histogram("c", buckets=[1]).observe(2.0)
        snap = reg.snapshot()
        assert snap["a_total"] == {"x": 3}
        assert snap["b"] == 1.5
        assert snap["c"]["count"] == 1 and snap["c"]["mean"] == 2.0


# -- serving byte-identical regression pin ----------------------------------
SERVING_GOLDEN_HEAD = """\
# HELP paddle_serving_qps completed requests per second over the trailing window
# TYPE paddle_serving_qps gauge
paddle_serving_qps 0
# HELP paddle_serving_p50_ms request latency p50 in milliseconds
# TYPE paddle_serving_p50_ms gauge
paddle_serving_p50_ms 0
# HELP paddle_serving_p99_ms request latency p99 in milliseconds
# TYPE paddle_serving_p99_ms gauge
paddle_serving_p99_ms 0
# HELP paddle_serving_padding_waste_ratio padded input elements / dispatched input elements (batch-slot AND sequence padding)
# TYPE paddle_serving_padding_waste_ratio gauge
paddle_serving_padding_waste_ratio 0.25
# HELP paddle_serving_compile_count predictor shape-bucket compilations since start
# TYPE paddle_serving_compile_count gauge
paddle_serving_compile_count 5
# HELP paddle_serving_requests_total request outcomes by result
# TYPE paddle_serving_requests_total counter
paddle_serving_requests_total{result="accepted"} 3
paddle_serving_requests_total{result="responses"} 0
paddle_serving_requests_total{result="rejected_queue_full"} 1
paddle_serving_requests_total{result="rejected_draining"} 0
paddle_serving_requests_total{result="deadline_expired"} 0
paddle_serving_requests_total{result="cancelled"} 0
paddle_serving_requests_total{result="errors"} 0
# HELP paddle_serving_batch_size requests coalesced per dispatched batch
# TYPE paddle_serving_batch_size histogram
paddle_serving_batch_size_bucket{le="1"} 0
paddle_serving_batch_size_bucket{le="2"} 1
paddle_serving_batch_size_bucket{le="4"} 2
paddle_serving_batch_size_bucket{le="8"} 2
paddle_serving_batch_size_bucket{le="16"} 2
paddle_serving_batch_size_bucket{le="32"} 2
paddle_serving_batch_size_bucket{le="64"} 2
paddle_serving_batch_size_bucket{le="128"} 2
paddle_serving_batch_size_bucket{le="+Inf"} 2
paddle_serving_batch_size_sum 5
paddle_serving_batch_size_count 2
# HELP paddle_serving_queue_latency_ms milliseconds a request waited in the batch queue
# TYPE paddle_serving_queue_latency_ms histogram
paddle_serving_queue_latency_ms_bucket{le="0.5"} 0
paddle_serving_queue_latency_ms_bucket{le="1"} 0
paddle_serving_queue_latency_ms_bucket{le="2"} 1
paddle_serving_queue_latency_ms_bucket{le="5"} 1
paddle_serving_queue_latency_ms_bucket{le="10"} 1
paddle_serving_queue_latency_ms_bucket{le="20"} 1
paddle_serving_queue_latency_ms_bucket{le="50"} 1
paddle_serving_queue_latency_ms_bucket{le="100"} 1
paddle_serving_queue_latency_ms_bucket{le="250"} 1
paddle_serving_queue_latency_ms_bucket{le="500"} 1
paddle_serving_queue_latency_ms_bucket{le="1000"} 1
paddle_serving_queue_latency_ms_bucket{le="5000"} 1
paddle_serving_queue_latency_ms_bucket{le="+Inf"} 1
paddle_serving_queue_latency_ms_sum 1.2
paddle_serving_queue_latency_ms_count 1
# HELP paddle_serving_request_latency_ms end-to-end request latency in milliseconds
# TYPE paddle_serving_request_latency_ms histogram
paddle_serving_request_latency_ms_bucket{le="1"} 0
paddle_serving_request_latency_ms_bucket{le="2"} 0
paddle_serving_request_latency_ms_bucket{le="5"} 0
paddle_serving_request_latency_ms_bucket{le="10"} 0
paddle_serving_request_latency_ms_bucket{le="20"} 0
paddle_serving_request_latency_ms_bucket{le="50"} 0
paddle_serving_request_latency_ms_bucket{le="100"} 0
paddle_serving_request_latency_ms_bucket{le="250"} 0
paddle_serving_request_latency_ms_bucket{le="500"} 0
paddle_serving_request_latency_ms_bucket{le="1000"} 0
paddle_serving_request_latency_ms_bucket{le="5000"} 0
paddle_serving_request_latency_ms_bucket{le="+Inf"} 0
paddle_serving_request_latency_ms_sum 0
paddle_serving_request_latency_ms_count 0
"""


class TestServingExpositionPin:
    def test_byte_identical_after_registry_migration(self):
        """The golden text was captured from the PRE-migration
        serving/metrics.py on this deterministic scenario; the
        registry-backed implementation must reproduce it byte for
        byte."""
        from paddle_tpu.serving.metrics import ServingMetrics

        m = ServingMetrics()
        m.count("accepted", 3)
        m.count("rejected_queue_full")
        m.observe_batch(3, 4)
        m.observe_batch(2, 4, real_elems=6, total_elems=8)
        m.observe_queue_wait(0.0012)
        m.set_compile_count(5)
        assert m.prometheus_text() == SERVING_GOLDEN_HEAD

    def test_counters_attribute_still_dictlike(self):
        from paddle_tpu.serving.metrics import ServingMetrics

        m = ServingMetrics()
        m.count("errors")
        assert m.counters["errors"] == 1
        assert m.counters["accepted"] == 0
        assert m.snapshot()["errors"] == 1


# -- MFU + memory meters ----------------------------------------------------
class TestMfuAndMeters:
    def test_mfu_hand_computed(self, tmp_path):
        """4 steps of a 2 GFLOP step in 2.0 s on a 1 TFLOP/s device:
        MFU = 2e9 * 4 / 2.0 / 1e12 = 0.004 exactly."""
        from paddle_tpu.monitor import TrainTelemetry

        t = TrainTelemetry(telemetry_dir=str(tmp_path))
        t.set_flops_per_step(2e9, peak=1e12)
        rec = t.window(step=4, epoch=0, steps=4, wall_s=2.0, batch_size=8,
                       loss=1.0, lr=0.1)
        assert rec["mfu"] == pytest.approx(0.004)
        assert t.g_mfu.get() == pytest.approx(0.004)
        assert rec["samples_per_sec"] == pytest.approx(16.0)
        t.close()

    def test_mfu_zero_without_flops(self, tmp_path):
        from paddle_tpu.monitor import TrainTelemetry

        t = TrainTelemetry(telemetry_dir=str(tmp_path))
        rec = t.window(step=1, epoch=0, steps=1, wall_s=0.1, batch_size=8)
        assert rec["mfu"] == 0.0
        t.close()

    def test_first_step_interval_lands_in_gauge_not_histogram(self, tmp_path):
        """With mark_start() anchored before the first dispatch, the
        FIRST measured interval (the compile-bearing one) goes to
        paddle_train_first_step_ms and later steps to the histogram
        (review fix: the compile interval was discarded and step 2
        mislabeled as the first)."""
        import time as _time

        from paddle_tpu.monitor import TrainTelemetry

        t = TrainTelemetry(telemetry_dir=str(tmp_path))
        t.on_fit_begin()
        before = t.h_step.total
        t.mark_start()
        _time.sleep(0.05)  # the "compile"
        t.step_mark()
        for _ in range(3):
            t.step_mark()
        assert t.g_first_step_ms.get() >= 45.0, \
            "compile interval missing from first-step gauge"
        assert t.h_step.total - before == 3, \
            "steady-state steps miscounted in the histogram"
        t.close()

    def test_warning_hook_counts_every_repeat(self, tmp_path):
        """Python's default filter dedups same-location warnings before
        showwarning — the donation counter must still count every
        occurrence (review fix), while the console sees it once."""
        import warnings

        from paddle_tpu.monitor import TrainTelemetry

        t = TrainTelemetry(telemetry_dir=str(tmp_path))
        before = t.c_donation_fallback.get()
        restore = t.install_warning_hook()
        try:
            for _ in range(5):
                warnings.warn("Some donated buffers were not usable",
                              UserWarning)
        finally:
            restore()
        assert t.c_donation_fallback.get() - before == 5
        # restore() puts the filter stack back: the same warning no
        # longer reaches the (restored) hook chain for counting
        warnings.warn("Some donated buffers were not usable", UserWarning)
        assert t.c_donation_fallback.get() - before == 5
        t.close()

    def test_device_memory_stats_graceful_none(self):
        """CPU backend has no memory_stats — the meter must answer None,
        not crash or fake zeros."""
        from paddle_tpu.monitor import device_memory_stats

        stats = device_memory_stats()
        assert stats is None or "bytes_in_use" in stats

    def test_peak_flops_flag_override(self):
        from paddle_tpu.framework import flags
        from paddle_tpu.monitor import peak_flops_per_device

        prev = flags.get_flags(["FLAGS_device_peak_flops"])
        try:
            flags.set_flags({"FLAGS_device_peak_flops": 123.0})
            assert peak_flops_per_device() == 123.0
        finally:
            flags.set_flags(prev)

    def test_engine_cost_analysis_reports_flops(self):
        """The number the MFU gauge is built on: the compiled train
        step's XLA cost analysis carries a positive 'flops'."""
        m = _model()
        eng = m._engine or None
        from paddle_tpu.hapi.engine import TrainEngine

        eng = TrainEngine(m).begin()
        x = paddle.to_tensor(np.zeros((8, 8), "float32"))
        y = paddle.to_tensor(np.zeros((8, 1), "float32"))
        ca = eng.step_cost_analysis([x], [y])
        assert ca.get("flops", 0) > 0


# -- the peak table ---------------------------------------------------------
class _Kind:
    def __init__(self, kind):
        self.device_kind = kind


class TestPeaks:
    """One table states what a chip can do (monitor/telemetry.py PEAKS):
    the MFU's denominator and the op table's roofline read the same
    row."""

    @pytest.mark.parametrize(
        "kind", ["TPU v4", "TPU v5 lite", "TPU v5p", "TPU v6e"])
    def test_one_row_gives_both_axes(self, kind):
        from paddle_tpu.monitor import (PEAKS, peak_bw_per_device,
                                        peak_flops_per_device)

        got = (peak_flops_per_device(_Kind(kind)),
               peak_bw_per_device(_Kind(kind)))
        assert got == PEAKS[kind[len("TPU "):]]
        assert got[0] > got[1] > 0

    def test_v5e_row_is_the_benchmarks(self):
        """The package does not read benchmarks/peaks.json (a lower layer
        imports no higher one); the ledger's rooflines and the package's
        MFU still divide by the same numbers."""
        from paddle_tpu.monitor import (peak_bw_per_device,
                                        peak_flops_per_device)

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(repo, "benchmarks", "peaks.json")) as fh:
            (kind, row), = json.load(fh)["devices"].items()
        assert peak_flops_per_device(_Kind(kind)) == row["bf16_flops_per_s"]
        assert peak_bw_per_device(_Kind(kind)) == row["hbm_bytes_per_s"]


# -- JSONL event log --------------------------------------------------------
class TestJsonl:
    def test_schema_and_rotation(self, tmp_path):
        from paddle_tpu.monitor import JsonlWriter

        w = JsonlWriter(str(tmp_path), rotate_mb=0.004, keep=3)
        for i in range(400):
            w.write({"event": "window", "step": i, "loss": 0.5})
        w.close()
        files = sorted(os.listdir(tmp_path))
        assert "events.jsonl" in files
        rotated = [f for f in files if f.startswith("events.jsonl.")]
        assert rotated, "rotation never happened"
        assert len(rotated) <= 3, f"rotation unbounded: {files}"
        # every line of every segment is valid JSON with the schema keys
        for f in files:
            for line in open(tmp_path / f):
                rec = json.loads(line)
                assert rec["event"] == "window" and "step" in rec

    def test_fit_event_stream_schema(self, monitored):
        m = _model()
        m.fit(_DS(), batch_size=8, epochs=1, log_freq=2, verbose=0)
        lines = [json.loads(x)
                 for x in open(monitored / "events.jsonl")]
        events = [x["event"] for x in lines]
        assert events[0] == "fit_begin" and events[-1] == "fit_end"
        windows = [x for x in lines if x["event"] == "window"]
        assert windows, "no step windows emitted"
        w = windows[-1]
        for key in ("ts", "step", "epoch", "steps", "samples_per_sec",
                    "step_ms_mean", "mfu", "loss", "lr", "phase_ms",
                    "mem"):
            assert key in w, f"window record missing {key}: {w}"
        assert {"data", "dispatch", "sync"} <= set(w["phase_ms"])
        assert w["samples_per_sec"] > 0
        # MFU is nonzero: XLA cost analysis + the nominal CPU peak
        assert w["mfu"] > 0
        # windows cover every dispatched step exactly once
        assert sum(x["steps"] for x in windows) == 6  # 48/8 per epoch


# -- HTTP surface -----------------------------------------------------------
class TestMonitorServer:
    def test_metrics_healthz_and_404(self, monitored):
        from paddle_tpu import monitor

        m = _model()
        m.fit(_DS(), batch_size=8, epochs=1, verbose=0)
        srv = monitor.get_monitor_server()
        assert srv is not None
        body = _scrape(srv.url + "/metrics")
        for want in ("paddle_train_mfu", "paddle_train_step_ms",
                     "paddle_train_samples_per_sec",
                     "paddle_train_step_time_p50_ms",
                     "paddle_train_step_time_p99_ms"):
            assert want in body, want
        h = json.loads(_scrape(srv.url + "/healthz"))
        assert h["status"] == "ok" and h["step"] == 6
        with pytest.raises(urllib.error.HTTPError) as e:
            _scrape(srv.url + "/nope")
        assert e.value.code == 404

    def test_debug_trace_requires_steps(self, monitored):
        from paddle_tpu import monitor

        monitor.fit_monitor()
        srv = monitor.get_monitor_server()
        with pytest.raises(urllib.error.HTTPError) as e:
            _scrape(srv.url + "/debug/trace")
        assert e.value.code == 400

    def test_federation_merges_rank_bodies(self):
        from paddle_tpu.monitor import MonitorServer

        rank_reg = MetricsRegistry()
        rank_reg.gauge("rank_only_gauge", "from the rank").set(42)
        with MonitorServer(registry=rank_reg, port=0) as rank_srv:
            rank_url = rank_srv.url
            own = MetricsRegistry()
            own.counter("launcher_counter").inc()
            with MonitorServer(registry=own, port=0,
                               federate=[rank_url]) as fed:
                body = _scrape(fed.url + "/metrics")
        assert "launcher_counter 1" in body
        assert f"# federated from {rank_url}/metrics" in body
        assert "rank_only_gauge 42" in body

    def test_federation_assigned_after_construction_still_counts(self):
        """The launcher assigns .federate AFTER construction (the rank
        ports derive from the bound port) — the error counter must
        still register and increment (review fix: it was created only
        when federate was non-empty at __init__)."""
        from paddle_tpu.monitor import MonitorServer

        own = MetricsRegistry()
        with MonitorServer(registry=own, port=0,
                           fetch_timeout_s=0.3) as fed:
            fed.federate = ["http://127.0.0.1:9"]
            body = _scrape(fed.url + "/metrics")
        assert "FETCH FAILED" in body
        assert own.counter(
            "paddle_monitor_federation_errors_total").get() == 1

    def test_federation_dead_ranks_cost_one_timeout_not_n(self):
        """N dead ranks fetch concurrently: the scrape must not take
        N x fetch_timeout_s (a pod scrape blowing the scraper deadline
        loses the healthy launcher counters too)."""
        import time as _time

        from paddle_tpu.monitor import MonitorServer

        dead = [f"http://127.0.0.1:{p}" for p in (9, 10, 11, 12, 13, 14)]
        own = MetricsRegistry()
        with MonitorServer(registry=own, port=0, federate=dead,
                           fetch_timeout_s=1.0) as fed:
            t0 = _time.monotonic()
            body = _scrape(fed.url + "/metrics")
            elapsed = _time.monotonic() - t0
        assert body.count("FETCH FAILED") == 6
        assert elapsed < 4.0, \
            f"6 dead ranks took {elapsed:.1f}s — fetches are sequential"

    def test_federation_survives_dead_rank(self):
        from paddle_tpu.monitor import MonitorServer

        own = MetricsRegistry()
        with MonitorServer(registry=own, port=0,
                           federate=["http://127.0.0.1:9"],
                           fetch_timeout_s=0.3) as fed:
            body = _scrape(fed.url + "/metrics")
        assert "FETCH FAILED" in body
        assert own.counter(
            "paddle_monitor_federation_errors_total").get() == 1

    def test_concurrent_scrapes_with_slow_rank_no_convoy(self):
        """One SLOW federated rank must not convoy the monitor: while
        N scrapes sit in its fetch, /healthz on the same server answers
        immediately (the rank fetch happens OUTSIDE the registry lock,
        and the HTTP server threads per request), and the N scrapes
        overlap on the slow rank instead of serializing behind it."""
        import threading
        import time as _time
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        from paddle_tpu.monitor import MonitorServer

        class _SlowRank(BaseHTTPRequestHandler):
            def do_GET(self):
                _time.sleep(1.2)
                body = b"slow_rank_gauge 7\n"
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        rank_httpd = ThreadingHTTPServer(("127.0.0.1", 0), _SlowRank)
        rank_httpd.daemon_threads = True
        threading.Thread(target=rank_httpd.serve_forever,
                         daemon=True).start()
        rank_url = "http://127.0.0.1:%d" % rank_httpd.server_address[1]
        own = MetricsRegistry()
        own.counter("launcher_counter").inc()
        try:
            with MonitorServer(registry=own, port=0, federate=[rank_url],
                               fetch_timeout_s=5.0) as fed:
                bodies = {}

                def scrape(i):
                    bodies[i] = _scrape(fed.url + "/metrics")

                t0 = _time.monotonic()
                threads = [threading.Thread(target=scrape, args=(i,))
                           for i in range(4)]
                for t in threads:
                    t.start()
                _time.sleep(0.2)   # scrapes are now parked in the fetch
                t1 = _time.monotonic()
                h = json.loads(_scrape(fed.url + "/healthz"))
                healthz_s = _time.monotonic() - t1
                for t in threads:
                    t.join()
                total = _time.monotonic() - t0
        finally:
            rank_httpd.shutdown()
            rank_httpd.server_close()
        assert h["status"] == "ok"
        assert healthz_s < 1.0, \
            f"/healthz took {healthz_s:.2f}s behind a slow rank scrape"
        assert len(bodies) == 4
        for b in bodies.values():
            assert "launcher_counter 1" in b and "slow_rank_gauge 7" in b
        assert total < 3.5, \
            f"4 scrapes of a 1.2s rank took {total:.1f}s — serialized"


# -- on-demand trace capture on a RUNNING fit -------------------------------
def _trace_files(root):
    out = []
    for base, _dirs, files in os.walk(root):
        out.extend(os.path.join(base, f) for f in files)
    return out


class TestTraceCapture:
    def test_debug_trace_captures_running_fit(self, monitored):
        """Arm /debug/trace?steps=2 from a callback DURING the fit (the
        HTTP hit happens while the job is running) and assert a
        non-empty jax.profiler trace directory exists afterwards —
        without the fit restarting or failing."""
        from paddle_tpu import monitor
        from paddle_tpu.hapi.callbacks import Callback

        armed = {}

        class ArmTrace(Callback):
            def on_train_batch_end(self, step, logs=None):
                if step == 1 and not armed:
                    srv = monitor.get_monitor_server()
                    armed.update(json.loads(_scrape(
                        srv.url + "/debug/trace?steps=2")))

        m = _model()
        m.fit(_DS(), batch_size=8, epochs=1, verbose=0,
              callbacks=[ArmTrace()])
        assert armed["armed_steps"] == 2
        files = _trace_files(armed["trace_dir"])
        assert files, f"trace dir {armed['trace_dir']} is empty"
        telem, _srv = monitor.fit_monitor()
        assert telem.c_traces.get() >= 1

    def test_sigusr1_arms_bounded_capture(self, monitored):
        """SIGUSR1 mid-fit (the headless /debug/trace) arms a bounded
        capture that completes on the training thread."""
        from paddle_tpu import monitor
        from paddle_tpu.hapi.callbacks import Callback

        fired = []

        class Kick(Callback):
            def on_train_batch_end(self, step, logs=None):
                if step == 1 and not fired:
                    fired.append(True)
                    os.kill(os.getpid(), signal.SIGUSR1)

        m = _model()
        m.fit(_DS(), batch_size=8, epochs=1, verbose=0,
              callbacks=[Kick()])
        telem, _srv = monitor.fit_monitor()
        assert telem.c_traces.get() >= 1
        assert telem.last_trace_dir and _trace_files(telem.last_trace_dir)

    def test_trace_armed_past_fit_end_still_closes(self, monitored):
        """A capture armed for more steps than remain must be finalized
        at fit exit (valid artifact, profiler not left running)."""
        from paddle_tpu import monitor

        telem, _srv = monitor.fit_monitor()
        m = _model()
        telem.arm_trace(10_000)
        m.fit(_DS(), batch_size=8, epochs=1, verbose=0)
        assert not telem.trace_pending
        assert _trace_files(telem.last_trace_dir)


# -- checkpoint durability counters -----------------------------------------
class TestCheckpointCounters:
    def test_save_restore_quarantine_counters(self, tmp_path):
        from paddle_tpu.distributed.checkpoint import CheckpointManager

        reg = default_registry()
        before = reg.snapshot()
        state = {"w": np.arange(8, dtype=np.float32)}
        with CheckpointManager(str(tmp_path / "ck"), max_to_keep=3) as mgr:
            mgr.save(1, state, force=True)
            mgr.save(2, state, force=True)
            # corrupt the newest committed generation: restore must
            # quarantine it and cascade
            gen2 = mgr._gen_dir(2)
            leaf = next(
                os.path.join(gen2, "leaves", f)
                for f in os.listdir(os.path.join(gen2, "leaves")))
            with open(leaf, "r+b") as f:
                f.write(b"\xff\xff\xff\xff")
            step, back = mgr.restore_latest(template={"w": None})
        assert step == 1
        after = reg.snapshot()
        assert after["paddle_ckpt_saves_total"]["ok"] - \
            before["paddle_ckpt_saves_total"]["ok"] == 2
        assert after["paddle_ckpt_quarantines_total"] - \
            before["paddle_ckpt_quarantines_total"] == 1
        assert after["paddle_ckpt_cascade_depth"] == 1
        assert after["paddle_ckpt_save_ms"]["count"] - \
            before["paddle_ckpt_save_ms"]["count"] == 2
        assert after["paddle_ckpt_restore_ms"]["count"] - \
            before["paddle_ckpt_restore_ms"]["count"] == 1

    def test_fit_ckpt_stall_histogram(self, monitored, tmp_path):
        from paddle_tpu import monitor

        m = _model()
        m.fit(_DS(), batch_size=8, epochs=1, verbose=0,
              resume=str(tmp_path / "ck"), save_dir=str(tmp_path / "ck"),
              checkpoint_interval=2)
        telem, _srv = monitor.fit_monitor()
        assert telem.h_ckpt_stall.total >= 1
        srv = monitor.get_monitor_server()
        assert "paddle_ckpt_step_stall_ms" in _scrape(srv.url + "/metrics")


# -- launcher restart accounting --------------------------------------------
class TestLaunchCounters:
    def test_failure_reasons_preset(self):
        """The restart-reason series exist (zero-valued) from import, so
        dashboards can alert on them before the first failure."""
        from paddle_tpu.distributed import launch  # noqa: F401

        text = default_registry().prometheus_text()
        for reason in ("preempted", "watchdog", "durability", "crash"):
            assert (f'paddle_launch_trainer_failures_total'
                    f'{{reason="{reason}"}}') in text
