"""Fault-tolerant serving fleet (serving/router.py failover paths,
serving/fleet.py supervisor, utils/chaos.py replica dials).

The tentpole contract: the generation fleet loses a replica under load
with ZERO failed requests.  Pieces under test here:

  * mid-stream failover — a replica's SSE stream severed after K tokens
    is resumed on a survivor with the emitted prefix appended to the
    prompt and ``resume_pos`` fast-forwarding the per-request PRNG
    chain; the client's reassembled stream is BITWISE the uninterrupted
    run (greedy) / deterministically identical (seeded sampling).
  * elastic membership — the router subscribed to the pod coordinator
    evicts a dead rank on the EPOCH DELTA (no probe-timeout wait) and
    re-admits a revived rank without restart.
  * probe flap damping — a dead replica needs `healthy_after`
    CONSECUTIVE probe successes before taking traffic again.
  * retry budget — against a fully-failing fleet, total upstream
    dispatches are pinned at requests + budget; exhaustion degrades to
    fast 503, never a retry storm.
  * hedged dispatch — a slow replica's non-streaming request is
    duplicated after the hedge delay and the fast replica's answer
    wins, exactly once.
  * client retries — idempotent non-streaming requests retry on 5xx /
    connection failure with Retry-After honored on 429, and report
    attempts.

The multi-process drill (real SIGKILL of a replica subprocess, real
supervisor respawn) is marked `slow`; tools/serve_smoke.sh runs the
same scenario end-to-end from the shell.

Run via tools/serve_smoke.sh (`pytest -m fleetchaos`); fast cases also
ride tier-1.
"""
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.serving.client import ServingClient, ServingHTTPError
from paddle_tpu.serving.generation import GenerationEngine
from paddle_tpu.serving.router import FleetRouter, RetryBudget

pytestmark = pytest.mark.fleetchaos

PROMPT = list(range(3, 11))          # 8 tokens
MAX_NEW = 12
SAMPLE_KW = dict(do_sample=True, temperature=0.8, top_k=5)


def _gpt(seed=0):
    paddle.seed(seed)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=211, hidden_size=48, num_layers=2, num_heads=4,
        max_position_embeddings=64, dropout=0.0, attn_dropout=0.0))
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _gpt(0)


@pytest.fixture(scope="module")
def eng(model):
    """Oracle engine: buckets must cover RESUMED prompts (prompt +
    emitted prefix), not just originals."""
    e = GenerationEngine(model, max_slots=2, max_seq_len=64,
                         prompt_buckets=(8, 16, 32), page_size=4).start()
    yield e
    e.stop()


@pytest.fixture(scope="module")
def real_server(model):
    from paddle_tpu.serving.server import ServingServer

    e = GenerationEngine(model, max_slots=2, max_seq_len=64,
                         prompt_buckets=(8, 16, 32), page_size=4)
    srv = ServingServer(None, gen_engine=e, port=0,
                        install_signal_handlers=False).start()
    yield srv
    srv.shutdown()


# ---------------------------------------------------------------------------
# stub replicas
# ---------------------------------------------------------------------------
class _FlakyGen(BaseHTTPRequestHandler):
    """A replica that computes the TRUE stream (via the oracle engine,
    honoring resume_pos) but severs the connection after
    `server.cut_after` token events on its first request — the
    in-process stand-in for a SIGKILL mid-stream."""

    protocol_version = "HTTP/1.1"

    def do_GET(self):  # noqa: N802
        body = b'{"status": "ok"}'
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _chunk(self, obj):
        data = b"data: " + json.dumps(obj).encode() + b"\n\n"
        self.wfile.write(b"%X\r\n" % len(data) + data + b"\r\n")
        self.wfile.flush()

    def do_POST(self):  # noqa: N802
        raw = self.rfile.read(int(self.headers.get("Content-Length", 0)))
        p = json.loads(raw)
        h = self.server.eng.submit(
            p["prompt"], p.get("max_new_tokens", 32),
            do_sample=p.get("do_sample", False),
            temperature=p.get("temperature", 1.0),
            top_k=p.get("top_k", 0), seed=p.get("seed", 0),
            resume_pos=p.get("resume_pos", 0))
        tokens = h.result(60)
        cut = None
        if not self.server.cut_done:
            self.server.cut_done = True
            cut = self.server.cut_after
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Transfer-Encoding", "chunked")
        self.send_header("Connection", "close")
        self.end_headers()
        self.close_connection = True
        for i, t in enumerate(tokens):
            if cut is not None and i >= cut:
                return  # no done event, no terminal chunk: severed
            self._chunk({"token": int(t)})
        self._chunk({"done": True, "tokens": len(tokens)})
        self.wfile.write(b"0\r\n\r\n")

    def log_message(self, *a):  # noqa: D102
        pass


def _start_stub(handler_cls, **attrs):
    stub = ThreadingHTTPServer(("127.0.0.1", 0), handler_cls)
    for k, v in attrs.items():
        setattr(stub, k, v)
    threading.Thread(target=stub.serve_forever, daemon=True).start()
    return stub, f"http://127.0.0.1:{stub.server_address[1]}"


class _FailingGen(BaseHTTPRequestHandler):
    """Healthy /healthz, every POST 500 — a fleet that accepts probes
    but fails every request (the retry-budget exhaustion scenario)."""

    def do_GET(self):  # noqa: N802
        body = b'{"status": "ok"}'
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_POST(self):  # noqa: N802
        self.rfile.read(int(self.headers.get("Content-Length", 0)))
        with self.server.lock:
            self.server.posts += 1
        body = b'{"error": "internal"}'
        self.send_response(500)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):  # noqa: D102
        pass


class _SpeedGen(BaseHTTPRequestHandler):
    """Answers /predict after `server.delay_s`, tagging who answered."""

    def do_GET(self):  # noqa: N802
        body = b'{"status": "ok"}'
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_POST(self):  # noqa: N802
        self.rfile.read(int(self.headers.get("Content-Length", 0)))
        time.sleep(self.server.delay_s)
        body = json.dumps({"who": self.server.tag}).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):  # noqa: D102
        pass


class _FlakyOnce(BaseHTTPRequestHandler):
    """POST fails once (with `server.first_status`), then succeeds —
    the client-retry scenario."""

    def do_POST(self):  # noqa: N802
        self.rfile.read(int(self.headers.get("Content-Length", 0)))
        with self.server.lock:
            self.server.posts += 1
            first = self.server.posts == 1
        if first:
            body = b'{"error": "transient"}'
            self.send_response(self.server.first_status)
            if self.server.first_status == 429:
                self.send_header("Retry-After", "0")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        body = json.dumps({"outputs": [[1.0]],
                           "dtypes": ["float32"]}).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):  # noqa: D102
        pass


# ---------------------------------------------------------------------------
# engine-level resume determinism
# ---------------------------------------------------------------------------
class TestResumeDeterminism:
    def test_greedy_resume_bitwise(self, eng):
        """Splitting a greedy run at any point and resuming with the
        emitted prefix appended reproduces the suffix bitwise."""
        full = eng.submit(PROMPT, MAX_NEW, seed=3).result(60)
        assert len(full) == MAX_NEW
        for cut in (1, 5, MAX_NEW - 1):
            head = full[:cut]
            tail = eng.submit(PROMPT + head, MAX_NEW - cut, seed=3,
                              resume_pos=cut).result(60)
            assert head + tail == full, f"cut={cut}"

    def test_sampled_resume_same_chain(self, eng):
        """The per-request PRNG chain is positional: resume_pos=K
        fast-forwards K splits, so the resumed sampled stream continues
        the SAME chain the uninterrupted run walked."""
        full = eng.submit(PROMPT, MAX_NEW, seed=7,
                          **SAMPLE_KW).result(60)
        for cut in (2, 6):
            head = full[:cut]
            tail = eng.submit(PROMPT + head, MAX_NEW - cut, seed=7,
                              resume_pos=cut, **SAMPLE_KW).result(60)
            assert head + tail == full, f"cut={cut}"

    def test_resume_pos_zero_is_identity(self, eng):
        """resume_pos=0 is exactly the historical behavior."""
        a = eng.submit(PROMPT, 6, seed=11, **SAMPLE_KW).result(60)
        b = eng.submit(PROMPT, 6, seed=11, resume_pos=0,
                       **SAMPLE_KW).result(60)
        assert a == b

    def test_resume_pos_validation(self, eng):
        with pytest.raises(ValueError):
            eng.submit(PROMPT, 4, resume_pos=-1)


# ---------------------------------------------------------------------------
# router mid-stream failover
# ---------------------------------------------------------------------------
class TestMidStreamFailover:
    def _run(self, eng, real_server, gen_kw, cut=5):
        stub, stub_url = _start_stub(_FlakyGen, eng=eng, cut_after=cut,
                                     cut_done=False)
        router = FleetRouter([stub_url, real_server.url], port=0,
                             page_size=4, probe_interval_s=0.2,
                             dead_after=2,
                             install_signal_handlers=False).start()
        try:
            c = ServingClient(router.url, timeout=60.0)
            toks, err = [], None
            for evt in c.generate_stream(PROMPT, MAX_NEW, **gen_kw):
                if "token" in evt:
                    toks.append(evt["token"])
                if evt.get("done"):
                    err = evt.get("error")
            snap = router.metrics.snapshot()
            return toks, err, snap
        finally:
            router.shutdown()
            stub.shutdown()

    def test_greedy_stream_resumes_bitwise(self, eng, real_server):
        """r0 dies after 5 relayed tokens; the client stream must be
        the full uninterrupted greedy output, zero failed requests."""
        oracle = eng.submit(PROMPT, MAX_NEW, seed=3).result(60)
        toks, err, snap = self._run(eng, real_server, dict(seed=3))
        assert err is None
        assert toks == oracle
        assert snap["failovers"].get("mid_stream") == 1
        assert snap["requests_failed"] == 0
        assert snap["availability_ratio"] == 1.0

    def test_sampled_stream_resumes_deterministically(self, eng,
                                                      real_server):
        """Same contract under seeded sampling: the survivor continues
        the request's PRNG chain, not a fresh one."""
        oracle = eng.submit(PROMPT, MAX_NEW, seed=7,
                            **SAMPLE_KW).result(60)
        toks, err, snap = self._run(eng, real_server,
                                    dict(seed=7, **SAMPLE_KW))
        assert err is None
        assert toks == oracle
        assert snap["failovers"].get("mid_stream") == 1

    def test_done_event_carries_total_count(self, eng, real_server):
        """The rewritten done event reports tokens across BOTH legs."""
        stub, stub_url = _start_stub(_FlakyGen, eng=eng, cut_after=4,
                                     cut_done=False)
        router = FleetRouter([stub_url, real_server.url], port=0,
                             page_size=4, probe_interval_s=0.2,
                             dead_after=2,
                             install_signal_handlers=False).start()
        try:
            c = ServingClient(router.url, timeout=60.0)
            done = None
            n = 0
            for evt in c.generate_stream(PROMPT, MAX_NEW, seed=3):
                if "token" in evt:
                    n += 1
                if evt.get("done"):
                    done = evt
            assert done is not None and done["tokens"] == n == MAX_NEW
        finally:
            router.shutdown()
            stub.shutdown()


# ---------------------------------------------------------------------------
# elastic membership
# ---------------------------------------------------------------------------
class TestMembership:
    def test_epoch_eviction_and_readmission(self, real_server):
        """Coordinator-declared death evicts on the epoch delta (ahead
        of any probe evidence — probes still see the server healthy);
        mark_live re-admits without a router restart."""
        from paddle_tpu.distributed.podcoord import (PodClient,
                                                     PodCoordinator)

        coord = PodCoordinator(2, heartbeat_timeout_s=60.0).start()
        router = None
        try:
            kv = PodClient(coord.address, rank=-1)
            kv.kv_set("serving/replica/0/url",
                      real_server.url.encode())
            kv.kv_set("serving/replica/1/url",
                      real_server.url.encode())
            router = FleetRouter([], coord=coord.address, port=0,
                                 page_size=4, probe_interval_s=30.0,
                                 dead_after=2, membership_poll_s=0.05,
                                 install_signal_handlers=False).start()
            assert sorted(r.name for r in router.replicas) == ["r0",
                                                               "r1"]
            assert all(r.alive for r in router.replicas)
            coord.mark_dead(0, "exit")
            deadline = time.monotonic() + 3.0
            while time.monotonic() < deadline \
                    and router.replicas[0].alive:
                time.sleep(0.02)
            assert not router.replicas[0].alive, \
                "epoch-delta eviction did not land"
            assert router.metrics.snapshot()["membership_epoch"] >= 1
            # requests keep flowing on the survivor
            c = ServingClient(router.url)
            assert len(c.generate(PROMPT, 3)["tokens"]) == 3
            # supervisor-style revive: same rank re-admitted live
            coord.mark_live(0)
            deadline = time.monotonic() + 3.0
            while time.monotonic() < deadline \
                    and not router.replicas[0].alive:
                time.sleep(0.02)
            assert router.replicas[0].alive, \
                "membership re-admission did not land"
        finally:
            if router is not None:
                router.shutdown()
            coord.close()


# ---------------------------------------------------------------------------
# probe flap damping
# ---------------------------------------------------------------------------
class _ToggleHealth(BaseHTTPRequestHandler):
    def do_GET(self):  # noqa: N802
        code = 200 if self.server.healthy else 500
        body = b"{}"
        self.send_response(code)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):  # noqa: D102
        pass


class TestFlapDamping:
    def test_dead_needs_consecutive_successes(self):
        """2 failed probes mark a replica dead; re-admission takes
        `healthy_after`=3 CONSECUTIVE successes — an interleaved
        failure resets the count."""
        stub, url = _start_stub(_ToggleHealth, healthy=False)
        router = FleetRouter([url], dead_after=2, healthy_after=3,
                             install_signal_handlers=False)
        rep = router.replicas[0]
        try:
            for _ in range(2):
                router._probe_one(rep)
            assert not rep.alive
            stub.healthy = True
            router._probe_one(rep)
            assert not rep.alive and rep.succs == 1
            router._probe_one(rep)
            assert not rep.alive and rep.succs == 2
            # one flap resets the streak
            stub.healthy = False
            router._probe_one(rep)
            assert not rep.alive and rep.succs == 0
            stub.healthy = True
            for _ in range(3):
                assert not rep.alive
                router._probe_one(rep)
            assert rep.alive, "3 consecutive successes must re-admit"
        finally:
            stub.shutdown()

    def test_probe_loop_staggers(self):
        """The probe loop spaces per-replica probes at interval/N —
        one replica at a time, never the whole fleet as a herd.  The
        loop's only clock is its stop event's `wait(step)`: a stand-in
        that records each wait shows the order and the spacing asked
        for, whatever the host's load."""
        router = FleetRouter(["http://127.0.0.1:1", "http://127.0.0.1:2"],
                             probe_interval_s=0.2,
                             install_signal_handlers=False)
        log = []

        class _StopAfterFourProbes:
            def is_set(self):
                return sum(what == "probe" for what, _ in log) >= 4

            def wait(self, step):
                log.append(("wait", step))
                return self.is_set()

        router._stop_probe = _StopAfterFourProbes()
        router._probe_one = lambda rep: log.append(("probe", rep.name))
        router._probe_loop()
        assert [x for what, x in log if what == "probe"] \
            == ["r0", "r1", "r0", "r1"]
        # a wait of interval/N before every probe, none back-to-back
        assert [what for what, _ in log] == ["wait", "probe"] * 4
        assert all(x == pytest.approx(0.1) for what, x in log
                   if what == "wait")


# ---------------------------------------------------------------------------
# retry budget + circuit breaking
# ---------------------------------------------------------------------------
class TestRetryBudget:
    def test_bucket_math(self):
        b = RetryBudget(ratio=0.5, min_budget=2.0)
        assert b.withdraw() and b.withdraw()
        assert not b.withdraw(), "floor budget is 2 retries"
        for _ in range(4):
            b.deposit()
        assert b.withdraw() and b.withdraw()
        assert not b.withdraw()

    def test_exhaustion_pins_dispatches(self):
        """Fully-failing fleet, M requests: total upstream dispatches
        are pinned at M + budget_min — the budget converts a retry
        storm into fast 503s."""
        lock = threading.Lock()
        stubs = []
        urls = []
        for _ in range(2):
            s, u = _start_stub(_FailingGen, lock=lock, posts=0)
            stubs.append(s)
            urls.append(u)
        router = FleetRouter(urls, port=0, page_size=4,
                             probe_interval_s=30.0, dead_after=10,
                             retry_budget_min=2.0,
                             retry_budget_ratio=0.0,
                             breaker_threshold=100,
                             install_signal_handlers=False).start()
        try:
            c = ServingClient(router.url)
            n_req = 6
            statuses = []
            for _ in range(n_req):
                with pytest.raises(ServingHTTPError) as ei:
                    c.generate(PROMPT, 3)
                statuses.append(ei.value.status)
            total = sum(s.posts for s in stubs)
            assert total <= n_req + 2, \
                f"dispatches {total} exceed requests+budget"
            assert total >= n_req
            # the router answers a request before it counts its outcome
            # (ROADMAP.md Design 20): read the metrics until the last one
            # is counted
            deadline = time.monotonic() + 10.0
            snap = router.metrics.snapshot()
            while snap["requests_failed"] < n_req \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
                snap = router.metrics.snapshot()
            assert snap["retry_budget_exhausted"] >= 1
            assert snap["requests_failed"] == n_req
            assert snap["availability_ratio"] == 0.0
            assert all(s in (502, 503) for s in statuses), statuses
        finally:
            router.shutdown()
            for s in stubs:
                s.shutdown()

    def test_breaker_stops_dispatch(self):
        """After `breaker_threshold` consecutive request failures the
        replica stops receiving dispatches entirely (fast 503, zero
        upstream traffic) until the cooldown expires."""
        lock = threading.Lock()
        stub, url = _start_stub(_FailingGen, lock=lock, posts=0)
        router = FleetRouter([url], port=0, page_size=4,
                             probe_interval_s=30.0, dead_after=10,
                             retry_budget_min=100.0,
                             breaker_threshold=2,
                             breaker_cooldown_s=60.0,
                             install_signal_handlers=False).start()
        try:
            c = ServingClient(router.url)
            for _ in range(4):
                with pytest.raises(ServingHTTPError):
                    c.generate(PROMPT, 3)
            # threshold=2: dispatches stop once the breaker opens
            assert stub.posts == 2, stub.posts
        finally:
            router.shutdown()
            stub.shutdown()


# ---------------------------------------------------------------------------
# hedged dispatch
# ---------------------------------------------------------------------------
class TestHedging:
    def test_slow_replica_hedge_wins_exactly_once(self):
        """r0 sits on the request past the hedge delay; the duplicate
        lands on r1 and its answer wins — once, with both the hedge
        counter and the won/lost split recording it."""
        slow, slow_url = _start_stub(_SpeedGen, delay_s=1.2, tag="slow")
        fast, fast_url = _start_stub(_SpeedGen, delay_s=0.0, tag="fast")
        router = FleetRouter([slow_url, fast_url], port=0, page_size=4,
                             probe_interval_s=30.0,
                             hedge_floor_ms=100.0,
                             install_signal_handlers=False).start()
        try:
            req = urllib.request.Request(
                router.url + "/predict", data=b"{}",
                headers={"Content-Type": "application/json"},
                method="POST")
            t0 = time.monotonic()
            with urllib.request.urlopen(req, timeout=10.0) as r:
                out = json.loads(r.read())
            assert out["who"] == "fast"
            assert time.monotonic() - t0 < 1.0, \
                "hedge should beat the slow replica"
            time.sleep(1.3)  # let the abandoned primary finish
            snap = router.metrics.snapshot()
            assert snap["hedges"].get("won") == 1
            assert snap["hedges"].get("lost", 0) == 0
            assert snap["failovers"].get("hedge") == 1
        finally:
            router.shutdown()
            slow.shutdown()
            fast.shutdown()


# ---------------------------------------------------------------------------
# deadline admission
# ---------------------------------------------------------------------------
class TestDeadlineAdmission:
    def test_hopeless_deadline_rejected_504(self):
        """A request whose deadline is already smaller than the
        estimated queue wait is rejected at the router — the replica
        never sees the doomed dispatch."""
        stub, url = _start_stub(_SpeedGen, delay_s=0.0, tag="x")
        router = FleetRouter([url], port=0, probe_interval_s=30.0,
                             replica_slots=1,
                             install_signal_handlers=False).start()
        try:
            router._observe_latency(0.5)      # ~500ms per request
            router.replicas[0].inflight = 4   # 4 waves queued ahead
            c = ServingClient(router.url)
            with pytest.raises(ServingHTTPError) as ei:
                c.generate(PROMPT, 3, deadline_ms=10)
            assert ei.value.status == 504
            assert router.metrics.snapshot()["deadline_rejected"] == 1
        finally:
            router.replicas[0].inflight = 0   # let the drain finish
            router.shutdown()
            stub.shutdown()

    def test_no_estimate_admits_everything(self):
        """With no latency history the estimate is 0 — the router never
        rejects on a model it does not have yet."""
        router = FleetRouter(["http://127.0.0.1:1"],
                             install_signal_handlers=False)
        assert router._est_wait_ms(router.replicas[0]) == 0.0


# ---------------------------------------------------------------------------
# client retries
# ---------------------------------------------------------------------------
class TestClientRetries:
    def _predict(self, url, retries=2):
        c = ServingClient(url, retries=retries, retry_backoff_s=0.01)
        out = c.predict([np.zeros(1, np.float32)])
        return c, out

    def test_retries_5xx_and_reports_attempts(self):
        stub, url = _start_stub(_FlakyOnce, lock=threading.Lock(),
                                posts=0, first_status=500)
        try:
            c, out = self._predict(url)
            assert out[0].tolist() == [1.0]
            assert c.last_attempts == 2
        finally:
            stub.shutdown()

    def test_honors_retry_after_on_429(self):
        stub, url = _start_stub(_FlakyOnce, lock=threading.Lock(),
                                posts=0, first_status=429)
        try:
            c, out = self._predict(url)
            assert out[0].tolist() == [1.0]
            assert c.last_attempts == 2
        finally:
            stub.shutdown()

    def test_default_is_no_retry(self):
        stub, url = _start_stub(_FlakyOnce, lock=threading.Lock(),
                                posts=0, first_status=500)
        try:
            with pytest.raises(ServingHTTPError) as ei:
                self._predict(url, retries=0)
            assert ei.value.status == 500
            assert stub.posts == 1
        finally:
            stub.shutdown()

    def test_connection_refused_retries_then_raises(self):
        # unroutable port: every attempt fails; retries=2 -> 3 attempts
        c = ServingClient("http://127.0.0.1:1", retries=2,
                          retry_backoff_s=0.01, timeout=0.5)
        with pytest.raises(OSError):
            c._request("/predict", {"inputs": []})
        assert c.last_attempts == 3


# ---------------------------------------------------------------------------
# chaos dials
# ---------------------------------------------------------------------------
class TestChaosDials:
    def test_replica_dials_parse_from_env(self, monkeypatch):
        from paddle_tpu.utils import chaos

        monkeypatch.setenv("PADDLE_CHAOS_REPLICA_KILL", "1@3")
        monkeypatch.setenv("PADDLE_CHAOS_REPLICA_SLOW", "0@2:0.5")
        monkeypatch.setenv("PADDLE_CHAOS_REPLICA_PARTITION", "2@4")
        cfg = chaos.ChaosConfig.from_env()
        assert cfg.replica_kill == (1, 3)
        assert cfg.replica_slow == (0, 2, 0.5)
        assert cfg.replica_partition == (2, 4)
        assert not cfg.is_noop()

    def test_partition_dial_fires_hook_once(self, monkeypatch):
        from paddle_tpu.utils import chaos

        monkeypatch.setenv("PADDLE_POD_RANK", "0")
        fired = []
        chaos.register_partition_hook(lambda: fired.append(1))
        with chaos.inject(replica_partition=(0, 2)):
            chaos.on_step(0)
            chaos.on_step(1)
            assert not fired
            chaos.on_step(2)
            chaos.on_step(3)
        assert fired == [1], "partition is one-shot"

    def test_replica_slow_is_persistent(self, monkeypatch):
        from paddle_tpu.utils import chaos

        monkeypatch.setenv("PADDLE_POD_RANK", "0")
        with chaos.inject(replica_slow=(0, 1, 0.01)):
            t0 = time.monotonic()
            chaos.on_step(0)
            fast = time.monotonic() - t0
            t0 = time.monotonic()
            chaos.on_step(1)
            chaos.on_step(2)
            slow = time.monotonic() - t0
            assert chaos.active_config().replica_slow is not None, \
                "slow dial must persist (not one-shot)"
        assert slow >= 0.02 > fast


# ---------------------------------------------------------------------------
# the real drill: SIGKILL a replica subprocess mid-stream
# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestSigkillDrill:
    def test_mid_stream_sigkill_resumes_and_respawns(self, tmp_path):
        """End-to-end: supervisor fleet of 2 real replica processes,
        router on the coordinator, a streaming request whose replica is
        SIGKILLed mid-stream.  The stream must complete bitwise equal
        to the undisturbed run, the router must count zero failed
        requests, and the supervisor must respawn the victim."""
        from conftest import cpu_subprocess_env

        from paddle_tpu.serving.fleet import ReplicaSupervisor

        cmd = [sys.executable, "-m", "paddle_tpu.serving.generation",
               "--port", "0", "--slots", "2", "--page-size", "4",
               "--prompt-buckets", "8,16,32", "--max-seq-len", "64",
               "--seed", "0"]
        sup = ReplicaSupervisor(
            cmd, 2, env=cpu_subprocess_env(),
            heartbeat_timeout_s=5.0, respawn_backoff_s=0.2,
            telemetry_dir=str(tmp_path / "telemetry"),
            log_dir=str(tmp_path / "logs")).start()
        router = None
        try:
            assert sup.wait_ready(240), "fleet bring-up timed out"
            router = FleetRouter([], coord=sup.coord.address, port=0,
                                 page_size=4, probe_interval_s=0.3,
                                 dead_after=3, membership_poll_s=0.05,
                                 install_signal_handlers=False).start()
            c = ServingClient(router.url, timeout=120.0)
            oracle = c.generate(PROMPT, MAX_NEW)["tokens"]
            assert len(oracle) == MAX_NEW

            toks, err = [], None
            for evt in c.generate_stream(PROMPT, MAX_NEW):
                if "token" in evt:
                    toks.append(evt["token"])
                    if len(toks) == 3:
                        victim = max(router.replicas,
                                     key=lambda r: r.inflight)
                        rank = int(victim.name[1:])
                        os.kill(sup.procs[rank].pid, signal.SIGKILL)
                if evt.get("done"):
                    err = evt.get("error")
            assert err is None, f"stream failed: {err}"
            assert toks == oracle, "resumed stream is not bitwise equal"
            snap = router.metrics.snapshot()
            assert snap["failovers"].get("mid_stream", 0) >= 1
            assert snap["requests_failed"] == 0
            # the supervisor respawns the victim and the router
            # re-admits it on the membership channel
            deadline = time.monotonic() + 180
            while time.monotonic() < deadline \
                    and not (sup.respawn_count >= 1 and sup.wait_ready(1)):
                time.sleep(0.5)
            assert sup.respawn_count >= 1
            assert sup.wait_ready(60)
            assert sup.downs and sup.downs[0] > 0
            # availability accounting left a replica_lost dump
            dumps = [p for p in
                     os.listdir(tmp_path / "telemetry")
                     if p.startswith("flightrec-")]
            assert dumps, "supervisor left no replica_lost dump"
            doc = json.loads(
                (tmp_path / "telemetry" / dumps[0]).read_text())
            assert doc["reason"] == "replica_lost"
            assert doc["accounting"]["down_s"] > 0
        finally:
            if router is not None:
                router.shutdown()
            sup.shutdown()
