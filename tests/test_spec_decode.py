"""Speculative decode, chunked prefill, and the fleet router
(serving/generation.py draft path, serving/router.py).

Three contracts under test.  SPECULATIVE DECODE must be invisible to
the stream: greedy output bitwise-identical to the non-speculative
engine (and the model's own generate loop) whatever the draft proposes
— acceptance only changes HOW FAST tokens come, never WHICH tokens —
including mid-decode admission, rejection-heavy drafts (the drafted KV
of rejected proposals is overwritten before any emitted query attends
it), and seeded sampling lanes riding the same executable.  CHUNKED
PREFILL must hold token parity with unchunked admission while never
starving armed decode lanes, and a cancel mid-chunk must return every
privately-written page to the pool (the occupancy tripwire).  The
ROUTER must bind page-aligned prefixes to replicas (prefix_hit),
fail over off dead replicas, treat 429 as backpressure (retry, no
health flap), and carry one trace across client → router → replica.

Run via tools/serve_smoke.sh (`pytest -m specdec`); also in tier-1.
"""
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import flags as _flags
from paddle_tpu.framework.transfer import host_fetch
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.serving.generation import GenerationEngine

pytestmark = pytest.mark.specdec

SAMPLE_KW = dict(do_sample=True, temperature=0.8, top_k=5)


def _gpt(layers, seed, max_pos=128):
    paddle.seed(seed)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=211, hidden_size=48, num_layers=layers, num_heads=4,
        max_position_embeddings=max_pos, dropout=0.0, attn_dropout=0.0))
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _gpt(2, seed=0)


@pytest.fixture(scope="module")
def draft(model):
    """1-layer draft seeded from the target's own weights (embeddings +
    first block) — the standard deployment shape, agrees often."""
    d = _gpt(1, seed=0)
    sd, dsd = model.state_dict(), d.state_dict()
    d.set_state_dict({k: (sd[k] if k in sd
                          and tuple(sd[k].shape) == tuple(v.shape) else v)
                      for k, v in dsd.items()})
    return d


@pytest.fixture(scope="module")
def bad_draft():
    """Independently-initialized draft: proposals are mostly wrong, so
    nearly every iteration exercises the rejection path."""
    return _gpt(1, seed=99)


@pytest.fixture(scope="module")
def eng_plain(model):
    eng = GenerationEngine(model, max_slots=3, max_seq_len=40,
                           prompt_buckets="8,16").start()
    yield eng
    eng.stop()


@pytest.fixture(scope="module")
def eng_spec(model, draft):
    eng = GenerationEngine(model, max_slots=3, max_seq_len=40,
                           prompt_buckets="8,16", draft_model=draft,
                           spec_tokens=3).start()
    yield eng
    eng.stop()


def solo(model, prompt, max_new, **kw):
    ids = paddle.to_tensor(np.array([prompt], np.int32))
    out = model.generate(ids, max_new_tokens=max_new, **kw)
    return np.array(out.numpy())[0, len(prompt):].tolist()


PROMPTS = [list(range(3, 10)), [5, 9, 2], list(range(50, 62)),
           [7, 7, 7, 11, 2, 4]]


# ---------------------------------------------------------------------------
# speculative decode
# ---------------------------------------------------------------------------
class TestSpecParity:
    def test_greedy_bitwise_vs_nonspec(self, model, eng_plain, eng_spec):
        """The headline contract: same tokens, with and without the
        draft, on a full concurrent batch."""
        hp = [eng_plain.submit(p, 12, seed=i) for i, p in
              enumerate(PROMPTS)]
        hs = [eng_spec.submit(p, 12, seed=i) for i, p in
              enumerate(PROMPTS)]
        plain = [h.result(60) for h in hp]
        spec = [h.result(60) for h in hs]
        assert spec == plain
        assert spec[0] == solo(model, PROMPTS[0], 12)

    def test_mid_decode_admission(self, eng_plain, eng_spec):
        """A lane admitted while others are mid-speculation gets the
        same stream it would get alone."""
        def staggered(eng):
            hs = []
            for i, p in enumerate(PROMPTS):
                hs.append(eng.submit(p, 10, seed=i))
                time.sleep(0.03)   # land mid-iteration of the others
            return [h.result(60) for h in hs]
        assert staggered(eng_spec) == staggered(eng_plain)

    def test_sampling_matched_distribution(self, eng_plain, eng_spec):
        """Seeded sampling lanes ride the speculative executable with an
        unchanged PRNG chain: bitwise-equal streams, not just equal in
        distribution."""
        a = eng_plain.generate(PROMPTS[1], 12, timeout=60, seed=7,
                               **SAMPLE_KW)
        b = eng_spec.generate(PROMPTS[1], 12, timeout=60, seed=7,
                              **SAMPLE_KW)
        assert a == b

    def test_rejection_rollback(self, model, bad_draft):
        """A near-always-wrong draft: every iteration writes drafted KV
        for proposals the target then rejects.  Those pages are inside
        the slot's reservation and the next iteration's scatter
        overwrites them before any emitted query attends them — output
        must stay bitwise-correct across sequential slot reuse."""
        eng = GenerationEngine(model, max_slots=2, max_seq_len=40,
                               prompt_buckets="8,16",
                               draft_model=bad_draft, spec_tokens=3)
        eng.start()
        try:
            for i, p in enumerate(PROMPTS):
                assert eng.generate(p, 10, timeout=60) == \
                    solo(model, p, 10)
            snap = eng.metrics.snapshot()
            assert snap["spec_proposed"] > 0
            # mostly-rejected, never negative; strictly below a shared-
            # weight draft's ratio
            assert 0.0 <= snap["spec_accept_ratio"] < 0.9
        finally:
            eng.stop()

    @pytest.mark.parametrize("device_ms", [0, 6])
    def test_sixteen_staggered_requests_equal_solo_with_a_step_in_flight(
            self, model, draft, slow_steps, device_ms):
        """spec_step k+1 is launched before step k's 1..K+1 tokens a lane
        are handed out: greedy and seeded sampling lanes of unequal
        lengths, five times the slots, each the stream it has alone."""
        eng = GenerationEngine(model, max_slots=3, max_seq_len=40,
                               prompt_buckets="8,16", draft_model=draft,
                               spec_tokens=3).start()
        try:
            if device_ms:
                slow_steps(eng, device_ms / 1e3)
            jobs = []
            for i in range(16):
                p, n = PROMPTS[i % 4], 3 + (5 * i) % 17
                kw = dict(seed=i, **SAMPLE_KW) if i % 2 else {}
                jobs.append((eng.submit(p, n, **kw), p, n, kw))
                time.sleep(0.004)
            for h, p, n, kw in jobs:
                assert h.result(120) == solo(model, p, n, **kw)
            assert eng.drain(timeout=60) and eng._flight is None
            snap = eng.metrics.snapshot()
            assert snap["retired"] == 16 and snap["steps"] == eng._iter
            c = eng.timers.counts
            assert c["spec_decode"] == c["fetch"] == c["distribute"] \
                == eng._iter
        finally:
            eng.stop()

    def test_an_ended_lanes_slot_takes_nothing_of_the_spec_step_after(
            self, model, draft, slow_steps):
        """One slot, steps of 20 ms: A ends inside a speculative run while
        the next step is out; B takes the slot before that is collected."""
        eng = GenerationEngine(model, max_slots=1, max_seq_len=40,
                               prompt_buckets="8,16", draft_model=draft,
                               spec_tokens=3).start()
        try:
            slow_steps(eng)
            a = eng.submit(PROMPTS[0], 7)
            b = eng.submit(PROMPTS[1], 9, seed=7, **SAMPLE_KW)
            assert a.result(60) == solo(model, PROMPTS[0], 7)
            assert b.result(60) == solo(model, PROMPTS[1], 9, seed=7,
                                        **SAMPLE_KW)
            assert eng.drain(timeout=60)
            snap = eng.metrics.snapshot()
            assert snap["empty_steps"] == 2 and snap["retired"] == 2
        finally:
            eng.stop()

    def test_accept_ratio_counter(self, eng_spec):
        """The acceptance counters move and the PTA007-clean gauge is
        exposed on /metrics."""
        eng_spec.generate(PROMPTS[0], 12, timeout=60)
        snap = eng_spec.metrics.snapshot()
        assert snap["spec_proposed"] > 0
        assert 0.0 < snap["spec_accept_ratio"] <= 1.0
        text = eng_spec.metrics.prometheus_text()
        assert "paddle_genserve_spec_accept_ratio" in text
        assert "paddle_genserve_spec_proposed_total" in text


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def eng_chunk(model):
    eng = GenerationEngine(model, max_slots=3, max_seq_len=96,
                           prompt_buckets=(16, 64), page_size=4,
                           prefill_chunk=8).start()
    yield eng
    eng.stop()


class TestChunkedPrefill:
    def test_token_parity_vs_unchunked(self, model, eng_chunk):
        """A prompt sliced into 7 chunks decodes the same stream as the
        model's own one-shot generate."""
        rs = np.random.RandomState(3)
        for L in (40, 56, 23):
            p = [int(t) for t in rs.randint(1, 211, L)]
            assert eng_chunk.generate(p, 8, timeout=60) == \
                solo(model, p, 8)
        assert eng_chunk.metrics.snapshot()["prefill_chunks"] > 0

    def test_no_starvation_of_decode(self, eng_chunk):
        """The pin the chunking exists for: a short stream admitted
        BEFORE a long prompt keeps decoding one token per iteration
        while the long prompt's chunks interleave — it finishes before
        the long prompt emits its first token (4 decode iterations vs 7
        prefill chunks)."""
        short = eng_chunk.submit(list(range(2, 10)), 4)
        assert short.next_token(timeout=60) is not None  # admitted
        long_h = eng_chunk.submit([int(t) for t in
                                   np.random.RandomState(5)
                                   .randint(1, 211, 56)], 4)
        t_first_long = [None]

        def watch_long():
            if long_h.next_token(timeout=60) is not None:
                t_first_long[0] = time.monotonic()
            long_h.result(60)

        w = threading.Thread(target=watch_long)
        w.start()
        short.result(60)
        t_short_done = time.monotonic()
        w.join(60)
        assert t_first_long[0] is not None
        assert t_short_done < t_first_long[0], \
            "short stream stalled behind a long prefill"

    def test_cancel_mid_chunk_pool_tripwire(self, model):
        """Cancel a prompt halfway through its chunk schedule, repeat;
        every privately-written page must be back on the free stack
        (free_count returns to baseline — a leak here only surfaces in
        production as slow pool exhaustion)."""
        eng = GenerationEngine(model, max_slots=2, max_seq_len=96,
                               prompt_buckets=(64,), page_size=4,
                               prefill_chunk=8, prefix_cache=False)
        eng.start()
        try:
            with host_fetch():
                free0 = int(np.array(eng._state["free_count"]))
            for cycle in range(3):
                h = eng.submit(list(range(1, 57)), 4)
                time.sleep(0.04)          # a few chunks land
                h.cancel()
                h.result(60)
                # a full request through the same slots still works
                assert len(eng.generate(list(range(3, 59)), 3,
                                        timeout=60)) == 3
            deadline = time.monotonic() + 30
            while eng._sched.occupied and time.monotonic() < deadline:
                time.sleep(0.02)
            with host_fetch():
                free1 = int(np.array(eng._state["free_count"]))
            assert free1 == free0, f"page leak: {free0} -> {free1}"
            assert eng.metrics.snapshot()["prefill_chunks"] > 0
        finally:
            eng.stop()


class TestPrefixCachePressure:
    def test_distinct_prompts_do_not_starve_pool(self, model):
        """Regression: idle prefix-cache residents must be LRU-evicted
        when admission needs their pages.  A stream of DISTINCT prompts
        once parked one-reader prefixes over the whole pool —
        ``pages_available`` hit zero, nothing ever evicted (entry-count
        capacity never trips on a small pool), and the backlog head
        waited forever."""
        eng = GenerationEngine(model, max_slots=2, max_seq_len=24,
                               prompt_buckets=(8,), page_size=4,
                               num_pages=9)
        eng.start()
        try:
            rs = np.random.RandomState(3)
            prompts = [rs.randint(1, 200, 8).tolist() for _ in range(10)]
            handles = [eng.submit(p, 8) for p in prompts]
            for h in handles:
                h.result(120)          # raises on stall — the old bug
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# fleet router
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def fleet(model):
    """Two real replica servers + the router in front of them."""
    from paddle_tpu.serving.router import FleetRouter
    from paddle_tpu.serving.server import ServingServer

    servers = []
    for _ in range(2):
        eng = GenerationEngine(model, max_slots=2, max_seq_len=64,
                               prompt_buckets=(16,), page_size=4)
        servers.append(ServingServer(
            None, gen_engine=eng, port=0,
            install_signal_handlers=False).start())
    router = FleetRouter([s.url for s in servers], port=0, page_size=4,
                         probe_interval_s=0.1, dead_after=2,
                         install_signal_handlers=False).start()
    yield router, servers
    router.shutdown()
    for s in servers:
        s.shutdown()


PREFIX = list(range(1, 13))   # 12 tokens -> 2 shareable pages (ps=4)


class _Stub429(BaseHTTPRequestHandler):
    """A healthy replica at capacity: /healthz 200, /generate 429."""

    def do_GET(self):  # noqa: N802
        body = b'{"status": "ok"}'
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_POST(self):  # noqa: N802
        self.rfile.read(int(self.headers.get("Content-Length", 0)))
        body = b'{"error": "generation queue full"}'
        self.send_response(429)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):  # noqa: D102
        pass


class TestRouter:
    def test_prefix_affinity(self, fleet):
        """A shared-prefix burst sticks to one replica after the first
        request binds the prefix (so the replica-side prefix cache can
        actually hit)."""
        from paddle_tpu.serving.client import ServingClient

        router, _ = fleet
        c = ServingClient(router.url)
        for i in range(6):
            out = c.generate(PREFIX + [20 + i], max_new_tokens=3)
            assert len(out["tokens"]) == 3
        routed = router.metrics.snapshot()["routed"]
        hits = {k: v for k, v in routed.items()
                if k.endswith("|prefix_hit")}
        assert sum(hits.values()) >= 5, routed
        assert len(hits) == 1, f"prefix bounced between replicas: {routed}"

    def test_429_is_backpressure_not_death(self, fleet):
        """A replica answering 429 gets the request retried elsewhere
        and keeps its health: no failover flap under load."""
        from paddle_tpu.serving.client import ServingClient
        from paddle_tpu.serving.router import FleetRouter

        _, servers = fleet
        stub = ThreadingHTTPServer(("127.0.0.1", 0), _Stub429)
        threading.Thread(target=stub.serve_forever, daemon=True).start()
        stub_url = f"http://127.0.0.1:{stub.server_address[1]}"
        router = FleetRouter([stub_url, servers[0].url], port=0,
                             page_size=4, probe_interval_s=0.1,
                             dead_after=2,
                             install_signal_handlers=False).start()
        try:
            c = ServingClient(router.url)
            # both replicas idle -> least_loaded tie-break picks r0 (the
            # stub), which 429s; the router must retry on r1 and succeed
            out = c.generate(PREFIX + [50], max_new_tokens=3)
            assert len(out["tokens"]) == 3
            snap = router.metrics.snapshot()
            assert snap["backpressure"].get("r0") == 1, snap
            assert sum(v for k, v in snap["routed"].items()
                       if k.startswith("r1|")) == 1, snap
            time.sleep(0.3)   # several probe rounds
            assert router.replicas[0].alive, \
                "429 bumped the health-failure count"
            assert router.metrics.snapshot()["replicas_healthy"] == 2
        finally:
            router.shutdown()
            stub.shutdown()
            stub.server_close()

    def test_traceparent_continuity(self, fleet):
        """One trace across the hop: client root -> router.generate ->
        replica server.generate land in the same in-process span ring
        under the same trace id."""
        import paddle_tpu.monitor as monitor
        from paddle_tpu.monitor import tracing
        from paddle_tpu.serving.client import ServingClient

        router, _ = fleet
        old = _flags.flag("FLAGS_trace_sample_rate")
        _flags.set_flags({"FLAGS_trace_sample_rate": 1.0})
        monitor.reset()
        try:
            c = ServingClient(router.url)
            out = c.generate(PREFIX + [88], max_new_tokens=3)
            assert len(out["tokens"]) == 3
            assert c.last_traceparent is not None
            trace_id = c.last_traceparent.split("-")[1]
            want = {"client.generate", "router.generate",
                    "server.generate"}
            deadline = time.monotonic() + 5
            names = set()
            while time.monotonic() < deadline and not want <= names:
                # the router handler ends its span just AFTER the client
                # finishes reading the response body — poll briefly
                names = {s["name"] for s in tracing.default_tracer()
                         .spans(trace_id=trace_id)}
                time.sleep(0.02)
            assert want <= names, names
        finally:
            _flags.set_flags({"FLAGS_trace_sample_rate": old})
            monitor.reset()

    def test_metrics_federation(self, fleet):
        """One scrape shows router counters AND every replica's genserve
        gauges under its banner."""
        from paddle_tpu.serving.client import ServingClient

        router, _ = fleet
        text = ServingClient(router.url).metrics()
        assert "paddle_router_requests_total" in text
        assert "# replica=r0" in text and "# replica=r1" in text
        assert "paddle_genserve_decode_tokens_per_sec" in text

    def test_dead_replica_failover(self, fleet):
        """Kill the replica that owns the burst prefix: probes mark it
        dead, the next same-prefix request lands on the survivor as
        health_failover, and the affinity REBINDS (stickiness to a
        corpse would re-miss forever).  Runs last — it downs a
        replica."""
        from paddle_tpu.serving.client import ServingClient

        router, servers = fleet
        c = ServingClient(router.url)
        c.generate(PREFIX + [60], max_new_tokens=2)
        routed = router.metrics.snapshot()["routed"]
        owner = max((k for k in routed if "|prefix_hit" in k
                     or "|least_loaded" in k),
                    key=routed.get).split("|")[0]
        idx = int(owner[1:])
        servers[idx].shutdown()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if router.metrics.snapshot()["replicas_healthy"] == 1:
                break
            time.sleep(0.05)
        assert router.metrics.snapshot()["replicas_healthy"] == 1
        out = c.generate(PREFIX + [61], max_new_tokens=2)
        assert len(out["tokens"]) == 2
        snap = router.metrics.snapshot()
        assert any(k.endswith("|health_failover") for k in
                   snap["routed"]), snap
        # rebound: the NEXT same-prefix request is a prefix_hit on the
        # survivor, not another failover
        c.generate(PREFIX + [62], max_new_tokens=2)
        survivor = f"r{1 - idx}"
        assert router.metrics.snapshot()["routed"].get(
            f"{survivor}|prefix_hit", 0) >= 1
