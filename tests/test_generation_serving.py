"""Continuous-batching generation serving (paddle_tpu.serving.generation).

The contract under test: continuous batching must be INVISIBLE to each
request — a prompt admitted into a busy decode batch produces tokens
bitwise-identical to running `model.generate` alone (greedy AND
temperature/top-k sampling, per-request seed); slots are reused without
leaking a prior occupant's KV; preemption (cancel / deadline) frees the
slot mid-decode; drain finishes every in-flight decode; and after
start()'s AOT warmup the steady state NEVER compiles.

Run via tools/serve_smoke.sh (`pytest -m genserve`); also in tier-1.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (DeadlineExceededError, EngineStoppedError,
                                GenerationEngine)
from paddle_tpu.serving.kv_cache import CacheGeometry
from paddle_tpu.serving.scheduler import SlotScheduler

pytestmark = pytest.mark.genserve

PROMPT_A = list(range(3, 10))          # L=7  -> bucket 8
PROMPT_B = [5, 9, 2]                   # L=3  -> bucket 8
PROMPT_C = list(range(50, 62))         # L=12 -> bucket 16
SAMPLE_KW = dict(do_sample=True, temperature=0.8, top_k=5)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=211, hidden_size=48, num_layers=2, num_heads=4,
        max_position_embeddings=64, dropout=0.0, attn_dropout=0.0))
    m.eval()
    return m


@pytest.fixture(scope="module")
def engine(model):
    eng = GenerationEngine(model, max_slots=3, max_seq_len=40,
                           prompt_buckets="8,16").start()
    yield eng
    eng.stop()


def solo(model, prompt, max_new, **kw):
    """The reference: the model's own single-sequence generate loop."""
    ids = paddle.to_tensor(np.array([prompt], np.int32))
    out = model.generate(ids, max_new_tokens=max_new, **kw)
    return np.array(out.numpy())[0, len(prompt):].tolist()


class TestBitwiseParity:
    def test_greedy_matches_solo(self, model, engine):
        got = engine.generate(PROMPT_A, 12, timeout=60)
        assert got == solo(model, PROMPT_A, 12)

    def test_sampled_matches_solo(self, model, engine):
        got = engine.generate(PROMPT_B, 12, timeout=60, seed=7,
                              **SAMPLE_KW)
        assert got == solo(model, PROMPT_B, 12, seed=7, **SAMPLE_KW)

    def test_seed_determinism_across_slots(self, model, engine):
        """Same prompt+seed in different slots of the same batch → the
        same tokens; the per-slot PRNG chain is the request's alone."""
        hs = [engine.submit(PROMPT_B, 12, seed=7, **SAMPLE_KW)
              for _ in range(3)]
        outs = [h.result(60) for h in hs]
        ref = solo(model, PROMPT_B, 12, seed=7, **SAMPLE_KW)
        assert all(o == ref for o in outs)
        # and a different seed decodes a different (still solo-exact)
        # stream from a neighboring slot
        other = engine.generate(PROMPT_B, 12, timeout=60, seed=8,
                                **SAMPLE_KW)
        assert other == solo(model, PROMPT_B, 12, seed=8, **SAMPLE_KW)

    def test_mid_decode_admission_bitwise(self, model, engine):
        """A request submitted while another is mid-decode is admitted
        at an iteration boundary and decodes the SAME tokens it would
        alone — the acceptance criterion of the subsystem."""
        long_h = engine.submit(PROMPT_C, 25)
        first = long_h.next_token(timeout=60)   # decode provably underway
        mid = engine.submit(PROMPT_B, 12, seed=7, **SAMPLE_KW)
        assert mid.result(60) == solo(model, PROMPT_B, 12, seed=7,
                                      **SAMPLE_KW)
        rest = [first] + list(long_h)
        assert rest == solo(model, PROMPT_C, 25)

    def test_slot_reuse_isolation(self, model, engine):
        """More requests than slots: every retirement hands its slot to
        a new occupant; stale KV from the previous occupant must never
        leak into the next (the attention validity mask exposes only
        positions <= pos, and freed pages are re-written before reuse)."""
        refs = {
            "a": solo(model, PROMPT_A, 12),
            "b": solo(model, PROMPT_B, 12, seed=7, **SAMPLE_KW),
            "c": solo(model, PROMPT_C, 9),
        }
        jobs = [("a", engine.submit(PROMPT_A, 12)),
                ("b", engine.submit(PROMPT_B, 12, seed=7, **SAMPLE_KW)),
                ("c", engine.submit(PROMPT_C, 9))] * 3
        for name, h in jobs:
            assert h.result(60) == refs[name]

    def test_eos_and_single_token(self, model, engine):
        ref = solo(model, PROMPT_A, 12)
        eos = ref[4]
        got = engine.generate(PROMPT_A, 12, timeout=60, eos_token_id=eos)
        assert got == ref[:ref.index(eos) + 1]
        assert engine.generate(PROMPT_A, 1, timeout=60) == ref[:1]


class TestPreemption:
    def test_cancel_mid_decode_frees_slot(self, model, engine):
        h = engine.submit(PROMPT_C, 25)
        assert h.next_token(timeout=60) is not None
        h.cancel()
        t0 = time.monotonic()
        while not h.done and time.monotonic() - t0 < 30:
            time.sleep(0.01)
        assert h.done and h.error is None
        assert 0 < len(h.tokens) < 25
        # the slot is genuinely free: a full batch still fits
        hs = [engine.submit(PROMPT_A, 8) for _ in range(3)]
        ref = solo(model, PROMPT_A, 8)
        assert all(h2.result(60) == ref for h2 in hs)

    def test_deadline_mid_decode_frees_slot(self, model):
        """Deterministic mid-decode expiry: slow each decode iteration
        so the deadline provably lands while the lane is in flight."""
        paddle.seed(0)
        eng = GenerationEngine(model, max_slots=2, max_seq_len=40,
                               prompt_buckets="8").start()
        try:
            fast = eng._decode_exec

            def slow(params, state):
                time.sleep(0.02)
                return fast(params, state)

            eng._decode_exec = slow
            h = eng.submit(PROMPT_A, 30, deadline_ms=120)
            with pytest.raises(DeadlineExceededError):
                h.result(60)
            assert 0 < len(h.tokens) < 30      # it WAS decoding
            eng._decode_exec = fast
            # the preempted lane is free again: full batch still fits
            hs = [eng.submit(PROMPT_A, 6) for _ in range(2)]
            ref = solo(model, PROMPT_A, 6)
            assert all(h2.result(60) == ref for h2 in hs)
        finally:
            eng.stop()

    def test_validation_rejected_at_submit(self, engine):
        with pytest.raises(ValueError):
            engine.submit([], 4)
        with pytest.raises(ValueError):
            engine.submit(list(range(20)), 4)     # > largest bucket
        with pytest.raises(ValueError):
            engine.submit(PROMPT_A, 40)           # L+new > max_seq_len
        with pytest.raises(ValueError):
            engine.submit(PROMPT_A, 0)
        with pytest.raises(ValueError):
            engine.submit(PROMPT_A, 4, do_sample=True, top_k=10_000)


class TestLifecycle:
    def test_drain_finishes_inflight(self, model):
        """The SIGTERM-drain contract (ServingServer.shutdown calls
        exactly this): no new work, every queued + in-flight decode
        completes in full, loop exits."""
        paddle.seed(0)
        eng = GenerationEngine(model, max_slots=2, max_seq_len=40,
                               prompt_buckets="8").start()
        hs = [eng.submit(PROMPT_A, 10) for _ in range(4)]  # 2 queued
        assert eng.drain(timeout=120)
        ref = solo(model, PROMPT_A, 10)
        for h in hs:
            assert h.result(1) == ref        # finished BEFORE drain ret
        with pytest.raises(EngineStoppedError):
            eng.submit(PROMPT_A, 2)
        eng.stop()

    def test_warmup_logs_the_decode_steps_temporaries(self, model, caplog):
        """start() reports what the compiled decode step holds beside the
        donated pools (the compiler's `temp_size_in_bytes`), next to the
        cache's size: a copied plane or pool shows there before any run."""
        import logging

        paddle.seed(0)
        with caplog.at_level(logging.INFO, logger="paddle_tpu.serving"):
            eng = GenerationEngine(model, max_slots=2, max_seq_len=40,
                                   prompt_buckets="8").start()
        try:
            assert isinstance(eng.decode_temp_bytes, int)
            assert eng.decode_temp_bytes == int(
                eng._decode_exec.memory_analysis().temp_size_in_bytes)
            line = next(r.getMessage() for r in caplog.records
                        if "generation start-up" in r.getMessage())
            assert (f"decode temps={eng.decode_temp_bytes / 1048576:.1f} MB"
                    in line), line
            assert "cache=" in line
        finally:
            eng.stop()

    def test_stop_fails_inflight(self, model):
        paddle.seed(0)
        eng = GenerationEngine(model, max_slots=2, max_seq_len=40,
                               prompt_buckets="8").start()
        eng.submit(PROMPT_A, 30)
        eng.stop()
        # every handle resolves (no stranded client threads)


class _CompileTripwire:
    def __enter__(self):
        import jax._src.compiler as C

        self._mod = C
        self._orig = C.compile_or_get_cached

        def hook(*a, **k):
            raise AssertionError("XLA compilation after generation warmup "
                                 "— steady state must never compile")

        C.compile_or_get_cached = hook
        return self

    def __exit__(self, *exc):
        self._mod.compile_or_get_cached = self._orig
        return False


class TestZeroRecompile:
    def test_steady_state_never_compiles(self, model, engine):
        """With jax's compile entry point booby-trapped, admission +
        decode + retirement across both prompt buckets and both sampling
        modes must run purely from the warmed executables."""
        before = engine.compile_count
        with _CompileTripwire():
            hs = [engine.submit(PROMPT_A, 10),
                  engine.submit(PROMPT_B, 10, seed=3, **SAMPLE_KW),
                  engine.submit(PROMPT_C, 10)]
            for h in hs:
                assert len(h.result(120)) == 10
        assert engine.compile_count == before
        assert engine.metrics.snapshot()["compile_count"] == before


class TestMetrics:
    def test_snapshot_and_prometheus(self, engine, model):
        engine.generate(PROMPT_A, 8, timeout=60)
        snap = engine.metrics.snapshot()
        assert snap["decode_tokens_per_sec"] > 0
        assert snap["ttft_p50_ms"] > 0
        assert snap["inter_token_p99_ms"] >= snap["inter_token_p50_ms"] > 0
        assert snap["retired"] >= 1
        text = engine.metrics.prometheus_text()
        for name in ("paddle_genserve_decode_tokens_per_sec",
                     "paddle_genserve_inter_token_p99_ms",
                     "paddle_genserve_slot_occupancy",
                     "paddle_genserve_requests_total",
                     "paddle_genserve_compile_count"):
            assert name in text

    def test_monitor_co_exposure(self, engine):
        """One MonitorServer port serves training AND genserve metrics
        via extra_registries."""
        from paddle_tpu.monitor.server import MonitorServer

        mon = MonitorServer(port=0, extra_registries=(engine.metrics,))
        text = mon.metrics_text()
        assert "paddle_genserve_decode_tokens_per_sec" in text


class TestUnits:
    def test_scheduler(self):
        s = SlotScheduler(2)
        assert s.has_free() and s.free_slots == 2

        class R:
            cancelled = False
            deadline = None

        r1, r2 = R(), R()
        a, b = s.admit(r1), s.admit(r2)
        assert {a, b} == {0, 1} and not s.has_free()
        r2.cancelled = True
        swept = s.sweep()
        assert swept == [(b, r2, "cancelled")]
        assert s.retire(b) is r2
        r3 = R()
        r3.deadline = time.monotonic() - 1
        c = s.admit(r3)
        assert s.sweep() == [(c, r3, "deadline_expired")]

    def test_geometry(self):
        g = CacheGeometry(num_layers=2, max_slots=4, max_seq_len=8,
                          num_heads=2, head_dim=4, vocab_size=100,
                          page_size=4)
        assert g.pages_per_slot == 2
        assert g.num_pages == 8                    # dense-equivalent
        assert g.pool_shape == (2, 8, 4, 2, 4)
        # HBM formula: num_pages * page_bytes, page_bytes = 2(k+v) *
        # layers * page_size * heads * head_dim * itemsize
        assert g.page_bytes() == 2 * 2 * 4 * 2 * 4 * 4
        assert g.kv_bytes() == g.num_pages * g.page_bytes()
        assert g.pages_for(1) == 1 and g.pages_for(4) == 1 \
            and g.pages_for(5) == 2
        small = CacheGeometry(num_layers=2, max_slots=4, max_seq_len=8,
                              num_heads=2, head_dim=4, vocab_size=100,
                              page_size=4, num_pages=3)
        assert small.num_pages == 3                # oversubscribed pool

    def test_scheduler_page_accounting(self):
        """A free slot with an exhausted pool must NOT admit — the
        admit-and-crash (in-graph free-list underflow) failure mode."""

        class R:
            cancelled = False
            deadline = None

        s = SlotScheduler(3, num_pages=10)
        assert s.pages_available == 10
        assert s.can_admit(10) and not s.can_admit(11)
        a = s.admit(R(), n_pages=4)
        b = s.admit(R(), n_pages=4)
        assert s.pages_available == 2
        assert s.has_free() and not s.can_admit(4)   # slot free, pages not
        assert s.can_admit(2)
        s.set_shared_resident(1)                     # prefix-cache pages
        assert s.pages_available == 1 and not s.can_admit(2)
        s.retire(a)
        assert s.pages_available == 5 and s.can_admit(4)
        s.retire(b)
        s.set_shared_resident(0)
        assert s.pages_available == 10


class TestPagedPool:
    """The paged tentpole: a pool smaller than slots * pages_per_slot
    oversubscribes lanes against actual footprint; admission must queue
    (never crash) on pool exhaustion, and retirement must genuinely
    recycle pages."""

    def test_pool_exhaustion_queues_not_crashes(self, model):
        """Deterministic pool exhaustion with lanes free: a 5-page pool
        and 4-page requests serialize — the second request waits for the
        first retirement, then decodes its exact solo stream."""
        paddle.seed(0)
        eng = GenerationEngine(model, max_slots=3, max_seq_len=40,
                               prompt_buckets="8,16", page_size=4,
                               num_pages=5, prefix_cache=False).start()
        try:
            # pages_for(7 + 6) = 4 <= 5: admits alone, not alongside
            hs = [eng.submit(PROMPT_A, 6, seed=i) for i in range(3)]
            ref = solo(model, PROMPT_A, 6)
            assert hs[0].result(60) == ref
            assert hs[1].result(60) == ref and hs[2].result(60) == ref
            snap = eng.metrics.snapshot()
            assert snap["retired"] == 3 and snap.get("errors", 0) == 0
        finally:
            eng.stop()

    def test_request_larger_than_pool_rejected(self, model):
        paddle.seed(0)
        eng = GenerationEngine(model, max_slots=3, max_seq_len=40,
                               prompt_buckets="8,16", page_size=4,
                               num_pages=5, prefix_cache=False).start()
        try:
            with pytest.raises(ValueError, match="KV pages"):
                eng.submit(PROMPT_C, 12)    # pages_for(24) = 6 > 5
            assert eng.metrics.snapshot()["rejected_pages_exhausted"] == 1
        finally:
            eng.stop()

    def test_page_reuse_after_retirement(self, model):
        """Many waves through a minimal pool: every wave's pages are
        recycled from the previous wave's retirement and decode exactly
        the solo stream (stale-KV leak across page reuse would break
        parity)."""
        paddle.seed(0)
        eng = GenerationEngine(model, max_slots=3, max_seq_len=40,
                               prompt_buckets="8,16", page_size=4,
                               num_pages=8, prefix_cache=False).start()
        try:
            refs = {"a": solo(model, PROMPT_A, 6),
                    "b": solo(model, PROMPT_B, 6, seed=7, **SAMPLE_KW)}
            for _ in range(3):
                ha = eng.submit(PROMPT_A, 6)
                hb = eng.submit(PROMPT_B, 6, seed=7, **SAMPLE_KW)
                assert ha.result(60) == refs["a"]
                assert hb.result(60) == refs["b"]
        finally:
            eng.stop()


class TestPrefixCache:
    @pytest.fixture(scope="class")
    def peng(self, model):
        paddle.seed(0)
        eng = GenerationEngine(model, max_slots=3, max_seq_len=40,
                               prompt_buckets="8,16", page_size=4,
                               prefix_cache=True).start()
        yield eng
        eng.stop()

    def test_hit_tokens_identical_to_miss(self, model, peng):
        """The acceptance bar: a prefix-cache hit (suffix-only prefill
        over shared pages) decodes the SAME tokens as the cold miss."""
        ref = solo(model, PROMPT_C, 8, seed=7, **SAMPLE_KW)
        miss = peng.submit(PROMPT_C, 8, seed=7, **SAMPLE_KW).result(60)
        snap0 = peng.metrics.snapshot()
        hit = peng.submit(PROMPT_C, 8, seed=7, **SAMPLE_KW).result(60)
        snap1 = peng.metrics.snapshot()
        assert miss == ref and hit == ref
        assert snap1["prefix_cache_hits"] == snap0["prefix_cache_hits"] + 1
        assert snap1["prefix_cache_hit_ratio"] > 0

    def test_partial_prefix_hit(self, model, peng):
        """A prompt sharing only SOME leading full pages of a cached
        prompt still hits (longest page-aligned prefix) and still
        matches its own solo stream."""
        p = PROMPT_C[:8] + [7, 3, 11, 13]   # shares 2 of C's 2 pages?
        before = peng.metrics.snapshot()["prefix_cache_hits"]
        got = peng.submit(p, 8, seed=2).result(60)
        assert got == solo(model, p, 8, seed=2)
        assert peng.metrics.snapshot()["prefix_cache_hits"] == before + 1

    def test_no_hit_for_short_prompt(self, model, peng):
        """Prompts shorter than one full page + 1 token can never
        share; they run the plain prefill path."""
        before = peng.metrics.snapshot()["prefix_cache_misses"]
        got = peng.submit(PROMPT_B, 6, seed=7, **SAMPLE_KW).result(60)
        assert got == solo(model, PROMPT_B, 6, seed=7, **SAMPLE_KW)
        assert peng.metrics.snapshot()["prefix_cache_misses"] == before + 1

    def test_hit_path_never_compiles(self, peng):
        """The insert_prefix executables are warmed at start(): a hit
        admission mid-steady-state must not trigger XLA."""
        peng.generate(PROMPT_C, 4, timeout=60)      # ensure registered
        before = peng.compile_count
        with _CompileTripwire():
            assert len(peng.generate(PROMPT_C, 6, timeout=120)) == 6
        assert peng.compile_count == before

    def test_prefix_cache_units(self):
        from paddle_tpu.serving.prefix_cache import PrefixCache

        pc = PrefixCache(page_size=4)
        assert pc.shareable_pages(4) == 0       # needs >= 1 suffix token
        assert pc.shareable_pages(5) == 1
        assert pc.shareable_pages(12) == 2
        prompt = np.arange(12, dtype=np.int32)
        assert pc.lookup(prompt) == (0, ())
        row = np.array([10, 11, 12], np.int32)
        pc.pin([10, 11])
        assert pc.register(prompt, row, 0, 2) == []
        j, pages = pc.lookup(prompt)
        assert j == 2 and pages == (10, 11)
        # a prompt sharing one page hits the shorter entry
        other = np.array([0, 1, 2, 3, 9, 9], np.int32)
        assert pc.lookup(other) == (1, (10,))
        assert pc.resident_pages == 2
        # unpin: entries still reference both pages -> nothing reclaimed
        assert pc.unpin([10, 11]) == []
        assert pc.resident_pages == 2

    def test_prefix_cache_eviction_reclaims(self):
        from paddle_tpu.serving.prefix_cache import PrefixCache

        pc = PrefixCache(page_size=2, capacity=2)
        a = np.array([1, 2, 3], np.int32)       # 1 shareable page
        b = np.array([4, 5, 6], np.int32)
        c = np.array([7, 8, 9], np.int32)
        assert pc.register(a, np.array([0], np.int32), 0, 1) == []
        assert pc.register(b, np.array([1], np.int32), 0, 1) == []
        # third entry LRU-evicts a's entry; page 0 is unreferenced
        assert pc.register(c, np.array([2], np.int32), 0, 1) == [0]
        assert pc.lookup(a) == (0, ()) and pc.lookup(c) == (1, (2,))


class TestTensorParallel:
    def test_tp2_token_parity_and_zero_compiles(self, model):
        """One engine, tp=2 mesh: the page pool's head axis shards over
        tp, every executable compiles under NamedSharding at start(),
        steady state never compiles, and tokens match the unsharded
        engine exactly."""
        import jax

        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices")
        paddle.seed(0)
        eng = GenerationEngine(model, max_slots=3, max_seq_len=40,
                               prompt_buckets="8,16", page_size=4,
                               mesh={"tp": 2}).start()
        try:
            assert eng._mesh.devices.size == 2
            ref_a = solo(model, PROMPT_A, 8)
            ref_b = solo(model, PROMPT_B, 8, seed=7, **SAMPLE_KW)
            ref_c = solo(model, PROMPT_C, 6, seed=1)
            before = eng.compile_count
            with _CompileTripwire():
                ha = eng.submit(PROMPT_A, 8)
                hb = eng.submit(PROMPT_B, 8, seed=7, **SAMPLE_KW)
                assert ha.result(120) == ref_a
                assert hb.result(120) == ref_b
                # prefix hit under the mesh too
                hc = eng.submit(PROMPT_C, 6, seed=1)
                hc2 = eng.submit(PROMPT_C, 6, seed=1)
                assert hc.result(120) == hc2.result(120) == ref_c
            assert eng.compile_count == before
            assert eng.metrics.snapshot()["prefix_cache_hits"] >= 1
        finally:
            eng.stop()


@pytest.fixture(scope="module")
def server(model):
    from paddle_tpu.serving.server import ServingServer

    eng = GenerationEngine(model, max_slots=3, max_seq_len=40,
                           prompt_buckets="8,16")
    srv = ServingServer(None, gen_engine=eng, port=0,
                        install_signal_handlers=False).start()
    yield srv
    srv.shutdown()


class TestHTTP:
    def test_blocking_generate(self, model, server):
        from paddle_tpu.serving.client import ServingClient

        cli = ServingClient(server.url)
        out = cli.generate(PROMPT_A, 10)
        assert out["tokens"] == solo(model, PROMPT_A, 10)
        assert out["ttft_ms"] > 0 and out["latency_ms"] > 0

    def test_streaming_sse(self, model, server):
        from paddle_tpu.serving.client import ServingClient

        cli = ServingClient(server.url)
        toks, done = [], None
        for evt in cli.generate_stream(PROMPT_B, 10, seed=7, **SAMPLE_KW):
            if "token" in evt:
                toks.append(evt["token"])
            if evt.get("done"):
                done = evt
        assert toks == solo(model, PROMPT_B, 10, seed=7, **SAMPLE_KW)
        assert done["tokens"] == 10 and "error" not in done

    def test_concurrent_streams(self, model, server):
        from paddle_tpu.serving.client import ServingClient

        cli = ServingClient(server.url)
        ref, outs = solo(model, PROMPT_A, 10), {}

        def go(i):
            outs[i] = [e["token"] for e in cli.generate_stream(PROMPT_A, 10)
                       if "token" in e]

        ts = [threading.Thread(target=go, args=(i,)) for i in range(5)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert all(outs[i] == ref for i in range(5))

    def test_admission_errors(self, server):
        from paddle_tpu.serving.client import (ServingClient,
                                               ServingHTTPError)

        cli = ServingClient(server.url)
        with pytest.raises(ServingHTTPError) as e:
            cli.generate([], 4)
        assert e.value.status == 400
        with pytest.raises(ServingHTTPError) as e:
            cli.generate(PROMPT_A, 500)
        assert e.value.status == 400
        with pytest.raises(ServingHTTPError) as e:
            cli.predict([[1.0, 2.0]])       # no predict engine mounted
        assert e.value.status == 404

    def test_metrics_endpoint(self, server):
        from paddle_tpu.serving.client import ServingClient

        text = ServingClient(server.url).metrics()
        assert "paddle_genserve_decode_tokens_per_sec" in text
        assert "paddle_genserve_compile_count" in text


# -- one step in flight ------------------------------------------------------
def staggered_mix(n=16):
    """`n` requests of unequal lengths, greedy and sampled by turns."""
    prompts = (PROMPT_A, PROMPT_B, PROMPT_C, [7, 7, 7, 11, 2, 4])
    return [(prompts[i % 4], 3 + (5 * i) % 17,
             dict(seed=i, **SAMPLE_KW) if i % 2 else {}) for i in range(n)]


class TestStepInFlight:
    """The loop launches step k+1 before it fetches and hands out step k
    (`_launch`, `_collect`): the streams stay each request's own."""

    @pytest.mark.parametrize("device_ms", [0, 6])
    def test_sixteen_staggered_requests_equal_the_solo_reference(
            self, model, slow_steps, device_ms):
        """Greedy and sampled, unequal lengths, five times the slots;
        with steps that outlast the host's part of an iteration too."""
        eng = GenerationEngine(model, max_slots=3, max_seq_len=40,
                               prompt_buckets="8,16").start()
        try:
            if device_ms:
                slow_steps(eng, device_ms / 1e3)
            jobs = []
            for prompt, n, kw in staggered_mix():
                jobs.append((eng.submit(prompt, n, **kw), prompt, n, kw))
                time.sleep(0.004)      # lands mid-iteration of the others
            for h, prompt, n, kw in jobs:
                assert h.result(120) == solo(model, prompt, n, **kw)
            assert eng.drain(timeout=60) and eng._flight is None
            snap = eng.metrics.snapshot()
            assert snap["retired"] == 16 and snap["steps"] == eng._iter
        finally:
            eng.stop()

    @pytest.mark.parametrize("ending", ["max_new_tokens", "eos"])
    def test_an_ended_lanes_slot_takes_nothing_of_the_step_after(
            self, model, slow_steps, ending):
        """One slot, steps of 20 ms: request A ends in step k while step
        k+1 (launched before the host knew) is in flight; B is admitted
        into A's slot before k+1 is collected.  Neither reads k+1."""
        ref_a = solo(model, PROMPT_A, 12)
        kw = {"eos_token_id": ref_a[5]} if ending == "eos" else {}
        want_a = ref_a[:ref_a.index(ref_a[5]) + 1] if kw else ref_a[:6]
        eng = GenerationEngine(model, max_slots=1, max_seq_len=40,
                               prompt_buckets="8").start()
        try:
            slow_steps(eng)
            a = eng.submit(PROMPT_A, 12 if kw else 6, **kw)
            b = eng.submit(PROMPT_B, 9, seed=7, **SAMPLE_KW)
            assert a.result(60) == want_a
            assert b.result(60) == solo(model, PROMPT_B, 9, seed=7,
                                        **SAMPLE_KW)
            assert eng.drain(timeout=60)
            snap = eng.metrics.snapshot()
            # A's and B's last steps were each followed by a step that
            # ran with no lane armed, and nobody took a token of either
            assert snap["empty_steps"] == 2 and snap["retired"] == 2
            assert eng._flight is None
        finally:
            eng.stop()

    def test_cancel_and_deadline_with_a_step_in_flight(self, model,
                                                       slow_steps):
        eng = GenerationEngine(model, max_slots=2, max_seq_len=40,
                               prompt_buckets="8,16").start()
        try:
            slow_steps(eng)
            gone = eng.submit(PROMPT_C, 25)
            late = eng.submit(PROMPT_A, 30, deadline_ms=150)
            for _ in range(3):                  # steps are going out
                assert gone.next_token(timeout=60) is not None
            gone.cancel()
            with pytest.raises(DeadlineExceededError):
                late.result(60)
            t0 = time.monotonic()
            while not gone.done and time.monotonic() - t0 < 30:
                time.sleep(0.005)
            assert gone.done and gone.error is None
            assert 0 < len(gone.tokens) < 25 and 0 < len(late.tokens) < 30
            # what they had is a prefix of their own streams, and both
            # slots serve the next requests exactly
            assert gone.tokens == solo(model, PROMPT_C, 25)[:len(gone.tokens)]
            assert late.tokens == solo(model, PROMPT_A, 30)[:len(late.tokens)]
            hs = [eng.submit(PROMPT_B, 8, seed=3, **SAMPLE_KW)
                  for _ in range(2)]
            ref = solo(model, PROMPT_B, 8, seed=3, **SAMPLE_KW)
            assert all(h.result(60) == ref for h in hs)
            # steps went out ahead all the while (not those behind an
            # admission, whose fetch waits for the device)
            snap = eng.metrics.snapshot()
            assert snap["steps_launched_ahead"] / snap["steps"] > 0.3
            assert snap["cancelled"] == 1 and snap["deadline_expired"] == 1
        finally:
            eng.stop()

    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning")
    @pytest.mark.parametrize("how", ["drain", "stop", "raise"])
    def test_no_handle_is_left_unfinished(self, model, slow_steps, how):
        from paddle_tpu.utils import chaos

        eng = GenerationEngine(model, max_slots=2, max_seq_len=40,
                               prompt_buckets="8").start()
        slow_steps(eng)
        hs = [eng.submit(PROMPT_A, 20), eng.submit(PROMPT_B, 14),
              eng.submit(PROMPT_A, 9)]          # the third waits for a slot
        assert hs[0].next_token(timeout=60) is not None
        if how == "drain":
            assert eng.drain(timeout=120)
            assert [len(h.result(1)) for h in hs] == [20, 14, 9]
        elif how == "stop":
            eng.stop()
        else:
            with chaos.inject(crash_at_step=eng._iter + 3):
                for h in hs:
                    with pytest.raises(EngineStoppedError):
                        h.result(60)
            eng._thread.join(30)
            # the step launched before the failing one was collected: its
            # tokens reached their lanes before everything failed
            assert eng.timers.counts["fetch"] \
                == eng.timers.counts["distribute"] == eng._iter - 1
        assert all(h.done for h in hs)
        assert eng._flight is None
        ref = solo(model, PROMPT_A, 20)
        assert hs[0].tokens == ref[:len(hs[0].tokens)]
        eng.stop()

    def test_launched_ahead_and_empty_steps_are_counted(self, model,
                                                        slow_steps):
        eng = GenerationEngine(model, max_slots=2, max_seq_len=40,
                               prompt_buckets="8").start()
        try:
            slow_steps(eng)
            assert len(eng.generate(PROMPT_A, 30, timeout=60)) == 30
            assert eng.drain(timeout=60)
            snap = eng.metrics.snapshot()
            assert snap["steps"] == eng._iter == 30
            # every step but the first found the one before it running
            assert snap["steps_launched_ahead"] / snap["steps"] > 0.9
            # the lane's last token came from step 29; step 30 had been
            # launched by then and ran with no lane armed
            assert snap["empty_steps"] == 1
            text = eng.metrics.prometheus_text()
            assert "paddle_genserve_steps_launched_ahead_total " \
                f"{snap['steps_launched_ahead']}" in text
            assert "paddle_genserve_empty_steps_total 1" in text
            assert "paddle_genserve_steps_total 30" in text
        finally:
            eng.stop()

    @pytest.mark.parametrize("page_size", [4, 8])
    def test_page_walk_counters_follow_the_lanes_lengths(self, model,
                                                         page_size):
        """`paged_pages_live / paged_page_slots` is the share of the page
        tables that the live lanes' extents cover, from the lanes'
        lengths alone: a lane with a prompt of L tokens attends from L + n
        - 1 in its n-th decode step, over the pages up to that one."""
        eng = GenerationEngine(model, max_slots=3, max_seq_len=40,
                               prompt_buckets="8",
                               page_size=page_size).start()
        try:
            asked = [(PROMPT_A, 20), (PROMPT_B, 9), (PROMPT_A, 2)]
            hs = [eng.submit(p, m) for p, m in asked]
            assert [len(h.result(60)) for h in hs] == [m for _, m in asked]
            assert eng.drain(timeout=60)
            snap = eng.metrics.snapshot()
            layers, cols = 2, 40 // page_size
            # a request's first token is its prefill's: m - 1 decode steps
            live = sum((len(p) + n - 1) // page_size + 1
                       for p, m in asked for n in range(1, m))
            assert snap["paged_pages_live"] == layers * live
            assert snap["paged_page_slots"] \
                == snap["steps"] * layers * 3 * cols
            assert 0 < snap["paged_pages_live"] < snap["paged_page_slots"]
            text = eng.metrics.prometheus_text()
            assert "paddle_genserve_paged_page_slots_total " \
                f"{snap['paged_page_slots']}" in text
            assert "paddle_genserve_paged_pages_live_total " \
                f"{snap['paged_pages_live']}" in text
        finally:
            eng.stop()
