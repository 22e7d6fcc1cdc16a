"""paddle_tpu.serving — adaptive-batching serving engine tests.

Pins the four serving contracts (ISSUE 3 acceptance):
  * adaptive batching — flush on max_batch_size OR batch_timeout_ms,
    padded into shape buckets, responses bitwise-identical to a direct
    single-request Predictor.run (batched-vs-single parity)
  * zero XLA compilations after warmup — a compile tripwire on
    jax's compile entry point stays silent across concurrent traffic
    spanning multiple shape buckets
  * bounded-queue backpressure, deadlines, and cancellation
  * graceful SIGTERM drain (utils.chaos self-preemption): in-flight and
    queued requests complete, new work is rejected, clean exit
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import inference, serving
from paddle_tpu.serving import (
    BucketSpec,
    DeadlineExceededError,
    EngineStoppedError,
    QueueFullError,
    ServingClient,
    ServingEngine,
    ServingServer,
)
from paddle_tpu.utils import chaos

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def exported_mlp(tmp_path_factory):
    """Symbolic-batch, symbolic-seq Linear stack: (B, S, 8) -> (B, S, 3).
    Row- and token-independent math, so padded slots cannot perturb real
    outputs — the bitwise parity oracle."""
    paddle.seed(7)
    net = paddle.nn.Sequential(paddle.nn.Linear(8, 16), paddle.nn.ReLU(),
                               paddle.nn.Linear(16, 3))
    net.eval()
    prefix = str(tmp_path_factory.mktemp("serving") / "mlp")
    from paddle_tpu.static import InputSpec
    inference.save_inference_model(
        prefix, net, input_spec=[InputSpec([-1, -1, 8], "float32")],
        example_inputs=[np.zeros((2, 4, 8), np.float32)])
    return prefix


def _sample(i, seq=4):
    return np.random.RandomState(i).randn(seq, 8).astype(np.float32)


class TestBucketSpec:
    def test_parse_batch_only(self):
        b = BucketSpec.parse("1,2,4,8")
        assert b.batch_sizes == [1, 2, 4, 8]
        assert b.seq_lens is None
        assert b.max_batch == 8
        assert b.batch_for(3) == 4
        assert b.batch_for(9) == 8  # clamped to largest
        assert b.seq_for(999) == 999  # pass-through without seq buckets

    def test_parse_batch_x_seq(self):
        b = BucketSpec.parse("1,4x16,32")
        assert b.batch_sizes == [1, 4]
        assert b.seq_lens == [16, 32]
        assert b.seq_for(10) == 16
        assert b.seq_for(17) == 32
        with pytest.raises(ValueError, match="exceeds"):
            b.seq_for(33)

    def test_powers_of_two(self):
        assert BucketSpec.powers_of_two(8).batch_sizes == [1, 2, 4, 8]
        assert BucketSpec.powers_of_two(6).batch_sizes == [1, 2, 4, 6]

    def test_invalid(self):
        with pytest.raises(ValueError):
            BucketSpec.parse("")
        with pytest.raises(ValueError):
            BucketSpec([0, 2])


class TestAdaptiveBatching:
    def test_timeout_flush_coalesces_partial_batch(self, exported_mlp):
        """3 concurrent requests < max_batch: ONE batch dispatched at the
        timeout, padded to the bucket (4), every response bitwise-equal
        to its direct single-request run."""
        eng = ServingEngine(exported_mlp, max_batch_size=8,
                            batch_timeout_ms=20, buckets="1,2,4,8x4")
        with eng:
            samples = [_sample(i) for i in range(3)]
            futs = [eng.submit([s]) for s in samples]
            outs = [f.result(timeout=10) for f in futs]
        pred = inference.create_predictor(inference.Config(exported_mlp))
        for s, (o,) in zip(samples, outs):
            direct, = pred.run([s[None]])
            np.testing.assert_array_equal(o, direct[0])
        snap = eng.metrics.snapshot()
        assert snap["batches"] == 1          # coalesced, not 3 singles
        assert snap["mean_batch_size"] == 3.0
        assert snap["padding_waste_ratio"] == pytest.approx(0.25)  # 1/4

    def test_full_batch_flushes_without_waiting(self, exported_mlp):
        """max_batch requests flush immediately (well before a long
        timeout)."""
        eng = ServingEngine(exported_mlp, max_batch_size=4,
                            batch_timeout_ms=5_000, buckets="1,2,4x4")
        with eng:
            t0 = time.monotonic()
            futs = [eng.submit([_sample(i)]) for i in range(4)]
            for f in futs:
                f.result(timeout=10)
            elapsed = time.monotonic() - t0
        assert elapsed < 2.0  # nowhere near the 5s timeout
        assert eng.metrics.snapshot()["mean_batch_size"] == 4.0

    def test_multi_bucket_bitwise_parity(self, exported_mlp):
        """E2E acceptance: concurrent requests across ≥2 shape buckets
        (seq 4 and seq 8) return responses bitwise-identical to direct
        Predictor.run."""
        eng = ServingEngine(exported_mlp, batch_timeout_ms=2,
                            buckets="1,2,4x4,8")
        pred = inference.create_predictor(inference.Config(exported_mlp))
        with eng:
            cases = [(i, _sample(i, seq=4 if i % 2 else 8))
                     for i in range(12)]
            results = {}

            def fire(i, s):
                results[i] = eng.predict([s], timeout=10)

            threads = [threading.Thread(target=fire, args=c) for c in cases]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        assert len(results) == 12
        for i, s in cases:
            direct, = pred.run([s[None]])
            np.testing.assert_array_equal(results[i][0], direct[0])

    def test_seq_padding_unpads_to_original_length(self, exported_mlp):
        """A seq-3 request padded into the seq-4 bucket comes back
        sliced to 3 tokens and equal to its unpadded direct run to a few
        ulp (rtol 1e-6), not bitwise: the bucket's executable has another
        shape than the direct run's, and the compiler may order a
        product's sums differently in each."""
        eng = ServingEngine(exported_mlp, batch_timeout_ms=2,
                            buckets="1,2x4")
        with eng:
            s = _sample(0, seq=3)
            out, = eng.predict([s], timeout=10)
        assert out.shape == (3, 3)
        pred = inference.create_predictor(inference.Config(exported_mlp))
        direct, = pred.run([s[None]])
        np.testing.assert_allclose(out, direct[0], rtol=1e-6)
        assert eng.metrics.snapshot()["padding_waste_ratio"] > 0

    def test_oversized_seq_rejected_at_submit(self, exported_mlp):
        eng = ServingEngine(exported_mlp, buckets="1,2x4")
        with eng:
            with pytest.raises(ValueError, match="exceeds"):
                eng.submit([_sample(0, seq=5)])

    def test_fixed_seq_export_only_pads_to_that_dim(self):
        """With a FIXED export seq dim, only requests whose bucket IS
        that dim are admitted — a request landing in any other bucket
        would be a shape the artifact cannot serve (and warmup never
        compiled), so it must fail at submit, not dispatch."""
        class Echo:
            def run(self, arrays):
                return [np.asarray(arrays[0])]

        eng = ServingEngine(Echo(), batch_timeout_ms=1, buckets="1,2x4,8",
                            input_specs=[((-1, 8, 2), "float32")])
        with eng:
            with pytest.raises(ValueError, match="dim 0"):
                eng.submit([np.zeros((3, 2), np.float32)])  # bucket 4 != 8
            out, = eng.predict([np.zeros((5, 2), np.float32)], timeout=10)
            assert out.shape == (5, 2)  # padded to 8, sliced back to 5


class _BlockingRunner:
    """Duck-typed predictor whose run() blocks until released — makes
    queue-pressure and deadline timing deterministic."""

    def __init__(self):
        self.release = threading.Event()
        self.calls = 0

    def run(self, arrays):
        self.calls += 1
        assert self.release.wait(30)
        return [np.asarray(arrays[0]) * 2.0]


class TestBackpressureDeadlinesCancellation:
    def _engine(self, runner, **kw):
        return ServingEngine(runner, max_batch_size=1, batch_timeout_ms=0,
                             buckets="1", **kw)

    def _start_blocked(self, eng, runner):
        fut = eng.submit([np.ones(2, np.float32)])
        deadline = time.monotonic() + 10
        while runner.calls == 0:  # batcher now blocked inside run()
            assert time.monotonic() < deadline
            time.sleep(0.001)
        return fut

    def test_queue_full_backpressure(self):
        runner = _BlockingRunner()
        eng = self._engine(runner, queue_depth=2)
        with eng:
            first = self._start_blocked(eng, runner)
            ok = [eng.submit([np.ones(2, np.float32)]) for _ in range(2)]
            with pytest.raises(QueueFullError):
                eng.submit([np.ones(2, np.float32)])
            assert eng.metrics.counters["rejected_queue_full"] == 1
            runner.release.set()
            for f in [first] + ok:
                np.testing.assert_array_equal(
                    f.result(timeout=10)[0], np.full(2, 2.0, np.float32))

    def test_deadline_expires_while_queued(self):
        runner = _BlockingRunner()
        eng = self._engine(runner, queue_depth=8)
        with eng:
            first = self._start_blocked(eng, runner)
            doomed = eng.submit([np.ones(2, np.float32)], deadline_ms=30)
            time.sleep(0.08)          # deadline passes while blocked
            runner.release.set()
            first.result(timeout=10)
            with pytest.raises(DeadlineExceededError):
                doomed.result(timeout=10)
            assert eng.metrics.counters["deadline_expired"] == 1

    def test_cancelled_request_never_runs(self):
        runner = _BlockingRunner()
        eng = self._engine(runner, queue_depth=8)
        with eng:
            first = self._start_blocked(eng, runner)
            victim = eng.submit([np.ones(2, np.float32)])
            assert victim.cancel()
            runner.release.set()
            first.result(timeout=10)
            eng.drain(timeout=10)
            assert victim.cancelled()
        assert runner.calls == 1      # the cancelled request cost no batch
        assert eng.metrics.counters["cancelled"] == 1

    def test_cancelled_then_expired_request_does_not_kill_batcher(self):
        """A request that is BOTH cancelled and deadline-expired must be
        dropped by the sweep, not set_exception'd (InvalidStateError
        would kill the batcher thread)."""
        runner = _BlockingRunner()
        eng = self._engine(runner, queue_depth=8)
        with eng:
            first = self._start_blocked(eng, runner)
            victim = eng.submit([np.ones(2, np.float32)], deadline_ms=10)
            assert victim.cancel()
            time.sleep(0.05)          # deadline long past when swept
            runner.release.set()
            first.result(timeout=10)
            out, = eng.predict([np.ones(2, np.float32)], timeout=10)
            np.testing.assert_array_equal(out, np.full(2, 2.0, np.float32))

    def test_batchless_output_fails_batch_not_engine(self):
        """A model output missing the batch dim fails that batch's
        futures — the batcher survives and keeps draining."""
        class NoBatchDim:
            def run(self, arrays):
                return [np.float32(1.0)]

        eng = self._engine(NoBatchDim(), queue_depth=8)
        with eng:
            with pytest.raises(Exception):
                eng.predict([np.ones(2, np.float32)], timeout=10)
            assert eng.drain(timeout=10)   # batcher alive to finish
        assert eng.metrics.counters["errors"] == 1

    def test_shape_signature_cap_without_specs(self):
        """No input specs = no shape validation — the max_buckets cap is
        what stops shape-cycling traffic from forcing one compile per
        request (each cached forever)."""
        class Echo:
            def run(self, arrays):
                return [np.asarray(arrays[0]) * 2.0]

        eng = ServingEngine(Echo(), max_batch_size=1, batch_timeout_ms=0,
                            buckets="1", queue_depth=8, max_buckets=2)
        with eng:
            eng.predict([np.ones(2, np.float32)], timeout=10)
            eng.predict([np.ones(3, np.float32)], timeout=10)
            with pytest.raises(ValueError, match="max_buckets"):
                eng.submit([np.ones(4, np.float32)])
            # known signatures still served after the cap trips
            out, = eng.predict([np.ones(2, np.float32)], timeout=10)
            np.testing.assert_array_equal(out, np.full(2, 2.0, np.float32))

    def test_submit_after_drain_rejected(self):
        runner = _BlockingRunner()
        runner.release.set()
        eng = self._engine(runner, queue_depth=8)
        with eng:
            eng.predict([np.ones(2, np.float32)], timeout=10)
            assert eng.drain(timeout=10)
            with pytest.raises(EngineStoppedError):
                eng.submit([np.ones(2, np.float32)])

    def test_batch_error_fails_those_futures_not_the_engine(self):
        class Exploding:
            calls = 0

            def run(self, arrays):
                self.calls += 1
                if self.calls == 1:
                    raise RuntimeError("boom")
                return [np.asarray(arrays[0]) * 2.0]

        eng = self._engine(Exploding(), queue_depth=8)
        with eng:
            with pytest.raises(RuntimeError, match="boom"):
                eng.predict([np.ones(2, np.float32)], timeout=10)
            # engine survives and serves the next request
            out, = eng.predict([np.ones(2, np.float32)], timeout=10)
            np.testing.assert_array_equal(out, np.full(2, 2.0, np.float32))
        assert eng.metrics.counters["errors"] == 1


class _CompileTripwire:
    """Fails the test on ANY XLA compilation while armed — the serving
    analog of test_train_engine's sync tripwires."""

    def __enter__(self):
        import jax._src.compiler as C

        self._mod = C
        self._orig = C.compile_or_get_cached

        def hook(*a, **k):
            raise AssertionError(
                "XLA compilation after serving warmup — the bucket cache "
                "missed (recompile storm)")

        C.compile_or_get_cached = hook
        return self

    def __exit__(self, *exc):
        self._mod.compile_or_get_cached = self._orig
        return False


class TestZeroRecompileAfterWarmup:
    def test_steady_state_never_compiles(self, exported_mlp):
        """Warm every (batch × seq) bucket, then serve concurrent mixed
        traffic with jax's compile entry point booby-trapped: any
        compilation fails the test.  Responses stay bitwise-correct."""
        pred = inference.create_predictor(inference.Config(exported_mlp))
        eng = ServingEngine(pred, batch_timeout_ms=2, buckets="1,2,4x4,8")
        # oracle outputs (and their batch-1 buckets) computed BEFORE
        # arming the tripwire
        cases = [(i, _sample(i, seq=4 + 4 * (i % 2))) for i in range(16)]
        oracle = {i: pred.run([s[None]])[0][0] for i, s in cases}
        eng.start()
        warmed = pred.compile_count
        assert warmed >= 6  # 3 batch × 2 seq buckets (+ oracle shapes)
        with _CompileTripwire():
            results = {}

            def fire(i, s):
                results[i] = eng.predict([s], timeout=30)

            threads = [threading.Thread(target=fire, args=c) for c in cases]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert eng.drain(timeout=30)
        assert pred.compile_count == warmed
        assert eng.metrics.snapshot()["compile_count"] == warmed
        for i, s in cases:
            np.testing.assert_array_equal(results[i][0], oracle[i])

    def test_tripwire_catches_real_compile(self):
        """Meta-test: the tripwire actually fires on a fresh compile."""
        import jax
        import jax.numpy as jnp

        with _CompileTripwire():
            with pytest.raises(AssertionError, match="recompile"):
                jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0))


class TestHTTPServer:
    @pytest.fixture()
    def server(self, exported_mlp):
        eng = ServingEngine(exported_mlp, batch_timeout_ms=2,
                            buckets="1,2,4x4")
        srv = ServingServer(eng, port=0,
                            install_signal_handlers=False).start()
        yield srv
        srv.shutdown()

    def test_predict_healthz_metrics(self, server, exported_mlp):
        client = ServingClient(server.url)
        h = client.healthz()
        assert h["status_code"] == 200 and h["status"] == "ok"
        # enriched identity fields (PR 12): fleet sweeps compare these
        # to detect version skew
        assert h["pid"] > 0 and h["device_count"] >= 1
        assert "version" in h and "jax_version" in h
        assert h["uptime_s"] >= 0.0
        s = _sample(3)
        out, = client.predict([s])
        pred = inference.create_predictor(inference.Config(exported_mlp))
        direct, = pred.run([s[None]])
        np.testing.assert_array_equal(out, direct[0])
        text = client.metrics()
        for needle in ("paddle_serving_qps", "paddle_serving_p99_ms",
                       "paddle_serving_p50_ms",
                       "paddle_serving_padding_waste_ratio",
                       "paddle_serving_batch_size_bucket",
                       "paddle_serving_queue_latency_ms_bucket"):
            assert needle in text, needle

    def test_bad_requests(self, server):
        client = ServingClient(server.url)
        # raw bodies straight to the server (bypassing client-side
        # validation): ragged input, missing key, unknown route
        status, _ = client._request("/predict",
                                    {"inputs": [[[1.0], [1.0, 2.0]]]})
        assert status == 400
        status, _ = client._request("/predict", {"not_inputs": 1})
        assert status == 400
        # wrong rank vs the export manifest: rejected at submit, not a
        # 500 out of XLA
        status, _ = client._request("/predict", {"inputs": [[1.0, 2.0]]})
        assert status == 400
        status, _ = client._request("/nope")
        assert status == 404


class TestSigtermDrain:
    def test_chaos_preemption_drains_clean(self, exported_mlp):
        """E2E acceptance: chaos.inject self-preemption (SIGTERM from the
        batcher thread, latched by the resilience guard) → server drains
        — every accepted request completes, new work is rejected, wait()
        returns 0."""
        # max bucket 8 + a 60ms flush window: all 8 requests (across TWO
        # seq buckets) are accepted before the first dispatch fires the
        # injected self-SIGTERM, so every one of them is in-flight when
        # the drain starts — the drain must complete them all
        eng = ServingEngine(exported_mlp, batch_timeout_ms=60,
                            buckets="1,2,4,8x4,8")
        srv = ServingServer(eng, port=0).start()  # installs the latch
        client = ServingClient(srv.url)
        samples = {i: _sample(i, seq=4 if i % 2 else 8) for i in range(8)}
        results, errors = [], []

        def fire(i):
            try:
                results.append((i, client.predict([samples[i]])))
            except Exception as e:  # noqa: BLE001
                errors.append((i, e))

        with chaos.inject(preempt_at_step=1):
            threads = [threading.Thread(target=fire, args=(i,))
                       for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert srv.wait(timeout=30) == 0  # clean drain exit
        assert chaos.active_config().fired == []  # inject popped
        assert not errors, errors
        assert len(results) == 8
        pred = inference.create_predictor(inference.Config(exported_mlp))
        for i, (out,) in results:
            direct, = pred.run([samples[i][None]])
            np.testing.assert_array_equal(out, direct[0])
        # engine rejects post-drain work; the listener is closed
        with pytest.raises(EngineStoppedError):
            eng.submit([_sample(0)])
        with pytest.raises(Exception):
            client.healthz()

    def test_programmatic_shutdown_is_clean(self, exported_mlp):
        eng = ServingEngine(exported_mlp, batch_timeout_ms=2, buckets="1x4")
        srv = ServingServer(eng, port=0,
                            install_signal_handlers=False).start()
        ServingClient(srv.url).predict([_sample(1)])
        assert srv.shutdown() is True
        assert srv.wait(timeout=5) == 0
        assert srv.shutdown() is True  # idempotent


class TestModelServe:
    def test_model_serve_roundtrip(self):
        paddle.seed(0)
        net = paddle.nn.Sequential(paddle.nn.Linear(8, 16),
                                   paddle.nn.ReLU(),
                                   paddle.nn.Linear(16, 3))
        model = paddle.Model(net)
        srv = model.serve(
            port=0, blocking=False, install_signal_handlers=False,
            input_spec=[paddle.static.InputSpec([-1, 8], "float32")],
            max_batch_size=4, batch_timeout_ms=2)
        try:
            x = np.random.RandomState(0).randn(8).astype(np.float32)
            out, = ServingClient(srv.url).predict([x])
            ref = np.asarray(model.predict_batch(
                [paddle.to_tensor(x[None])]).numpy())[0]
            np.testing.assert_array_equal(out, ref)
            assert srv.engine._predictor.compile_count >= 3  # warmed
        finally:
            srv.shutdown()
