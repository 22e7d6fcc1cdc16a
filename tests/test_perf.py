"""Performance introspection suite (`perf` marker — ISSUE 13):

  * monitor/perf.py HLO parser vs XLA's own cost analysis (summed table
    flops within 5% — in practice exact — on a compiled grad step);
  * op-table schema, bound classification, trace-time join, tail rollup
    (sums stay exact);
  * engine.op_report() end-to-end on a CPU train step;
  * buffer census bucket math with known owner-tagged arrays;
  * fake RESOURCE_EXHAUSTED → flight-recorder "oom" dump carrying the
    census;
  * GET /debug/perf JSON + ?format=chrome (span AND device-op tracks).
"""
import json
import os
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.hapi.model import Model
from paddle_tpu.monitor import flightrec, perf

pytestmark = pytest.mark.perf


def _model(d=8, h=16):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(d, h), nn.Tanh(), nn.Linear(h, 1))
    m = Model(net)
    m.prepare(paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=net.parameters()),
              nn.MSELoss())
    return m


def _engine(m):
    from paddle_tpu.hapi.engine import TrainEngine
    return TrainEngine(m).begin()


def _batch(n=8, d=8):
    x = paddle.to_tensor(np.zeros((n, d), "float32"))
    y = paddle.to_tensor(np.zeros((n, 1), "float32"))
    return [x], [y]


@pytest.fixture(autouse=True)
def _perf_isolation():
    perf.reset()
    yield
    perf.reset()


# -- HLO parser vs XLA cost analysis ----------------------------------------
class TestOpTable:
    def _compiled(self):
        import jax
        import jax.numpy as jnp

        def loss(w1, w2, x):
            return jnp.mean(jnp.tanh(x @ w1) @ w2)

        g = jax.jit(jax.grad(loss, argnums=(0, 1)))
        args = (jnp.zeros((16, 32)), jnp.zeros((32, 4)),
                jnp.zeros((8, 16)))
        return g.lower(*args).compile()

    def test_summed_flops_match_xla_within_5pct(self):
        c = self._compiled()
        ca = c.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        tbl = perf.op_table(c.as_text())
        want = float(ca["flops"])
        got = float(tbl["totals"]["flops"])
        assert want > 0
        assert abs(got - want) <= 0.05 * want, (got, want)
        # the tanh contributes transcendentals, tracked separately
        assert tbl["totals"]["transcendentals"] > 0
        if ca.get("transcendentals"):
            assert tbl["totals"]["transcendentals"] == \
                int(ca["transcendentals"])

    @pytest.mark.parametrize("case", ["plain", "batched", "in_fusion"])
    def test_contraction_is_read_from_named_operands(self, case):
        """jax 0.9 prints `dot(%x.1, %w.1)`: the contraction's size is
        in no shape beside the operands, only where they are defined."""
        import jax
        import jax.numpy as jnp

        f32 = jnp.float32
        fn, shapes = {
            "plain": (lambda x, w: x @ w, [(8, 16), (16, 32)]),
            "batched": (lambda x, w: jnp.einsum("bik,bkj->bij", x, w),
                        [(3, 8, 16), (3, 16, 32)]),
            # the CPU compiler fuses a matrix-vector product with the
            # add that follows it (`dot_add_fusion`, kOutput)
            "in_fusion": (lambda x, w, b: x @ w + b,
                          [(8, 64), (64,), (8,)]),
        }[case]
        c = jax.jit(fn).lower(
            *[jnp.zeros(s, f32) for s in shapes]).compile()
        text = c.as_text()
        comps, entry = perf.parse_hlo(text)
        where = [name for name, instrs in comps.items()
                 for i in instrs if i.opcode == "dot"]
        assert len(where) == 1
        assert (where[0] != entry) == (case == "in_fusion"), text
        want = float(c.cost_analysis()["flops"])
        got = float(perf.op_table(text)["totals"]["flops"])
        assert want > 0
        assert abs(got - want) <= 0.05 * want, (got, want)

    def test_row_schema_and_classification(self):
        tbl = perf.op_table(self._compiled().as_text())
        assert tbl["ops"], "empty op table"
        keys = {"name", "op", "source", "flops", "transcendentals",
                "bytes", "intensity", "bound", "est_ms", "time_ms",
                "time_source", "roofline_frac"}
        for r in tbl["ops"]:
            assert keys <= set(r), r
            assert r["bound"] in ("compute", "memory", "collective",
                                  "mixed")
        assert any(r["op"] in ("dot", "fusion") for r in tbl["ops"])
        # rows are sorted hottest-first
        times = [r["time_ms"] for r in tbl["ops"]]
        assert times == sorted(times, reverse=True)
        assert tbl["ridge_intensity"] > 0

    def test_trace_join_and_attribution(self):
        c = self._compiled()
        base = perf.op_table(c.as_text())
        hot = base["ops"][0]["name"]
        tbl = perf.op_table(
            c.as_text(), measured_step_ms=10.0,
            trace_times={hot: {"total_us": 2000.0, "count": 2}})
        rows = {r["name"]: r for r in tbl["ops"]}
        assert rows[hot]["time_source"] == "trace"
        assert rows[hot]["time_ms"] == pytest.approx(1.0)
        others = [r for r in tbl["ops"] if r["name"] != hot]
        assert all(r["time_source"] == "attributed" for r in others)
        # attributed residual: traced 1ms + spread 9ms == measured wall
        assert sum(r["time_ms"] for r in tbl["ops"]) == \
            pytest.approx(10.0, rel=1e-3)

    def test_tail_rollup_preserves_sums(self):
        text = self._compiled().as_text()
        full = perf.op_table(text)
        rolled = perf.op_table(text, top=2)
        assert len(rolled["ops"]) <= 3
        assert rolled["ops"][-1]["name"] == "(other)"
        assert sum(r["flops"] for r in rolled["ops"]) == \
            full["totals"]["flops"]
        assert rolled["totals"] == full["totals"]


# -- engine.op_report() -----------------------------------------------------
class TestEngineOpReport:
    def test_non_empty_and_flops_match_cost_analysis(self):
        eng = _engine(_model())
        xs, ys = _batch()
        report = eng.op_report(xs, ys)
        assert report["name"] == "train"
        assert report["ops"]
        ca = eng.step_cost_analysis(xs, ys)
        want = float(ca["flops"])
        got = float(report["totals"]["flops"])
        assert abs(got - want) <= 0.05 * want, (got, want)

    def test_cached_batch_allows_argless_call(self):
        eng = _engine(_model())
        xs, ys = _batch()
        eng.step_cost_analysis(xs, ys)   # stashes the example batch
        report = eng.op_report()
        assert report["ops"]

    def test_argless_without_prior_batch_raises(self):
        eng = _engine(_model())
        with pytest.raises(ValueError, match="op_report"):
            eng.op_report()


# -- buffer census ----------------------------------------------------------
class TestBufferCensus:
    def test_bucket_math_with_known_owners(self):
        import jax.numpy as jnp

        a = jnp.zeros((128, 128), jnp.float32)
        b = jnp.zeros((128, 128), jnp.float32)
        c = jnp.zeros((64,), jnp.int32)
        census = perf.buffer_census(owners={"params": [a, b],
                                            "kv_pages": [c]})
        assert census["by_tag"]["params"] == a.nbytes + b.nbytes
        assert census["by_tag"]["kv_pages"] == c.nbytes
        bucket = next(bk for bk in census["buckets"]
                      if bk["tag"] == "params"
                      and bk["shape"] == [128, 128])
        assert bucket["count"] == 2
        assert bucket["bytes"] == 2 * 128 * 128 * 4
        assert census["total_bytes"] == sum(census["by_tag"].values())
        assert census["n_arrays"] >= 3

    def test_unclaimed_arrays_are_activations(self):
        import jax.numpy as jnp

        stray = jnp.ones((33, 7), jnp.float32)
        census = perf.buffer_census(owners={})
        acts = [bk for bk in census["buckets"]
                if bk["tag"] == "activations" and bk["shape"] == [33, 7]]
        assert acts and acts[0]["bytes"] >= stray.nbytes

    def test_registered_suppliers_and_reset(self):
        import jax.numpy as jnp

        w = jnp.zeros((16, 16), jnp.float32)
        perf.register_owner("opt_state", lambda: {"m": w})
        census = perf.buffer_census()
        assert census["by_tag"].get("opt_state", 0) >= w.nbytes
        perf.reset()
        census2 = perf.buffer_census()
        assert "opt_state" not in census2["by_tag"]


# -- OOM postmortem ---------------------------------------------------------
class TestOOMPostmortem:
    def test_is_oom_marker_matching(self):
        assert perf.is_oom(RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 1234 bytes"))
        assert perf.is_oom(RuntimeError("Resource exhausted: hbm"))
        assert not perf.is_oom(ValueError("shape mismatch"))
        assert not perf.is_oom(None)

    def test_fake_oom_dump_contains_census(self, tmp_path):
        flightrec.reset()
        flightrec.configure(str(tmp_path))
        try:
            import jax.numpy as jnp

            w = jnp.zeros((32, 32), jnp.float32)
            perf.register_owner("params", lambda: [w])
            perf.register_provider("train",
                                   lambda: {"ops": [], "totals": {}})
            exc = RuntimeError("RESOURCE_EXHAUSTED: Out of memory "
                               "allocating 9999999 bytes")
            path = perf.oom_postmortem(exc)
            assert path and os.path.exists(path)
            doc = json.load(open(path))
            assert doc["reason"] == "oom"
            census = doc["perf"]["census"]
            assert census["by_tag"]["params"] >= w.nbytes
            assert "train" in doc["perf"]["op_reports"]
            assert "RESOURCE_EXHAUSTED" in doc["perf"]["error"]
            # ring also carries the oom record
            assert any(r["kind"] == "oom" for r in doc["records"])
        finally:
            flightrec.reset()

    def test_enricher_upgrades_crash_to_oom(self, tmp_path):
        flightrec.reset()
        flightrec.configure(str(tmp_path))
        try:
            perf.install_oom_hook()
            out = perf._oom_enricher(
                RuntimeError,
                RuntimeError("RESOURCE_EXHAUSTED: oom"))
            assert out["reason"] == "oom"
            assert "census" in out["extra"]["perf"]
            assert perf._oom_enricher(ValueError,
                                      ValueError("not oom")) is None
        finally:
            flightrec.reset()


# -- /debug/perf endpoint ---------------------------------------------------
class TestDebugPerfEndpoint:
    def _fetch(self, url):
        return json.loads(
            urllib.request.urlopen(url, timeout=5).read().decode())

    def test_json_and_chrome_roundtrip(self):
        from paddle_tpu.monitor import MonitorServer
        from paddle_tpu.monitor.tracing import Tracer

        eng = _engine(_model())
        xs, ys = _batch()
        perf.register_provider("train",
                               lambda: eng.op_report(xs, ys))
        tracer = Tracer(sample_rate=1.0)
        with tracer.start_span("request"):
            pass
        srv = MonitorServer(port=0, tracer=tracer).start()
        try:
            doc = self._fetch(srv.url + "/debug/perf")
            assert doc["providers"] == ["train"]
            assert doc["reports"]["train"]["ops"]
            assert "census" in doc and "hbm" in doc
            chrome = self._fetch(srv.url + "/debug/perf?format=chrome")
            evs = chrome["traceEvents"]
            # span track (tracer pid) AND device-op track (synthetic pid)
            dev = [e for e in evs if e.get("pid") == 999999
                   and e.get("ph") == "X"]
            spans = [e for e in evs if e.get("pid") != 999999
                     and e.get("ph") == "X"]
            assert dev and spans
            assert any(e["name"] == "request" for e in spans)
            names = [e["name"] for e in evs if e.get("ph") == "M"]
            assert "process_name" in names and "thread_name" in names
            for e in dev:
                assert e["dur"] > 0 and "bound" in e["args"]
        finally:
            srv.shutdown()

    def test_provider_error_does_not_poison_endpoint(self):
        from paddle_tpu.monitor import MonitorServer

        def broken():
            raise RuntimeError("engine gone")

        perf.register_provider("train", broken)
        srv = MonitorServer(port=0).start()
        try:
            doc = self._fetch(srv.url + "/debug/perf")
            assert "RuntimeError" in doc["reports"]["train"]["error"]
        finally:
            srv.shutdown()


# -- bounded capture helper -------------------------------------------------
class TestCaptureDeviceTrace:
    def test_standalone_bounded_capture(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.utils.profiler import capture_device_trace

        f = jax.jit(lambda x: (x @ x).sum())
        x = jnp.zeros((64, 64))
        float(f(x))
        out = str(tmp_path / "trace")
        cap = capture_device_trace(2, out)
        # no monitored fit in this process → context-manager form
        assert not isinstance(cap, str)
        with cap:
            for _ in range(4):
                float(f(x))
                cap.step()
        times = perf.load_trace_op_times(out)
        assert times, "no device events captured"

    def test_trace_feeds_op_table(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.utils.profiler import capture_device_trace

        f = jax.jit(lambda a, b: jnp.tanh(a @ b).sum())
        a, b = jnp.zeros((64, 64)), jnp.zeros((64, 64))
        c = f.lower(a, b).compile()
        float(c(a, b))
        out = str(tmp_path / "trace")
        with capture_device_trace(1, out) as cap:
            float(c(a, b))
            cap.step()
        report = perf.build_report(c, name="probe", trace_dir=out)
        assert any(r["time_source"] == "trace" for r in report["ops"])
