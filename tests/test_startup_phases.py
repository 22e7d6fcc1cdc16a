"""Start-up names its own time: `utils.profiler.startup()` is the one
process-wide `StepTimers` (prefix `paddle.start`) that the package's
import, `GenerationEngine.start()` and `Model.fit` write, with one row
for every executable built, to which jax's own compile and cache events
are put down.  The scope names documented in README.md "Reading a trace"
are a contract, like the loops' (tests/test_loop_phases.py); `/metrics`
and the benchmark's reader `benchmarks/readers/startup.py` read the same
record.  No test here compares host time with a constant: a scope is
held against the scopes it contains, on the recorder's own clock."""
import json
import logging
import os
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import Model, inference
from paddle_tpu.io import TensorDataset
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import GenerationEngine
from paddle_tpu.utils import profiler
from paddle_tpu.utils.profiler import StartupTimers, StepTimers

from test_loop_phases import assert_children_within_parents

BUILT = {"decode_step", "release_step", "reclaim_step", "prefill.8",
         "insert.8", "insert_prefix.8", "chunk.8", "prefill.16",
         "insert.16", "insert_prefix.16"}
GENSERVE_CHILDREN = ({"genserve/state", "genserve/publish"}
                     | {f"genserve/build/{n}" for n in BUILT})


def tiny_gpt():
    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=211, hidden_size=48, num_layers=2, num_heads=4,
        max_position_embeddings=64, dropout=0.0, attn_dropout=0.0))
    m.eval()
    return m


def tiny_engine(model):
    return GenerationEngine(model, max_slots=3, max_seq_len=40,
                            prompt_buckets="8,16", page_size=4,
                            prefix_cache=True, prefill_chunk=8)


@pytest.fixture(scope="module")
def started():
    """One engine's start on a recorder of its own (the process's one
    holds every other test's engines too): (engine, recorder)."""
    boot = StartupTimers()
    real = profiler._startup
    profiler._startup = boot            # what `startup()` hands out
    import jax

    jax.monitoring.register_event_listener(boot.on_jax_event)
    jax.monitoring.register_event_duration_secs_listener(boot.on_jax_event)
    try:
        eng = tiny_engine(tiny_gpt()).start()
    finally:
        profiler._startup = real
        jax.monitoring.unregister_event_listener(boot.on_jax_event)
        jax.monitoring.unregister_event_duration_listener(boot.on_jax_event)
    yield eng, boot
    eng.stop()


class TestStepTimersMaxima:
    def test_the_longest_run_and_the_count_at_which_it_fell(self,
                                                            monkeypatch):
        clock = iter([0.0, 0.0, 0.0, 1.0,       # first run: 1 s
                      2.0, 2.0, 2.0, 5.0,       # second: 3 s
                      6.0, 6.0, 6.0, 8.0])      # third: 2 s
        monkeypatch.setattr(profiler.time, "perf_counter",
                            lambda: next(clock))
        t = StepTimers()
        for _ in range(3):
            with t.scope("sync"):
                pass
        assert t.maxima == {"sync": (3.0, 2)}
        assert t.totals == {"sync": 6.0}
        assert t.summary()["sync"] == {
            "total_s": 6.0, "count": 3, "mean_ms": 2000.0,
            "max_ms": 3000.0, "max_at": 2}

    def test_cleared_maxima_start_anew_and_leave_the_totals(self):
        t = StepTimers()
        with t.scope("data"):
            pass
        t.maxima.clear()
        assert "max_ms" not in t.summary()["data"]
        with t.scope("data"):
            pass
        assert t.maxima["data"][1] == 2 and t.counts["data"] == 2
        t.reset()
        assert t.maxima == {} and t.totals == {}


class TestGenerationStartup:
    def test_one_row_for_every_executable_the_engine_holds(self, started):
        eng, boot = started
        held = [eng._decode_exec, eng._release_exec, eng._reclaim_exec]
        for per_bucket in (eng._prefill_execs, eng._insert_execs,
                           eng._insert_prefix_execs, eng._chunk_execs):
            held += list(per_bucket.values())
        rows = [r for r in boot.rows if r["built"]]
        assert sorted(r["name"] for r in rows) == sorted(
            f"genserve/build/{n}" for n in BUILT)
        assert len(rows) == len(held) == eng.compile_count == 10
        assert eng.metrics.snapshot()["compile_count"] == 10
        # jax built one executable in each row, and none in a window
        # of work that is not start-up's
        assert all(r["executables"] == 1 for r in rows)

    def test_scope_names_are_the_documented_set_and_nest(self, started):
        _, boot = started
        assert {n for n, p in boot.parents.items() if p is None} \
            == {"genserve"}
        assert {n for n, p in boot.parents.items() if p == "genserve"} \
            == GENSERVE_CHILDREN
        for name in BUILT:
            row = f"genserve/build/{name}"
            assert {n for n, p in boot.parents.items() if p == row} \
                == {f"{row}/lower", f"{row}/compile"}
        assert all(c == 1 for c in boot.counts.values())
        assert_children_within_parents(boot)

    def test_the_phases_cover_the_start(self, started):
        """On the recorder's own clock: what `genserve` took against
        what its children took, so a loaded host cannot fail it."""
        _, boot = started
        covered = sum(boot.totals[n] for n in GENSERVE_CHILDREN)
        assert 0.9 * boot.totals["genserve"] <= covered \
            <= boot.totals["genserve"]

    def test_a_rows_tracing_and_lowering_is_its_lower_scope(self, started):
        _, boot = started
        rows = [r for r in boot.rows if r["built"]]
        for r in rows:
            assert 0.0 < r["compile_s"] <= r["wall_s"]
            assert r["wall_s"] == pytest.approx(boot.totals[r["name"]],
                                                rel=0.05)
            # `lower` lies inside the row and apart from the compile
            assert boot.totals[r["name"] + "/lower"] \
                <= r["wall_s"] - r["compile_s"] + 1e-3
        left = sum(r["wall_s"] - r["compile_s"] for r in rows)
        lowered = sum(boot.totals[r["name"] + "/lower"] for r in rows)
        assert lowered == pytest.approx(left, rel=0.1)

    def test_events_outside_a_row_go_to_the_scope_or_outside(self):
        import jax
        import jax.numpy as jnp

        boot = profiler.startup()
        n = len(boot.rows)
        jax.jit(lambda x: x * 3 + 7)(jnp.ones((3,), jnp.float32))
        outside = next(r for r in boot.rows if r["name"] == boot.OUTSIDE)
        before = outside["executables"]
        jax.jit(lambda x: x * 5 + 11)(jnp.ones((3,), jnp.float32))
        assert outside["executables"] == before + 1
        assert not outside["built"] and outside["wall_s"] is None
        with boot.scope("probe"):
            jax.jit(lambda x: x * 7 + 13)(jnp.ones((3,), jnp.float32))
        probe = [r for r in boot.rows[n:] if r["name"] == "probe"]
        assert len(probe) == 1 and probe[0]["executables"] == 1
        assert not probe[0]["built"]

    def test_a_second_start_reads_hits_where_the_first_read_misses(
            self, tmp_path, monkeypatch):
        from jax._src import compilation_cache

        from paddle_tpu.framework import flags

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        old = flags.get_flags(["FLAGS_jit_cache_dir",
                               "FLAGS_jit_cache_min_compile_secs"])
        boot = profiler.startup()
        model = tiny_gpt()
        try:
            paddle.set_flags({"FLAGS_jit_cache_dir": str(tmp_path),
                              "FLAGS_jit_cache_min_compile_secs": 0.0})
            compilation_cache.reset_cache()
            tables = []
            for _ in range(2):
                since = boot.mark()
                eng = tiny_engine(model).start()
                eng.stop()
                tables.append({r["name"]: r for r in boot.table(
                    ["genserve/build"], since)})
        finally:
            paddle.set_flags(old)
            compilation_cache.reset_cache()
        first, second = tables
        assert set(first) == set(second) == {
            f"genserve/build/{n}" for n in BUILT}
        for name, row in first.items():
            assert (row["cache_misses"], row["cache_hits"]) == (1, 0), name
            again = second[name]
            assert (again["cache_misses"], again["cache_hits"]) == (0, 1)
            assert 0.0 < again["cache_load_s"] <= again["compile_s"]
        counts = boot.cache_counts()
        assert counts["hit"] >= 10 and counts["miss"] >= 10

    def test_a_predictor_bucket_is_named_the_same_way(self):
        boot = profiler.startup()
        since = boot.mark()
        pred = inference.Predictor.from_layer(paddle.nn.Linear(4, 2))
        assert pred.warm([(2, 4)]) and pred.warm([(2, 4)])  # built once
        rows = [r for r in boot.table(since=since) if r["built"]]
        assert [r["name"] for r in rows] == ["build/predict.2x4"]
        assert rows[0]["executables"] == 1 and pred.compile_count == 1
        assert boot.parents["build/predict.2x4/lower"] \
            == boot.parents["build/predict.2x4/compile"] \
            == "build/predict.2x4"

    def test_the_log_line_is_the_table_slowest_first(self, caplog):
        with caplog.at_level(logging.INFO, logger="paddle_tpu.serving"):
            eng = tiny_engine(tiny_gpt()).start()
        eng.stop()
        text = next(r.getMessage() for r in caplog.records
                    if "generation start-up" in r.getMessage())
        assert "10 executable(s) built" in text
        rows = [ln.split() for ln in text.splitlines()
                if ln.startswith("  genserve/build/")]
        assert len(rows) == 10
        walls = [float(r[1]) for r in rows]
        assert walls == sorted(walls, reverse=True)
        assert not any("warmup compiled" in r.getMessage()
                       for r in caplog.records)

    def test_a_build_that_raises_closes_its_row_and_scopes(self):
        boot = profiler.startup()

        def bad(x):
            raise ValueError("does not trace")

        with pytest.raises(ValueError):
            with boot.scope("probe"), \
                    boot.executable("probe/build/bad") as row:
                inference.aot_compile(bad, (np.float32(0),))
        assert boot._open == [] and boot._building == []
        assert row["wall_s"] is not None and row["executables"] == 0
        assert boot.parents["probe/build/bad/lower"] == "probe/build/bad"
        assert "probe/build/bad/compile" not in boot.totals


def fit_once(epochs=1, **kw):
    paddle.seed(0)
    net = paddle.nn.Sequential(paddle.nn.Linear(4, 8), paddle.nn.ReLU(),
                               paddle.nn.Linear(8, 2))
    rs = np.random.RandomState(0)
    x = rs.randn(16, 4).astype("float32")
    y = (x.sum(1) > 0).astype("int64")
    model = Model(net)
    model.prepare(
        paddle.optimizer.Adam(learning_rate=0.01,
                              parameters=net.parameters()),
        paddle.nn.CrossEntropyLoss())
    model.fit(TensorDataset([x, y]), batch_size=8, epochs=epochs,
              shuffle=False, verbose=0, **kw)
    return model


class TestFitStartup:
    def test_a_two_step_fit_has_its_begin_and_its_steps_row(self):
        boot = profiler.startup()
        since = boot.mark()
        t0 = dict(boot.totals)
        fit_once()
        assert boot.parents["fit"] is None
        assert boot.parents["fit/begin"] == boot.parents["fit/build/step"] \
            == "fit"
        rows = [r for r in boot.table(["fit"], since) if r["built"]]
        assert [r["name"] for r in rows] == ["fit/build/step"]
        assert rows[0]["executables"] >= 1          # the step, compiled
        assert 0.0 < rows[0]["compile_s"] <= rows[0]["wall_s"]
        took = {n: boot.totals[n] - t0.get(n, 0.0)
                for n in ("fit", "fit/begin", "fit/build/step")}
        assert took["fit/begin"] + took["fit/build/step"] <= took["fit"]
        assert boot._open == []                     # closed after step one

    def test_a_fit_that_raises_before_its_first_step_closes_the_scope(self):
        boot = profiler.startup()
        n = boot.counts.get("fit", 0)
        with pytest.raises(RuntimeError):
            Model(paddle.nn.Linear(2, 2)).fit(
                TensorDataset([np.zeros((4, 2), "float32")]), batch_size=2)
        assert boot._open == [] and boot.counts["fit"] == n + 1

    def test_with_telemetry_on_the_second_lowering_has_a_row(
            self, tmp_path):
        from paddle_tpu import monitor
        from paddle_tpu.framework import flags

        prev = flags.get_flags(["FLAGS_telemetry_dir", "FLAGS_monitor_port"])
        monitor.reset()
        flags.set_flags({"FLAGS_telemetry_dir": str(tmp_path),
                         "FLAGS_monitor_port": 0})
        boot = profiler.startup()
        since = boot.mark()
        try:
            fit_once(epochs=2, log_freq=1)
        finally:
            monitor.reset()
            flags.set_flags(prev)
        names = [r["name"] for r in boot.table(since=since) if r["built"]]
        assert sorted(names) == ["cost_analysis", "fit/build/step"]
        assert boot.parents["cost_analysis"] is None
        assert boot.parents["cost_analysis/lower"] \
            == boot.parents["cost_analysis/compile"] == "cost_analysis"
        # the window line carries each phase's longest run of the
        # epoch where it fell in that window, beside the means.  What
        # the host's clock decides (whether step two outlasted step
        # one, compile and all) is not asserted: what holds on any host
        # is the order.  An epoch starts anew, so its first window has
        # every step phase's first run as the longest so far; its second
        # carries a phase only with a longer run than the first showed
        windows = [json.loads(x) for x in open(tmp_path / "events.jsonl")]
        windows = [w for w in windows if w["event"] == "window"]
        assert len(windows) == 4
        for first, second in (windows[0:2], windows[2:4]):
            assert {"data", "dispatch", "dispatch/call", "sync"} \
                <= set(first["phase_max_ms"]) <= set(first["phase_ms"])
            later = second.get("phase_max_ms", {})
            assert set(later) <= set(second["phase_ms"])
            assert all(later[n] >= first["phase_max_ms"][n] >= 0.0
                       for n in later)

    def test_the_epoch_log_carries_each_phases_longest_run(self, caplog):
        with caplog.at_level(logging.INFO, logger="paddle_tpu.hapi"):
            model = fit_once(epochs=2)
        lines = [r.getMessage() for r in caplog.records
                 if r.getMessage().startswith("fit epoch")]
        assert len(lines) == 2
        for line in lines:
            fields = dict(f.split("=") for f in line.split(": ")[1].split())
            for phase in ("data", "dispatch", "dispatch/call", "sync"):
                longest, at = fields[f"{phase}_max_ms"].split("@")
                assert float(longest) >= float(fields[f"{phase}_ms"])
                assert int(at) >= 1
        # the first step's compile is epoch 0's longest call, not epoch 1's
        assert lines[0].split("dispatch/call_max_ms=")[1].split()[0] \
            .endswith("@1")
        table = next(r.getMessage() for r in caplog.records
                     if r.getMessage().startswith("fit took"))
        assert "fit/build/step" in table
        assert model._last_fit_timers.counts["dispatch"] == 4


class TestReaders:
    def test_the_import_is_a_scope(self):
        boot = profiler.startup()
        assert boot.totals["import"] > 0.0 and boot.counts["import"] == 1
        assert boot.parents["import"] is None

    def test_metrics_of_both_servers_carry_both_families(self, started):
        from paddle_tpu.monitor.server import MonitorServer
        from paddle_tpu.serving.server import ServingServer

        eng, _ = started
        srv = ServingServer(None, gen_engine=eng, port=0,
                            install_signal_handlers=False).start()
        try:
            serving = urllib.request.urlopen(
                srv.url + "/metrics", timeout=5).read().decode()
        finally:
            srv._httpd.shutdown()       # the engine is the fixture's
            srv._httpd.server_close()
        with MonitorServer(port=0) as mon:
            monitor = urllib.request.urlopen(
                mon.url + "/metrics", timeout=5).read().decode()
        for text in (serving, monitor):
            assert "# TYPE paddle_startup_seconds gauge" in text
            assert 'paddle_startup_seconds{phase="import"} ' in text
            assert 'paddle_startup_executables{cache="hit"} ' in text
            assert 'paddle_startup_executables{cache="miss"} ' in text

    def test_the_benchmarks_reader_reads_the_record(self, started,
                                                    monkeypatch, capsys):
        from benchmarks.readers import startup as reader

        _, boot = started
        monkeypatch.setattr(profiler, "_startup", boot)
        monkeypatch.setattr(reader, "_noted", False)
        scopes = ["genserve", "fit"]
        program = reader.read(None, "scope_s", scopes)
        lower = reader.read(None, "trace_lower_s", scopes)
        compiles = reader.read(None, "compile_s", scopes)
        slowest = reader.read(None, "slowest_build_s", scopes)
        assert program == boot.totals["genserve"]
        assert lower + compiles <= program
        assert slowest == max(r["wall_s"] for r in boot.rows if r["built"])
        assert compiles == pytest.approx(         # `genserve/state`'s too
            sum(r["compile_s"] for r in boot.rows
                if r["name"] != boot.OUTSIDE))
        assert reader.read(None, "scope_s", ["import"]) is None  # its own
        noted = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
        assert len(noted) == 1              # the table, once
        assert len(noted[0]["startup"]["executables"]) == len(boot.rows)
        assert "genserve/build/decode_step" in noted[0]["startup"]["phases"]

    def test_the_reader_finds_nothing_in_an_empty_record(self, monkeypatch,
                                                         capsys):
        from benchmarks.readers import startup as reader

        monkeypatch.setattr(profiler, "_startup", StartupTimers())
        monkeypatch.setattr(reader, "_noted", False)
        for field in ("scope_s", "trace_lower_s", "compile_s",
                      "slowest_build_s"):
            assert reader.read(None, field, ["genserve", "fit"]) is None
        # nor in a program from before the recorder
        monkeypatch.delattr(profiler, "startup")
        assert reader.read(None, "scope_s", ["import"]) is None
        assert capsys.readouterr().out == ""

    def test_the_five_metric_files_resolve(self, started, monkeypatch):
        """`benchmarks/tests/test_add_by_files.py`'s way: the entries of
        `BENCHMARK.json` and the files they name, read by `run.py`."""
        from benchmarks import common
        from benchmarks import run as bench_run
        from benchmarks.readers import startup as reader

        names = ["start_import_s", "start_program_s", "start_trace_lower_s",
                 "start_compile_s", "start_slowest_build_s"]
        manifest = common.load_manifest()
        # every cell owes them (the fifth joined the lists with PR 41)
        cells = [w["name"] for w in manifest["workloads"]]
        entries = {m["name"]: m for m in manifest["per_layer"]}
        for name in names:
            assert entries[name] == {
                "name": name, "unit": "s", "better": "lower",
                "source": "program_span", "layer": "start-up",
                "moves": "setup_s", "workloads": cells}
        # the five stand together, in this order (later PRs' metrics follow)
        order = [m["name"] for m in manifest["per_layer"]]
        first = order.index(names[0])
        assert order[first:first + 5] == names
        _, boot = started
        boot.stamp("import", profiler.time.perf_counter())
        monkeypatch.setattr(profiler, "_startup", boot)
        monkeypatch.setattr(reader, "_noted", True)
        for workload in cells:
            cell, entry, config, traffic = common.resolve_cell(manifest,
                                                               workload)
            owed = common.metrics_for(manifest, cell, traffic, trace=1)
            assert set(names) <= set(owed)
            assert not set(names) & set(
                common.metrics_for(manifest, cell, traffic, trace=0))
        run = bench_run.Run(manifest, cell, entry, config, traffic, 1, 1.0, 1)
        got = bench_run.read_metrics(run, names)
        assert list(got) == names
        assert all(v["unit"] == "s" and v["value"] >= 0.0
                   for v in got.values())
        assert got["start_trace_lower_s"]["value"] \
            + got["start_compile_s"]["value"] \
            <= got["start_program_s"]["value"]
        assert os.path.isfile(os.path.join(common.HERE, "readers",
                                           "startup.py"))
