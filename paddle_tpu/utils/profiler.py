"""Profiler.

Reference parity: paddle/fluid/platform/profiler.* (RecordEvent RAII scopes,
EnableProfiler/DisableProfiler, chrome-trace via tools/timeline.py) and
python fluid/profiler.py.

TPU-native: jax.profiler does the heavy lifting — traces carry XLA/TPU
device activity and land in TensorBoard/perfetto format (the
CUPTI DeviceTracer + timeline.py analog).  RecordEvent maps to
jax.profiler.TraceAnnotation so named scopes appear inside device traces.
"""
from __future__ import annotations

import contextlib
import logging
import time

import jax
import jax.monitoring

from .metrics import default_registry

logger = logging.getLogger("paddle_tpu.profiler")


class RecordEvent:
    """Named scope visible in profiler traces (platform/profiler.cc:53).
    Annotates both the XLA device trace (jax.profiler) and the native host
    event buffer (csrc/core.cc) when host profiling is enabled."""

    def __init__(self, name: str):
        self.name = name
        self._ann = jax.profiler.TraceAnnotation(name)
        self.begin = None

    def __enter__(self):
        from .. import core as _native
        self._native = _native if _native.profiler_enabled() else None
        if self._native:
            self._native.event_push(self.name)
        self.begin = time.perf_counter()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        self.elapsed = time.perf_counter() - self.begin
        if self._native:
            self._native.event_pop()
        return False


_NO_RUN = (0.0, 0)      # what `maxima` reads for a phase that never ran


class StepTimers:
    """Phase timing for a host loop: `Model.fit` (prefix `paddle.fit`)
    and the generation engine's decode loop (`paddle.genserve`).

    Each `scope(name)` is a RecordEvent named `<prefix>/<name>`, so the
    phase lies in a jax.profiler trace on the trace's own clock beside
    the device's events, plus a host-side accumulator cheap enough to
    leave on, so `summary()` answers "where does the loop's time go"
    without a trace viewer.  Scopes nest: `parents[name]` is the scope
    a phase ran under (None at the top level), and by convention a
    child is named `parent/child`; a phase's self time is its total
    less its children's (`self_seconds`).  The top-level phases of a
    loop that is wholly covered sum to its wall time.  One thread
    drives a recorder; another may read `totals` at any time.  Under
    the async train engine `dispatch` measures enqueue cost only;
    device execution overlaps and is paid for inside `sync`.

    `maxima[name]` is the phase's longest single run and the count at
    which it fell, since the recorder began or `maxima` was last
    cleared (`Model.fit` clears it at each epoch's end): a mean hides
    one stalled step, this names it."""

    def __init__(self, prefix: str = "paddle.fit"):
        self.prefix = prefix
        self.totals: dict[str, float] = {}
        self.counts: dict[str, int] = {}
        self.parents: dict[str, str | None] = {}
        self.maxima: dict[str, tuple[float, int]] = {}
        self._open: list[str] = []

    def reset(self):
        """Zero the accumulators: per-epoch phase summaries should
        describe that epoch, not the whole process lifetime."""
        self.totals.clear()
        self.counts.clear()
        self.parents.clear()
        self.maxima.clear()

    @contextlib.contextmanager
    def scope(self, name: str):
        # the clock is read around the annotation, so that what the
        # annotation itself costs counts into the phase and not into
        # the time between phases
        begin = time.perf_counter()
        self.parents[name] = self._open[-1] if self._open else None
        self._open.append(name)
        try:
            with RecordEvent(f"{self.prefix}/{name}"):
                yield
        finally:
            self._open.pop()
            took = time.perf_counter() - begin
            self.totals[name] = self.totals.get(name, 0.0) + took
            count = self.counts[name] = self.counts.get(name, 0) + 1
            if took > self.maxima.get(name, _NO_RUN)[0]:
                self.maxima[name] = (took, count)

    def self_seconds(self, name: str) -> float:
        """A phase's total less the totals of the phases that ran
        directly under it."""
        return self.totals.get(name, 0.0) - sum(
            t for child, t in self.totals.items()
            if self.parents.get(child) == name)

    def summary(self) -> dict:
        """{phase: {total_s, count, mean_ms, max_ms, max_at}} for every
        recorded phase; the last two are left out where `maxima` was
        cleared since the phase last ran."""
        out = {}
        for name, t in self.totals.items():
            out[name] = {"total_s": round(t, 6),
                         "count": self.counts[name],
                         "mean_ms": round(t / self.counts[name] * 1e3, 4)}
            if name in self.maxima:
                longest, at = self.maxima[name]
                out[name].update(max_ms=round(longest * 1e3, 4), max_at=at)
        return out


class StartupTimers(StepTimers):
    """Start-up's phases (prefix `paddle.start`): the package's `import`,
    `genserve` (all of `GenerationEngine.start()`), `fit` (from
    `Model.fit`'s entry to the return of its first step) and
    `cost_analysis`, with one row for every executable built under them.

    A row is opened by `executable(name)`: `wall_s` is its scope, and
    jax's own monitoring events that fire while it is the innermost
    open row are put down to it: `compile_s` and `executables` count
    `backend_compile_duration` (an XLA compile or a load from the
    persistent cache, both), `cache_hits`, `cache_misses` and
    `cache_load_s` the cache's events (jax counts a miss when it writes
    the entry, so an executable under the cache's thresholds is
    neither).  What is left of a row, `wall_s - compile_s`, is host
    Python: tracing and lowering.  An event that fires with no row open
    goes to a row named after the innermost open scope, which built
    nothing and has no `wall_s`; with no scope open either, to the
    row `(outside)` (a model's eager initialisers, the harness's own
    jits).  Start-up runs on one thread; `startup()` is the process's
    one recorder."""

    OUTSIDE = "(outside)"
    _EVENTS = {     # jax's event -> (the row's seconds, the row's count)
        "/jax/core/compile/backend_compile_duration":
            ("compile_s", "executables"),
        "/jax/compilation_cache/cache_retrieval_time_sec":
            ("cache_load_s", None),
        "/jax/compilation_cache/cache_hits": (None, "cache_hits"),
        "/jax/compilation_cache/cache_misses": (None, "cache_misses"),
    }

    def __init__(self):
        super().__init__("paddle.start")
        self.rows: list[dict] = []
        self._building: list[dict] = []     # the open rows, innermost last
        self._elsewhere: dict[tuple, dict] = {}

    def reset(self):
        super().reset()
        self.rows.clear()
        self._elsewhere.clear()

    def _row(self, name, built):
        row = {"name": name, "built": built, "wall_s": None,
               "compile_s": 0.0, "executables": 0, "cache_hits": 0,
               "cache_misses": 0, "cache_load_s": 0.0}
        self.rows.append(row)
        return row

    def on_jax_event(self, event, secs=0.0, **_):
        """The one listener of jax's monitoring events, of both kinds
        (with and without a duration); an event that is none of the
        four costs one lookup."""
        fields = self._EVENTS.get(event)
        if fields is None:
            return
        try:
            row = self._building[-1]
        except IndexError:
            try:
                name = self._open[-1]
            except IndexError:
                name = self.OUTSIDE
            key = (name, self.counts.get(name, 0))  # this run of the scope
            row = self._elsewhere.get(key)
            if row is None:
                row = self._elsewhere[key] = self._row(name, built=False)
        seconds, count = fields
        if seconds:
            row[seconds] += secs
        if count:
            row[count] += 1

    def under(self, name: str) -> str:
        """`name` as a child of the innermost open scope."""
        return f"{self._open[-1]}/{name}" if self._open else name

    def stamp(self, name: str, began: float):
        """A top-level scope that began at `began` (a `perf_counter`
        reading) and ends now: for a stretch that starts before this
        module can be imported (the package's `import`)."""
        took = time.perf_counter() - began
        self.parents[name] = None
        self.totals[name] = self.totals.get(name, 0.0) + took
        self.counts[name] = self.counts.get(name, 0) + 1

    @contextlib.contextmanager
    def executable(self, name: str):
        """The scope `name` with a row of its own."""
        row = self._row(name, built=True)
        self._building.append(row)
        begin = time.perf_counter()
        try:
            with self.scope(name):
                yield row
        finally:
            self._building.pop()
            row["wall_s"] = time.perf_counter() - begin

    def cache_counts(self) -> dict:
        """Executables the persistent cache gave (`hit`) and took
        (`miss`) since the process began."""
        return {"hit": sum(r["cache_hits"] for r in self.rows),
                "miss": sum(r["cache_misses"] for r in self.rows)}

    def mark(self):
        """A place in the record: `table` and `report` with
        `since=mark` tell only what came after it (a process may start
        more than one engine, or fit more than once)."""
        return len(self.rows), dict(self.totals)

    def table(self, under=None, since=None) -> list[dict]:
        """The rows, slowest first: every row, or those at or below one
        of the scopes `under`."""
        rows = self.rows[since[0]:] if since else self.rows
        if under is not None:
            rows = [r for r in rows if any(
                r["name"] == s or r["name"].startswith(s + "/")
                for s in under)]
        return sorted(rows, key=lambda r: -(r["wall_s"] or 0.0))

    def report(self, scope: str, since=None) -> str:
        """What `scope` took, by phase and by executable, as text."""
        rows = self.table([scope], since)
        built = [r for r in rows if r["built"]]
        before = since[1] if since else {}
        lines = ["%s took %.3f s: %d executable(s) built, tracing and "
                 "lowering %.3f s, compile or cache load %.3f s (%d from "
                 "the cache)" % (
                     scope, self.totals.get(scope, 0.0)
                     - before.get(scope, 0.0), len(built),
                     sum(r["wall_s"] - r["compile_s"] for r in built),
                     sum(r["compile_s"] for r in rows),
                     sum(r["cache_hits"] for r in rows))]
        names = {r["name"] for r in built}
        for name, total in self.totals.items():
            if self.parents[name] == scope and name not in names:
                lines.append("  phase %-34s %8.3f s" % (
                    name, total - before.get(name, 0.0)))
        lines.append("  %-40s %8s %11s %9s %3s %4s" % (
            "executable", "wall_s", "trace_lower", "compile_s", "n", "hit"))
        for r in rows:
            lines.append("  %-40s %8s %11s %9.3f %3d %4d" % (
                r["name"], "%.3f" % r["wall_s"] if r["built"] else "-",
                "%.3f" % (r["wall_s"] - r["compile_s"]) if r["built"]
                else "-", r["compile_s"], r["executables"],
                r["cache_hits"]))
        return "\n".join(lines)


_startup = StartupTimers()
jax.monitoring.register_event_listener(_startup.on_jax_event)
jax.monitoring.register_event_duration_secs_listener(_startup.on_jax_event)


def startup() -> StartupTimers:
    """The process's start-up recorder."""
    return _startup


# on every /metrics that serves the process-wide registry
# (serving/server.py, monitor/server.py)
default_registry().gauge(
    "paddle_startup_seconds",
    "seconds of start-up by phase (utils.profiler.startup(): import, "
    "genserve, fit, cost_analysis; a/b ran under a; build/<executable> "
    "is one executable's tracing, lowering and compile or cache load)",
    fn=lambda: dict(_startup.totals), label="phase")
default_registry().gauge(
    "paddle_startup_executables",
    "executables the persistent compile cache gave (hit) and took (miss) "
    "since the process began, by jax's own cache events",
    fn=_startup.cache_counts, label="cache")


class _BoundedCapture:
    """Self-driven bounded capture for loops without a TrainTelemetry:
    the caller IS the dispatching thread, so it brackets its own step
    loop — ``with`` starts the trace, ``step()`` after each dispatched
    step counts it down, and the trace stops at zero (or scope exit,
    whichever first)."""

    def __init__(self, steps: int, out_dir: str):
        self.steps_left = max(1, int(steps))
        self.trace_dir = out_dir
        self._active = False

    def __enter__(self):
        import os

        os.makedirs(self.trace_dir, exist_ok=True)
        jax.profiler.start_trace(self.trace_dir)
        self._active = True
        return self

    def step(self):
        if self._active:
            self.steps_left -= 1
            if self.steps_left <= 0:
                self._stop()

    def _stop(self):
        if self._active:
            self._active = False
            jax.profiler.stop_trace()

    def __exit__(self, *exc):
        self._stop()
        return False


def capture_device_trace(steps: int, out_dir: str, telemetry=None):
    """Bounded ``jax.profiler`` capture of the next ``steps`` steps.

    With a live monitored fit (a TrainTelemetry — passed explicitly or
    the process one), the capture is ARMED on it and returns the trace
    dir: start/stop happen at step boundaries ON the training thread
    (monitor/telemetry.py arm/poll — jax.profiler must be driven from
    the dispatching thread), so any thread may call this against a
    running job.  Without one, returns a ``_BoundedCapture`` context
    manager for the caller's own step loop.  Either way the artifacts
    under ``out_dir`` feed ``monitor.perf.load_trace_op_times`` /
    ``op_report(trace_dir=...)``."""
    if telemetry is None:
        from ..monitor import get_telemetry

        telemetry = get_telemetry()
    if telemetry is not None:
        return telemetry.arm_trace(steps, trace_dir=out_dir)
    return _BoundedCapture(steps, out_dir)


_trace_dir = None


def start_profiler(log_dir="/tmp/paddle_tpu_profile", state=None,
                   tracer_option=None):
    global _trace_dir
    _trace_dir = log_dir
    from .. import core as _native
    _native.trace_clear()
    _native.profiler_enable(True)
    jax.profiler.start_trace(log_dir)


def stop_profiler(sorted_key=None, profile_path=None):
    import os

    jax.profiler.stop_trace()
    from .. import core as _native
    _native.profiler_enable(False)
    if _native.available():
        # profile_path may be the jax trace DIRECTORY (the fluid API passes
        # one path for both); host events go to a file inside it
        target = profile_path
        if not target or os.path.isdir(target):
            target = os.path.join(target or _trace_dir or ".",
                                  "host_trace.json")
        n = export_chrome_trace(target)
        if n < 0:
            logger.warning("host trace export to %s failed", target)
    logger.info("profiler trace written to %s (open with TensorBoard or "
                "perfetto)", _trace_dir)


def export_chrome_trace(path: str) -> int:
    """Dump host RecordEvent scopes as chrome://tracing JSON — the
    tools/timeline.py analog. Returns number of events."""
    from .. import core as _native
    return _native.trace_export(path)


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/paddle_tpu_profile",
             tracer_option=None):
    """fluid.profiler.profiler context-manager parity (profiler.py:255)."""
    start_profiler(profile_path, state, tracer_option)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


class ProfilerOptions:
    """Option bag (reference utils/profiler.py ProfilerOptions): a dict
    facade over the knobs the TPU profiler honors (output_dir; the
    CUDA-specific ones are accepted and inert)."""

    DEFAULTS = {
        "state": "All", "sorted_key": "default", "tracer_level": "Default",
        "batch_range": [0, 100], "output_thread_detail": False,
        "profile_path": "/tmp/paddle_tpu_profile",
        "timeline_path": "/tmp/paddle_tpu_profile/host_trace.json",
        "op_summary_path": "", "exit_on_finished": False,
    }

    def __init__(self, options=None):
        self._options = dict(self.DEFAULTS)
        if options:
            self._options.update(options)

    def __getitem__(self, name):
        if name not in self._options:
            raise ValueError(f"ProfilerOptions does not have an option "
                             f"named {name}.")
        return self._options[name]


class Profiler:
    """Start/stop facade over the jax.profiler + host-event tracing
    (reference utils/profiler.py Profiler; use as a context manager or
    via start()/stop())."""

    def __init__(self, enabled=True, options=None):
        self.enabled = enabled
        self.profiler_options = ProfilerOptions(options)
        self._running = False

    def start(self):
        if self.enabled and not self._running:
            start_profiler(self.profiler_options["profile_path"],
                           self.profiler_options["state"])
            self._running = True
        return self

    def stop(self):
        if self._running:
            stop_profiler(self.profiler_options["sorted_key"],
                          self.profiler_options["profile_path"])
            self._running = False

    def reset(self):
        from .. import core as _native
        _native.trace_clear()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


_profiler_singleton = None


def get_profiler(options=None):
    global _profiler_singleton
    if _profiler_singleton is None:
        _profiler_singleton = Profiler(options=options)
    return _profiler_singleton
