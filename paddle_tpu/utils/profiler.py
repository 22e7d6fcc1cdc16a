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
    device execution overlaps and is paid for inside `sync`."""

    def __init__(self, prefix: str = "paddle.fit"):
        self.prefix = prefix
        self.totals: dict[str, float] = {}
        self.counts: dict[str, int] = {}
        self.parents: dict[str, str | None] = {}
        self._open: list[str] = []

    def reset(self):
        """Zero the accumulators: per-epoch phase summaries should
        describe that epoch, not the whole process lifetime."""
        self.totals.clear()
        self.counts.clear()
        self.parents.clear()

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
            self.totals[name] = (self.totals.get(name, 0.0)
                                 + time.perf_counter() - begin)
            self.counts[name] = self.counts.get(name, 0) + 1

    def self_seconds(self, name: str) -> float:
        """A phase's total less the totals of the phases that ran
        directly under it."""
        return self.totals.get(name, 0.0) - sum(
            t for child, t in self.totals.items()
            if self.parents.get(child) == name)

    def summary(self) -> dict:
        """{phase: {total_s, count, mean_ms}} for every recorded phase."""
        return {
            name: {"total_s": round(t, 6),
                   "count": self.counts[name],
                   "mean_ms": round(t / self.counts[name] * 1e3, 4)}
            for name, t in self.totals.items()
        }


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
