"""Training telemetry: per-step metrics, JSONL event log, MFU/memory
meters, and bounded on-demand jax.profiler trace capture.

Reference parity: the platform observability layer of the source stack —
platform/profiler.* RecordEvent scopes + DeviceTracer + tools/timeline.py
(PAPER.md layer 1) — rebuilt as one runtime surface: `Model.fit`
instruments every step through a `TrainTelemetry`, which writes

  * the shared `utils.metrics.default_registry()` (scraped over HTTP by
    `monitor.MonitorServer` at /metrics, federated across ranks by the
    launcher), and
  * a rotating append-only JSONL event log under `FLAGS_telemetry_dir`
    (one line per step window, safe to `tail -f`; schema in README
    "Observability").

MFU comes from XLA's own cost model: the engine's `lower_step()` gives
the compiled train step's PER-DEVICE flops (the same numbers the dp
scaling tests assert on), divided by measured step wall time and the
device's peak FLOP/s from `PEAKS` (overridable via
`FLAGS_device_peak_flops`).  Memory comes from the PJRT device's
`memory_stats()` — gracefully None on backends that lack it (CPU).

Trace capture is ARMED (from /debug/trace?steps=N, SIGUSR1, or
`arm_trace()`) and then EXECUTED on the training thread at the next step
boundary — `jax.profiler.start_trace` must run on the thread that
dispatches the computation, and a bounded step count guarantees the
capture ends even on a job nobody is watching.  That is what makes a
stuck or slow production fit profile-able without restarting it.

Everything here is jax-free except the trace start/stop and the
memory-stats read, both of which run on the training thread; metric
increments from other threads (checkpoint writer, HTTP handlers) are
pure-python registry work under the registry lock.
"""
from __future__ import annotations

import json
import logging
import os
import signal
import threading
import time

from ..framework import flags as _flags
from ..utils.metrics import default_registry

logger = logging.getLogger("paddle_tpu.monitor")

__all__ = ["PEAKS", "peak_flops_per_device", "peak_bw_per_device",
           "device_memory_stats",
           "TrainTelemetry", "JsonlWriter", "install_sigusr1"]

# One chip's (bf16 FLOP/s, HBM byte/s) by device kind: the two axes of
# the roofline (MFU reads the first, monitor/perf.py's op table both).
# The v5e row is benchmarks/peaks.json's, whose source is named there;
# tests/test_monitor.py holds the two equal.  The "cpu" row is NOMINAL so
# that a CPU run reports a nonzero MFU and a classification that compare
# run over run — absolute CPU figures are not meaningful and README says
# so.
PEAKS = {
    "v2": (45e12, 700e9), "v3": (123e12, 900e9), "v4": (275e12, 1228e9),
    "v5 lite": (197e12, 819e9), "v5e": (197e12, 819e9),
    "v5p": (459e12, 2765e9), "v5": (459e12, 2765e9),
    "v6 lite": (918e12, 1640e9), "v6e": (918e12, 1640e9),
    "cpu": (1e11, 5e10),
}


def _peaks(device=None):
    """The PEAKS row of the longest kind that `device`'s kind contains.
    A device kind the table does not know raises: no other chip's figure
    stands in."""
    import jax

    d = device if device is not None else jax.devices()[0]
    kind = (getattr(d, "device_kind", "") or "").lower()
    for k in sorted(PEAKS, key=len, reverse=True):
        if k in kind:
            return PEAKS[k]
    raise KeyError(
        f"no peak FLOP/s and bytes/s known for device_kind {kind!r}; add "
        f"it to PEAKS or set FLAGS_device_peak_flops and "
        f"FLAGS_device_peak_bw")


def peak_flops_per_device(device=None) -> float:
    """Peak FLOP/s for one device: FLAGS_device_peak_flops when set,
    else its PEAKS row's."""
    override = float(_flags.flag("FLAGS_device_peak_flops") or 0.0)
    return override if override > 0 else _peaks(device)[0]


def peak_bw_per_device(device=None) -> float:
    """HBM bytes/s for one device: FLAGS_device_peak_bw when set, else
    its PEAKS row's."""
    override = float(_flags.flag("FLAGS_device_peak_bw") or 0.0)
    return override if override > 0 else _peaks(device)[1]


def device_memory_stats(device=None):
    """{"bytes_in_use": int, "peak_bytes_in_use": int} from the PJRT
    device, or None on backends without memory stats (CPU) — callers
    must treat None as "meter unavailable", not zero."""
    import jax

    try:
        d = device if device is not None else jax.local_devices()[0]
        stats = d.memory_stats()
    except Exception:  # noqa: BLE001 - a meter, never a crash
        return None
    if not stats:
        return None
    out = {}
    if "bytes_in_use" in stats:
        out["bytes_in_use"] = int(stats["bytes_in_use"])
    if "peak_bytes_in_use" in stats:
        out["peak_bytes_in_use"] = int(stats["peak_bytes_in_use"])
    return out or None


class JsonlWriter:
    """Append-only JSONL event log with size-based rotation.

    One `write(record)` = one flushed line, so `tail -f events.jsonl`
    sees complete records.  When the live file exceeds `rotate_bytes`
    it is renamed to `events.jsonl.<n>` (monotonically increasing) and a
    fresh file opened; at most `keep` rotated segments are retained
    (oldest pruned) so a long job's log is bounded."""

    def __init__(self, directory: str, base: str = "events.jsonl",
                 rotate_mb: float = 64.0, keep: int = 4):
        self.directory = directory
        self.base = base
        self.rotate_bytes = max(4096, int(rotate_mb * 1024 * 1024))
        self.keep = keep
        self._lock = threading.Lock()
        self._fh = None
        os.makedirs(directory, exist_ok=True)

    @property
    def path(self) -> str:
        return os.path.join(self.directory, self.base)

    def _open(self):
        if self._fh is None:
            self._fh = open(self.path, "a", encoding="utf-8")

    def _rotated(self):
        pre = self.base + "."
        out = []
        for n in os.listdir(self.directory):
            if n.startswith(pre) and n[len(pre):].isdigit():
                out.append(int(n[len(pre):]))
        return sorted(out)

    def _rotate_locked(self):
        self._fh.close()
        self._fh = None
        nums = self._rotated()
        nxt = (nums[-1] + 1) if nums else 1
        os.rename(self.path, f"{self.path}.{nxt}")
        for old in nums[:max(0, len(nums) + 1 - self.keep)]:
            try:
                os.remove(f"{self.path}.{old}")
            except OSError:
                pass
        self._open()

    def write(self, record: dict):
        line = json.dumps(record, separators=(",", ":"),
                          default=_json_default)
        with self._lock:
            self._open()
            self._fh.write(line + "\n")
            self._fh.flush()
            if self._fh.tell() >= self.rotate_bytes:
                self._rotate_locked()

    def close(self):
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None


def _json_default(o):
    try:
        import numpy as np

        if isinstance(o, np.generic):
            return o.item()
    except Exception:  # noqa: BLE001
        pass
    return str(o)


class TrainTelemetry:
    """One training job's telemetry stream: registry gauges + JSONL
    events + bounded trace capture.

    `Model.fit` drives it:
      on_fit_begin(meta)      → "fit_begin" event, compile-event counter
      poll_trace()            every step (training thread): start/stop an
                              armed jax.profiler capture
      step_mark()             every step: per-step wall time into the
                              step-time histogram/reservoir (first step —
                              the compile — is recorded as a gauge, not
                              in the histogram)
      window(...)             at log_freq boundaries / epoch ends: phase
                              deltas, samples/s, MFU, memory → gauges +
                              one JSONL line
      ckpt_stall(ms)          checkpoint-induced training-thread stall
      on_fit_end(summary)     → "fit_end" event

    All methods are cheap when nothing fired; the per-step cost with no
    armed trace is two attribute reads and one perf_counter call."""

    def __init__(self, telemetry_dir: str = None, registry=None,
                 rotate_mb: float = None, job: str = "train"):
        self.registry = registry if registry is not None \
            else default_registry()
        self.job = job
        rotate_mb = rotate_mb if rotate_mb is not None else \
            float(_flags.flag("FLAGS_telemetry_rotate_mb") or 64.0)
        self.writer = (JsonlWriter(telemetry_dir, rotate_mb=rotate_mb)
                       if telemetry_dir else None)
        self.telemetry_dir = telemetry_dir
        reg = self.registry
        self.g_mfu = reg.gauge(
            "paddle_train_mfu", "model FLOPs utilization of the train "
            "step (XLA cost-analysis flops / wall / device peak)")
        self.g_samples = reg.gauge(
            "paddle_train_samples_per_sec",
            "training throughput over the last step window")
        self.g_loss = reg.gauge("paddle_train_loss",
                                "last drained training loss")
        self.g_lr = reg.gauge("paddle_train_lr", "current learning rate")
        self.g_step = reg.gauge("paddle_train_step",
                                "global fit iteration counter")
        self.g_epoch = reg.gauge("paddle_train_epoch", "current epoch")
        self.g_first_step_ms = reg.gauge(
            "paddle_train_first_step_ms",
            "wall time of the first dispatched step (compile + warmup)")
        self.g_mem_peak = reg.gauge(
            "paddle_train_device_mem_peak_mb",
            "device peak bytes in use (MB); 0 when the backend has no "
            "memory stats")
        self.g_mem_use = reg.gauge(
            "paddle_train_device_mem_in_use_mb",
            "device bytes in use (MB); 0 when the backend has no "
            "memory stats")
        self.g_hbm_in_use = reg.gauge(
            "paddle_hbm_in_use_bytes",
            "device bytes in use at the last per-step sample (PJRT "
            "memory_stats); 0 when the backend has no memory stats")
        self.g_hbm_watermark = reg.gauge(
            "paddle_hbm_watermark_bytes",
            "high-watermark of device peak bytes in use across the "
            "whole run (sampled every step on the training thread)")
        self._hbm_watermark = 0
        self._hbm_unavailable = False
        self.h_step = reg.histogram(
            "paddle_train_step_ms", "per-step wall time (training-thread "
            "enqueue-to-enqueue; device execution overlaps under the "
            "async engine)",
            [1, 2, 5, 10, 20, 50, 100, 250, 500, 1000, 5000, 30000])
        self.r_step = reg.reservoir("paddle_train_step_ms", size=4096)
        reg.gauge("paddle_train_step_time_p50_ms",
                  "per-step wall time p50 over the recent window",
                  fn=lambda: self.r_step.quantile_locked(0.50))
        reg.gauge("paddle_train_step_time_p99_ms",
                  "per-step wall time p99 over the recent window",
                  fn=lambda: self.r_step.quantile_locked(0.99))
        self.h_phase = {
            name: reg.histogram(
                f"paddle_train_{name}_ms",
                f"per-step mean '{name}' phase time per window (from "
                "StepTimers)", [0.1, 0.5, 1, 2, 5, 10, 20, 50, 100, 500,
                                1000])
            for name in ("data", "dispatch", "sync")}
        self.c_compiles = reg.counter(
            "paddle_train_compile_events_total",
            "jitted train-step (re)builds — cache misses of the "
            "engine's step cache")
        self.c_donation_fallback = reg.counter(
            "paddle_train_donation_fallbacks_total",
            "steps where XLA declined to consume a donated buffer "
            "(counted from jax's donation warnings)")
        self.c_windows = reg.counter(
            "paddle_train_windows_total", "telemetry step windows emitted")
        self.c_traces = reg.counter(
            "paddle_train_traces_total",
            "completed on-demand jax.profiler captures")
        self.h_ckpt_stall = reg.histogram(
            "paddle_ckpt_step_stall_ms",
            "training-thread stall per checkpoint save (host snapshot + "
            "submit/flush)", [1, 5, 10, 25, 50, 100, 250, 500, 1000,
                              5000, 30000])
        # trace arming: mutated from signal handlers / HTTP threads,
        # consumed on the training thread.  _signal_armed is the
        # SIGNAL-SAFE mailbox: a handler may interrupt the training
        # thread INSIDE a _trace_lock critical section, so the handler
        # must never touch the lock (or logging) — it writes one int,
        # and poll_trace converts it to a real arm on the next step
        self._signal_armed = 0
        self._trace_lock = threading.Lock()
        self._armed_steps = 0
        self._trace_steps_left = 0
        self._trace_active = False
        self._trace_dir = None
        self._last_trace_dir = None
        # window bookkeeping (training thread only)
        self._flops_per_step = None
        self._flops_resolved = False
        self._peak_flops = None
        self._last_mark = None
        self._steps_marked = 0

    # -- events ------------------------------------------------------------
    def _emit(self, event: str, **fields):
        if self.writer is None:
            return
        rec = {"ts": round(time.time(), 3), "event": event, "job": self.job}
        rec.update(fields)
        try:
            self.writer.write(rec)
        except OSError as e:
            # the event log is a meter: a full disk must not kill the fit
            logger.warning("telemetry event log write failed: %s", e)

    def on_fit_begin(self, meta: dict = None, compiled: bool = False):
        if compiled:
            self.c_compiles.inc()
        self._last_mark = None
        self._steps_marked = 0
        # each fit re-resolves its own step flops (a different model or
        # mesh changes the program behind the MFU gauge)
        self._flops_per_step = None
        self._flops_resolved = False
        self._emit("fit_begin", **(meta or {}))

    def on_fit_end(self, summary: dict = None):
        self._emit("fit_end", **(summary or {}))

    # -- MFU ---------------------------------------------------------------
    def set_flops_per_step(self, flops: float, peak: float = None):
        """Per-DEVICE flops of one compiled train step (engine
        `lower_step().compile().cost_analysis()` — per-device for SPMD
        modules) against the per-device peak."""
        self._flops_per_step = float(flops) if flops else None
        self._flops_resolved = True
        self._peak_flops = peak if peak is not None \
            else peak_flops_per_device()

    def ensure_flops(self, cost_fn):
        """Resolve flops-per-step ONCE per fit from a `lambda:
        engine.step_cost_analysis(...)` thunk (cached on the engine, so
        repeat fits of the same model don't re-lower).  Any failure
        downgrades the MFU gauge to 0 instead of breaking training."""
        if self._flops_resolved:
            return
        self._flops_resolved = True  # one attempt per fit, success or not
        try:
            ca = cost_fn() or {}
            self.set_flops_per_step(float(ca.get("flops", 0.0)) or None)
        except Exception as e:  # noqa: BLE001 - a meter, never a crash
            logger.warning("telemetry: step cost analysis failed (%s: %s) "
                           "— MFU gauge disabled for this fit",
                           type(e).__name__, e)
            self._flops_per_step = None
        if self._peak_flops is None:
            self._peak_flops = peak_flops_per_device()

    @property
    def flops_per_step(self):
        return self._flops_per_step

    # -- per-step hooks (training thread) ----------------------------------
    def mark_start(self):
        """Anchor the step clock at the START of the first dispatch
        (idempotent): without it the interval containing the jit
        compile — the one `paddle_train_first_step_ms` exists for —
        would be discarded because there is no earlier mark."""
        if self._last_mark is None:
            self._last_mark = time.perf_counter()

    def sample_hbm(self):
        """Per-step HBM watermark sample (training thread): one local
        PJRT memory_stats read — no device sync.  Backends without
        stats (CPU) disable the sampler after the first None so the hot
        loop doesn't pay the probe every step."""
        if self._hbm_unavailable:
            return
        mem = device_memory_stats()
        if mem is None:
            self._hbm_unavailable = True
            return
        self.g_hbm_in_use.set(int(mem.get("bytes_in_use", 0)))
        peak = int(mem.get("peak_bytes_in_use", 0))
        if peak > self._hbm_watermark:
            self._hbm_watermark = peak
            self.g_hbm_watermark.set(peak)

    def step_mark(self):
        now = time.perf_counter()
        self.sample_hbm()
        if self._last_mark is not None:
            dt_ms = (now - self._last_mark) * 1e3
            self._steps_marked += 1
            if self._steps_marked == 1:
                # first dispatched step = compile + warmup: a gauge, so
                # one 4-second compile doesn't own the p99 forever
                self.g_first_step_ms.set(round(dt_ms, 3))
            else:
                with self.registry._lock:
                    self.h_step._observe_locked(dt_ms)
                self.r_step.observe(dt_ms)
        else:
            # direct caller without mark_start: nothing to measure yet
            self._steps_marked += 1
        self._last_mark = now

    def request_trace_signal(self, steps: int):
        """ASYNC-SIGNAL-SAFE trace request (the SIGUSR1 handler): one
        int assignment, no lock, no logging — the handler can interrupt
        the training thread inside _trace_lock, where arm_trace would
        self-deadlock."""
        self._signal_armed = max(1, int(steps))

    def poll_trace(self):
        """Start/advance/stop an armed capture; called at each step
        boundary ON THE TRAINING THREAD (jax.profiler must be driven
        from the dispatching thread).  A few attribute reads when
        idle."""
        if self._signal_armed:
            steps, self._signal_armed = self._signal_armed, 0
            tdir = self.arm_trace(steps)
            logger.warning("SIGUSR1: armed a %d-step trace capture -> %s",
                           steps, tdir)
        if not self._armed_steps and not self._trace_active:
            return
        with self._trace_lock:
            armed, active = self._armed_steps, self._trace_active
            if armed and not active:
                self._armed_steps = 0
                self._trace_steps_left = armed
                tdir = self._trace_dir or self._default_trace_dir()
                try:
                    import jax

                    jax.profiler.start_trace(tdir)
                except Exception as e:  # noqa: BLE001 - meter
                    logger.error("trace capture failed to start: %s", e)
                    return
                self._trace_active = True
                self._last_trace_dir = tdir
                logger.info("trace capture ARMED for %d steps -> %s",
                            armed, tdir)
                self._emit("trace_begin", steps=armed, trace_dir=tdir)
                return
            if active:
                self._trace_steps_left -= 1
                if self._trace_steps_left <= 0:
                    self._stop_trace_locked()

    def _stop_trace_locked(self):
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001
            logger.error("trace capture failed to stop: %s", e)
        self._trace_active = False
        self.c_traces.inc()
        logger.info("trace capture complete -> %s", self._last_trace_dir)
        self._emit("trace_end", trace_dir=self._last_trace_dir)

    def finish_trace(self):
        """Stop a still-active capture at fit exit (a trace armed for
        more steps than remained must still produce a valid artifact)."""
        with self._trace_lock:
            if self._trace_active:
                self._stop_trace_locked()

    def _default_trace_dir(self):
        base = self.telemetry_dir or os.path.join(
            os.environ.get("TMPDIR", "/tmp"), "paddle_tpu_telemetry")
        return os.path.join(base, "traces",
                            time.strftime("%Y%m%d-%H%M%S"))

    def arm_trace(self, steps: int, trace_dir: str = None) -> str:
        """Arm a bounded capture of the next `steps` training steps.
        Safe from any thread AND from a signal handler (one lock-free
        assignment would suffice; the lock orders racing armers).
        Returns the directory the trace will land in."""
        steps = max(1, int(steps))
        with self._trace_lock:
            tdir = trace_dir or self._default_trace_dir()
            if self._trace_active:
                # already capturing: extend, keep the live dir
                self._trace_steps_left = max(self._trace_steps_left, steps)
                return self._last_trace_dir
            self._trace_dir = tdir
            self._armed_steps = steps
            return tdir

    @property
    def trace_pending(self) -> bool:
        return bool(self._armed_steps or self._trace_active
                    or self._signal_armed)

    @property
    def last_trace_dir(self):
        return self._last_trace_dir

    # -- window emission (training thread) ---------------------------------
    def window(self, *, step: int, epoch: int, steps: int, wall_s: float,
               batch_size: int, loss=None, lr=None, timers=None,
               phase_deltas: dict = None, phase_maxima: dict = None,
               extra: dict = None) -> dict:
        """Close one step window: update every gauge/histogram and emit
        one JSONL line.  `phase_deltas` is {phase: (d_total_s, d_count)}
        from StepTimers since the previous window, `phase_maxima`
        {phase: seconds} the longest single run of each phase that fell
        inside the window (`phase_max_ms` beside `phase_ms`, the means:
        a stalled step shows in the one and not in the other)."""
        steps = max(1, int(steps))
        wall_s = max(1e-9, float(wall_s))
        sps = steps * batch_size / wall_s
        step_ms = wall_s / steps * 1e3
        mfu = 0.0
        if self._flops_per_step and self._peak_flops:
            mfu = self._flops_per_step * steps / wall_s / self._peak_flops
        mem = device_memory_stats()
        rec = {
            "step": int(step), "epoch": int(epoch), "steps": steps,
            "samples_per_sec": round(sps, 3),
            "step_ms_mean": round(step_ms, 4),
            # 9 digits: a CPU-smoke MFU against the nominal peak is
            # ~1e-6 and must not round to a dead gauge
            "mfu": round(mfu, 9),
        }
        if loss is not None:
            rec["loss"] = float(loss)
            self.g_loss.set(float(loss))
        if lr is not None:
            rec["lr"] = float(lr)
            self.g_lr.set(float(lr))
        phase_ms = {}
        if phase_deltas:
            for name, (d_total, d_count) in phase_deltas.items():
                if d_count <= 0:
                    continue
                mean_ms = d_total / d_count * 1e3
                phase_ms[name] = round(mean_ms, 4)
                h = self.h_phase.get(name)
                if h is not None:
                    h.observe(mean_ms)
        if phase_ms:
            rec["phase_ms"] = phase_ms
        if phase_maxima:
            rec["phase_max_ms"] = {name: round(seconds * 1e3, 4)
                                   for name, seconds in phase_maxima.items()}
        if self._flops_per_step:
            rec["flops_per_step"] = self._flops_per_step
        if mem is not None:
            mb = 1.0 / (1024 * 1024)
            rec["mem"] = {
                "in_use_mb": round(mem.get("bytes_in_use", 0) * mb, 2),
                "peak_mb": round(mem.get("peak_bytes_in_use", 0) * mb, 2)}
            self.g_mem_use.set(rec["mem"]["in_use_mb"])
            self.g_mem_peak.set(rec["mem"]["peak_mb"])
        else:
            rec["mem"] = None
        if extra:
            rec.update(extra)
        self.g_mfu.set(round(mfu, 9))
        self.g_samples.set(round(sps, 3))
        self.g_step.set(int(step))
        self.g_epoch.set(int(epoch))
        self.c_windows.inc()
        self._emit("window", **rec)
        return rec

    def ckpt_stall(self, ms: float):
        self.h_ckpt_stall.observe(ms)
        self._emit("ckpt", stall_ms=round(ms, 3))

    def install_warning_hook(self):
        """Count donation-fallback warnings (jax's "Some donated buffers
        were not usable") without touching the engine's hot path: wrap
        `warnings.showwarning` for the duration of a fit.

        The default warning filter deduplicates repeats from the same
        code location BEFORE showwarning runs — a chronic every-step
        fallback would count 1.  So an "always" filter is pushed for
        donation warnings while the hook is installed; the hook itself
        de-duplicates the CONSOLE output back to once per fit, so the
        counter is exact without turning a chronic fallback into ten
        thousand log lines.  Returns a restore() callable; chains to the
        previous hook so user-installed hooks keep firing."""
        import warnings

        prev = warnings.showwarning
        prev_filters = list(warnings.filters)
        warnings.filterwarnings("always", message=".*[Dd]onated")
        counter = self.c_donation_fallback
        printed = [0]

        def hook(message, category, filename, lineno, file=None,
                 line=None):
            if "donated" in str(message).lower():
                counter.inc()
                printed[0] += 1
                if printed[0] > 1:
                    return  # counted; don't spam the console
            prev(message, category, filename, lineno, file, line)

        warnings.showwarning = hook

        def restore():
            if warnings.showwarning is hook:
                warnings.showwarning = prev
            warnings.filters[:] = prev_filters

        return restore

    def close(self):
        self.finish_trace()
        if self.writer is not None:
            self.writer.close()


def install_sigusr1(telemetry: TrainTelemetry, steps: int = None):
    """SIGUSR1 → arm a bounded trace capture (the headless equivalent of
    /debug/trace?steps=N).  Main-thread only (signal.signal raises
    elsewhere — returns None then).  Returns a restore() callable."""
    steps = steps if steps is not None else \
        int(_flags.flag("FLAGS_trace_steps") or 3)

    def _handler(signum, frame):
        # handler body must be async-signal-safe: no locks, no logging
        # (either could be held by the very frame this interrupts)
        telemetry.request_trace_signal(steps)

    try:
        prev = signal.signal(signal.SIGUSR1, _handler)
    except (ValueError, OSError, AttributeError):
        return None

    def restore():
        try:
            signal.signal(signal.SIGUSR1, prev)
        except (ValueError, OSError):
            pass

    return restore
