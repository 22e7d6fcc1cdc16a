"""paddle_tpu.monitor — unified runtime telemetry for training jobs.

The platform observability layer (PAPER.md layer 1: platform/profiler.*
RecordEvent scopes, DeviceTracer, tools/timeline.py chrome traces)
rebuilt TPU-native as one surface over the shared metrics registry
(`utils/metrics.py`):

  * `TrainTelemetry` — per-step metrics (loss, lr, phase times, MFU,
    samples/s, device memory), a rotating JSONL event log under
    `FLAGS_telemetry_dir`, and bounded on-demand jax.profiler captures.
  * `MonitorServer`  — /metrics (Prometheus), /healthz, and
    /debug/trace?steps=N against a RUNNING fit; the launcher federates
    per-rank endpoints into one.
  * SIGUSR1 — the headless /debug/trace equivalent.

`Model.fit` wires all of it automatically when `FLAGS_telemetry_dir` is
set and/or `FLAGS_monitor_port` >= 0; see README "Observability".
"""
from __future__ import annotations

import logging
import threading

from ..framework import flags as _flags
from ..utils.metrics import default_registry
from . import flightrec, perf, tracing
from .flightrec import FlightRecorder
from .server import MonitorServer, runtime_health
from .telemetry import (PEAKS, JsonlWriter, TrainTelemetry,
                        device_memory_stats, install_sigusr1,
                        peak_bw_per_device, peak_flops_per_device)
from .tracing import NullSpan, Span, Tracer, default_tracer

logger = logging.getLogger("paddle_tpu.monitor")

__all__ = ["TrainTelemetry", "MonitorServer", "JsonlWriter", "PEAKS",
           "peak_flops_per_device", "peak_bw_per_device",
           "device_memory_stats",
           "install_sigusr1", "default_registry", "fit_monitor",
           "get_monitor_server", "get_telemetry", "reset",
           "runtime_health",
           "Tracer", "Span", "NullSpan", "default_tracer",
           "FlightRecorder", "tracing", "flightrec", "perf"]

_lock = threading.Lock()
_telemetry: TrainTelemetry | None = None
_server: MonitorServer | None = None


def fit_monitor():
    """The process-wide (telemetry, server) pair Model.fit attaches to,
    created lazily from flags.  Returns (None, None) when both
    `FLAGS_telemetry_dir` and `FLAGS_monitor_port` are off — the fit
    loop then skips every telemetry hook (zero overhead).

    Singleton by design: gauges live in the shared default registry and
    the HTTP port is bound once; a second fit in the same process reuses
    both (the JSONL log simply grows more fit_begin/fit_end markers)."""
    global _telemetry, _server
    tdir = str(_flags.flag("FLAGS_telemetry_dir") or "")
    port = int(_flags.flag("FLAGS_monitor_port", -1))
    if not tdir and port < 0:
        return None, None
    with _lock:
        if _telemetry is None:
            _telemetry = TrainTelemetry(telemetry_dir=tdir or None)
            if tdir:
                # crash flight recorder rides along whenever the event
                # log is on: spans mirror into its ring, and the
                # excepthook/atexit hooks leave a postmortem dump
                rec = flightrec.configure(tdir)
                flightrec.install_hooks()
                perf.install_oom_hook()
                default_tracer().add_listener(rec.on_span)
        if _server is None and port >= 0:
            try:
                _server = MonitorServer(telemetry=_telemetry,
                                        port=port).start()
            except OSError as e:
                logger.error("monitor server failed to bind port %s: %s "
                             "— metrics endpoint disabled, telemetry "
                             "continues", port, e)
                _server = None
        elif _server is not None:
            _server.telemetry = _telemetry
        return _telemetry, _server


def get_monitor_server():
    return _server


def get_telemetry():
    """The live TrainTelemetry, or None when no monitored fit has
    started — existence check only, never creates (fit_monitor
    does)."""
    return _telemetry


def reset():
    """Tear down the process singletons (tests)."""
    global _telemetry, _server
    with _lock:
        if _server is not None:
            _server.shutdown()
            _server = None
        if _telemetry is not None:
            _telemetry.close()
            _telemetry = None
    tracing.reset()
    flightrec.reset()
    perf.reset()
