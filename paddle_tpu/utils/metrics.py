"""Shared runtime metrics registry: counters, gauges, histograms,
reservoir quantiles, Prometheus text exposition.

Reference parity: paddle/fluid/platform/monitor.* (the StatRegistry that
backed Fluid's runtime counters) generalized for every subsystem here —
serving (paddle_tpu.serving.metrics builds its exposition on these
types), training telemetry (paddle_tpu.monitor), checkpoint durability
(distributed/checkpoint.py), and the launcher's restart accounting.

Dependency-free by design (no prometheus_client): the exposition format
is a few lines of text
(https://prometheus.io/docs/instrumenting/exposition_formats/) and the
framework needs exactly counters, gauges, histograms, and order-statistic
quantiles.  Every metric registered in a `MetricsRegistry` shares ONE
lock — recording threads (training loop, checkpoint writer, batcher, HTTP
handlers) and the /metrics scraper all touch the same state, and a single
RLock keeps the exposition a consistent snapshot without per-metric lock
ordering.

None of the record/render paths touch jax: incrementing a counter from
the checkpoint writer thread (which must stay jax-free — see
distributed/checkpoint.py) is pure-python dict work under the lock.

Quantiles come from a bounded reservoir of recent observations rather
than histogram interpolation, so a scraped `*_p99_ms` reads an exact
order statistic over the last window instead of a bucket-boundary
estimate.
"""
from __future__ import annotations

import bisect
import collections
import threading
import time

__all__ = ["Counter", "Gauge", "Histogram", "Reservoir", "MetricsRegistry",
           "default_registry"]


def _fmt(v) -> str:
    """Value formatting for exposition lines: ints verbatim (counters,
    counts), floats through %g (gauges, sums) — matching what the
    pre-registry serving exposition emitted byte-for-byte."""
    if isinstance(v, bool):
        return str(int(v))
    if isinstance(v, int):
        return str(v)
    return format(float(v), "g")


class Counter:
    """Monotonic counter; optionally labeled.

    `label=` is ONE label key (a str) or a TUPLE of label keys — with a
    tuple, `inc()` takes a matching tuple of label values and each
    series renders as `name{k1="v1",k2="v2"}` (the
    `paddle_pallas_fallbacks_total{kernel,reason}` shape).  Values are
    tracked per label value (a `collections.Counter`); `preset=`
    pre-creates entries so zero-valued series still render, in
    declaration order.  `fixed=True` restricts the exposition to exactly
    the preset series (extra recorded names stay readable
    programmatically but are not rendered) — the serving exposition
    contract.
    """

    kind = "counter"

    def __init__(self, name: str, help_: str, lock, label=None,
                 preset=(), fixed: bool = False):
        self.name = name
        self.help = help_
        self._lock = lock
        self.label = label
        self.fixed = fixed
        self.values = collections.Counter()
        self._order = []
        for key in preset:
            self.values[key] = 0
            self._order.append(key)
        self._preset_len = len(self._order)
        self.value = 0  # unlabeled total

    def inc(self, arg=1, n: int = None):
        """Unlabeled: `inc()` / `inc(3)`.  Labeled: `inc("reason")` /
        `inc("reason", 3)`.  A float labeled increment stays a float
        (seconds-style counters, e.g. the goodput ledger's badput
        accounting); integral increments keep rendering as ints."""
        with self._lock:
            if self.label is None:
                self.value += int(arg)
                return
            if isinstance(self.label, tuple):
                key = tuple(str(a) for a in arg)
            else:
                key = str(arg)
            if key not in self.values:
                self._order.append(key)
            self.values[key] += 1 if n is None else \
                (float(n) if isinstance(n, float) else int(n))

    def set_totals(self, totals: dict):
        """Labeled: mirror running totals {label value: total} that are
        accumulated elsewhere (the decode loop's StepTimers) and only
        ever grow."""
        values = self.values
        with self._lock:
            for key, total in totals.items():
                if key not in values:
                    self._order.append(key)
                values[key] = total

    def get(self, key=None) -> int:
        with self._lock:
            return self.value if key is None else self.values[key]

    def render(self) -> list[str]:
        lines = [f"# HELP {self.name} {self.help}",
                 f"# TYPE {self.name} counter"]
        if self.label is None:
            lines.append(f"{self.name} {_fmt(self.value)}")
            return lines
        keys = self._order[:self._preset_len] if self.fixed else self._order
        for key in keys:
            if isinstance(self.label, tuple):
                lbl = ",".join(f'{k}="{v}"'
                               for k, v in zip(self.label, key))
            else:
                lbl = f'{self.label}="{key}"'
            lines.append(f'{self.name}{{{lbl}}} {_fmt(self.values[key])}')
        return lines


class Gauge:
    """Instantaneous value; either `set()` explicitly or computed at
    scrape time via `fn` (called with the registry lock held — keep it
    lock-free or reentrant).  With `label=` (one label key) the value is
    a dict {label value: number} and each renders as `name{key="value"}`."""

    kind = "gauge"

    def __init__(self, name: str, help_: str, lock, fn=None, label=None):
        self.name = name
        self.help = help_
        self._lock = lock
        self.fn = fn
        self.label = label
        self.value = {} if label else 0

    def set(self, v):
        with self._lock:
            self.value = v

    def add(self, v):
        with self._lock:
            self.value += v

    def get(self):
        with self._lock:
            return self.fn() if self.fn is not None else self.value

    def render(self) -> list[str]:
        v = self.fn() if self.fn is not None else self.value
        head = [f"# HELP {self.name} {self.help}",
                f"# TYPE {self.name} gauge"]
        if self.label:
            return head + [f'{self.name}{{{self.label}="{k}"}} {_fmt(x)}'
                           for k, x in v.items()]
        return head + [f"{self.name} {_fmt(v)}"]


class Histogram:
    """Cumulative-bucket histogram (Prometheus `histogram` type)."""

    kind = "histogram"

    def __init__(self, name: str, help_: str, buckets, lock=None):
        self.name = name
        self.help = help_
        self._lock = lock or threading.RLock()
        self.uppers = sorted(float(b) for b in buckets)
        self.counts = [0] * len(self.uppers)  # per-bucket (non-cumulative)
        self.total = 0
        self.sum = 0.0

    def observe(self, value: float):
        with self._lock:
            self._observe_locked(value)

    def _observe_locked(self, value: float):
        self.total += 1
        self.sum += value
        i = bisect.bisect_left(self.uppers, value)
        if i < len(self.counts):
            self.counts[i] += 1

    def render(self) -> list[str]:
        lines = [f"# HELP {self.name} {self.help}",
                 f"# TYPE {self.name} histogram"]
        cum = 0
        for upper, c in zip(self.uppers, self.counts):
            cum += c
            le = f"{upper:g}"
            lines.append(f'{self.name}_bucket{{le="{le}"}} {cum}')
        lines.append(f'{self.name}_bucket{{le="+Inf"}} {self.total}')
        lines.append(f"{self.name}_sum {self.sum:g}")
        lines.append(f"{self.name}_count {self.total}")
        return lines


class Reservoir:
    """Bounded window of recent observations for exact order-statistic
    quantiles.  Not itself rendered — pair it with computed `Gauge`s
    (`fn=lambda: res.quantile(0.99)`).

    Bounded by COUNT (the last `size` observations, the default) and
    optionally by TIME: with `window_s` set, observations older than the
    window are evicted before every quantile, so a scraped p99 after a
    traffic lull describes recent behavior instead of stale history.
    `window_s=None` keeps the lifetime-cumulative default."""

    def __init__(self, size: int = 4096, lock=None,
                 window_s: float = None):
        self._lock = lock or threading.RLock()
        self.values = collections.deque(maxlen=size)
        self.window_s = float(window_s) if window_s else None
        self._stamps = collections.deque(maxlen=size) \
            if self.window_s else None

    def observe(self, v: float):
        with self._lock:
            self.values.append(float(v))
            if self._stamps is not None:
                self._stamps.append(time.monotonic())

    def _evict_locked(self):
        # values/_stamps share maxlen and are appended in lockstep, so
        # ring overflow drops the same (oldest) entries from both
        cutoff = time.monotonic() - self.window_s
        while self._stamps and self._stamps[0] < cutoff:
            self._stamps.popleft()
            self.values.popleft()

    def __len__(self):
        return len(self.values)

    def quantile(self, q: float) -> float:
        with self._lock:
            return self.quantile_locked(q)

    def quantile_locked(self, q: float) -> float:
        if self._stamps is not None:
            self._evict_locked()
        if not self.values:
            return 0.0
        xs = sorted(self.values)
        idx = min(len(xs) - 1, max(0, int(round(q * (len(xs) - 1)))))
        return xs[idx]


class MetricsRegistry:
    """Ordered collection of metrics sharing one RLock, rendered as one
    Prometheus text document in registration order.

    `counter`/`gauge`/`histogram`/`reservoir` are get-or-create: a second
    registration of the same name returns the existing metric (so a
    second `Model.fit` in the same process reuses the gauges instead of
    colliding)."""

    def __init__(self):
        self._lock = threading.RLock()
        self._metrics: dict[str, object] = {}
        self._reservoirs: dict[str, Reservoir] = {}

    # -- registration (get-or-create) --------------------------------------
    def _existing(self, name: str, kind: str):
        """Get-or-create guard: a second registration of `name` must ask
        for the SAME kind — `counter("x")` after `gauge("x")` would hand
        back a Gauge and fail later at `.inc()`, far from the typo.
        (PTA007 catches the static cases; this is the runtime
        complement for dynamically-built names.)"""
        m = self._metrics.get(name)
        if m is not None and m.kind != kind:
            raise ValueError(
                f"metric {name!r} already registered as {m.kind}, "
                f"re-requested as {kind}")
        return m

    def counter(self, name: str, help_: str = "", label=None,
                preset=(), fixed: bool = False) -> Counter:
        with self._lock:
            m = self._existing(name, "counter")
            if m is None:
                m = Counter(name, help_, self._lock, label=label,
                            preset=preset, fixed=fixed)
                self._metrics[name] = m
            return m

    def gauge(self, name: str, help_: str = "", fn=None,
              label=None) -> Gauge:
        with self._lock:
            m = self._existing(name, "gauge")
            if m is None:
                m = Gauge(name, help_, self._lock, fn=fn, label=label)
                self._metrics[name] = m
            elif fn is not None:
                m.fn = fn
            return m

    def histogram(self, name: str, help_: str = "", buckets=(1, 10, 100)) \
            -> Histogram:
        with self._lock:
            m = self._existing(name, "histogram")
            if m is None:
                m = Histogram(name, help_, buckets, lock=self._lock)
                self._metrics[name] = m
            return m

    def reservoir(self, name: str, size: int = 4096,
                  window_s: float = None) -> Reservoir:
        """Unrendered observation window (see Reservoir); keyed separately
        from rendered metrics.  `window_s=None` defers to
        `FLAGS_metrics_window_s` (0 = lifetime-cumulative, the
        default)."""
        with self._lock:
            r = self._reservoirs.get(name)
            if r is None:
                if window_s is None:
                    try:  # lazy: utils.metrics stays importable standalone
                        from ..framework import flags as _flags
                        window_s = float(
                            _flags.flag("FLAGS_metrics_window_s", 0.0)
                            or 0.0)
                    except Exception:  # noqa: BLE001
                        window_s = 0.0
                r = Reservoir(size, lock=self._lock,
                              window_s=window_s or None)
                self._reservoirs[name] = r
            return r

    def get(self, name: str):
        with self._lock:
            return self._metrics.get(name)

    # -- exposition --------------------------------------------------------
    def prometheus_text(self) -> str:
        with self._lock:
            lines = []
            for m in self._metrics.values():
                lines.extend(m.render())
            return "\n".join(lines) + "\n"

    def snapshot(self) -> dict:
        """Programmatic view: {name: value | {label: value} | {hist
        summary}} for bench fields and tests."""
        with self._lock:
            out = {}
            for name, m in self._metrics.items():
                if m.kind == "counter":
                    if m.label is None:
                        out[name] = m.value
                    else:
                        # tuple-labeled series join their label values so
                        # the snapshot stays JSON-serializable
                        out[name] = {
                            (",".join(k) if isinstance(k, tuple) else k): v
                            for k, v in m.values.items()}
                elif m.kind == "gauge":
                    out[name] = m.fn() if m.fn is not None else m.value
                else:
                    out[name] = {"count": m.total, "sum": m.sum,
                                 "mean": (m.sum / m.total) if m.total
                                 else 0.0}
            return out


_default_registry = None
_default_lock = threading.Lock()


def default_registry() -> MetricsRegistry:
    """The process-wide registry that training telemetry
    (paddle_tpu.monitor), checkpoint durability counters
    (distributed/checkpoint.py), the NaN-policy counters
    (distributed/resilience.py), and the launcher all share — one
    /metrics endpoint describes the whole job.  Serving keeps its own
    per-engine registry (ServingMetrics) so multiple engines in one
    process don't collide."""
    global _default_registry
    if _default_registry is None:
        with _default_lock:
            if _default_registry is None:
                _default_registry = MetricsRegistry()
    return _default_registry
