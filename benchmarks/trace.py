"""Profiler trace: start, stop, and the reduction from the `.xplane.pb`
to what the readers ask for.  The benchmark's own: it reads the device
planes only for device time (the program's `load_trace_op_times` sums
host threads in), and clips everything to the `bench.window` annotation
the harness writes around the measured window.

Layout of a TPU trace as jax 0.9 writes it (looked at by hand with
`benchmarks/tools/trace_summary.py`): one plane `/device:TPU:<n>` a chip,
with the lines `XLA Modules` (one event an executable run, named
`<jit name>(<fingerprint>)`), `XLA Ops` (one event an HLO instruction) and
`Steps`; and a plane `/host:CPU` with one line a thread, holding the
`TraceAnnotation`s (`bench.window`, `paddle.fit/*`, `paddle.genserve/*`).
All times are nanoseconds on one clock.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile

WINDOW = "bench.window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def start(run):
    import jax

    run.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # no per-call Python events
    opts.host_tracer_level = 2
    jax.profiler.start_trace(run.trace_dir, profiler_options=opts)


def stop(run):
    import jax

    jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(run.trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    try:
        if not paths:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        keep = getattr(run, "keep_trace", None)
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(paths[0], os.path.join(
                keep, f"{run.cell['name']}.xplane.pb"))
        run.trace_data = TraceData.from_file(paths[0], run.cell["chips"],
                                             require_device=run.on_chip)
    finally:
        shutil.rmtree(run.trace_dir, ignore_errors=True)


_NUMBERED = re.compile(r"(%[A-Za-z_\-]+)[.\d]*")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(hlo):
    """An `XLA Ops` event is named by its whole HLO text.  Without the
    layouts (`{1,0:T(8,128)(2,1)}`) and the attributes after the operands
    it is `%name = shapes op(operand shapes)`: what the patterns of the
    kernel metrics match, and short enough for the breakdown."""
    text = _LAYOUT.sub("", _LAYOUT.sub("", hlo))
    for cut in ("), custom_call_target", "), kind=", "), calls=",
                "), metadata", "), frontend_attributes", "), backend_config"):
        i = text.find(cut)
        if i >= 0:
            text = text[:i + 1]
            break
    return text[:240]


def union_length(intervals):
    """Total length of the union of [start, end) intervals."""
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def gaps(intervals, lo, hi):
    """The idle gaps [a, b) inside [lo, hi) that no interval covers."""
    out, end = [], lo
    for a, b in sorted(intervals):
        if a > end:
            out.append((end, min(a, hi)))
        end = max(end, b)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi))
    return [(a, b) for a, b in out if b > a]


def clip(events, lo, hi):
    """(name, start, end) events cut to [lo, hi); those outside go."""
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if b > lo and a < hi]


class TraceData:
    """Device and host events of one trace, in nanoseconds.

    devices: {plane name: {line name: [(name, start, end)]}}
    host:    [(name, start, end)] of every host thread
    window:  (lo, hi) of the `bench.window` annotation
    """

    def __init__(self, devices, host, chips, require_device=True):
        self.devices = dict(sorted(devices.items())[:chips])
        self.host = host
        marks = [e for e in host if e[0] == WINDOW]
        if not marks:
            raise RuntimeError(f"no {WINDOW!r} annotation in the trace")
        self.window = (marks[0][1], marks[0][2])
        if not self.devices:
            if require_device:
                raise RuntimeError("no device plane in the trace")
            self.devices = {"none (rehearsal off the chip)": {}}

    @classmethod
    def from_file(cls, path, chips=1, require_device=True):
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        devices, host = {}, []
        for plane in data.planes:
            is_dev = plane.name.startswith("/device:TPU:")
            lines = {}
            for line in plane.lines:
                short = short_name if line.name == OPS_LINE else str
                evs = [(short(e.name), int(e.start_ns),
                        int(e.start_ns + e.duration_ns))
                       for e in line.events]
                if is_dev:
                    lines[line.name] = evs
                elif plane.name.startswith("/host:"):
                    host.extend(evs)
            if is_dev and re.fullmatch(r"/device:TPU:\d+", plane.name):
                devices[plane.name] = lines
        return cls(devices, host, chips, require_device)

    # -- device time -------------------------------------------------------
    def _ops(self, lines):
        return clip(lines.get(OPS_LINE, []), *self.window)

    @property
    def window_s(self):
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self):
        """Seconds in which an operation ran, averaged over the chips."""
        per = [union_length([(a, b) for _, a, b in self._ops(lines)])
               for lines in self.devices.values()]
        return sum(per) / len(per) / 1e9

    def events(self, line, pattern):
        """Durations (ns) of the events of `line` on the first chip whose
        name matches `pattern`, wholly inside the window."""
        rx = re.compile(pattern)
        lo, hi = self.window
        first = next(iter(self.devices.values()))
        return [(n, b - a) for n, a, b in first.get(line, [])
                if a >= lo and b <= hi and rx.search(n)]

    def breakdown(self, top=10):
        first = next(iter(self.devices.values()))
        ops = self._ops(first)
        by_name = {}
        for n, a, b in ops:
            # one row a kind of operation: `%fusion.12` and `%fusion.40`
            # with the same shapes are the same work in different layers
            n = _NUMBERED.sub(r"\1", n)
            by_name[n] = by_name.get(n, 0) + (b - a)
        device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        idle = gaps([(a, b) for _, a, b in ops], *self.window)
        idle = sorted(idle, key=lambda g: g[0] - g[1])[:top]
        return {
            "device_ops": [[n, d / 1e9] for n, d in device_ops],
            "idle_gaps": [[self.host_doing((a + b) // 2), (b - a) / 1e9]
                          for a, b in idle]}

    def host_doing(self, t):
        """The innermost program annotation that covers instant t."""
        best = None
        for n, a, b in self.host:
            if a <= t < b and n != WINDOW and n.startswith("paddle."):
                if best is None or (b - a) < best[1]:
                    best = (n, b - a)
        return best[0] if best else "unannotated"
