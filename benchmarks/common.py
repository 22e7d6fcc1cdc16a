"""What every kind of cell shares: finding a cell's files by the names in
BENCHMARK.json, the device check, the compile cache, jax's own compile
count, the peaks table and the result line."""
from __future__ import annotations

import importlib
import json
import os
import re
import sys
import threading
import time

T_PROCESS = time.monotonic()        # as close to process start as we see
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class BenchFailure(Exception):
    """The run cannot give a result: exit non-zero, print no result line."""


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def named_file(kind_dir, name):
    """`benchmarks/<kind_dir>/<name>.json`, by a name from BENCHMARK.json."""
    if not NAME_RE.match(name):
        raise BenchFailure(f"not a name: {name!r}")
    path = os.path.join(HERE, kind_dir, name + ".json")
    if not os.path.isfile(path):
        raise BenchFailure(f"no file {kind_dir}/{name}.json for {name!r}")
    return load_json(kind_dir, name + ".json")


def plugin(kind_dir, name):
    """`benchmarks/<kind_dir>/<name>.py`, found by name as data names it."""
    if not NAME_RE.match(name) or not os.path.isfile(
            os.path.join(HERE, kind_dir, name + ".py")):
        raise BenchFailure(f"no {kind_dir}/{name}.py")
    return importlib.import_module(f"benchmarks.{kind_dir}.{name}")


def load_manifest(path=None):
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve_cell(manifest, workload):
    """(cell, config entry, config file, traffic file) of one workload."""
    cell = next((w for w in manifest["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise BenchFailure(f"no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    traffic = named_file("traffic", cell["traffic"])
    return cell, entry, config, traffic


def metrics_for(manifest, cell, traffic, trace):
    """Names of the metrics one run of this cell owes: with --trace 0 the
    end-to-end metrics the traffic file reports (and setup_s), with
    --trace 1 the per-layer metrics that list this cell under `workloads`
    or, listing none, move an end-to-end metric this cell reports."""
    reports = set(traffic["reports"]) | {"setup_s"}
    if not trace:
        return [m["name"] for m in manifest["end_to_end"]
                if m["name"] in reports]
    return [m["name"] for m in manifest["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in reports)]


def use_compile_cache():
    """JAX's persistent cache at JAX_COMPILATION_CACHE_DIR, else at the
    fixed path <checkout>/.jax_cache.  Set before jax is imported: the
    program takes the directory the environment names."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    return os.environ["JAX_COMPILATION_CACHE_DIR"]


def cache_everything(jax):
    """The program caches only compiles over half a second; the rest
    would compile again in every run of every check."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def require_devices(jax, chips, platform="tpu"):
    devs = jax.devices()
    if devs[0].platform != platform or len(devs) < chips:
        raise BenchFailure(
            f"needs {chips} {platform} device(s); jax.devices() = {devs}")
    return devs


def device_info(jax, chips):
    devs = jax.devices()[:chips]
    peaks = []
    for d in devs:
        st = d.memory_stats() or {}
        peaks.append(st.get("peak_bytes_in_use", 0))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks)}


def peaks_for(device_kind):
    table = load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise BenchFailure(f"no peaks for device kind {device_kind!r} in "
                           "benchmarks/peaks.json")
    return table[device_kind]


class CompileMeter:
    """Executables jax built or loaded from its cache, by jax's own
    monitoring events (copied from chip_smoke.py)."""

    def __init__(self, jax):
        self._lock = threading.Lock()
        self.executables = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.executables += 1
                self.seconds += secs

    def read(self):
        with self._lock:
            return self.executables, self.seconds


class Checks:
    """Every number compared, printed beside its limit; `ok` is their
    conjunction."""

    def __init__(self):
        self.rows = []

    def add(self, name, value, limit, ok=None, note=""):
        """value <= limit unless `ok` is given."""
        if ok is None:
            ok = value is not None and value == value and value <= limit
        self.rows.append({"check": name, "value": value, "limit": limit,
                          "ok": bool(ok), "note": note})
        print(json.dumps(self.rows[-1]), flush=True)
        return ok

    @property
    def ok(self):
        return bool(self.rows) and all(r["ok"] for r in self.rows)


class Phases:
    """Seconds of set-up by phase, printed on an earlier line so that a
    slow set-up says where it went (and how much of it was compiling)."""

    def __init__(self, meter):
        self.meter, self.rows = meter, []
        self.t, self.c = T_PROCESS, (0, 0.0)

    def mark(self, name):
        now, c = time.monotonic(), self.meter.read()
        self.rows.append({"phase": name, "seconds": round(now - self.t, 3),
                          "executables": c[0] - self.c[0],
                          "compile_seconds": round(c[1] - self.c[1], 3)})
        self.t, self.c = now, c


def note(**fields):
    print(json.dumps(fields), flush=True)


def result_line(correct, attempted, failed, metrics, device, breakdown=None):
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
