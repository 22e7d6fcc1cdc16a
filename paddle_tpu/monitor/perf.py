"""Per-op performance attribution, HBM accounting, and OOM postmortem.

Reference parity: `fluid.profiler.profiler()` + `tools/timeline.py` gave
the reference stack an op-level view (which operator burned the time)
and gperftools gave it heap attribution.  Under XLA neither exists as a
library surface — the unit of execution is an HLO instruction inside a
fused module, and device memory is opaque PJRT buffers.  This module
rebuilds both views from what XLA *does* expose:

  * **op table** — the compiled step's HLO text (``compiled.as_text()``)
    is parsed into per-instruction analytic costs (dot/conv flops,
    elementwise flops, transcendentals, boundary bytes — the same
    accounting ``HloCostAnalysis`` uses, which is why the summed table
    matches ``cost_analysis()['flops']``), then joined with measured
    per-op times from a bounded ``jax.profiler`` capture: XLA's thunk
    executor emits one trace event per entry instruction, named after
    it, so ``dot.8`` in the table meets ``dot.8`` in the trace.  Ops the
    trace did not cover get the measured step wall attributed
    proportionally to their roofline cost.  Each row carries the
    achieved fraction of roofline and a compute/memory/collective-bound
    classification (arithmetic intensity vs. the device ridge point).
  * **buffer census** — ``jax.live_arrays()`` bucketed by
    (owner tag, dtype, shape).  Owner tags come from registered
    suppliers (the train engine tags params/opt state/buffers, the
    generation engine tags params/KV pages); device arrays nobody claims
    are ``activations`` — in a training process that residue is
    activations, inputs, and XLA temporaries.  This is the accounting
    surface the paged-KV work will report page occupancy into.
  * **OOM postmortem** — a ``RESOURCE_EXHAUSTED`` escaping to the crash
    hook (or caught by an engine thread) dumps the census plus every
    registered op report into the flight recorder under reason
    ``"oom"``, so the first question after an OOM ("what was resident,
    what was the step doing") is answered by a file, not a rerun.

Module-level registries (`register_provider` / `register_owner`) let
engines publish their reports without the monitor server holding engine
references; `MonitorServer GET /debug/perf` serves `collect_reports()`
and `?format=chrome` merges the op timeline into the span export so one
perfetto load shows request spans AND device ops.
"""
from __future__ import annotations

import glob
import gzip
import json
import logging
import os
import re

from ..framework import flags as _flags
from . import flightrec as _flightrec
from .telemetry import peak_bw_per_device, peak_flops_per_device

__all__ = [
    "parse_hlo", "op_table",
    "build_report", "load_trace_op_times", "register_provider",
    "unregister_provider", "collect_reports", "register_owner",
    "unregister_owner", "buffer_census", "hbm_stats", "is_oom",
    "oom_postmortem", "install_oom_hook", "chrome_document", "reset",
]

logger = logging.getLogger("paddle_tpu.monitor")

# ---------------------------------------------------------------------------
# HLO text parsing + analytic per-op costs
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "s8": 1, "u2": 1, "u4": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "opaque": 0, "tuple": 0,
}

# XLA's HloCostAnalysis buckets: transcendental elementwise ops count in
# 'transcendentals', every other elementwise op is one flop per output
# element, and data movement is bytes only.
_TRANSCENDENTAL = {
    "exponential", "exponential-minus-one", "log", "log-plus-one",
    "tanh", "sine", "cosine", "tan", "sqrt", "rsqrt", "cbrt", "power",
    "logistic", "erf", "erf-inv", "atan2",
}
_EW_FLOPS = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "compare", "select", "and", "or", "xor", "not", "negate", "abs",
    "sign", "floor", "ceil", "round-nearest-afz", "round-nearest-even",
    "clamp", "remainder", "convert", "is-finite", "shift-left",
    "shift-right-arithmetic", "shift-right-logical", "popcnt", "clz",
    "real", "imag", "complex", "stochastic-convert", "map",
}
_COLLECTIVES = {
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "ragged-all-to-all",
    "all-reduce-start", "all-reduce-done", "all-gather-start",
    "all-gather-done", "collective-permute-start",
    "collective-permute-done", "send", "send-done", "recv", "recv-done",
}
# no runtime work at all: don't even count bytes
_FREE = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "bitcast-convert", "after-all", "partition-id", "replica-id",
    "domain", "opt-barrier", "optimization-barrier",
    "get-dimension-size", "add-dependency",
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,<=\s]*)\]")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(")
_INSTR_RE = re.compile(
    r"^\s+(?:ROOT\s+)?%([\w.\-]+)\s*=\s*"
    r"(\((?:[^()]|\([^()]*\))*\)|[a-z][a-z0-9]*\[[^\]]*\](?:\{[^}]*\})?)"
    r"\s+([a-zA-Z][\w\-]*)\(")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLED_RE = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|"
    r"false_computation|select|scatter)=%([\w.\-]+)")
_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([0-9,\s]*)\}")
_DIMLBL_RE = re.compile(r"dim_labels=([\w?]+)_([\w?]+)->([\w?]+)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def _shape_stats(text):
    """(elements, bytes) summed over every array shape literal in
    ``text`` — one shape for a plain result type, the components for a
    tuple type or an operand list."""
    elems = by = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        n = 1
        for d in dims.split(","):
            d = d.strip().lstrip("<=").strip()
            if d:
                n *= int(d)
        elems += n
        by += n * _DTYPE_BYTES.get(dt, 4)
    return elems, by


class _Instr:
    __slots__ = ("name", "shape", "opcode", "args", "attrs")

    def __init__(self, name, shape, opcode, args, attrs):
        self.name = name
        self.shape = shape      # result type text
        self.opcode = opcode
        self.args = args        # operand list text (inside the parens)
        self.attrs = attrs      # everything after the closing paren


def parse_hlo(text: str):
    """Parse HLO module text into ``(computations, entry_name)`` where
    computations maps name -> [_Instr].  Only the structure the cost
    model needs — result/operand shapes, opcode, attributes — no full
    grammar.  Where the text names an operand without its shape
    (``dot(%x.1, %w1.1)``, jax 0.9's printer) ``args`` gets the shape
    the operand was defined with, so every cost that reads an operand's
    shape reads the same text either way."""
    comps, entry, cur = {}, None, None
    shapes = {}                # instruction name -> result type text
    for line in text.splitlines():
        if not line:
            continue
        if not line[0].isspace():
            m = _COMP_RE.match(line)
            if m and line.rstrip().endswith("{"):
                cur = m.group(1)
                comps[cur] = []
                if line.lstrip().startswith("ENTRY"):
                    entry = cur
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, shape, opcode = m.groups()
        shapes[name] = shape
        # operand list: scan from the opcode's '(' to its matching ')'
        start = m.end()            # index just past the '('
        depth, i = 1, start
        while i < len(line) and depth:
            c = line[i]
            if c == "(":
                depth += 1
            elif c == ")":
                depth -= 1
            i += 1
        comps[cur].append(_Instr(name, shape, opcode,
                                 line[start:i - 1], line[i:]))
    if entry is None:
        raise ValueError("no ENTRY computation in HLO text")
    for instrs in comps.values():
        for ins in instrs:
            if not _SHAPE_RE.search(ins.args):
                ins.args = ", ".join(
                    shapes.get(n, "") for n in _OPERAND_RE.findall(ins.args))
    return comps, entry


def _instr_cost(ins, comps, memo):
    """(flops, transcendentals, bytes) for one instruction, rolling up
    called computations (fusion/call/while once-through, conditional
    max-branch) the way HloCostAnalysis does."""
    op = ins.opcode
    if op in _FREE:
        return 0, 0, 0
    out_elems, out_bytes = _shape_stats(ins.shape)
    in_elems, in_bytes = _shape_stats(ins.args)
    byts = in_bytes + out_bytes
    if op == "fusion" or op == "call":
        called = _CALLED_RE.findall(ins.attrs)
        fl = tr = 0
        for c in called:
            cf, ct, _ = _comp_cost(c, comps, memo)
            fl, tr = fl + cf, tr + ct
        return fl, tr, byts
    if op == "while":
        fl = tr = 0
        for c in _CALLED_RE.findall(ins.attrs):
            cf, ct, cb = _comp_cost(c, comps, memo)
            fl, tr, byts = fl + cf, tr + ct, byts + cb
        return fl, tr, byts
    if op == "conditional":
        best = (0, 0, 0)
        for c in _CALLED_RE.findall(ins.attrs):
            cc = _comp_cost(c, comps, memo)
            if cc[0] + cc[1] > best[0] + best[1]:
                best = cc
        return best[0], best[1], byts
    if op == "dot":
        red = 1
        m = _CDIMS_RE.search(ins.attrs)
        lhs = _SHAPE_RE.search(ins.args)
        if m and lhs:
            dims = [d for d in lhs.group(2).split(",") if d.strip()]
            for ix in m.group(1).split(","):
                ix = ix.strip()
                if ix and int(ix) < len(dims):
                    red *= int(dims[int(ix)].strip())
        return 2 * out_elems * red, 0, byts
    if op == "convolution":
        shapes = _SHAPE_RE.findall(ins.args)
        fl = 2 * out_elems
        if len(shapes) >= 2:
            kdims = [int(d) for d in shapes[1][1].split(",") if d.strip()]
            kelems = 1
            for d in kdims:
                kelems *= d
            m = _DIMLBL_RE.search(ins.attrs)
            ochan = kdims[m.group(2).index("o")] \
                if m and "o" in m.group(2) and kdims else 1
            fl = 2 * out_elems * max(1, kelems // max(1, ochan))
        return fl, 0, byts
    if op in ("reduce", "reduce-window", "select-and-scatter", "scatter"):
        fl = tr = 0
        apps = max(0, in_elems - out_elems)
        called = _CALLED_RE.findall(ins.attrs)
        if called:
            bf, bt, _ = _comp_cost(called[0], comps, memo)
            fl, tr = apps * max(1, bf), apps * bt
        else:
            fl = apps
        return fl, tr, byts
    if op in _COLLECTIVES:
        # host-visible cost is wire bytes, not math
        return 0, 0, byts
    if op in _TRANSCENDENTAL:
        return 0, out_elems, byts
    if op in _EW_FLOPS:
        return out_elems, 0, byts
    if op in ("rng", "rng-bit-generator"):
        return 0, out_elems, byts
    if op == "sort":
        n = max(2, out_elems)
        return int(n * max(1, n.bit_length() - 1)), 0, byts
    # data movement and anything unrecognized (custom-call included):
    # zero math, boundary bytes
    return 0, 0, byts


def _comp_cost(name, comps, memo):
    if name in memo:
        return memo[name]
    memo[name] = (0, 0, 0)     # cycle guard
    fl = tr = by = 0
    for ins in comps.get(name, ()):
        f, t, b = _instr_cost(ins, comps, memo)
        fl, tr, by = fl + f, tr + t, by + b
    memo[name] = (fl, tr, by)
    return memo[name]


def _source_label(attrs: str) -> str:
    m = _OPNAME_RE.search(attrs)
    if not m:
        return ""
    return m.group(1).rsplit("/", 1)[-1]


def load_trace_op_times(trace_dir: str) -> dict:
    """Per-event-name durations from a ``jax.profiler`` capture dir:
    {name: {"total_us": float, "count": int}} summed over every
    ``*.trace.json(.gz)`` under it.  XLA's thunk executor names device
    events after entry HLO instructions, which is the join key the op
    table uses."""
    acc = {}
    for pat in ("**/*.trace.json.gz", "**/*.trace.json"):
        for path in glob.glob(os.path.join(trace_dir, pat),
                              recursive=True):
            try:
                if path.endswith(".gz"):
                    with gzip.open(path, "rt") as fh:
                        doc = json.load(fh)
                else:
                    with open(path) as fh:
                        doc = json.load(fh)
            except (OSError, ValueError):
                continue
            for ev in doc.get("traceEvents", ()):
                if ev.get("ph") != "X" or ev.get("dur") is None:
                    continue
                a = acc.setdefault(ev.get("name") or "", [0.0, 0])
                a[0] += float(ev["dur"])
                a[1] += 1
    return {n: {"total_us": t, "count": c} for n, (t, c) in acc.items()}


def op_table(hlo_text: str, *, peak_flops: float = None,
             peak_bw: float = None, measured_step_ms: float = None,
             trace_times: dict = None, top: int = None) -> dict:
    """Build the per-op attribution table from compiled HLO text.

    Rows carry analytic flops/transcendentals/bytes, a roofline time
    estimate, a measured-or-attributed ``time_ms`` (``time_source`` says
    which: "trace" when the profiler capture covered the op,
    "attributed" when a measured step wall was spread by roofline share,
    "estimated" when neither exists), the achieved fraction of roofline,
    and a compute/memory/collective-bound classification.  Rows beyond
    ``top`` roll up into one ``(other)`` row so summed columns stay
    exact."""
    if peak_flops is None:
        peak_flops = peak_flops_per_device()
    if peak_bw is None:
        peak_bw = peak_bw_per_device()
    if top is None:
        top = int(_flags.flag("FLAGS_perf_ops_top") or 48)
    comps, entry = parse_hlo(hlo_text)
    memo = {}
    ridge = peak_flops / max(1.0, peak_bw)   # flops/byte at the knee
    rows = []
    for ins in comps[entry]:
        fl, tr, by = _instr_cost(ins, comps, memo)
        if fl == 0 and tr == 0 and by == 0:
            continue
        est_ms = max((fl + tr) / peak_flops, by / peak_bw) * 1e3
        intensity = (fl + tr) / by if by else float("inf")
        if ins.opcode in _COLLECTIVES:
            bound = "collective"
        elif intensity >= ridge:
            bound = "compute"
        else:
            bound = "memory"
        rows.append({
            "name": ins.name, "op": ins.opcode,
            "source": _source_label(ins.attrs),
            "flops": int(fl), "transcendentals": int(tr),
            "bytes": int(by), "intensity": round(intensity, 3)
            if intensity != float("inf") else None,
            "bound": bound, "est_ms": est_ms,
        })
    # -- measured-time join -------------------------------------------------
    traced_ms = 0.0
    unmatched = []
    for r in rows:
        tt = (trace_times or {}).get(r["name"])
        if tt and tt["count"]:
            r["time_ms"] = (tt["total_us"] / tt["count"]) / 1e3
            r["time_source"] = "trace"
            traced_ms += r["time_ms"]
        else:
            unmatched.append(r)
    if measured_step_ms and unmatched:
        residual = max(0.0, measured_step_ms - traced_ms)
        est_sum = sum(r["est_ms"] for r in unmatched) or 1.0
        for r in unmatched:
            r["time_ms"] = residual * (r["est_ms"] / est_sum)
            r["time_source"] = "attributed"
    else:
        for r in unmatched:
            r["time_ms"] = r["est_ms"]
            r["time_source"] = "estimated"
    for r in rows:
        r["roofline_frac"] = round(min(1.0, r["est_ms"] / r["time_ms"]), 4) \
            if r["time_ms"] > 0 else None
        r["est_ms"] = round(r["est_ms"], 6)
        r["time_ms"] = round(r["time_ms"], 6)
    rows.sort(key=lambda r: -r["time_ms"])
    totals = {
        "flops": sum(r["flops"] for r in rows),
        "transcendentals": sum(r["transcendentals"] for r in rows),
        "bytes": sum(r["bytes"] for r in rows),
        "time_ms": round(sum(r["time_ms"] for r in rows), 6),
        "n_ops": len(rows),
    }
    if len(rows) > top:
        tail = rows[top:]
        rows = rows[:top]
        rows.append({
            "name": "(other)", "op": "(rollup)",
            "source": f"{len(tail)} smaller ops",
            "flops": sum(r["flops"] for r in tail),
            "transcendentals": sum(r["transcendentals"] for r in tail),
            "bytes": sum(r["bytes"] for r in tail),
            "intensity": None, "bound": "mixed",
            "est_ms": round(sum(r["est_ms"] for r in tail), 6),
            "time_ms": round(sum(r["time_ms"] for r in tail), 6),
            "time_source": "rollup", "roofline_frac": None,
        })
    return {"ops": rows, "totals": totals,
            "step_ms": measured_step_ms,
            "peak_flops": peak_flops, "peak_bw": peak_bw,
            "ridge_intensity": round(ridge, 3)}


def build_report(compiled, *, name: str, cost_analysis: dict = None,
                 measured_step_ms: float = None,
                 trace_dir: str = None) -> dict:
    """Op report for one compiled executable: ``compiled`` is anything
    with ``as_text()`` (a ``jax.stages.Compiled``) or raw HLO text."""
    text = compiled.as_text() if hasattr(compiled, "as_text") \
        else str(compiled)
    trace_times = load_trace_op_times(trace_dir) if trace_dir else None
    tbl = op_table(text, measured_step_ms=measured_step_ms,
                   trace_times=trace_times)
    tbl["name"] = name
    if cost_analysis:
        tbl["xla"] = {k: cost_analysis.get(k) for k in
                      ("flops", "transcendentals", "bytes accessed")
                      if cost_analysis.get(k) is not None}
    return tbl


# ---------------------------------------------------------------------------
# report providers (engines publish, /debug/perf collects)
# ---------------------------------------------------------------------------

_providers: dict = {}


def register_provider(name: str, fn):
    """Publish a zero-arg callable returning an op report under
    ``name`` ("train", "decode", ...).  Re-registering replaces."""
    _providers[name] = fn


def unregister_provider(name: str):
    _providers.pop(name, None)


def collect_reports(names=None) -> dict:
    """{name: report} over registered providers; a provider that raises
    yields {"error": ...} instead of poisoning the endpoint."""
    out = {}
    for name, fn in sorted(_providers.items()):
        if names and name not in names:
            continue
        try:
            out[name] = fn()
        except Exception as e:  # noqa: BLE001 - introspection never kills
            out[name] = {"name": name,
                         "error": f"{type(e).__name__}: {e}"}
    return out


# ---------------------------------------------------------------------------
# HBM accounting
# ---------------------------------------------------------------------------

_owner_suppliers: dict = {}


def register_owner(tag: str, supplier):
    """Register a zero-arg callable returning a pytree whose leaves are
    the device arrays owned by ``tag`` ("params", "opt_state",
    "kv_pages", ...).  Suppliers are invoked at census time; a raising
    supplier is skipped."""
    _owner_suppliers[tag] = supplier


def unregister_owner(tag: str):
    _owner_suppliers.pop(tag, None)


def hbm_stats() -> list:
    """Per-device PJRT memory stats; empty on backends without them
    (CPU)."""
    import jax

    out = []
    for d in jax.devices():
        try:
            ms = d.memory_stats()
        except Exception:  # noqa: BLE001 - backend-dependent surface
            ms = None
        if not ms:
            continue
        out.append({"device": str(d),
                    "bytes_in_use": int(ms.get("bytes_in_use", 0)),
                    "peak_bytes_in_use":
                        int(ms.get("peak_bytes_in_use", 0)),
                    "bytes_limit": int(ms.get("bytes_limit", 0) or 0)})
    return out


def buffer_census(owners=None, top: int = 64) -> dict:
    """Bucket every live device array by (owner tag, dtype, shape).

    ``owners`` overrides the registered suppliers: a dict or iterable of
    ``(tag, pytree_or_supplier)``.  Arrays no supplier claims are tagged
    ``activations`` (in a training process the unclaimed residue is
    activations, input batches, and XLA temporaries).  ``nbytes`` is the
    logical (global) size of a sharded array."""
    import jax

    if owners is None:
        items = list(_owner_suppliers.items())
    elif isinstance(owners, dict):
        items = list(owners.items())
    else:
        items = list(owners)
    id2tag = {}
    for tag, sup in items:
        try:
            tree = sup() if callable(sup) else sup
            for leaf in jax.tree_util.tree_leaves(tree):
                if hasattr(leaf, "nbytes"):
                    id2tag[id(leaf)] = tag
        except Exception:  # noqa: BLE001 - a dead engine ref is fine
            continue
    buckets, by_tag = {}, {}
    total = count = 0
    for arr in jax.live_arrays():
        try:
            nb = int(arr.nbytes)
            key = (id2tag.get(id(arr), "activations"),
                   str(arr.dtype), tuple(arr.shape))
            # the per-device cost of a GSPMD-sharded array is its
            # largest local shard, not the logical nbytes — this is
            # the number that proves a mesh-sharded table (or ZeRO
            # param) fits where the full array would not
            try:
                shard_nb = max((int(s.data.nbytes)
                                for s in arr.addressable_shards),
                               default=nb)
            except Exception:  # noqa: BLE001 - backend w/o shards API
                shard_nb = nb
        except Exception:  # noqa: BLE001 - deleted mid-iteration
            continue
        b = buckets.get(key)
        if b is None:
            b = buckets[key] = {"tag": key[0], "dtype": key[1],
                                "shape": list(key[2]),
                                "count": 0, "bytes": 0, "shard_bytes": 0}
        b["count"] += 1
        b["bytes"] += nb
        b["shard_bytes"] += shard_nb
        by_tag[key[0]] = by_tag.get(key[0], 0) + nb
        total += nb
        count += 1
    blist = sorted(buckets.values(), key=lambda b: -b["bytes"])
    return {"total_bytes": total, "n_arrays": count, "by_tag": by_tag,
            "buckets": blist[:top], "n_buckets": len(blist),
            "devices": hbm_stats()}


# ---------------------------------------------------------------------------
# OOM postmortem
# ---------------------------------------------------------------------------

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Resource exhausted",
                "Out of memory", "out of memory")


def is_oom(exc) -> bool:
    """True for a PJRT/XLA allocation failure (RESOURCE_EXHAUSTED in
    any spelling the runtime uses)."""
    if exc is None:
        return False
    msg = f"{type(exc).__name__}: {exc}"
    return any(m in msg for m in _OOM_MARKERS)


def _postmortem_payload(exc=None) -> dict:
    payload = {"error": str(exc)[:500] if exc is not None else None}
    try:
        payload["census"] = buffer_census()
    except Exception as e:  # noqa: BLE001 - runtime may be torn down
        payload["census_error"] = f"{type(e).__name__}: {e}"
    try:
        payload["op_reports"] = collect_reports()
    except Exception as e:  # noqa: BLE001
        payload["op_reports_error"] = f"{type(e).__name__}: {e}"
    return payload


def oom_postmortem(exc=None) -> str:
    """Dump census + op reports into the flight recorder under reason
    "oom"; returns the dump path ("" when no recorder is configured).
    Engine threads that CATCH the failure call this directly; uncaught
    failures reach the same payload via the crash-hook enricher."""
    payload = _postmortem_payload(exc)
    _flightrec.record("oom", error=payload.get("error"),
                      total_bytes=payload.get("census", {})
                      .get("total_bytes"))
    return _flightrec.dump("oom", extra={"perf": payload})


def _oom_enricher(exc_type, exc):
    if not is_oom(exc):
        return None
    return {"reason": "oom", "extra": {"perf": _postmortem_payload(exc)}}


def install_oom_hook():
    """Attach the OOM enricher to the flight recorder's crash hook: an
    uncaught RESOURCE_EXHAUSTED turns the crash dump into an "oom" dump
    carrying the buffer census and op reports."""
    _flightrec.add_enricher(_oom_enricher)


# ---------------------------------------------------------------------------
# chrome-trace merge (/debug/perf?format=chrome)
# ---------------------------------------------------------------------------

_DEVICE_PID = 999999   # disjoint from the tracer's os.getpid() span pid


def chrome_document(reports: dict, base: dict = None) -> dict:
    """Merge op-report timelines into a chrome-trace document.  ``base``
    is typically ``tracer.chrome_trace()`` so one perfetto load shows
    request spans and device ops side by side; op rows lay out
    sequentially per report on a synthetic "device ops" process."""
    doc = base if base is not None else {"traceEvents": [],
                                         "displayTimeUnit": "ms"}
    events = doc.setdefault("traceEvents", [])
    events.append({"ph": "M", "pid": _DEVICE_PID, "tid": 0,
                   "name": "process_name",
                   "args": {"name": "device ops"}})
    for tid, (rname, report) in enumerate(sorted(reports.items())):
        events.append({"ph": "M", "pid": _DEVICE_PID, "tid": tid,
                       "name": "thread_name", "args": {"name": rname}})
        cursor = 0.0
        for r in report.get("ops", ()):
            dur = max(0.001, float(r.get("time_ms") or 0.0) * 1e3)
            events.append({
                "ph": "X", "cat": "device", "name": r["name"],
                "ts": round(cursor, 3), "dur": round(dur, 3),
                "pid": _DEVICE_PID, "tid": tid,
                "args": {"op": r.get("op"), "source": r.get("source"),
                         "flops": r.get("flops"),
                         "bytes": r.get("bytes"),
                         "bound": r.get("bound"),
                         "roofline_frac": r.get("roofline_frac"),
                         "time_source": r.get("time_source")}})
            cursor += dur
    return doc


def reset():
    """Test isolation: drop registered providers and owner suppliers."""
    _providers.clear()
    _owner_suppliers.clear()
