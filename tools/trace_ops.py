#!/usr/bin/env python3
"""Every device operation of a kept benchmark trace, summed by kind, with
those that touch a KV plane or pool apart.

    python3 tools/trace_ops.py FILE.xplane.pb [SHAPE ...]

`benchmarks/run.py --trace 1 --keep-trace DIR` keeps the `.xplane.pb`; its
result line lists only the ten heaviest kinds.  A SHAPE is the text of an
operand or result shape as the trace prints it, e.g. `[24,1024,16,16,128]`
(a whole pool of the chat cell) or `[1024,16,16,128]` (one layer's plane);
operations whose text holds one are listed first.  Reads the trace with
the benchmark's own reduction (`benchmarks/trace.py`), on the CPU: run it
on the machine that wrote the trace when the file is too large to bring
back, and keep its text.
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import trace  # noqa: E402


def main(path, shapes, top=40):
    td = trace.TraceData.from_file(path, 1)
    first = next(iter(td.devices.values()))
    by_kind = {}
    for name, a, b in trace.clip(first.get(trace.OPS_LINE, []), *td.window):
        kind = trace._NUMBERED.sub(r"\1", name)
        by_kind[kind] = by_kind.get(kind, 0) + (b - a)
    ranked = sorted(by_kind.items(), key=lambda kv: -kv[1])
    shaped = [(k, d) for k, d in ranked if any(s in k for s in shapes)]
    steps = td.events("XLA Modules", "jit_decode_step")
    print(json.dumps({
        "window_s": td.window_s, "busy_s": td.busy_s,
        "decode_steps": len(steps),
        "decode_steps_s": sum(d for _, d in steps) / 1e9,
        "ops_s": sum(by_kind.values()) / 1e9,
        "shaped_s": sum(d for _, d in shaped) / 1e9}))
    for title, rows in (("WITH A SHAPE", shaped), (f"TOP {top}", ranked[:top])):
        print(title)
        for kind, d in rows:
            print(f"{d / 1e9:9.4f} {kind[:230]}")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2:])
