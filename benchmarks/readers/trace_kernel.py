"""A kernel's share of its roofline over the traced window, in percent.

`parts` lists the kernel's device operations: each a `pattern` (a regular
expression on the operation's HLO text on the trace's `XLA Ops` line, in
which `{NAME}` stands for one of the cell's own sizes: no Pallas call has
a stable name yet, so a kernel is known by its operands' shapes) and, if
its calls are to be costed, the `cost_args` for the function `cost` under
`benchmarks/costs/`.  The sizes are data: the `kernel_sizes` of the cell's
configuration file (heads, head size, ...) and of its traffic file (batch,
sequence, slots), and the metric's own `products` of those (`"BH": ["B",
"NH"]`).  The share is the least time the chip could take,
the larger of operations over peak FLOP/s and bytes over peak bytes/s,
summed over the costed calls, over the time of all the parts' calls.  The
bound that applies is printed on an earlier line.
"""
import math

from benchmarks import common, trace


def sizes(run, products):
    sz = {**run.config.get("kernel_sizes", {}),
          **run.traffic.get("kernel_sizes", {})}
    for name, factors in products.items():
        sz[name] = math.prod(sz[f] for f in factors)
    return sz


def read(run, cost, parts, products=None):
    td = run.trace_data
    if td is None or run.peaks is None:
        return None
    mod = common.plugin("costs", cost)
    sz = sizes(run, products or {})
    time_ns, ops, nbytes = 0, 0.0, 0.0
    for part in parts:
        pattern = part["pattern"]
        for k, v in sz.items():
            pattern = pattern.replace("{" + k + "}", str(v))
        evs = td.events(trace.OPS_LINE, pattern)
        time_ns += sum(d for _, d in evs)
        if "cost_args" in part and evs:
            c = mod.for_window(run, len(evs), sz, **part["cost_args"])
            ops += c["ops"]
            nbytes += c["bytes"]
    if time_ns == 0 or (ops == 0 and nbytes == 0):
        return None
    t_ops = ops / run.peaks["bf16_flops_per_s"]
    t_bytes = nbytes / run.peaks["hbm_bytes_per_s"]
    common.note(kernel=cost, device_seconds=time_ns / 1e9,
                least_seconds_by_ops=t_ops, least_seconds_by_bytes=t_bytes,
                bound="operations" if t_ops >= t_bytes else "bytes")
    return 100.0 * max(t_ops, t_bytes) / (time_ns / 1e9)
