"""The device seconds of the operations matching `ops` (regular
expressions on the HLO text of the trace's `XLA Ops` line, `{NAME}` a
cell size as in `trace_kernel`) as a share, in percent, of the device
seconds of the executables matching `executable` on `XLA Modules`.  None
without a trace or where either matches nothing."""
from benchmarks import trace
from benchmarks.readers import trace_kernel


def read(run, ops, executable, products=None):
    td = run.trace_data
    if td is None:
        return None
    sz = trace_kernel.sizes(run, products or {})
    part = 0
    for pattern in ops:
        for k, v in sz.items():
            pattern = pattern.replace("{" + k + "}", str(v))
        part += sum(d for _, d in td.events(trace.OPS_LINE, pattern))
    whole = sum(d for _, d in td.events(trace.MODULES_LINE, executable))
    if part == 0 or whole == 0:
        return None
    return 100.0 * part / whole
