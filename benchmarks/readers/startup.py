"""Seconds of start-up from the program's own record,
`paddle_tpu.utils.profiler.startup()`: the process-wide `StepTimers` that
the package's import, `GenerationEngine.start()` and `Model.fit` write,
with one row for every executable built (its scope's `wall_s`, and the
`compile_s` jax's own compile events put there).  The record is complete
before the window opens, so nothing is asked of the harness.

`field` over the scopes named (each with everything under it):
  scope_s          the scopes' own seconds
  trace_lower_s    sum over the executables built of wall_s - compile_s:
                   host Python, tracing and lowering
  compile_s        sum over every row of compile_s: XLA compiles and
                   loads from the persistent cache
  slowest_build_s  the largest wall_s of one executable built

None where the program keeps no such record (a checkout from before it)
or the record holds nothing under the scopes.  The first call notes the
phases and the by-executable table on a line of their own."""
from benchmarks import common

_noted = False


def read(run, field, scopes=None):
    global _noted
    try:
        from paddle_tpu.utils import profiler

        boot = profiler.startup()
    except (ImportError, AttributeError):
        return None
    if not boot.totals:
        return None
    if not _noted:
        _noted = True
        common.note(startup={"phases": boot.summary(),
                             "executables": boot.table()})
    if field == "scope_s":
        found = [boot.totals[s] for s in scopes or () if s in boot.totals]
        return sum(found) if found else None
    rows = boot.table(scopes)
    built = [r for r in rows if r["built"]]
    if field == "compile_s":
        return sum(r["compile_s"] for r in rows) if rows else None
    if not built:
        return None
    if field == "trace_lower_s":
        return sum(r["wall_s"] - r["compile_s"] for r in built)
    if field == "slowest_build_s":
        return max(r["wall_s"] for r in built)
    raise common.BenchFailure(f"readers/startup.py: no field {field!r}")
