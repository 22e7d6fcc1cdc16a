"""Device-idle time of the traced window that falls under the program's
host annotations matching `annotation`, over the runs of the executables
matching `per_executable` on the trace's `XLA Modules` line: the
milliseconds a run in which the chip waited while the host was in a named
phase of its loop.

The device's events are stamped by the device's clock and the host's
annotations by the host's, and the two differ by a millisecond or so,
anew in every trace.  The offset (device minus host) is bounded from the
trace itself.  The k-th run of `per_executable` cannot start before the
k-th annotation named `skew.opens` opens (it is dispatched inside it),
nor end after the first `skew.closes` annotation that began later closes
(that scope waits for its result): `end - close <= offset <= start -
open` for every pair.  Where the host plane also holds the runtime's own
events, the pair tightens itself: the run cannot start before the last
`skew.launch` event inside its `opens` annotation begins (the program's
enqueue), nor end after the first `skew.done` event after that one (the
host reading the completion flag) ends.  Those two names are the
runtime's, not the program's: where a runtime writes others the
annotations alone bound the offset, more widely, and the line says so.
The reader moves the device's events by the middle of the interval that
all pairs leave and only then lays the idle gaps over the annotations.
An interval that is empty says that causality is violated (the pairs are
wrong, or a clock drifted) and is printed so.

Printed on an earlier line: idle seconds by innermost `paddle.*` phase
(`unannotated` a row; the rows sum to `idle_s`), the part of it under a
phase that has phases of its own beneath it (`idle_under_parents_s`: time
nothing finer names), the part inside a run of any executable (gaps
between its operations, which no host phase causes), and both intervals.
None without a device plane, or where the program has no `skew.opens`
annotation (an older program)."""
import bisect
import re

from benchmarks import common, trace

UNANNOTATED = "unannotated"


def skew_interval(td, executable, opens, closes, launch=None, done=None):
    """(lo, hi, pairs) in nanoseconds for device clock - host clock, or
    None where the trace lacks the runs or the annotations.  A run may
    have been in flight when the trace began, with no annotation of its
    own: of the two ways to pair runs and annotations in order, the one
    whose interval lies nearer zero is taken (the other is a whole
    iteration away)."""
    rx = re.compile(executable)
    first = next(iter(td.devices.values()))
    runs = sorted((a, b) for n, a, b in first.get(trace.MODULES_LINE, [])
                  if rx.search(n))
    named = {name: [] for name in (opens, closes, launch, done)}
    for n, a, b in td.host:
        if n in named:
            named[n].append((a, b))
    opened, closing, launched, noticed = (
        sorted(named[name]) if name else []
        for name in (opens, closes, launch, done))
    close_at = [a for a, _ in closing]
    launch_at = [a for a, _ in launched]
    notice_at = [a for a, _ in noticed]
    best = None
    for skip in (0, 1):
        lo, hi, pairs = None, None, 0
        for (a, b), (o, c) in zip(runs[skip:], opened):
            i = bisect.bisect_left(close_at, o)
            if i == len(closing):
                break
            t_open, t_close = o, closing[i][1]
            j = bisect.bisect_right(launch_at, c) - 1
            if j >= 0 and launch_at[j] >= o:
                t_open = launch_at[j]
                k = bisect.bisect_left(notice_at, launched[j][1])
                if k < len(noticed) and noticed[k][1] <= t_close:
                    t_close = noticed[k][1]
            pairs += 1
            lo = b - t_close if lo is None else max(lo, b - t_close)
            hi = a - t_open if hi is None else min(hi, a - t_open)
        if pairs:
            off_zero = 0 if lo <= 0 <= hi else min(abs(lo), abs(hi))
            if best is None or off_zero < best[0]:
                best = (off_zero, lo, hi, pairs)
    return best and best[1:]


def innermost(annotations, lo, hi):
    """[lo, hi) cut at every annotation's edges: ([(start, end, name)],
    parents) with the shortest annotation that covers each piece (None
    where none does), and the names that ever covered a piece under a
    shorter one."""
    anns = sorted((a, b, n) for n, a, b in annotations if b > lo and a < hi)
    cuts = sorted({lo, hi, *(t for a, b, _ in anns for t in (a, b)
                             if lo < t < hi)})
    pieces, parents, active, i = [], set(), [], 0
    for t0, t1 in zip(cuts, cuts[1:]):
        while i < len(anns) and anns[i][0] <= t0:
            a, b, n = anns[i]
            active.append((b - a, n, b))
            i += 1
        active = sorted(x for x in active if x[2] > t0)
        pieces.append((t0, t1, active[0][1] if active else None))
        parents.update(n for _, n, _ in active[1:])
    return pieces, parents


def overlap(gaps, pieces):
    """For sorted disjoint `gaps` [(a, b)] and sorted disjoint `pieces`
    [(a, b, ...)]: yields (length, piece) for every piece a gap meets."""
    j = 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            yield min(b, pieces[k][1]) - max(a, pieces[k][0]), pieces[k]
            k += 1


def idle_by_phase(td, offset):
    """The window's idle gaps, with the device's events moved by -offset,
    laid over the program's annotations: (nanoseconds by innermost phase,
    nanoseconds under a phase that has phases beneath it, nanoseconds
    inside a run of any executable, the runs' names)."""
    lo, hi = td.window
    first = next(iter(td.devices.values()))

    def moved(line):
        return trace.clip([(n, a - offset, b - offset)
                           for n, a, b in first.get(line, [])], lo, hi)

    idle = trace.gaps([(a, b) for _, a, b in moved(trace.OPS_LINE)], lo, hi)
    pieces, parents = innermost(
        [e for e in td.host if e[0].startswith("paddle.")], lo, hi)
    by_phase, under_parents = {}, 0
    for length, (_, _, name) in overlap(idle, pieces):
        by_phase[name or UNANNOTATED] = \
            by_phase.get(name or UNANNOTATED, 0) + length
        if name in parents:
            under_parents += length
    runs = moved(trace.MODULES_LINE)
    inside = sum(n for n, _ in overlap(
        idle, sorted((a, b) for _, a, b in runs)))
    return by_phase, under_parents, inside, [n for n, _, _ in runs]


def read(run, annotation, per_executable, skew):
    td = run.trace_data
    if td is None or not any(td.devices.values()):
        return None
    wide = skew_interval(td, per_executable, skew["opens"], skew["closes"])
    if wide is None:
        return None
    s_lo, s_hi, pairs = skew_interval(td, per_executable, **skew)
    offset = (s_lo + s_hi) // 2
    by_phase, under_parents, inside, names = idle_by_phase(td, offset)
    runs = sum(1 for n in names if re.search(per_executable, n))
    under = sum(v for k, v in by_phase.items() if re.search(annotation, k))
    common.note(
        idle_under=annotation, idle_s=sum(by_phase.values()) / 1e9,
        window_s=td.window_s, runs_of_executable=runs,
        idle_by_phase_s={k: v / 1e9 for k, v in
                         sorted(by_phase.items(), key=lambda kv: -kv[1])},
        idle_under_parents_s=under_parents / 1e9,
        idle_inside_executables_s=inside / 1e9,
        device_minus_host_clock_ns=[s_lo, s_hi], skew_pairs=pairs,
        by_the_annotations_alone_ns=list(wide[:2]),
        skew=("consistent" if s_lo <= s_hi and wide[0] <= wide[1]
              else "EMPTY INTERVAL: causality violated"),
        device_events_moved_by_ns=-offset)
    return under / 1e6 / runs if runs else None
