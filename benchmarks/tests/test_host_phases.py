"""The readers that lay the device's idle time over the program's host
phases (`trace_idle_under`) and time one phase (`annotation`), on traces
built by hand, where every number can be worked out on paper, and on the
two small traces recorded on a TPU v5 lite: the one the other tests read,
and `data/small_phases.xplane.pb` (by `tools/record_phase_trace.py`: six
iterations of decode, fetch, distribute around a 0.36 ms program)."""
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks import trace
from benchmarks.readers import annotation, trace_idle_under as tiu

DATA = os.path.join(os.path.dirname(__file__), "data")
PATH = os.path.join(DATA, "small_tpu.xplane.pb")
PHASES = os.path.join(DATA, "small_phases.xplane.pb")
MS = 1_000_000


def loop_trace(offset, steps=4, late_close=0, runtime=False):
    """A decode loop of 10 ms iterations from t = 0: `decode` 0-1 ms,
    the device's step 1-7 ms (two operations with 1 ms between them),
    `fetch` 1-7.2 ms, `distribute` to 8 ms inside which `distribute/push`
    takes 7.5-8 ms, `admit` 8-9.5 ms holding `prefill` 8.2-9 ms, 0.5 ms
    nothing.  The device's clock reads `offset` more than the host's.
    With `runtime` the host plane also holds the runtime's own events:
    the program is enqueued at 0.8 ms and its completion read from 7.0 to
    7.1 ms (and another program's, which is not this loop's, at 9 ms)."""
    host = [("bench.window", 0, steps * 10 * MS)]
    ops, runs = [], []
    for k in range(steps):
        t = k * 10 * MS

        def at(name, a, b):
            host.append((f"paddle.genserve/{name}", t + round(a * MS),
                         t + round(b * MS)))

        at("decode", 0, 1)
        at("fetch", 1, 7.2 - late_close)
        at("distribute", 7.2, 8)
        at("distribute/push", 7.5, 8)
        at("admit", 8, 9.5)
        at("prefill", 8.2, 9)
        if runtime:
            host.append(("DoEnqueueProgram", t + 800_000, t + 850_000))
            host.append(("ReadSyncFlag", t + 7 * MS, t + 7_100_000))
            host.append(("DoEnqueueProgram", t + 8_500_000, t + 8_550_000))
            host.append(("ReadSyncFlag", t + 9 * MS, t + 9_100_000))
        runs.append(("jit_decode_step(1)", t + 1 * MS + offset,
                     t + 7 * MS + offset))
        ops.append(("%a = f32[] fusion()", t + 1 * MS + offset,
                    t + 3 * MS + offset))
        ops.append(("%b = f32[] fusion()", t + 4 * MS + offset,
                    t + 7 * MS + offset))
    devices = {"/device:TPU:0": {trace.OPS_LINE: ops,
                                 trace.MODULES_LINE: runs}}
    return trace.TraceData(devices, host, 1)


ARGS = dict(annotation=r"^paddle\.genserve/",
            per_executable=r"^jit_decode_step\(",
            skew={"opens": "paddle.genserve/decode",
                  "closes": "paddle.genserve/fetch"})


@pytest.mark.parametrize("offset", [0, 150_000, -1_100_000])
def test_a_known_skew_is_recovered_and_idle_laid_under_nested_phases(
        offset, capsys):
    td = loop_trace(offset)
    lo, hi, pairs = tiu.skew_interval(td, ARGS["per_executable"],
                                      **ARGS["skew"])
    # a run starts when `decode` has been open 1 ms and ends 0.2 ms
    # before `fetch` closes
    assert (lo, hi, pairs) == (offset - 200_000, offset + 1 * MS, 4)
    got = tiu.read(SimpleNamespace(trace_data=td), **ARGS)
    # the reader takes the interval's middle, 0.4 ms above the truth, so
    # it sees the step 0.4 ms early, at 0.6 to 6.6 ms of every 10 ms
    # iteration: idle 0.6 under decode, 1 inside the step (fetch), then
    # 0.6 fetch, 0.3 distribute, 0.5 push, 0.2 + 0.5 admit, 0.8 prefill
    # and 0.5 under nothing
    note = json.loads([line for line in capsys.readouterr().out.splitlines()
                       if "idle_by_phase_s" in line][-1])
    rows = note["idle_by_phase_s"]
    want = {"paddle.genserve/decode": 0.6, "paddle.genserve/fetch": 1.6,
            "paddle.genserve/distribute": 0.3,
            "paddle.genserve/distribute/push": 0.5,
            "paddle.genserve/admit": 0.7, "paddle.genserve/prefill": 0.8,
            "unannotated": 0.5}
    assert rows == {k: pytest.approx(4 * v * 1e-3) for k, v in want.items()}
    assert sum(rows.values()) == pytest.approx(note["idle_s"])
    assert note["idle_s"] == pytest.approx(4 * 5e-3)
    # distribute and admit have phases beneath them
    assert note["idle_under_parents_s"] == pytest.approx(4 * 1.0e-3)
    assert note["idle_inside_executables_s"] == pytest.approx(4 * 1e-3)
    assert note["device_minus_host_clock_ns"] == [lo, hi]
    assert note["skew"] == "consistent" and note["runs_of_executable"] == 4
    assert got == pytest.approx(5.0 - 0.5)


def test_the_runtimes_own_events_narrow_the_interval(capsys):
    td = loop_trace(-640_000, runtime=True)
    skew = dict(ARGS["skew"], launch="DoEnqueueProgram", done="ReadSyncFlag")
    # enqueued 0.2 ms before the run starts, its end read 0.1 ms after
    assert tiu.skew_interval(td, ARGS["per_executable"], **skew) == \
        (-640_000 - 100_000, -640_000 + 200_000, 4)
    got = tiu.read(SimpleNamespace(trace_data=td), **dict(ARGS, skew=skew))
    note = json.loads([line for line in capsys.readouterr().out.splitlines()
                       if "idle_by_phase_s" in line][-1])
    assert note["device_minus_host_clock_ns"] == [-740_000, -440_000]
    assert note["by_the_annotations_alone_ns"] == [-840_000, 360_000]
    assert note["device_events_moved_by_ns"] == 590_000
    # 0.05 ms off the truth, the step is seen from 0.95 to 6.95 ms: under
    # `fetch` the millisecond inside it and 0.25 ms after it
    assert got == pytest.approx(5.0 - 0.5)
    assert note["idle_by_phase_s"]["paddle.genserve/fetch"] == \
        pytest.approx(4 * (1.0 + 0.25) * 1e-3)
    assert note["idle_by_phase_s"]["paddle.genserve/decode"] == \
        pytest.approx(4 * 0.95e-3)


def test_an_impossible_skew_is_reported_not_hidden(capsys):
    """`fetch` closes 1.5 ms before the device's step ends by the
    device's own clock, which runs 0.3 ms behind: no offset satisfies
    both ends."""
    td = loop_trace(-300_000, late_close=1.7)
    lo, hi, _ = tiu.skew_interval(td, ARGS["per_executable"], **ARGS["skew"])
    assert lo > hi
    assert tiu.read(SimpleNamespace(trace_data=td), **ARGS) is not None
    assert "EMPTY INTERVAL: causality violated" in capsys.readouterr().out


def test_a_run_in_flight_when_the_trace_began_is_left_out():
    td = loop_trace(100_000)
    want = tiu.skew_interval(td, ARGS["per_executable"], **ARGS["skew"])
    first = td.devices["/device:TPU:0"]
    first[trace.MODULES_LINE].insert(0, ("jit_decode_step(1)", -9 * MS,
                                         -3 * MS))
    assert tiu.skew_interval(td, ARGS["per_executable"],
                             **ARGS["skew"]) == want


def test_without_the_scopes_or_the_device_nothing_is_read():
    td = loop_trace(0)
    run = SimpleNamespace(trace_data=td)
    old = dict(ARGS, skew={"opens": "paddle.genserve/none",
                           "closes": "paddle.genserve/fetch"})
    assert tiu.read(run, **old) is None                 # an older program
    assert annotation.read(run, "paddle.genserve/none", 50) is None
    off_chip = trace.TraceData({}, td.host, 1, require_device=False)
    run = SimpleNamespace(trace_data=off_chip)
    assert tiu.read(run, **ARGS) is None
    assert annotation.read(run, "paddle.genserve/admit", 50) is None
    assert tiu.read(SimpleNamespace(trace_data=None), **ARGS) is None


def test_period_against_duration_and_exact_names():
    td = loop_trace(0, steps=5)
    run = SimpleNamespace(trace_data=td)
    # `admit` is 1.5 ms long and does not match `admit/...` or `prefill`
    td.host.append(("paddle.genserve/admit/fetch", 8 * MS, 9 * MS))
    assert annotation.read(run, "paddle.genserve/admit", 50) == \
        pytest.approx(1.5)
    assert annotation.read(run, "paddle.genserve/decode", 95) == \
        pytest.approx(1.0)
    assert annotation.read(run, "paddle.genserve/decode", 95,
                           period=True) == pytest.approx(10.0)
    # the third iteration waited 30 ms on an empty engine: its period is
    # left out, the others stay
    moved = [(n, a + (30 * MS if a >= 30 * MS else 0),
              b + (30 * MS if a >= 30 * MS else 0)) for n, a, b in td.host
             if n != "bench.window"]
    moved += [("bench.window", 0, 80 * MS),
              ("paddle.genserve/wait", 29_600_000, 59_900_000)]
    run = SimpleNamespace(trace_data=trace.TraceData(td.devices, moved, 1))
    assert annotation.read(run, "paddle.genserve/decode", 100,
                           period=True) == pytest.approx(40.0)
    assert annotation.read(run, "paddle.genserve/decode", 100, period=True,
                           skip_if_holds="paddle.genserve/wait") == \
        pytest.approx(10.0)


def test_on_the_recorded_trace_the_rows_sum_to_the_idle_time_by_hand():
    """`small_tpu.xplane.pb`: three `paddle.fit/dispatch` annotations of
    four runs each.  Nanosecond by nanosecond: what is idle and under the
    annotation, against the reader's row."""
    td = trace.TraceData.from_file(PATH, 1)
    lo, hi = td.window
    by_phase, under_parents, inside, names = tiu.idle_by_phase(td, 0)
    busy = np.zeros(hi - lo, bool)
    first = next(iter(td.devices.values()))
    for _, a, b in trace.clip(first[trace.OPS_LINE], lo, hi):
        busy[a - lo:b - lo] = True
    under = np.zeros(hi - lo, bool)
    for n, a, b in trace.clip(td.host, lo, hi):
        if n == "paddle.fit/dispatch":
            under[a - lo:b - lo] = True
    assert by_phase["paddle.fit/dispatch"] == int((~busy & under).sum())
    assert by_phase["unannotated"] == int((~busy & ~under).sum())
    assert sum(by_phase.values()) == int((~busy).sum())
    assert under_parents == 0 and len(names) == 8
    assert 0 < inside < 8 * 100     # a few ns between a run's operations
    run = SimpleNamespace(trace_data=td)
    durs = sorted((b - a) / 1e6 for n, a, b in td.host
                  if n == "paddle.fit/dispatch" and a >= lo and b <= hi)
    assert annotation.read(run, "paddle.fit/dispatch", 50) == \
        pytest.approx(durs[1]) and len(durs) == 3


def test_on_the_recorded_loop_the_skew_is_bounded_and_consistent():
    """`small_phases.xplane.pb`: the device's clock ran about 1.5 ms
    behind the host's there (every run is stamped a millisecond before
    the annotation that dispatched it opens).  Both intervals hold it, the
    narrower inside the wider, and on the corrected clock no run starts
    before its `decode` scope opens or ends after its `fetch` closes."""
    td = trace.TraceData.from_file(PHASES, 1)
    args = (td, ARGS["per_executable"], "paddle.genserve/decode",
            "paddle.genserve/fetch")
    w_lo, w_hi, pairs = tiu.skew_interval(*args)
    lo, hi, _ = tiu.skew_interval(*args, launch="DoEnqueueProgram",
                                  done="ReadSyncFlag")
    assert pairs == 6
    assert -2_000_000 < w_lo <= lo < hi <= w_hi < -1_000_000
    assert hi - lo < 500_000 < w_hi - w_lo
    offset = (lo + hi) // 2
    first = next(iter(td.devices.values()))
    runs = sorted((a - offset, b - offset)
                  for _, a, b in first[trace.MODULES_LINE])
    opened = sorted((a, b) for n, a, b in td.host
                    if n == "paddle.genserve/decode")
    closed = sorted(b for n, _, b in td.host if n == "paddle.genserve/fetch")
    assert len(runs) == len(opened) == len(closed) == 6
    for (a, b), (o, _), c in zip(runs, opened, closed):
        assert o < a < b < c
    run = SimpleNamespace(trace_data=td)
    skew = {"opens": "paddle.genserve/decode",
            "closes": "paddle.genserve/fetch",
            "launch": "DoEnqueueProgram", "done": "ReadSyncFlag"}
    got = tiu.read(run, ARGS["annotation"], ARGS["per_executable"], skew)
    # most of a 3.3 ms iteration the chip waits: the host sleeps in
    # `distribute` and fetches for a millisecond
    assert 2.5 < got < 3.3
    assert annotation.read(run, "paddle.genserve/decode", 50,
                           period=True) == pytest.approx(3.3, abs=0.15)
