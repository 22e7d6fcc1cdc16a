"""The reduction from a profiler trace to busy time, idle gaps and kernel
sums, on a small trace recorded on a TPU v5 lite and kept here
(`data/small_tpu.xplane.pb`, by `tools/record_small_trace.py`: three
bursts of four runs of a small jitted program, sleeps between, inside a
`bench.window` annotation), against the same numbers counted the slow
way, nanosecond by nanosecond."""
import os

import numpy as np
import pytest

from benchmarks import trace

PATH = os.path.join(os.path.dirname(__file__), "data", "small_tpu.xplane.pb")


@pytest.fixture(scope="module")
def td():
    return trace.TraceData.from_file(PATH, 1)


@pytest.fixture(scope="module")
def raw():
    """(name, start, end) of the device's `XLA Ops`, read without
    `TraceData`, and the window annotation."""
    from jax.profiler import ProfileData

    ops, window = [], None
    for plane in ProfileData.from_file(PATH).planes:
        for line in plane.lines:
            for e in line.events:
                span = (e.name, int(e.start_ns),
                        int(e.start_ns + e.duration_ns))
                if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append(span)
                if e.name == "bench.window":
                    window = span[1:]
    return ops, window


def test_window_is_the_annotation(td, raw):
    assert td.window == raw[1] == (43100057, 78913055)
    assert td.window_s == pytest.approx(0.035812998)


def test_busy_is_the_union_counted_by_hand(td, raw):
    ops, (lo, hi) = raw
    covered = np.zeros(hi - lo, bool)
    for _, a, b in ops:
        covered[max(a, lo) - lo:max(min(b, hi), lo) - lo] = True
    assert td.busy_s * 1e9 == pytest.approx(int(covered.sum()), abs=0.5)
    # two bursts of the three fall inside the window (the device's clock
    # runs a millisecond ahead of the host's): 8 runs of about 12 us
    assert 8 * 10e-6 < td.busy_s < 8 * 14e-6
    first = next(iter(td.devices.values()))
    idle = trace.gaps([(a, b) for _, a, b in td._ops(first)], lo, hi)
    assert sum(b - a for a, b in idle) + int(covered.sum()) == hi - lo
    assert all(not covered[a - lo:b - lo].any() for a, b in idle)


def test_kernel_sum_and_executable_runs(td, raw):
    ops, (lo, hi) = raw
    want = [b - a for n, a, b in ops
            if a >= lo and b <= hi and " fusion(" in n]
    got = td.events(trace.OPS_LINE, r" fusion\(bf16\[1024,1024\]")
    assert sorted(d for _, d in got) == sorted(want) and len(want) == 8
    runs = td.events(trace.MODULES_LINE, r"^jit__lambda\(")
    assert len(runs) == 8 and all(10e3 < d < 14e3 for _, d in runs)
    assert td.events(trace.OPS_LINE, "no such kernel") == []


def test_breakdown_names_ops_and_gaps(td):
    bd = td.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    name, secs = bd["device_ops"][0]
    assert name == "%fusion = bf16[] fusion(bf16[1024,1024] %copy-done)"
    assert secs == pytest.approx(8 * 11.9e-6, rel=0.1)
    gaps = [g for _, g in bd["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and gaps[0] > 0.010
    assert td.host_doing(43105828 + 1000) == "paddle.fit/dispatch"
    assert td.host_doing(50_000_000) == "unannotated"


def test_interval_arithmetic_by_hand():
    assert trace.union_length([(0, 10), (5, 15), (20, 30), (22, 25)]) == 25
    assert trace.gaps([(5, 10), (12, 20)], 0, 25) == [(0, 5), (10, 12),
                                                      (20, 25)]
    assert trace.clip([("a", 0, 10), ("b", 20, 30)], 5, 25) == [
        ("a", 5, 10), ("b", 20, 25)]
    assert trace.short_name(
        '%f.1 = bf16[8,128]{1,0:T(8,128)(2,1)} custom-call(bf16[8,128]'
        '{1,0:T(8,128)(2,1)S(1)} %x.2), custom_call_target="tpu_custom_call"'
    ) == "%f.1 = bf16[8,128] custom-call(bf16[8,128] %x.2)"


def test_kernel_roofline_from_sizes_that_are_data(td):
    """`trace_kernel` on the recorded trace: the pattern's placeholders are
    filled from the files' `kernel_sizes`, the eight fusions are costed as
    if each were one causal attention forward of [1, 1024, 1, 1024]."""
    from types import SimpleNamespace

    from benchmarks.costs import flash
    from benchmarks.readers import trace_kernel

    run = SimpleNamespace(
        trace_data=td, config={"kernel_sizes": {"NH": 1, "HD": 1024}},
        traffic={"kernel_sizes": {"B": 1, "S": 1024}},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    parts = [{"pattern": r" fusion\(bf16\[{S},{HD}\]",
              "cost_args": {"backward": False}}]
    got = trace_kernel.read(run, "flash", parts)
    busy = sum(d for _, d in td.events(trace.OPS_LINE, r" fusion\(bf16\["))
    c = flash.cost(1, 1024, 1, 1024)
    least = 8 * max(c["ops"] / 197e12, c["bytes"] / 819e9)
    assert got == pytest.approx(100.0 * least / (busy / 1e9))
    assert trace_kernel.read(run, "flash", [{"pattern": "{NOT_A_SIZE}",
                                             "cost_args": {}}]) is None
