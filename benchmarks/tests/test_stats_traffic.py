"""Percentile and window arithmetic by hand-worked numbers, and the
traffic generator: the same requests for the same seed, others for
another, the same multiset of sizes for every seed."""
import json
import os

import pytest

from benchmarks import common, stats, traffic


def test_percentile_by_hand():
    xs = [10, 20, 30, 40, 50]
    assert stats.percentile(xs, 50) == 30
    assert stats.percentile(xs, 95) == pytest.approx(48.0)   # 40 + .8 * 10
    assert stats.percentile(xs, 0) == 10 and stats.percentile(xs, 100) == 50
    assert stats.percentile([], 95) is None
    assert stats.percentile([7], 95) == 7


def test_window_arithmetic_by_hand():
    stamps = [0.5, 1.0, 1.5, 2.5, 3.0]
    # gaps are counted where they END: 0.5->1.0 ends inside, 2.5->3.0 not
    assert stats.gaps_ending_in(stamps, 1.0, 3.0) == [0.5, 0.5, 1.0]


def test_iqr_share_is_the_contracts_spread():
    # statistics.quantiles(n=4) of 1..6 gives 1.75 and 5.25; median 3.5
    assert stats.iqr_share([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)


def _serve_specs():
    d = os.path.join(common.HERE, "traffic")
    for f in sorted(os.listdir(d)):
        spec = json.load(open(os.path.join(d, f)))
        if spec["kind"] == "serve":
            yield pytest.param(spec, id=f[:-5])


@pytest.mark.parametrize("spec", _serve_specs())
def test_same_seed_same_requests_other_seed_other_order(spec):
    big = 2 ** 31 + 12345          # the driver's seeds pass 32 signed bits
    a = traffic.serve_schedule(spec, big, 10.0, 50304)
    b = traffic.serve_schedule(spec, big, 10.0, 50304)
    c = traffic.serve_schedule(spec, big + 1, 10.0, 50304)
    assert a == b
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]
    n = spec.get("block", len(a))   # sizes repeat block by block
    sizes = lambda rs: sorted((len(r["prompt"]), r["max_new"])  # noqa: E731
                              for r in rs[:n])
    assert sorted(len(r["prompt"]) for r in a[:n]) == \
        sorted(len(r["prompt"]) for r in c[:n])
    assert sorted(r["max_new"] for r in a[:n]) == \
        sorted(r["max_new"] for r in c[:n])
    assert sizes(a) != [] and all(
        spec["prompt"]["min"] <= len(r["prompt"]) <= spec["prompt"]["max"]
        and spec["output"]["min"] <= r["max_new"] <= spec["output"]["max"]
        and len(r["prompt"]) + r["max_new"] <= spec["engine"]["max_seq_len"]
        and len(r["prompt"]) <= max(spec["engine"]["prompt_buckets"])
        for r in a)
    if spec["mode"] == "open":
        due = [r["due_s"] for r in a]
        assert due == sorted(due) and due[0] == 0 and due[-1] < 10.0
        assert len(a) == round(spec["rate_per_s"] * 10.0)
        # the same gaps in another order (the one before the first
        # arrival is what is left of the window)
        def gaps(rs):
            g = [y["due_s"] - x["due_s"] for x, y in zip(rs, rs[1:])]
            return sorted(g + [10.0 - sum(g)])
        assert gaps(a) == pytest.approx(gaps(c), abs=1e-9)
    shared = spec["prompt"].get("shared")
    if shared:
        heads = {tuple(r["prompt"][:shared["tokens"]]) for r in a}
        assert len(heads) <= shared["count"]


def test_train_rows_any_row_alone():
    a = traffic.train_rows(2 ** 31 + 5, 0, 4, 16, 512)
    b = traffic.train_rows(2 ** 31 + 5, 2, 2, 16, 512)
    assert (a[2:] == b).all() and a.shape == (4, 17)
    assert not (a[0] == a[1]).all()
    assert not (traffic.train_rows(7, 0, 1, 16, 512) == a[:1]).all()


def test_warmup_covers_every_bucket():
    spec = json.load(open(os.path.join(common.HERE, "traffic", "chat.json")))
    lens = [len(r["prompt"]) for r in
            traffic.warmup_requests(spec, 1, 50304)]
    assert set(spec["engine"]["prompt_buckets"]) <= set(lens)


def test_client_samples_and_percentile_by_hand():
    """TTFT counts the requests due in the window, one with no token as
    the rest of the run; the gap between tokens counts gaps that end in
    the window."""
    from types import SimpleNamespace

    from benchmarks.readers import client_percentile

    rec = lambda due, sent, t: {"due": due, "sent": sent, "t": t}  # noqa
    run = SimpleNamespace(window=(10.0, 20.0), client={
        "collected_until": 24.0, "records": [
            rec(9.0, 9.0, [9.5, 10.5, 11.0]),      # due before the window
            rec(10.0, 10.001, [10.030, 10.060, 10.100]),
            rec(12.0, 12.002, [12.050]),
            rec(19.0, 19.0, []),                   # never answered
            rec(20.0, 20.0, [20.1])]})             # due after it
    assert client_percentile.samples(run, "ttft") == pytest.approx(
        [30.0, 50.0, 5000.0])
    assert client_percentile.read(run, "ttft", 50) == pytest.approx(50.0)
    assert sorted(client_percentile.samples(run, "itl")) == pytest.approx(
        [30.0, 40.0, 500.0, 1000.0])
    assert client_percentile.samples(run, "late") == pytest.approx(
        [1.0, 2.0, 0.0])
    assert client_percentile.read(SimpleNamespace(client=None), "ttft", 75) is None
