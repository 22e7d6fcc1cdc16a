"""The cell `mellum2-12b-a2.5b.repochat` rehearsed on the CPU: its entries
in `BENCHMARK.json` as they stand, with `tiny-mellum` in the
configuration's place and `rehearsal-repochat` in the traffic's (not a
`tiny-<mix>`: `rehearse.py` puts every such file under `tiny-gpt`, and this
file's limit is set for `tiny-mellum`; `tiny-repochat.json` is tiny-gpt's).
A sound run is correct and reads the counter metrics, the control (the
reference in fp8) is not correct, and a served token altered where the
check reads it is caught.  And the kind `serve_own`'s prompts: a shared
prefix and behind it an own part of the law the traffic file gives.  Run by
hand: `python -m pytest benchmarks/tests -q`."""
import json
import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("PADDLE_TPU_ENABLE_X64", "0")

from benchmarks import common, traffic  # noqa: E402
from benchmarks.kinds import serve_own  # noqa: E402
from benchmarks.run import run_cell  # noqa: E402

CELL = "mellum2-12b-a2.5b.repochat"


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    m = common.load_manifest()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    cell["traffic"] = "rehearsal-repochat"
    entry = next(c for c in m["configs"] if c["name"] == cell["config"])
    entry["file"] = "benchmarks/configs/tiny-mellum.json"
    path = tmp_path_factory.mktemp("m") / "repochat.json"
    path.write_text(json.dumps(m))
    return str(path)


@pytest.mark.parametrize("mix", ["repochat", "tiny-repochat",
                                 "rehearsal-repochat"])
def test_prompts_are_a_shared_prefix_and_an_own_part(mix):
    spec = common.load_json("traffic", mix + ".json")
    shared, own = spec["prompt"]["shared"], spec["prompt"]["own"]
    big = 2 ** 31 + 12345
    with serve_own.own_part_law():
        a = traffic.serve_schedule(spec, big, 30.0, 512)
        b = traffic.serve_schedule(spec, big, 30.0, 512)
        c = traffic.serve_schedule(spec, big + 1, 30.0, 512)
    assert traffic.quantile_lengths is serve_own._plain     # put back
    assert a == b and len(a) == round(spec["rate_per_s"] * 30.0)
    lens = sorted(len(r["prompt"]) for r in a)
    # every seed the same sizes: the prefix's tokens + the own law's quantiles
    assert lens == sorted(len(r["prompt"]) for r in c)
    assert lens == sorted(shared["tokens"]
                          + traffic.quantile_lengths(own, len(a)))
    assert lens[0] >= shared["tokens"] + own["min"] \
        and lens[-1] <= shared["tokens"] + own["max"]
    assert lens[-1] <= max(spec["engine"]["prompt_buckets"]) \
        and lens[-1] + spec["output"]["max"] <= spec["engine"]["max_seq_len"]
    # the median of the own parts is the law's
    assert abs(np.median(lens) - shared["tokens"] - own["median"]) \
        <= 0.05 * own["median"]
    heads = {tuple(r["prompt"][:shared["tokens"]]) for r in a}
    assert len(heads) == shared["count"]
    tails = {tuple(r["prompt"][shared["tokens"]:]) for r in a}
    assert len(tails) == len(a)                 # no two own parts alike


def drive(tiny, seed, trace=0, **kw):
    return run_cell(CELL, seed, 3.0, trace, tiny, platform=None, **kw)


def test_sound_run_is_correct_and_counts_both_pools(tiny):
    run = drive(tiny, 2 ** 31 + 22)
    assert run.checks.ok, [r for r in run.checks.rows if not r["ok"]]
    c = run.counters
    assert c["prefix_cache_hits"] > 0 and c["decode_steps"] > 0
    # every live lane routes k = 2 assignments in each of the 8 layers
    assert c["moe_assignments"] > 0 and c["moe_assignments"] % (2 * 8) == 0
    # a lane's window row holds a window's pages, its full row the context
    assert 0 < c["kv_mapped_page_steps.window"] \
        < c["kv_mapped_page_steps.full"]
    assert c["kv_window_pages_released"] > 0


def test_traced_run_reads_the_counter_metrics(tiny, capsys):
    run = drive(tiny, 7, trace=1)
    assert run.checks.ok, [r for r in run.checks.rows if not r["ok"]]
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = line["metrics"]
    assert 0.0 < got["kv_window_pages_share"]["value"] < 1.0
    assert got["moe_expert_imbalance"]["value"] >= 1.0
    assert got["prefix_hit_share"]["value"] > 0.0


@pytest.mark.parametrize("seed", [4, 5])
def test_control_is_not_correct(tiny, seed):
    run = drive(tiny, seed, control="fp8")
    failed = {r["check"] for r in run.checks.rows if not r["ok"]}
    assert failed == {"served_token_logit_gap_max"}, failed


def test_an_altered_served_token_is_not_correct(tiny):
    def prepare(run):
        def alter(pairs):
            _, served = pairs[-1]
            served[len(served) // 2] = (served[len(served) // 2] + 1) % 500
            return pairs
        run.break_served = alter

    run = drive(tiny, 6, prepare=prepare)
    failed = {r["check"] for r in run.checks.rows if not r["ok"]}
    assert "served_token_logit_gap_max" in failed and not run.checks.ok
