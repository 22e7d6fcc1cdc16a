"""The cell `granite-4.0-h-micro.toolchat` rehearsed on the CPU: its entries
in `BENCHMARK.json` as they stand, with `tiny-granite` in the
configuration's place and `rehearsal-toolchat` in the traffic's (not a
`tiny-<mix>`: `rehearse.py` puts every such file under `tiny-gpt`, which
holds no recurrent state; `tiny-toolchat.json` is tiny-gpt's).  A sound run
is correct, restores nearly every admission from a snapshot and reads the
counter metrics; the control (the reference in fp8) is not correct, a
served token altered where the check reads it is caught, and so is a fault
planted in the restore or in a step's tail (`tools/state_faults.py`), by the
number that reads the state a request left in its slot.  Run by hand:
`python -m pytest benchmarks/tests -q`."""
import json
import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("PADDLE_TPU_ENABLE_X64", "0")

from benchmarks import common  # noqa: E402
from benchmarks.costs import ssm as cost  # noqa: E402
from benchmarks.run import run_cell  # noqa: E402
from benchmarks.tools import state_faults  # noqa: E402

CELL = "granite-4.0-h-micro.toolchat"


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    m = common.load_manifest()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    cell["traffic"] = "rehearsal-toolchat"
    entry = next(c for c in m["configs"] if c["name"] == cell["config"])
    entry["file"] = "benchmarks/configs/tiny-granite.json"
    path = tmp_path_factory.mktemp("m") / "toolchat.json"
    path.write_text(json.dumps(m))
    return str(path)


def drive(tiny, seed, trace=0, **kw):
    return run_cell(CELL, seed, 3.0, trace, tiny, platform=None, **kw)


def test_the_cell_is_entered_as_the_issue_gives_it():
    m = common.load_manifest()
    cell, entry, config, spec = common.resolve_cell(m, CELL)
    assert cell["chips"] == 1 and entry["reduced"] == config["reduced"] == []
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # serve_own, and the state a request left in its slot (kind_from)
    assert spec["kind"] == "serve_state" and spec["reports"] == ["itl_p95_ms"]
    assert spec["engine"] == {"max_slots": 48, "max_seq_len": 2048,
                              "page_size": 64, "prompt_buckets": [256, 1280],
                              "prefix_cache": True}
    assert spec["prompt"]["shared"] == {"count": 4, "tokens": 1024,
                                        "zipf_s": 1.0}
    # every number of the published config, under its key, at the top level
    src = config["source_keys"]
    assert all(config[k] == v for k, v in src.items())
    assert all(config["model"][k] == src[k] for k in config["model"]
               if k in src)
    assert config["model"]["layer_types"].count("attention") == 4
    # the new cell owes the four metrics of this PR in a traced run
    owed = common.metrics_for(m, cell, spec, 1)
    assert {"ssm_decode_roofline", "ssd_prefill_roofline", "ssm_decode_share",
            "state_restore_share"} <= set(owed)


def test_sound_run_is_correct_and_restores(tiny):
    run = drive(tiny, 2 ** 31 + 22)
    assert run.checks.ok, [r for r in run.checks.rows if not r["ok"]]
    c = run.counters
    hits, misses = c["prefix_cache_hits"], c["prefix_cache_misses"]
    # a hit is a restore: nothing is shared deeper than a snapshot lies
    # (the scan is counted at its dispatch, the hit after its fetch: an
    # admission across an end of the window is in one and not the other)
    assert hits > 0 and abs(c["state_restores"] - hits) <= 1, c
    assert misses <= 4 and abs(c["state_scans"] - hits - misses) <= 1, c
    # a restored admission scans its own part alone (8-32 tokens)
    assert c["state_scan_tokens"] <= 32 * (hits + 1) + 80 * misses
    assert 0 < c["state_lane_steps"] <= 6 * c["decode_steps"]


def test_traced_run_reads_the_counter_metrics(tiny, capsys):
    run = drive(tiny, 7, trace=1)
    assert run.checks.ok, [r for r in run.checks.rows if not r["ok"]]
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = line["metrics"]
    assert got["state_restore_share"]["value"] > 80.0
    assert got["prefix_hit_share"]["value"] > 80.0
    # the kernels' metrics need a device trace: left out here, not zero
    assert "ssm_decode_roofline" not in got


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


@pytest.mark.parametrize("fault", state_faults.FAULTS)
def test_a_fault_in_the_state_is_not_correct(tiny, fault):
    undo = []
    try:
        run = drive(tiny, 8, prepare=lambda run: undo.append(
            state_faults.plant(fault)))
    finally:
        for u in undo:
            u()
    failed = {r["check"] for r in run.checks.rows if not r["ok"]}
    assert "held_state_rel_err_max" in failed, run.checks.rows
    # nothing else of the run is touched: the requests come back, nothing
    # is built in the window, no kernel falls back
    assert failed <= {"held_state_rel_err_max", "served_token_logit_gap_max"}


def test_costs_follow_the_model_not_the_kernel():
    dims = (36, 64, 64, 128)
    one = cost.update_cost(1, *dims)
    # a lane-step reads and writes 36 x 2 MB
    assert one["bytes"] == 2 * 36 * 64 * 64 * 128 * 4
    assert cost.update_cost(30, *dims)["bytes"] == 30 * one["bytes"]
    # the scan: a short suffix costs its own tokens, not its bucket's
    short = cost.scan_cost(130, 1, *dims, 256)
    full = cost.scan_cost(256, 1, *dims, 256)
    assert short["ops"] < 0.5 * full["ops"]
    assert cost.scan_cost(0, 0, *dims, 256) == {"ops": 0, "bytes": 0}
    # two chunks are two chunks' squares, not one square of twice the side
    two = cost.scan_cost(512, 1, *dims, 256)
    assert two["ops"] == pytest.approx(2 * full["ops"])
