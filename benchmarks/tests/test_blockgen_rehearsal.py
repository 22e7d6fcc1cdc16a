"""The block-generation cell rehearsed on the CPU: `tiny-sdar` under
`tiny-blockgen` through the kind `serve_blocks`.  `rehearse.py` puts every
cell under `tiny-gpt`, which cannot generate by blocks, so this cell's
rehearsal lives here: a sound run is correct, the control (the reference
in fp8) is not, and a served token altered where the check reads it is
caught.  Run by hand: `python -m pytest benchmarks/tests -q`."""
import json
import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("PADDLE_TPU_ENABLE_X64", "0")

from benchmarks import common  # noqa: E402
from benchmarks.run import run_cell  # noqa: E402

CELL = "tiny-sdar.blockgen"


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    m = common.load_manifest()
    real = next(w["name"] for w in m["workloads"]
                if w["traffic"] == "blockgen")
    m["configs"] = [{"name": "tiny-sdar", "source": "none",
                     "file": "benchmarks/configs/tiny-sdar.json",
                     "reduced": [], "why": "rehearsal"}]
    m["workloads"] = [{"name": CELL, "config": "tiny-sdar",
                       "traffic": "tiny-blockgen", "chips": 1,
                       "why": "rehearsal"}]
    for metric in m["end_to_end"] + m["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [CELL for w in metric["workloads"]
                                   if w == real]
    path = tmp_path_factory.mktemp("m") / "blockgen.json"
    path.write_text(json.dumps(m))
    return str(path)


def drive(tiny, seed, trace=0, **kw):
    return run_cell(CELL, seed, 3.0, trace, tiny, platform=None, **kw)


def test_sound_run_is_correct_and_serves_whole_blocks(tiny):
    run = drive(tiny, 2 ** 31 + 22)
    assert run.checks.ok, [r for r in run.checks.rows if not r["ok"]]
    c = run.counters
    assert c["block_steps"] > 0 and c["block_tokens_emitted"] > 0
    assert c["block_lane_steps"] == (c["block_lane_steps_denoised"]
                                     + c["block_lane_steps_committed"])
    # k experts a row, 4 rows a live lane-step, in each of the 2 layers.
    # The device's count is published at a step's dispatch and the host's
    # lane-steps after its fetch, so each end of the window may cut one
    # step (4 lanes) between the two
    per = 4 * 3 * 2
    assert c["moe_assignments"] % per == 0
    assert abs(c["moe_assignments"] // per - c["block_lane_steps"]) <= 2 * 4


def test_traced_run_reads_the_counter_metrics(tiny, capsys):
    run = drive(tiny, 7, trace=1)
    assert run.checks.ok, [r for r in run.checks.rows if not r["ok"]]
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = line["metrics"]
    assert 0.3 < got["blockgen_tokens_per_lane_step"]["value"] <= 0.8
    assert got["moe_expert_imbalance"]["value"] >= 1.0
    assert got["prefix_hit_share"]["value"] >= 0.0


@pytest.mark.parametrize("seed", [4, 5])
def test_control_is_not_correct(tiny, seed):
    run = drive(tiny, seed, control="fp8")
    failed = {r["check"] for r in run.checks.rows if not r["ok"]}
    assert failed and failed <= {"served_gap_max", "order_gap_max"}, failed


def test_an_altered_served_token_is_not_correct(tiny):
    def prepare(run):
        def alter(triples):
            _, served, _ = triples[-1]
            served[len(served) // 2] = (served[len(served) // 2] + 1) % 500
            return triples
        run.break_served = alter

    run = drive(tiny, 6, prepare=prepare)
    failed = {r["check"] for r in run.checks.rows if not r["ok"]}
    assert "served_gap_max" in failed and not run.checks.ok
