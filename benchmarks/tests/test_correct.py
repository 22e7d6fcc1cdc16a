"""`correct` at a size a test run can hold (the `tiny-gpt` rehearsal cells
on the CPU): a sound run is correct; the control (the reference in fp8,
the precision below the configuration's bfloat16, in the program's place)
is not; and with the timed path broken underneath, the rest of a run,
driven without the harness's look for a chip, says not correct either.
Run by hand: `python -m pytest benchmarks/tests -q` (two minutes)."""
import json
import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("PADDLE_TPU_ENABLE_X64", "0")

from benchmarks import common, rehearse  # noqa: E402
from benchmarks.run import run_cell  # noqa: E402

CELLS = [w["name"] for w in rehearse.tiny_manifest(
    common.load_manifest())["workloads"]]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    path = tmp_path_factory.mktemp("m") / "rehearsal.json"
    path.write_text(json.dumps(rehearse.tiny_manifest(
        common.load_manifest())))
    return str(path)


def drive(cell, tiny, seed, **kw):
    return run_cell(cell, seed, 3.0, 0, tiny, platform=None, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, tiny):
    run = drive(cell, tiny, 2 ** 31 + 21)
    assert run.checks.ok, [r for r in run.checks.rows if not r["ok"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [4, 5])
def test_control_is_not_correct(cell, tiny, seed):
    control = common.resolve_cell(json.load(open(tiny)), cell)[3][
        "control_precision"]
    run = drive(cell, tiny, seed, control=control)
    assert not run.checks.ok
    failed = {r["check"] for r in run.checks.rows if not r["ok"]}
    assert failed <= {"grad_difference_worst_leaf",
                      "served_token_logit_gap_max"}, failed


def _fit_cells():
    m = rehearse.tiny_manifest(common.load_manifest())
    return [w["name"] for w in m["workloads"]
            if common.resolve_cell(m, w["name"])[3]["kind"] == "fit"]


@pytest.mark.parametrize("cell", _fit_cells())
def test_a_step_that_changes_nothing_is_not_correct(cell, tiny, monkeypatch):
    """The program's optimizer, and only the program's, gets a learning
    rate and a decay of zero: every step returns the parameters as they
    were, the losses stand still, and the parameters' change is 0."""
    def prepare(run):
        adapter = common.plugin("adapters", run.config["adapter"])
        real = adapter.build_trainer
        monkeypatch.setattr(
            adapter, "build_trainer", lambda net, opt: real(
                net, dict(opt, learning_rate=0.0, weight_decay=0.0)))

    run = drive(cell, tiny, 5, prepare=prepare)
    failed = {r["check"] for r in run.checks.rows if not r["ok"]}
    assert "param_change_norm_gap_worst_leaf" in failed and not run.checks.ok


@pytest.mark.parametrize("cell", [c for c in CELLS if c not in _fit_cells()])
def test_an_altered_served_token_is_not_correct(cell, tiny):
    """One token of one sampled request altered where the check reads
    what was served."""
    def prepare(run):
        def alter(pairs):
            prompt, served = pairs[-1]
            served[len(served) // 2] = (served[len(served) // 2] + 1) % 512
            return pairs
        run.break_served = alter

    run = drive(cell, tiny, 6, prepare=prepare)
    failed = {r["check"] for r in run.checks.rows if not r["ok"]}
    assert failed == {"served_token_logit_gap_max"} and not run.checks.ok
