"""bench.py driver plumbing (the parent loop never touches jax, so each
child has the device to itself): result-line extraction must skip phase
markers, per-config timeouts must resolve, and a failing config must make
the run exit non-zero — never be replaced by a run elsewhere."""
import json
import sys

import pytest

import bench


def test_extract_skips_partial_phase_markers():
    out = "\n".join([
        json.dumps({"partial": True, "phase": "compile_start"}),
        json.dumps({"partial": True, "phase": "compile_done",
                    "seconds": 41.2}),
        json.dumps({"metric": "bert_base_samples_per_sec_per_chip",
                    "value": 1000.0, "unit": "samples/s",
                    "vs_baseline": 1.3}),
    ])
    got = bench._extract(out)
    assert got["metric"] == "bert_base_samples_per_sec_per_chip"
    # a timed-out body that only emitted markers yields None, never a
    # marker masquerading as a result
    partial_only = json.dumps({"partial": True, "phase": "compile_start",
                               "metric": "x"})
    assert bench._extract(partial_only) is None


def test_extract_partials_collects_phases():
    out = "\n".join([
        "[bench] noise",
        json.dumps({"partial": True, "phase": "compile_start"}),
        json.dumps({"partial": True, "phase": "compile_done",
                    "seconds": 12.5}),
        "not json {",
    ])
    got = bench._extract_partials(out)
    assert [p["phase"] for p in got] == ["compile_start", "compile_done"]
    assert got[1]["seconds"] == 12.5


def test_per_config_timeouts():
    # big graphs get longer budgets; everything else the default
    assert bench.CONFIG_TIMEOUT["gpt13b"] > bench.CONFIG_TIMEOUT_S
    assert bench.CONFIG_TIMEOUT["bert"] > bench.CONFIG_TIMEOUT_S
    assert "mnist" not in bench.CONFIG_TIMEOUT


def test_configs_cover_all_baseline_targets():
    # every BASELINE config + kernels/longseq/serving evidence, bert last
    assert bench.CONFIGS[-1] == "bert"
    for cfg in ("mnist", "resnet50", "ernie", "gpt13b", "kernels",
                "longseq", "predictor", "dp8"):
        assert cfg in bench.CONFIGS, cfg


RESULT = {"metric": "m", "value": 1.0, "unit": "samples/s",
          "vs_baseline": 1.0, "platform": "cpu"}


@pytest.mark.parametrize("rc,out,failed", [
    (0, json.dumps(RESULT), False),
    (1, "", True),                                   # body raised
    (1, json.dumps({**RESULT, "unit": "error"}), True),  # body's error line
    (124, json.dumps({"partial": True, "phase": "compile_start"}), True),
])
def test_drive_propagates_failure(monkeypatch, capsys, rc, out, failed):
    """One child per config, each line printed as is; any child that
    failed makes drive() return non-zero, and no child is run twice or
    on another platform."""
    calls = []

    def fake_run(cfg):
        calls.append(cfg)
        bad = cfg == "kernels"
        return (rc, out, "boom") if bad else (0, json.dumps(RESULT), "")

    monkeypatch.setattr(bench, "_run", fake_run)
    assert bench.drive() == (1 if failed else 0)
    assert calls == list(bench.CONFIGS)
    printed = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    summary = printed[-1]
    assert summary["metric"] == "bench_summary"
    assert summary["failed"] == (["kernels"] if failed else [])
    assert not any("fallback_from_tpu" in ln for ln in printed)


def test_config_child_exits_nonzero_on_error_line(monkeypatch, capsys):
    monkeypatch.setattr(bench, "body_mnist", lambda on_tpu: {
        "metric": "mnist", "value": 0.0, "unit": "error",
        "vs_baseline": 0.0, "error": "needs 8 devices"})
    assert bench.body_config("mnist") == 1
    assert json.loads(capsys.readouterr().out.splitlines()[-1])[
        "platform"] == "cpu"


def test_driver_module_never_imports_jax():
    """The parent must leave the chip to its children: importing bench and
    calling drive() may not import jax (bodies import it themselves)."""
    import subprocess

    code = ("import sys, bench; "
            "bench._run = lambda cfg: (0, '', ''); bench.drive(); "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       cwd=bench.os.path.dirname(bench.__file__))
    assert r.returncode == 0, r.stderr[-500:]
