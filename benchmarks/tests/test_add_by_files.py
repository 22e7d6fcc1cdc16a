"""A later PR adds a configuration, a traffic mix and a per-layer metric
over an existing reader by adding files and entries only: done here with a
dummy of each, in a copy of `benchmarks/`'s data, without touching a file
that is there."""
import json
import os
import shutil

from benchmarks import common
from benchmarks import run as bench_run


def test_dummy_config_traffic_and_metric_resolve(tmp_path, monkeypatch):
    here = tmp_path / "benchmarks"
    for d in ("configs", "traffic", "layer_metrics", "end_to_end"):
        shutil.copytree(os.path.join(common.HERE, d), here / d)
    for d in ("readers", "kinds", "reference", "adapters", "costs"):
        os.symlink(os.path.join(common.HERE, d), here / d)
    # the three new files
    cfg = common.load_json("configs", "tiny-gpt.json")
    cfg["name"] = "dummy-gpt"
    cfg["model"]["num_layers"] = 3
    (here / "configs" / "dummy-gpt.json").write_text(json.dumps(cfg))
    mix = common.load_json("traffic", "tiny-chat.json")
    mix["rate_per_s"] = 7.0
    (here / "traffic" / "dummy-mix.json").write_text(json.dumps(mix))
    metric = {"reader": "span", "args": {"span": "gen.queued", "q": 95}}
    (here / "layer_metrics" / "queue_wait_p95_ms.json").write_text(
        json.dumps(metric))
    # and the entries
    manifest = common.load_manifest()
    manifest["configs"].append({
        "name": "dummy-gpt", "source": "none",
        "file": "benchmarks/configs/dummy-gpt.json", "reduced": [],
        "why": "dummy"})
    manifest["workloads"].append({
        "name": "dummy-gpt.dummy-mix", "config": "dummy-gpt",
        "traffic": "dummy-mix", "chips": 1, "why": "dummy"})
    manifest["per_layer"].append({
        "name": "queue_wait_p95_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "decode loop",
        "moves": "itl_p95_ms"})
    monkeypatch.setattr(common, "HERE", str(here))
    monkeypatch.setattr(common, "ROOT", str(tmp_path))
    cell, entry, config, traffic = common.resolve_cell(
        manifest, "dummy-gpt.dummy-mix")
    assert config["model"]["num_layers"] == 3
    assert traffic["rate_per_s"] == 7.0
    owed = common.metrics_for(manifest, cell, traffic, trace=1)
    assert "queue_wait_p95_ms" in owed and "slot_occupancy" in owed
    assert "prefix_hit_share" not in owed      # lists its cells, not this one
    assert "train_mfu" not in owed and "flash_roofline" not in owed
    # the new metric is read by the reader that was there
    run = bench_run.Run(manifest, cell, entry, config, traffic, 1, 1.0, 1)
    run.spans = [{"name": "gen.queued", "dur_ms": d} for d in (1, 2, 3)]
    got = bench_run.read_metrics(run, ["queue_wait_p95_ms"])
    assert got == {"queue_wait_p95_ms": {"value": 2.9, "unit": "ms"}}
    # a reader that finds nothing leaves its metric out of the line
    run.spans = []
    assert bench_run.read_metrics(run, ["queue_wait_p95_ms"]) == {}
