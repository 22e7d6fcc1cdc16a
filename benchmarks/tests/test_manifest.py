"""BENCHMARK.json against the contract's form, and every name in it
against the files the harness finds by name."""
import json
import os
import re

import pytest

from benchmarks import common

M = common.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_limits():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["paths"] == ["benchmarks"] and len(M["command"]) <= 32
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert 1 <= len(M["configs"]) <= 24 and 1 <= len(M["workloads"]) <= 24
    assert 1 <= len(M["end_to_end"]) <= 16 and 1 <= len(M["per_layer"]) <= 128
    assert os.path.getsize(os.path.join(common.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024


def test_names_units_and_lines():
    names = []
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert len(c["reduced"]) <= 16
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        names += [w["name"], w["config"], w["traffic"]]
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and 1 <= len(m["layer"]) <= 200
    for m in M["end_to_end"] + M["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads"):
        ns = [x["name"] for x in M[group]]
        assert len(ns) == len(set(ns))
    ms = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(ms) == len(set(ms)) and "setup_s" in ms
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in M["workloads"]) <= max(
        1, len(M["workloads"]) // 4)


def test_every_name_resolves_to_a_file():
    used = set()
    for w in M["workloads"]:
        cell, entry, config, traffic = common.resolve_cell(M, w["name"])
        used.add(entry["name"])
        assert entry["file"].startswith("benchmarks/configs/")
        common.plugin("kinds", traffic["kind"])
        common.plugin("reference", config["reference"])
        common.plugin("adapters", config["adapter"])
        assert set(traffic["reports"]) <= {m["name"]
                                           for m in M["end_to_end"]}
        assert set(config["reduced"]) == set(entry["reduced"])
        for trace in (0, 1):
            owed = common.metrics_for(M, cell, traffic, trace)
            assert owed, (w["name"], trace)
            if not trace:
                assert "setup_s" in owed and len(owed) >= 2
    assert used == {c["name"] for c in M["configs"]}
    files = [c["file"] for c in M["configs"]]
    assert len(files) == len(set(files))
    e2e = {m["name"]: m for m in M["end_to_end"]}
    cells = {w["name"] for w in M["workloads"]}
    for m in M["end_to_end"]:
        spec = common.named_file("end_to_end", m["name"])
        common.plugin("readers", spec["reader"])
        assert set(m.get("workloads", [])) <= cells
    for m in M["per_layer"]:
        spec = common.named_file("layer_metrics", m["name"])
        common.plugin("readers", spec["reader"])
        # one source of truth: the manifest holds these, the file the reader
        assert not set(spec) & {"layer", "unit", "moves", "workloads"}
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        if "cost" in spec.get("args", {}):
            common.plugin("costs", spec["args"]["cost"])
        for cell in m.get("workloads", []):
            _, _, _, traffic = common.resolve_cell(M, cell)
            assert m["moves"] in traffic["reports"]
    if "roofline" in json.dumps(M):
        for m in M["per_layer"]:
            if m["name"].endswith("_roofline"):
                assert m["unit"] == "%"


def test_file_names_under_paths_are_names():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for root, dirs, files in os.walk(common.HERE):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), common.ROOT)
            assert ok.match(rel), rel


@pytest.mark.parametrize("cfg", sorted(
    f for f in os.listdir(os.path.join(common.HERE, "configs"))))
def test_config_files_hold_the_model_as_run(cfg):
    c = common.load_json("configs", cfg)
    m = c["model"]
    assert m["hidden_size"] % m["num_heads"] == 0
    assert m["ffn_hidden_size"] == 4 * m["hidden_size"]
    for k in ("source", "changed", "assumed", "reduced", "reference",
              "adapter"):
        assert k in c
    k = c["kernel_sizes"]           # what trace_kernel's patterns stand on
    assert (k["NH"] * k["HD"], k["H"], k["V"], k["F"], k["L"]) == (
        m["hidden_size"], m["hidden_size"], m["vocab_size"],
        m["ffn_hidden_size"], m["num_layers"])
