"""Cells, configurations and per-layer metrics are files found by name: a
new one is added without an edit to a file that is there."""

import json
import os
import re
import shutil

import pytest

import run
from conftest import BENCH, REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_keeps_to_the_contract(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    for e in manifest["configs"] + manifest["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for c in manifest["configs"]:
        assert len(c["source"]) <= 200
        assert c["file"].startswith(manifest["paths"][0] + "/")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        # every cell it is read in reports the end-to-end metric it moves
        assert set(m["workloads"]) <= set(
            e2e[m["moves"]].get("workloads", cells))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_entry_has_its_file_and_they_agree(manifest):
    for w in manifest["workloads"]:
        _, conf, mix, _ = run.load_cell(w["name"])
        assert mix["config"] == w["config"] == conf["name"]
        assert conf["chips"] == w["chips"]
    for c in manifest["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            conf = json.load(f)
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
    for m in manifest["per_layer"]:
        reader = run.load_reader(m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == \
            (m["layer"], m["unit"], m["moves"], m["source"])
        assert reader.read.__code__.co_argcount == 2


def test_a_dropped_in_cell_configuration_and_metric_are_found(tmp_path,
                                                              manifest):
    """Copy the benchmark, add one file of each kind and their manifest
    entries, edit nothing that was there."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(root / "benchmarks/configs/node-1chip-10m.json") as f:
        conf = json.load(f)
    conf["name"] = "node-1chip-20m"
    (root / "benchmarks/configs/node-1chip-20m.json").write_text(
        json.dumps(conf))
    with open(root / "benchmarks/workloads/node10m.herd100.json") as f:
        mix = json.load(f)
    mix.update(config="node-1chip-20m", traffic="herd7", clients=7)
    (root / "benchmarks/workloads/node20m.herd7.json").write_text(
        json.dumps(mix))
    (root / "benchmarks/layer_metrics/windows_per_s.py").write_text(
        'LAYER = "combiner"\nSOURCE = "program_counter"\nUNIT = "1/s"\n'
        'MOVES = "decisions_per_s"\n\n\ndef read(scrapes, trace):\n'
        '    return scrapes["windows"] / scrapes["window_s"]\n')
    grown = json.loads(json.dumps(manifest))
    grown["configs"].append({
        "name": "node-1chip-20m", "source": conf["source"],
        "file": "benchmarks/configs/node-1chip-20m.json",
        "reduced": conf["reduced"], "why": "a second deployment"})
    grown["workloads"].append({
        "name": "node20m.herd7", "config": "node-1chip-20m",
        "traffic": "herd7", "chips": 1, "why": "seven callers"})
    (root / "BENCHMARK.json").write_text(json.dumps(grown))
    cell, conf2, mix2, _ = run.load_cell("node20m.herd7", repo=str(root))
    assert cell["config"] == conf2["name"] == "node-1chip-20m"
    assert mix2["clients"] == 7
    reader = run.load_reader("windows_per_s",
                             here=str(root / "benchmarks"))
    assert reader.read({"windows": 50, "window_s": 10.0}, None) == 5.0
    # and the cells that were there are still found
    assert run.load_cell("node10m.batch1000", repo=str(root))[2]["clients"] == 8


def test_an_unknown_cell_is_refused():
    with pytest.raises(run.RunFailed):
        run.load_cell("node10m.nothing")
