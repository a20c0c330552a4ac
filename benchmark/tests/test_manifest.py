"""BENCHMARK.json and every data file it names hold to the contract."""

import json
import os
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return load(ROOT, "BENCHMARK.json")


def test_keys_and_limits(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert manifest["paths"] == ["benchmark"]
    assert isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names


def test_metrics(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
        assert os.path.exists(os.path.join(BENCH, "end_to_end", m["name"] + ".py"))
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
        target = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in target.get("workloads", cells), (m["name"], cell)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in {"lower", "higher"} and m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        reported = lambda ms: [m for m in ms if cell in m.get("workloads", cells)]  # noqa: E731
        assert len(reported(manifest["end_to_end"])) >= 2 and reported(manifest["per_layer"])


def test_cells_and_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    assert len({c["file"] for c in manifest["configs"]}) == len(configs)
    pairs = set()
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cell = load(BENCH, "workloads", w["name"] + ".json")
        assert set(cell) == {"overrides", "expect"}
        assert set(cell["expect"]) == {"steps_per_epoch", "tiles_per_step"}
        traffic = load(BENCH, "traffic", w["traffic"] + ".json")
        assert traffic["warmup_epochs"] >= 2 and traffic["trace_seconds"] > 0
        cfg = load(ROOT, configs[w["config"]]["file"])
        assert os.path.exists(os.path.join(BENCH, "reference", cfg["reference"] + ".py"))
        assert cfg["reference_sample_tiles"] >= 1
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)
    assert {w["config"] for w in manifest["workloads"]} == set(configs)
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/") and len(c["reduced"]) <= 16
        assert c["reduced"] == load(ROOT, c["file"])["reduced"]
        assert all(NAME.match(k) for k in c["reduced"])


def test_no_name_of_a_cell_config_or_metric_in_run_py(manifest):
    text = open(os.path.join(BENCH, "run.py")).read()
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for e in manifest[k]]
    names += [w["traffic"] for w in manifest["workloads"]]
    assert not [n for n in names if re.search(rf"[\"']{re.escape(n)}[\"']", text)]


@pytest.mark.parametrize(
    "name,shipped,changed",
    [
        ("unet_flagship", "configs/vaihingen_unet_tpu_flagship.json", {}),
        ("unetpp", "configs/vaihingen_unetpp.json", {}),
        ("unet_pod4", "configs/vaihingen_unet_v5e8.json", {"parallel": {"data_axis_size": 4}}),
    ],
)
def test_config_is_the_shipped_file(name, shipped, changed):
    """The benchmark's copy equals the shipped file but for what ``reduced`` names."""
    ours, theirs = load(BENCH, "configs", name + ".json"), load(ROOT, shipped)
    for group in ("model", "data", "train", "parallel", "compression"):
        want = dict(theirs[group], **changed.get(group, {}))
        assert ours[group] == want, group
    assert ours["reduced"] == sorted(changed)
