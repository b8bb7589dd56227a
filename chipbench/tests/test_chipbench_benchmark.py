"""BENCHMARK.json holds together: every cell names a configuration and a
traffic file that exist, every per-layer metric has its reader and moves an
end-to-end metric that each of its cells reports, and names and units keep
to their characters."""

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = {w["name"]: w for w in BENCH["workloads"]}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}


def reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert (ROOT / BENCH["command"][1]).is_file()
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_files_exist_and_agree(cell):
    w = CELLS[cell]
    assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and NAME.match(w["config"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    traffic = json.loads((ROOT / f"chipbench/workloads/{cell}.json").read_text())
    assert traffic["config"] == w["config"]
    assert (ROOT / f"chipbench/traffic/{traffic['loop']}.py").is_file()
    config = json.loads((ROOT / f"chipbench/configs/{w['config']}.json").read_text())
    assert config["name"] == w["config"]
    assert (ROOT / f"chipbench/kinds/{config['kind']}.py").is_file()
    assert traffic["buckets"][-1] == traffic["batch"]
    assert len(traffic["shape"]) == config["spec"]["ndim"]
    assert reports(E2E["setup_s"], cell)
    assert sum(reports(m, cell) for m in BENCH["end_to_end"]) >= 2
    assert any(reports(m, cell) for m in BENCH["per_layer"])


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entries(config):
    assert NAME.match(config["name"])
    assert config["file"] == f"chipbench/configs/{config['name']}.json"
    body = json.loads((ROOT / config["file"]).read_text())
    assert body["source"] == config["source"] and body["reduced"] == config["reduced"]
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entries(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    for cell in metric.get("workloads", ()):
        assert cell in CELLS


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_reader_and_arrow(metric):
    from chipbench.metrics import reader

    assert callable(reader(metric["name"]))
    moved = E2E[metric["moves"]]
    for cell in metric["workloads"]:
        assert reports(moved, cell), f"{cell} does not report {moved['name']}"
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%" and metric["better"] == "higher"


def test_end_to_end_bounds():
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert E2E["setup_s"]["bound"] <= 0.25
