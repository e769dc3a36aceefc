"""BENCHMARK.json against the benchmark contract: names, units, keys, the
metric-to-cell wiring, and that every named piece has its file."""
import json
import re
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(harness.ROOT)
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(BENCH)) <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 1 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128


def test_full_check_fits_its_budget():
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_names_are_unique_and_well_formed(section):
    names = [e["name"] for e in BENCH[section]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n


def test_metric_names_unique_across_sections():
    names = list(E2E) + [m["name"] for m in BENCH["per_layer"]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("m", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(m):
    per_layer = "moves" in m
    keys = {"name", "unit", "better", "source"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert keys <= set(m) <= keys | {"workloads"}
    assert UNIT.match(m["unit"]), m["unit"]
    assert m["better"] in ("lower", "higher")
    assert m["source"] in SOURCES
    for w in m.get("workloads", []):
        assert w in CELLS
    if per_layer:
        assert _line(m["layer"])
    else:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    if m["unit"] == "%" and m["name"].endswith("_roofline"):
        assert per_layer


def test_setup_bound():
    assert E2E["setup_s"]["bound"] <= 0.25
    assert "workloads" not in E2E["setup_s"]


def _reports(metric, cell):
    return cell in metric.get("workloads", [cell])


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_moves_an_e2e_metric_of_every_listed_cell(m):
    assert m["moves"] in E2E
    for cell in m.get("workloads", CELLS):
        assert _reports(E2E[m["moves"]], cell), (m["name"], cell)


def test_layers_use_one_spelling():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert layers <= {"serving", "model step", "kernels", "device"}


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_reports_enough(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4)
    assert _line(cell["why"])
    assert NAME.match(cell["traffic"])
    e2e = [m for m in BENCH["end_to_end"] if _reports(m, cell["name"])]
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert any(_reports(m, cell["name"]) for m in BENCH["per_layer"])


def test_cells_are_distinct_pairs_and_few_take_four_chips():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert _line(conf["source"]) and _line(conf["why"])
    assert conf["file"].startswith("bench/") and (ROOT / conf["file"]).is_file()
    assert len(conf["reduced"]) <= 16
    for k in conf["reduced"]:
        assert NAME.match(k)
    assert any(w["config"] == conf["name"] for w in BENCH["workloads"])
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_named_piece_has_its_file(cell):
    conf = harness.load_json("configs", cell["config"])
    assert conf["check"]["limit"], "the check has limits"
    for v in conf["check"]["limit"].values():
        assert isinstance(v, (int, float)), "every limit is set"
    harness.find("configs", conf["reference"], ".py")
    harness.find("systems", conf["system"], ".py")
    harness.load_json("traffic", cell["traffic"])
    for m in harness.cell_metrics(BENCH, cell["name"], "end_to_end"):
        assert callable(harness.load_module("e2e_metrics", m["name"]).read)
    for m in harness.cell_metrics(BENCH, cell["name"], "per_layer"):
        assert callable(harness.load_module("layer_metrics", m["name"]).read)
