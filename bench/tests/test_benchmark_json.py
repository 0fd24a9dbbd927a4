"""``BENCHMARK.json`` keeps to the benchmark's contract, and every name in
it has the file that the harness looks for."""
import os
import re

import pytest

from bench import spec

BENCH = spec.read_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"] for m in BENCH["end_to_end"]}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def _names():
    for c in BENCH["configs"]:
        yield c["name"]
        yield from c["reduced"]
    for w in BENCH["workloads"]:
        yield from (w["name"], w["config"], w["traffic"])
    for m in METRICS:
        yield m["name"]


@pytest.mark.parametrize("name", sorted(set(_names())))
def test_name_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert os.path.isfile(os.path.join(spec.BENCH_DIR, "metrics",
                                       metric["name"] + ".py"))
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    if metric["name"] in E2E:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["moves"] in E2E
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_files_and_metrics(cell):
    w = CELLS[cell]
    assert w["chips"] in (1, 4)
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    c = spec.load_cell(cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    assert os.path.isfile(os.path.join(spec.BENCH_DIR, "families",
                                       c.config["family"] + ".py"))
    for e in c.traffic["panel"]:
        assert os.path.isfile(os.path.join(
            spec.BENCH_DIR, "reference", f"est_{e['class']}.py"))


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(config):
    assert config["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    assert spec.read_json(os.path.join(spec.ROOT, config["file"]))[
        "name"] == config["name"]
    assert 1 <= len(config["source"]) <= 200
    assert any(w["config"] == config["name"] for w in CELLS.values())


def test_top_level():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert "setup_s" in E2E
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"][0]
    assert setup["bound"] <= 0.25
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) <= 65536
