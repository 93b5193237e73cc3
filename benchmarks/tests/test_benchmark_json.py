"""Every entry of ``BENCHMARK.json`` resolves to files that exist, and every
per-layer metric to a reader that imports."""

import importlib
import json
import os

import pytest

from conftest import BENCH, ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)


@pytest.mark.parametrize("cell", BENCHMARK["workloads"], ids=lambda c: c["name"])
def test_cell_resolves(cell):
    config = next(c for c in BENCHMARK["configs"] if c["name"] == cell["config"])
    assert os.path.isfile(os.path.join(ROOT, config["file"]))
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    assert cfg["source"] == config["source"] and cfg["reduced"] == config["reduced"]
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        driver = json.load(f)["driver"]
    assert os.path.isfile(os.path.join(BENCH, "harness", driver + "_driver.py"))
    with open(os.path.join(BENCH, "limits", cell["name"] + ".json")) as f:
        limits = json.load(f)["limits"]
    assert limits and all(v["limit"] > 0 for v in limits.values())
    reported = [m["name"] for m in BENCHMARK["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]
    assert "setup_s" in reported and len(reported) >= 2
    layer = [m for m in BENCHMARK["per_layer"]
             if cell["name"] in m.get("workloads", [cell["name"]])]
    assert layer and all(m["moves"] in reported for m in layer)
    assert any("mfu" in m["name"] for m in layer)


@pytest.mark.parametrize("metric", BENCHMARK["per_layer"], ids=lambda m: m["name"])
def test_metric_resolves(metric):
    with open(os.path.join(BENCH, "metrics", metric["name"] + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("readers." + spec["reader"])
    assert callable(reader.read)
