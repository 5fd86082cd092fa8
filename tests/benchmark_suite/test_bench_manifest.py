"""``BENCHMARK.json`` against the files it names and the limits of its
contract, so a later PR that adds an entry finds its slip here and not in
the driver's refusal."""

import importlib
import json
import os
import re

import pytest

from benchmarks import run as bench_run

ROOT = bench_run.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    M = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = M["end_to_end"] + M["per_layer"]


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_top_level_keys_and_limits():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert M["command"][1].startswith(tuple(p + "/" for p in M["paths"]))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 4)


@pytest.mark.parametrize("entry", M["configs"], ids=lambda e: e["name"])
def test_config_entry_and_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and len(entry["why"]) <= 200
    assert entry["file"].startswith(tuple(p + "/" for p in M["paths"]))
    config = load(entry["file"])
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    family = importlib.import_module(
        f"benchmarks.families.{config['family']}")
    # the file holds every key its family maps onto the program's config,
    # and no width is named as reduced
    assert set(family.KEYS) <= set(config)
    assert set(family.KEYS) <= set({**config, **config["rehearse"]})
    assert not [k for k in entry["reduced"] if re.search(
        r"hidden|inner|intermediate|embd|head|_dim$|_rank$", k)]
    assert any(w["config"] == entry["name"] for w in M["workloads"])


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda w: w["name"])
def test_workload_entry_and_traffic_file(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    assert cell["config"] in {c["name"] for c in M["configs"]}
    traffic = load("benchmarks", "traffic", cell["traffic"] + ".json")
    driver = importlib.import_module(f"benchmarks.drivers.{traffic['kind']}")
    assert callable(driver.run) and "rehearse" in traffic
    # every cell reports setup_s, another end-to-end metric and a layer's
    e2e = [m["name"] for m in M["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any("workloads" not in m or cell["name"] in m["workloads"]
               for m in M["per_layer"])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry_and_reader_file(metric):
    per_layer = metric in M["per_layer"]
    want = {"name", "unit", "better", "source"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert set(metric) - {"workloads"} == want
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    if per_layer:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        moved = next(m for m in M["end_to_end"]
                     if m["name"] == metric["moves"])
        cells = metric.get("workloads") or [w["name"] for w in M["workloads"]]
        assert set(cells) <= set(moved.get("workloads") or cells)
    else:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    for cell in metric.get("workloads", []):
        assert cell in {w["name"] for w in M["workloads"]}
    spec = load("benchmarks", "metrics", metric["name"] + ".json")
    reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    assert callable(reader.read)


def test_names_are_unique():
    for group in (M["configs"], M["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))
