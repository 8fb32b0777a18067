"""BENCHMARK.json against the files it names (ISSUE 24): every cell,
configuration, generator and per-layer metric resolves to its file; every
per-layer metric's ``moves`` is an end-to-end metric reported in each of its
cells; names, units and lengths keep to the contract's characters."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import manifest  # noqa: E402
from benchmark.lib.readers import READERS  # noqa: E402

BENCH = manifest.benchmark_json()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head_dim|expansion|experts_per_tok")


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys_are_the_contracts():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_command_names_only_files_under_paths():
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in BENCH["paths"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = manifest.cell(cell, BENCH)
    assert os.path.isfile(c["profile_template"])
    assert "__SEED__" in open(c["profile_template"]).read()
    assert callable(c["generator"].plan) and callable(c["generator"].drive)
    assert callable(c["generator"].drain)
    ref = os.path.join(ROOT, c["config"]["reference"])
    assert os.path.isfile(ref)
    assert c["entry"]["chips"] in (1, 4)
    names = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert c["per_layer"], "every cell reports a per-layer metric"
    if c["params"]["generator"] == "open_loop_poisson":
        assert c["params"]["rate_rps"] > 0


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_file(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert any(cfg["file"].startswith(p + "/") for p in BENCH["paths"])
    data = json.load(open(os.path.join(ROOT, cfg["file"])))
    assert data["source"] == cfg["source"] and data["name"] == cfg["name"]
    assert data["reduced"] == cfg["reduced"]
    assert not any(WIDTH.search(k) for k in cfg["reduced"])
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])
    assert len([c for c in BENCH["configs"] if c["file"] == cfg["file"]]) == 1


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    spec = json.load(open(os.path.join(
        ROOT, "benchmark", "metrics", metric["name"] + ".json")))
    assert spec["name"] == metric["name"]
    assert spec["reduction"] in READERS
    assert spec["source_kind"] in ("metrics_delta", "flight", "trace", "log",
                                   "client")
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert metric["moves"] in e2e
    for cell in cells_of(metric):
        assert cell in CELLS
        assert cell in cells_of(e2e[metric["moves"]]), (
            f"{metric['name']} moves {metric['moves']}, which "
            f"{cell} does not report")


@pytest.mark.parametrize("metric", BENCH["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) <= {"name", "unit", "better", "bound", "source",
                           "workloads"}
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1
    for cell in cells_of(metric):
        assert cell in CELLS


def test_names_units_and_lines():
    entries = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
               + BENCH["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                    and "\t" not in e[key], (e["name"], key)
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for group in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_layers_are_the_ones_perf_md_lists():
    text = open(os.path.join(ROOT, "PERF.md")).read()
    for m in BENCH["per_layer"]:
        assert f"| {m['layer']} |" in text, m["layer"]


def test_four_chip_cells_are_at_most_a_quarter():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_files_under_paths_are_named_from_the_contracts_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in BENCH["paths"]:
        assert ok.match(path) and len(path) <= 200
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                assert ok.match(os.path.relpath(os.path.join(base, f), ROOT))


def test_unknown_cell_is_a_manifest_error():
    with pytest.raises(manifest.ManifestError):
        manifest.cell("no-such.cell", BENCH)
