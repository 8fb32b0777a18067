"""The program's own stalls in the benchmark (ISSUE 51): six per-layer
metrics, three quantities each once for the saturated cells and once for
the chat cells, all read by the reduction that is there
(``histogram_mean_ms``) from series ``helix_tpu/obs/metrics.py::EngineLoopObs``
registers.  They list PR 37's six cells: other tests pin the set of metrics
that name the LFM2, GigaChat, Nemotron, Laguna and Mellum2 cells, and only
a ``benchmark`` PR may edit those."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import manifest, prom  # noqa: E402
from benchmark.lib.readers import READERS  # noqa: E402
from helix_tpu.obs.metrics import Collector, EngineLoopObs  # noqa: E402

SATURATED = ["qwen2-7b.saturated", "mistral-7b.saturated",
             "deepseek-v2-lite.saturated-long", "brumby-14b.saturated-long"]
CHAT = ["qwen2-7b.chat", "mistral-7b.chat"]
SERIES = {
    "loop.stall_ms": ("helix_step_stall_seconds", "engine loop",
                      "program_span"),
    "loop.stall_offcpu_ms": ("helix_step_stall_offcpu_seconds",
                             "engine loop", "program_span"),
    "http.loop_lag_ms": ("helix_http_loop_lag_seconds", "HTTP surface",
                         "program_counter"),
}
METRICS = [base + suffix for base in SERIES
           for suffix in (".saturated", ".chat")]
BENCH = manifest.benchmark_json()


def spec_of(name):
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", METRICS)
def test_metric_resolves_in_its_cells_and_reads_a_registered_series(name):
    base, suffix = name.rsplit(".", 1)
    series, layer, source = SERIES[base]
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    cells = SATURATED if suffix == "saturated" else CHAT
    assert entry == {
        "name": name, "unit": "ms", "better": "lower", "source": source,
        "layer": layer, "workloads": cells,
        "moves": ("tokens_per_s" if suffix == "saturated"
                  else "tpot_p95_ms.chat")}
    e2e = next(m for m in BENCH["end_to_end"] if m["name"] == entry["moves"])
    for cell in cells:
        assert cell in e2e["workloads"]
        reader = next(m["reader"] for m in manifest.cell(cell)["per_layer"]
                      if m["name"] == name)
        assert reader == spec_of(name)
    col = Collector()
    EngineLoopObs().collect(col, {"model": "m"})
    assert series in col.families
    spec = spec_of(name)
    assert set(spec) == {"name", "source_kind", "reduction", "series",
                         "what"}
    assert (spec["name"], spec["source_kind"], spec["series"]) == (
        name, "metrics_delta", series)
    assert spec["reduction"] == "histogram_mean_ms" in READERS


@pytest.mark.parametrize("name", METRICS)
def test_a_scrape_that_lacks_the_series_reads_nothing(name):
    """The driver lays these files over the parent's checkout for its
    traced runs: a program without the series leaves the metric out."""
    text = ('helix_step_dispatch_seconds_sum{model="m"} 1.5\n'
            'helix_step_dispatch_seconds_count{model="m"} 100\n')
    ctx = {"scrapes": {"w0": prom.parse("", "m"),
                       "w1": prom.parse(text, "m")}}
    spec = spec_of(name)
    assert READERS[spec["reduction"]](ctx, spec) is None


@pytest.mark.parametrize("name", METRICS)
def test_the_reduction_reads_the_series_a_step(name):
    """One flagged step of 2 s in 40: the mean is 50 ms a step, and mean
    x steps the seconds the window lost."""
    series = spec_of(name)["series"]
    w0 = prom.parse(f'{series}_sum{{model="m"}} 0.0\n'
                    f'{series}_count{{model="m"}} 10\n', "m")
    w1 = prom.parse(f'{series}_sum{{model="m"}} 2.0\n'
                    f'{series}_count{{model="m"}} 50\n', "m")
    spec = spec_of(name)
    got = READERS[spec["reduction"]]({"scrapes": {"w0": w0, "w1": w1}}, spec)
    assert got == pytest.approx(50.0)


def test_the_six_are_the_last_entries_and_name_no_other_cell():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-6:] == METRICS
    assert len(names) == len(set(names))
    others = [w["name"] for w in BENCH["workloads"]
              if w["name"] not in SATURATED + CHAT]
    for cell in others:
        listed = [m["name"] for m in manifest.cell(cell)["per_layer"]]
        assert not set(listed) & set(METRICS), cell
