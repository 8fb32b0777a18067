"""The host's account in the benchmark (ISSUE 37): eighteen per-layer
metrics, nine quantities each once for the saturated cells and once for the
chat cells, all read by the reduction that is there (``histogram_mean_ms``)
from series ``helix_tpu/obs/metrics.py::EngineLoopObs`` registers.
``lfm2-8b-a1b.saturated-long`` is listed under none of them: another test
pins the set of metrics that name it, and only a ``benchmark`` PR may edit
that test (PERF.md section 7, item 23)."""

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
LFM2 = "lfm2-8b-a1b.saturated-long"
SERIES = {
    "loop.claim_ms": ("helix_step_claim_seconds", "engine loop"),
    "loop.plan_ms": ("helix_step_plan_seconds", "engine loop"),
    "loop.sync_state_ms": ("helix_step_sync_state_seconds", "engine loop"),
    "loop.launch_ms": ("helix_step_launch_seconds", "engine loop"),
    "loop.host_build_cpu_ms": ("helix_step_host_build_cpu_seconds",
                               "engine loop"),
    "loop.engine_cpu_ms": ("helix_step_engine_cpu_seconds", "engine loop"),
    "loop.deliver_cpu_ms": ("helix_step_emit_cpu_seconds", "engine loop"),
    "http.loop_cpu_ms": ("helix_step_http_cpu_seconds", "HTTP surface"),
    "loop.gc_ms": ("helix_step_gc_seconds", "engine loop"),
}
METRICS = [base + suffix for base in SERIES
           for suffix in (".saturated", ".chat")]
BENCH = manifest.benchmark_json()


def registered_series():
    col = Collector()
    EngineLoopObs().collect(col, {"model": "m"})
    return set(col.families)


@pytest.mark.parametrize("name", METRICS)
def test_metric_resolves_and_reads_a_registered_series(name):
    base, suffix = name.rsplit(".", 1)
    series, layer = SERIES[base]
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    cells = SATURATED if suffix == "saturated" else CHAT
    assert entry["workloads"] == cells and LFM2 not in entry["workloads"]
    assert (entry["unit"], entry["better"], entry["layer"]) == (
        "ms", "lower", layer)
    moves = ("tokens_per_s" if suffix == "saturated" else
             "ttft_mean_ms" if base == "loop.claim_ms" else
             "tpot_p95_ms.chat")
    assert entry["moves"] == moves
    e2e = next(m for m in BENCH["end_to_end"] if m["name"] == moves)
    for cell in cells:
        assert cell in e2e["workloads"]
        reader = next(m["reader"] for m in manifest.cell(cell)["per_layer"]
                      if m["name"] == name)
        assert reader["name"] == name
        assert reader["reduction"] == "histogram_mean_ms" in READERS
        assert reader["source_kind"] == "metrics_delta"
        assert reader["series"] == series
    assert series in registered_series()
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           name + ".json")) as f:
        assert set(json.load(f)) == {
            "name", "source_kind", "reduction", "series", "what"}


def test_the_eighteen_are_the_last_entries_and_nothing_else_names_them():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-18:] == METRICS
    assert len(names) == len(set(names)) == 66
    assert not any(n in METRICS for n in names[:-18])
    assert all(name not in [m["name"] for m in
                            manifest.cell(LFM2)["per_layer"]]
               for name in METRICS)


def test_the_reduction_reads_a_series_the_parent_lacks_as_nothing():
    """The driver lays these files over the parent's checkout for its
    traced runs: a program without the series leaves the metric out."""
    text = ('helix_step_dispatch_seconds_sum{model="m"} 1.5\n'
            'helix_step_dispatch_seconds_count{model="m"} 100\n')
    w0, w1 = prom.parse("", "m"), prom.parse(text, "m")
    ctx = {"scrapes": {"w0": w0, "w1": w1}}
    for name in METRICS:
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        assert READERS[spec["reduction"]](ctx, spec) is None
