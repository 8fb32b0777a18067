"""The reduction from a profiler trace to busy time, self time by name and
idle gaps (ISSUE 24): the arithmetic on hand-made intervals, and the whole
reduction on a small trace recorded on the chip and kept in
``benchmark/testdata``.  Reading the recorded trace needs
``jax.profiler.ProfileData`` (imported inside the test, never at import)."""

import glob
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import xplane  # noqa: E402


@pytest.mark.parametrize("intervals,want", [
    ([], 0),
    ([(0, 10)], 10),
    ([(0, 10), (5, 10)], 15),           # overlap counted once
    ([(0, 10), (2, 3)], 10),            # nested
    ([(0, 10), (20, 5)], 15),           # disjoint
    ([(20, 5), (0, 10), (9, 2)], 16),   # unsorted
])
def test_busy_is_the_union_of_intervals(intervals, want):
    assert xplane.union_s(intervals) == want


def test_self_time_takes_the_nested_operations_out():
    events = [("while", 0, 100), ("fusion.1", 10, 20), ("kernel", 40, 10),
              ("inner", 42, 3), ("copy", 200, 5)]
    got = {n: s for n, _, _, s in xplane.self_times(events)}
    assert got == {"while": 70, "fusion.1": 20, "kernel": 7, "inner": 3,
                   "copy": 5}
    assert sum(got.values()) == xplane.union_s(
        [(s, d) for _, s, d in events])


def test_idle_gaps_longest_first_inside_the_window():
    gaps = xplane.gaps_of([(10, 5), (30, 5)], (0, 50))
    assert gaps == [(15, 15), (35, 15), (0, 10)]
    assert xplane.gaps_of([(0, 50)], (0, 50)) == []
    busy = xplane.union_s([(10, 5), (30, 5)])
    assert busy + sum(d for _, d in gaps) == 50


@pytest.mark.parametrize("raw,want", [
    ("%fusion.12 = bf16[32,3584] fusion(...)", "fusion.12"),
    ("fusion.12", "fusion.12"),
    ("%custom-call.3 = (...) custom-call(...)", "custom-call.3"),
])
def test_short_operation_names(raw, want):
    assert xplane.short(raw) == want


def recorded():
    found = sorted(glob.glob(os.path.join(
        ROOT, "benchmark", "testdata", "*.xplane.pb")))
    if not found:
        pytest.skip("no recorded trace in benchmark/testdata")
    return found[0]


def test_recorded_trace_reduces_to_consistent_numbers():
    summary = xplane.reduce_trace(recorded())
    assert summary["devices"], "the recorded trace has a device plane"
    dev = summary["devices"][0]
    assert dev["plane"].startswith("/device:TPU:")
    assert 0 < dev["busy_s"] <= summary["window_s"]
    assert dev["idle_s"] == pytest.approx(
        summary["window_s"] - dev["busy_s"])
    # self times partition busy time
    assert sum(v[1] for v in dev["ops"].values()) == pytest.approx(
        dev["busy_s"], rel=1e-6)
    assert dev["modules"] and all(m["dur_s"] > 0 for m in dev["modules"])
    assert len(dev["gaps"]) <= xplane.N_GAPS
    gaps = [g["dur_s"] for g in dev["gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    # the longest gap: 24.5 ms between the one-step decode program and the
    # step that carries a prefill
    first = dev["gaps"][0]
    assert first["dur_s"] == pytest.approx(0.0245, abs=2e-4)
    assert first["after_program"].endswith(" 17.9ms")
    assert first["before_program"].endswith(" 35.9ms")


def test_recorded_trace_through_the_metric_readers():
    import json

    from benchmark.lib.readers import READERS

    def spec(name):
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               name + ".json")) as f:
            return json.load(f)

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen2-7b-int8.json")) as f:
        config = json.load(f)
    ctx = {"trace": xplane.reduce_trace(recorded()), "config": config,
           "flight": [{"kind": "decode", "kv_pages_used": 700}],
           "device_kind": "TPU v5 lite"}

    def read(name):
        s = spec(name)
        return READERS[s["reduction"]](ctx, s)

    # the recorded decode programs: 2 steps in 34.3 ms, 1 step in 17.9 ms
    assert read("step.decode_ms") == pytest.approx(17.4, abs=0.5)
    # one program carries a prefill (35.9 ms); the trimmed last one is cut
    assert read("step.prefill_ms") == pytest.approx(35.9, abs=0.2)
    assert 20 < read("kernel.attn_share.saturated") < 40
    assert 30 < read("device.idle_share.saturated") < 60
    # a share of the HBM peak cannot pass 100%
    assert 40 < read("step.decode_hbm_share") < 100
