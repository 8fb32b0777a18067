"""``tools/host_account.py`` on recorded text: two ``/metrics`` scrapes a
hundred steps apart and three flight records (ISSUE 37)."""

import importlib.util
import io
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MEANS_MS = {   # what the window's hundred steps read, a step
    "engine_step": 40.0, "host_build": 22.0, "host_build_cpu": 12.0,
    "claim": 1.0, "plan": 2.5, "sync_state": 4.0, "launch": 9.0,
    "admit": 1.5, "prefill_sync": 0.0, "dispatch": 20.5, "fetch": 11.0,
    "reconcile": 1.5, "emit": 5.5, "engine_cpu": 16.0, "emit_cpu": 9.0,
    "http_cpu": 7.0, "gc": 0.3,
}


def series(name):
    return ("helix_engine_step_seconds" if name == "engine_step"
            else f"helix_step_{name}_seconds")


def scrape(steps, means):
    """``/metrics`` after ``steps`` steps whose sums are ``means`` x the
    window's means."""
    lines = []
    for name, ms in MEANS_MS.items():
        lines.append(f'# TYPE {series(name)} histogram')
        lines.append(f'{series(name)}_bucket{{model="m",le="+Inf"}} {steps}')
        lines.append(f'{series(name)}_sum{{model="m"}} {means * ms / 1e3}')
        lines.append(f'{series(name)}_count{{model="m"}} {steps}')
    return "\n".join(lines) + "\n"


# 50 steps before the window at three times its means: only the delta,
# a hundred steps at the means, is read
W0, W1 = scrape(50, 150), scrape(150, 250)


def record(step, wall_s, gc_s):
    return {
        "step": step, "kind": "mixed", "wall_s": wall_s, "gc_s": gc_s,
        "phases": {"helix.loop.dispatch": 0.0205},
        "phases_cpu": {"helix.loop.dispatch": 0.011,
                       "helix.loop.fetch": 0.0002},
        "parts": {"helix.loop.launch": 0.009, "helix.loop.plan": 0.0025},
        "parts_cpu": {"helix.loop.launch": 0.003, "helix.loop.plan": 0.0024},
        "threads_cpu": {"engine": 0.016, "emit": 0.009, "http": 0.007},
    }


def tool():
    spec = importlib.util.spec_from_file_location(
        "host_account", os.path.join(ROOT, "tools", "host_account.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_table_reads_parts_remainder_cpu_threads_and_pauses():
    mod = tool()
    from benchmark.lib import prom

    recs = [record(7, 0.040, 0.0), record(8, 0.1085, 0.0683),
            record(9, 0.039, 0.0), {"step": 10, "kind": "idle"}]
    buf = io.StringIO()
    mod.table(prom.parse(W0, "m"), prom.parse(W1, "m"), recs, buf)
    text = buf.getvalue()
    rows = {ln.split("  ")[0].strip(): ln for ln in text.splitlines()}
    assert "steps 100.0  mean step  40.000 ms  flight records 3" in text
    assert rows["part launch"].split() == ["part", "launch", "9.000", "3.000"]
    assert rows["part claim"].split() == ["part", "claim", "1.000", "0.000"]
    # 22 - (1 + 2.5 + 4 + 9) = 5.5 ms, a quarter of host_build
    assert "remainder           5.500   (25.0% of host_build)" in text
    assert rows["host_build"].split() == ["host_build", "22.000", "12.000"]
    assert rows["phase dispatch"].split()[2:] == ["20.500", "11.000"]
    assert "(80.0% of the step's wall)" in text      # 16 + 9 + 7 of 40
    assert "total 0.0683 s, largest of one step 68.30 ms, 1 steps" in text
    slowest = text.split("slowest steps:\n")[1].splitlines()
    assert [ln.split()[1] for ln in slowest] == ["8", "7", "9"]
    assert "gc 68.30" in slowest[0] and "'launch': 9.0" in slowest[0]


def test_the_command_line_takes_two_texts_and_a_flight_answer(tmp_path):
    (tmp_path / "w0.txt").write_text(W0)
    (tmp_path / "w1.txt").write_text(W1)
    (tmp_path / "flight.json").write_text(json.dumps(
        {"models": {"m": {"recent": [record(3, 0.04, 0.001)]}}}))
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "host_account.py"),
         "--metrics", str(tmp_path / "w0.txt"), str(tmp_path / "w1.txt"),
         "--flight", str(tmp_path / "flight.json"), "--model", "m"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "part sync_state     4.000" in r.stdout
    assert "thread http" in r.stdout and "  7.000" in r.stdout
    assert "step 3 mixed wall 40.0 ms gc 1.00" in r.stdout


def test_the_closed_stall_records_are_printed_and_no_other_anomaly():
    mod = tool()
    buf = io.StringIO()
    mod.stalls([
        {"reason": "quarantine", "ts": 1.0, "step": None,
         "record": {"request_id": "r"}, "steps": []},
        {"reason": "slow_step", "ts": 2.0, "step": 9, "steps": [],
         "record": {"phases": {"helix.loop.fetch": 2.01}, "gc_s": 0.0},
         "where": "helix.loop.fetch",
         "stall": {"wall_s": 2.02, "offcpu_s": 2.0, "seen": True,
                   "compile_s": 0.0, "compiled_shapes": [15, 15],
                   "launch": {"program": "jit_step_fn_t0"},
                   "rusage": {"nivcsw": 3, "majflt": 0}},
         "during": {"span": "helix.loop.fetch", "stood_s": 1.1,
                    "rusage": {}, "threads": {"engine": {
                        "stack": ["engine/engine.py:2248:_fetch",
                                  "engine/engine.py:4931:_decode_complete"],
                        "cpu_since_step_s": 0.002}}}},
        {"reason": "stall", "ts": 3.0, "step": None, "steps": [],
         "record": {"where": "http"}, "where": "http",
         "stall": {"wall_s": 1.4, "seen": True}, "during": None},
    ], buf)
    text = buf.getvalue()
    assert text.startswith("stalls: 2 closed record(s)\n")
    assert " slow_step step 9 where helix.loop.fetch wall 2.02 s " in text
    assert "off-cpu 2.0 s" in text and "'nivcsw': 3" in text
    assert "phases {'helix.loop.fetch': 2.01}" in text
    assert ("engine cpu_since_step 0.002 s: engine/engine.py:2248:_fetch < "
            "engine/engine.py:4931:_decode_complete") in text
    assert " stall step None where http wall 1.4 s " in text
    assert "quarantine" not in text
