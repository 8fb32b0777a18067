#!/usr/bin/env python3
"""Run benchmark cells from two trees, one after another, in one chip
call (a chip has one owner; a cache entry is found again by the next run
of the same tree, so a side's second run starts warm).

    chiprun --timeout 3600 -- python3 tools/bench_pairs.py --tag A \
        change:qwen2-7b.saturated:3000003301:1 \
        parent:qwen2-7b.saturated:3000003302:0 \
        change:qwen2-7b.saturated:3000003302:0 ...

Each run is ``<side>:<cell>:<seed>:<trace>``: ``benchmark/run.py`` from
``.chip_parent/`` (``git archive <parent>`` with ``BENCHMARK.json``,
``benchmark/`` and ``tests/benchmark/`` laid over it) or from
``.chip_tree/final/`` (``git archive $(git write-tree)``: the files git
would commit); both are git-ignored; ``--tree <side>=<dir>`` names another
tree for a side.  Every run's JSON lines go to
``chiprun_out/pairs/<tag>.jsonl``, its server log and trace summary beside
it (after a traced run also ``programs.json``: ``tools/program_times.py`` over
the capture, with ``--op`` if given, and ``gaps.json``: ``tools/gap_spans.py``, its long idle gaps by
the program's spans), and one line a run (side, cell, seed, the metrics) to stdout.
A run is not started when ``--per-run`` seconds more would pass ``--budget``.
``--account`` runs each through the tree's ``tools/host_account.py --run``,
which keeps the window's two ``/metrics`` scrapes and its flight records
beside the rest and prints the host's account of the window.  A run's line
also counts the ``helix stall`` lines of its server log (ISSUE 51: one a stall
the program named; the records themselves are in the log and, with
``--account``, in ``anomalies.json``): ``stalls`` those between the window's
two ``/metrics`` scrapes (by the access log: the third ``GET /metrics`` of a
run is the window's start, the last but one its end), ``stalls_log`` all of
them (a run ends with one: the clients' cancel storm after the window).  ``--freeze AT,SECONDS`` makes a
stall from outside: the run's server process is stopped (``SIGSTOP``)
``SECONDS`` long, ``AT`` seconds after its load began (the warm seconds, 8,
then the window), and continued.
"""
import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TREES = {"parent": os.path.join(ROOT, ".chip_parent"),
         "change": os.path.join(ROOT, ".chip_tree", "final")}


def stall_lines(log):
    """(inside the window, in all) ``helix stall`` lines of a server log's
    lines: a line starts with its time, so the window's are those between
    the third ``GET /metrics`` and the last but one."""
    scrapes = [ln[:23] for ln in log if '"GET /metrics ' in ln]
    stalls = [ln[:23] for ln in log if " helix stall {" in ln]
    if len(scrapes) < 5:
        return 0, len(stalls)
    return (sum(scrapes[2] <= t <= scrapes[-2] for t in stalls),
            len(stalls))


def server_of(pid):
    """(pid, port) of the ``serve-node`` child of process ``pid``, or
    None while it has none."""
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
            with open(f"/proc/{entry}/cmdline") as f:
                cmd = f.read().split("\0")
        except (OSError, ValueError, IndexError):
            continue
        if ppid == pid and "serve-node" in cmd and "--port" in cmd:
            return int(entry), int(cmd[cmd.index("--port") + 1])
    return None


def freeze(proc, at, seconds):
    """Stop the server of the run ``proc`` for ``seconds``, ``at`` seconds
    after its load began: the load has begun once the server has generated
    more tokens than the probe and the warm requests ask for (its
    ``/metrics`` is asked twice a second until then, and not after)."""
    while proc.poll() is None:
        found = server_of(proc.pid)
        if found:
            break
        time.sleep(1.0)
    else:
        return
    pid, port = found
    while proc.poll() is None:
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
                text = r.read().decode()
            tokens = sum(float(ln.rsplit(" ", 1)[1])
                         for ln in text.splitlines()
                         if ln.startswith("helix_generated_tokens_total"))
            if tokens > 300:
                break
        except (OSError, ValueError):
            pass
        time.sleep(0.5)
    time.sleep(at)
    if proc.poll() is None:
        os.kill(pid, signal.SIGSTOP)
        t0 = time.monotonic()
        time.sleep(seconds)
        os.kill(pid, signal.SIGCONT)
        print(f"FROZE pid {pid} for {time.monotonic() - t0:.3f} s",
              flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tag", required=True)
    ap.add_argument("--budget", type=float, default=3300)
    ap.add_argument("--per-run", type=float, default=600,
                    help="seconds a run is expected to need at most")
    ap.add_argument("--tree", action="append", default=[],
                    metavar="SIDE=DIR", help="a side's tree, from the root")
    ap.add_argument("--account", action="store_true")
    ap.add_argument("--freeze", metavar="AT,SECONDS",
                    help="SIGSTOP each run's server SECONDS long, AT "
                    "seconds after its load began")
    ap.add_argument("--op", help="tools/program_times.py's --op for the "
                    "programs.json of a traced run ('.': every operation)")
    ap.add_argument("runs", nargs="+")
    a = ap.parse_args()
    trees = dict(TREES, **{
        side: os.path.join(ROOT, path)
        for side, path in (t.split("=", 1) for t in a.tree)})
    t0 = time.monotonic()
    out_dir = os.path.join(ROOT, "chiprun_out", "pairs")
    os.makedirs(out_dir, exist_ok=True)
    out = os.path.join(out_dir, f"{a.tag}.jsonl")
    for i, spec in enumerate(a.runs):
        side, cell, seed, trace = spec.split(":")
        if time.monotonic() - t0 + a.per_run > a.budget:
            print(f"SKIPPED {spec}: budget", flush=True)
            continue
        tree = trees[side]
        keep = os.path.join(out_dir, f"{a.tag}_{i}_{side}_{cell}")
        t1 = time.monotonic()
        r = subprocess.Popen(
            [sys.executable, *(["tools/host_account.py", "--run", keep]
                               if a.account else ["benchmark/run.py"]),
             "--workload", cell, "--seed",
             seed, "--seconds", "45", "--trace", trace],
            cwd=tree, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        if a.freeze:
            at, seconds = map(float, a.freeze.split(","))
            threading.Thread(target=freeze, args=(r, at, seconds),
                             daemon=True).start()
        stdout, stderr = r.communicate()
        wall = time.monotonic() - t1
        lines = [ln for ln in stdout.splitlines() if ln.startswith("{")]
        rec = {"side": side, "cell": cell, "seed": int(seed), "trace": int(trace),
               "exit": r.returncode, "wall_s": round(wall, 1),
               "lines": [json.loads(ln) for ln in lines],
               "stderr": stderr[-6000:] if r.returncode or a.account
               else ""}
        bo = os.path.join(tree, ".bench_out")
        os.makedirs(keep, exist_ok=True)
        stalls = stalls_log = 0
        for name in os.listdir(bo) if os.path.isdir(bo) else []:
            p = os.path.join(bo, name)
            if os.path.isfile(p) and os.path.getsize(p) < 8 << 20 and (
                    name.endswith(".log") or name.endswith(".json")):
                shutil.copy(p, keep)
                if name == f"server_{cell}.log":   # (a tree keeps every cell's)
                    with open(p, errors="replace") as f:
                        found = stall_lines(f.read().splitlines())
                    stalls, stalls_log = (a + b for a, b in zip(
                        (stalls, stalls_log), found))
        rec["stalls"], rec["stalls_log"] = stalls, stalls_log
        with open(out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        if int(trace) and os.path.isdir(os.path.join(bo, "profiles")):
            # (a reader that fails or runs out of time costs its file only)
            for tool, name in (("program_times.py", "programs.json"),
                               ("gap_spans.py", "gaps.json")):
                subprocess.run(
                    [sys.executable, os.path.join(ROOT, "tools", tool),
                     os.path.join(bo, "profiles"),
                     "--out", os.path.join(keep, name),
                     *(["--op", a.op] if a.op and name == "programs.json"
                       else [])],
                    env=dict(os.environ, JAX_PLATFORMS="cpu",
                             TPU_LOG_DIR="disabled"))
        last = rec["lines"][-1] if rec["lines"] else {}
        print(json.dumps({"side": side, "cell": cell, "seed": seed,
                          "trace": trace, "exit": r.returncode,
                          "wall_s": round(wall, 1), "stalls": stalls,
                          "stalls_log": stalls_log,
                          "correct": last.get("correct"),
                          "failed": last.get("failed"),
                          "metrics": {k: (v.get("value") if isinstance(v, dict) else v)
                                      for k, v in (last.get("metrics") or {}).items()}}),
              flush=True)
    print("elapsed", round(time.monotonic() - t0, 1), flush=True)


if __name__ == "__main__":
    main()
