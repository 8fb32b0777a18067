#!/usr/bin/env python3
"""Run a cell several times in one call and report the spread the bounds
are set from.

    python3 benchmark/sets.py --workload <cell> --seeds 11,12,13 --sets 2 \
        [--seconds S] [--traced-seed N] [--out chiprun_out/sets_<cell>.jsonl]

Each run is ``benchmark/run.py`` in a process of its own, one after another
(a chip has one owner).  ``--sets 2`` runs the seed list twice, the same
seeds in both sets, as the contract measures a bound.  ``--traced-seed``
adds one ``--trace 1`` run at the end.  Every run's last line goes to
``--out`` with its wall time; the spread of each metric in each set
(quartile distance over median) is printed last.  The server log and the
trace summary of the last run are copied beside ``--out``.
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib.stats import iqr_share  # noqa: E402


def run_once(workload, seed, seconds, trace, extra):
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", workload, "--seed", str(seed), "--trace", str(trace)]
    if seconds:
        cmd += ["--seconds", str(seconds)]
    t0 = time.monotonic()
    r = subprocess.run(cmd + extra, cwd=ROOT, capture_output=True, text=True)
    wall = time.monotonic() - t0
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
    last = json.loads(lines[-1]) if lines and r.returncode == 0 else None
    return {"workload": workload, "seed": seed, "trace": trace,
            "exit": r.returncode, "wall_s": wall, "result": last,
            "notes": [json.loads(ln) for ln in lines[:-1]] if lines else [],
            "stderr": r.stderr[-1500:] if r.returncode else ""}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--traced-seed", type=int, default=None)
    ap.add_argument("--out", default=None)
    args, extra = ap.parse_known_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    out = args.out or os.path.join(
        ROOT, "chiprun_out", f"sets_{args.workload}.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    sets = []
    with open(out, "a") as f:
        for k in range(args.sets):
            rows = []
            for seed in seeds:
                row = run_once(args.workload, seed, args.seconds, 0, extra)
                row["set"] = k
                rows.append(row)
                f.write(json.dumps(row) + "\n")
                f.flush()
                res = row["result"] or {}
                print(json.dumps({
                    "set": k, "seed": seed, "exit": row["exit"],
                    "wall_s": round(row["wall_s"], 1),
                    "correct": res.get("correct"),
                    "attempted": res.get("attempted"),
                    "failed": res.get("failed"),
                    "metrics": {n: m["value"] for n, m in
                                res.get("metrics", {}).items()},
                    "stderr": row["stderr"][-300:]}), flush=True)
            sets.append(rows)
        if args.traced_seed is not None:
            row = run_once(args.workload, args.traced_seed, args.seconds, 1,
                           extra)
            row["set"] = "traced"
            f.write(json.dumps(row) + "\n")
            print(json.dumps({k: row[k] for k in
                              ("exit", "wall_s", "result", "stderr")}),
                  flush=True)
            for n in row["notes"]:
                if n.get("phase") == "trace":
                    print(json.dumps(n), flush=True)
    keep = os.path.join(os.path.dirname(out), f"artifacts_{args.workload}")
    shutil.rmtree(keep, ignore_errors=True)
    os.makedirs(keep)
    bench_out = os.path.join(ROOT, ".bench_out")
    for name in os.listdir(bench_out) if os.path.isdir(bench_out) else ():
        p = os.path.join(bench_out, name)
        if os.path.isfile(p):
            shutil.copy(p, keep)
    # the spread of each metric: in each set all runs, and without the
    # first (it compiles where the cache is cold)
    for k, rows in enumerate(sets):
        names = sorted({n for r in rows if r["result"]
                        for n in r["result"]["metrics"]})
        for n in names:
            vals = [r["result"]["metrics"][n]["value"] for r in rows
                    if r["result"] and n in r["result"]["metrics"]]
            line = {"set": k, "metric": n, "values": vals}
            for label, xs in (("all", vals), ("without_first", vals[1:])):
                if len(xs) >= 2:
                    line["iqr_share_" + label] = iqr_share(xs)
                    line["median_" + label] = sorted(xs)[len(xs) // 2]
            print(json.dumps(line), flush=True)


    # the same for the statistics the runs print beside their metrics
    for k, rows in enumerate(sets):
        for what in ("ttft_ms", "tpot_ms"):
            for stat in ("mean", "midmean", "p25", "p50", "p75", "p90", "p95",
                         "p99"):
                vals = [n[what][stat] for r in rows for n in r["notes"]
                        if n.get("phase") == "samples"
                        and n[what].get(stat) is not None]
                if len(vals) >= 2:
                    print(json.dumps({
                        "set": k, "beside": f"{what}.{stat}",
                        "values": [round(v, 3) for v in vals],
                        "iqr_share": iqr_share(vals)}), flush=True)


if __name__ == "__main__":
    main()
