#!/usr/bin/env python3
"""The host's account of a serving window, for any model (ISSUE 37): the
parts of admit and dispatch and what is left of ``host_build``, wall against
CPU, the three host threads' CPU a step over the step's wall (an upper bound
on the one GIL's use), collector pauses, the ten slowest steps.

    python3 tools/host_account.py --metrics w0.txt w1.txt --flight flight.json
    python3 tools/host_account.py --url http://127.0.0.1:8000 --seconds 30
    python3 tools/host_account.py --run <out dir> --workload <cell> \
        --seed <n> --seconds 45 --trace 1

Wall means are the ``helix_step_*_seconds`` histograms' deltas between the two
``/metrics`` texts (one observation a step: they compare with ``loop.*_ms``
one for one); a part's or phase's CPU, ``gc_s`` and the slowest steps come
from flight records (a ``/v1/debug/flight?recent=512`` answer or a list).
``--run`` runs this tree's ``benchmark/run.py`` with the arguments after the
directory, keeps the window's two scrapes (``w0.txt``, ``w1.txt``) and flight
records (``flight.json``) there and prints their table to stderr: how a cell
no metric lists (``lfm2-8b-a1b.saturated-long``) is read;
``tools/bench_pairs.py --account`` runs a call's runs so (a parent tree needs
this file laid over it).
"""
import argparse
import json
import os
import pathlib
import runpy
import sys
import time
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark.lib import prom  # noqa: E402

PARTS = ("claim", "plan", "sync_state", "launch")
PHASES = ("admit", "prefill_sync", "dispatch", "fetch", "reconcile", "emit")
ENGINE_STEP, STEP = "helix_engine_step_seconds", "helix_step_%s_seconds"


def mean_ms(recs, field, key):
    vals = [r[field].get(key, 0.0) for r in recs]
    return sum(vals) / len(vals) * 1e3 if vals else None


def fmt(v):
    return "      -" if v is None else f"{v:7.3f}"


def table(w0, w1, recs, out=sys.stdout):
    def hist(name):
        return prom.mean_of_histogram_ms(w0, w1, STEP % name)

    step = prom.mean_of_histogram_ms(w0, w1, ENGINE_STEP)
    recs = [r for r in recs if "parts" in r]
    print(f"steps {prom.delta(w0, w1, ENGINE_STEP + '_count')}  "
          f"mean step {fmt(step)} ms  flight records {len(recs)}", file=out)
    print(f"{'':18}wall_ms  cpu_ms", file=out)
    for p in PARTS:
        print(f"{'part ' + p:18}{fmt(hist(p))} "
              f"{fmt(mean_ms(recs, 'parts_cpu', 'helix.loop.' + p))}",
              file=out)
    build, parts = hist("host_build"), sum(hist(p) or 0.0 for p in PARTS)
    if build:
        print(f"{'remainder':18}{fmt(build - parts)}   "
              f"({100 * (build - parts) / build:.1f}% of host_build)",
              file=out)
    print(f"{'host_build':18}{fmt(build)} {fmt(hist('host_build_cpu'))}",
          file=out)
    for p in PHASES:
        print(f"{'phase ' + p:18}{fmt(hist(p))} "
              f"{fmt(mean_ms(recs, 'phases_cpu', 'helix.loop.' + p))}",
              file=out)
    cpus = {n: hist(n + "_cpu") for n in ("engine", "emit", "http")}
    for name, cpu in cpus.items():
        print(f"{'thread ' + name:18}        {fmt(cpu)}", file=out)
    total = sum(v or 0.0 for v in cpus.values())
    if step:
        print(f"{'threads / step':18}        {fmt(total)}   "
              f"({100 * total / step:.1f}% of the step's wall)", file=out)
    gcs = [r["gc_s"] for r in recs]
    print(f"gc: mean {fmt(hist('gc'))} ms a step; flight: total "
          f"{sum(gcs):.4f} s, largest of one step "
          f"{max(gcs, default=0) * 1e3:.2f} ms, {sum(g > 0 for g in gcs)} "
          "steps with a pause", file=out)
    print("slowest steps:", file=out)
    for r in sorted(recs, key=lambda r: -r["wall_s"])[:10]:
        ms = {k: {n.split(".")[-1]: round(v * 1e3, 2)
                  for n, v in r[k].items()}
              for k in ("parts", "phases_cpu", "threads_cpu")}
        print(f" step {r['step']} {r['kind']} wall {r['wall_s'] * 1e3:.1f} "
              f"ms gc {r['gc_s'] * 1e3:.2f} {ms}", file=out)


def fetch(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return r.read().decode()


def run_benchmark(out, argv):
    from benchmark.lib import load as load_mod

    run = load_mod.Load.run

    async def run_kept(self, gen, plan, trace_seconds=0.0):
        await run(self, gen, plan, trace_seconds)
        os.makedirs(out, exist_ok=True)
        recs = [s for _, s in sorted(self.flight.items())
                if self.w0 <= s["ts"] - self.wall_offset < self.w1]
        for name in ("w0", "w1"):
            with open(os.path.join(out, name + ".txt"), "w") as f:
                f.write(self.scrapes[name][1])
        with open(os.path.join(out, "flight.json"), "w") as f:
            json.dump(recs, f)
        table(prom.parse(self.scrapes["w0"][1], self.model),
              prom.parse(self.scrapes["w1"][1], self.model), recs, sys.stderr)

    load_mod.Load.run = run_kept
    sys.argv = [os.path.join(ROOT, "benchmark", "run.py")] + argv
    runpy.run_path(sys.argv[0], run_name="__main__")


def main():
    if len(sys.argv) > 2 and sys.argv[1] == "--run":
        return run_benchmark(sys.argv[2], sys.argv[3:])
    ap = argparse.ArgumentParser()
    ap.add_argument("--metrics", nargs=2, metavar=("BEFORE", "AFTER"))
    ap.add_argument("--flight")
    ap.add_argument("--url")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--model")
    a = ap.parse_args()
    if a.url:
        before = fetch(a.url + "/metrics")
        time.sleep(a.seconds)
        after = fetch(a.url + "/metrics")
        flight = fetch(a.url + "/v1/debug/flight?recent=512")
    else:
        before, after, flight = (
            pathlib.Path(p).read_text() for p in (*a.metrics, a.flight))
    flight = json.loads(flight)
    if isinstance(flight, dict):    # a /v1/debug/flight answer
        models = flight["models"]
        flight = models[a.model or next(iter(models))]["recent"]
    table(prom.parse(before, a.model), prom.parse(after, a.model), flight)


if __name__ == "__main__":
    main()
