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
this file laid over it).  The benchmark polls the flight ring only in a
traced run, so until ISSUE 51 an untraced ``--run`` kept ``[]``: now the tool
asks the endpoint itself, every ten seconds of an untraced run (512 records
hold twenty seconds of steps) and once at the end of any, and keeps what the
recorder froze (``anomalies.json``), printing each closed stall record
(ISSUE 51: where, wall, off-CPU, switches and faults, compile seconds, the
top of each thread's stack while it hung).
"""
import argparse
import json
import os
import pathlib
import runpy
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark.lib import prom  # noqa: E402

PARTS = ("claim", "plan", "sync_state", "launch")
PHASES = ("admit", "prefill_sync", "dispatch", "fetch", "reconcile", "emit")
ENGINE_STEP, STEP = "helix_engine_step_seconds", "helix_step_%s_seconds"
LOOP_LAG = "helix_http_loop_lag_seconds"


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
    print(f"stalls: mean {fmt(hist('stall'))} ms a step, off the CPU "
          f"{fmt(hist('stall_offcpu'))}; the event loop's heartbeat ran "
          f"{fmt(prom.mean_of_histogram_ms(w0, w1, LOOP_LAG))} ms late",
          file=out)
    print("slowest steps:", file=out)
    for r in sorted(recs, key=lambda r: -r["wall_s"])[:10]:
        ms = {k: {n.split(".")[-1]: round(v * 1e3, 2)
                  for n, v in r[k].items()}
              for k in ("parts", "phases_cpu", "threads_cpu")}
        print(f" step {r['step']} {r['kind']} wall {r['wall_s'] * 1e3:.1f} "
              f"ms gc {r['gc_s'] * 1e3:.2f} {ms}", file=out)


def stalls(anomalies, out=sys.stdout):
    """The closed stall records among a recorder's frozen anomalies."""
    closed = [a for a in anomalies if "where" in a]
    print(f"stalls: {len(closed)} closed record(s)", file=out)
    for a in closed:
        st, during = a.get("stall") or {}, a.get("during")
        print(f" {a['reason']} step {a['step']} where {a['where']} wall "
              f"{st.get('wall_s')} s off-cpu {st.get('offcpu_s')} s seen "
              f"{st.get('seen')} compile {st.get('compile_s')} s shapes "
              f"{st.get('compiled_shapes')} launch {st.get('launch')} "
              f"rusage {st.get('rusage')}", file=out)
        rec = a.get("record") or {}
        for k in ("phases", "threads_cpu", "gc_s", "device_wait_s"):
            if k in rec:
                print(f"   {k} {rec[k]}", file=out)
        if during:
            print("   during: " + ", ".join(
                f"{k} {during[k]}" for k in during
                if k not in ("threads", "rusage")), file=out)
            for name, t in during["threads"].items():
                print(f"   {name} cpu_since_step "
                      f"{t.get('cpu_since_step_s')} s: "
                      + " < ".join(t["stack"][:6]), file=out)


def fetch(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return r.read().decode()


def poll_flight(load):
    """Take the flight ring's records into ``load.flight``; returns the
    anomalies the recorders hold."""
    data = json.loads(fetch(load.url + "/v1/debug/flight?recent=512"))
    for m in data["models"].values():
        for step in m["recent"]:
            load.flight[step["step"]] = step
    return [a for m in data["models"].values() for a in m["anomalies"]]


def run_benchmark(out, argv):
    from benchmark.lib import load as load_mod

    run = load_mod.Load.run

    async def run_kept(self, gen, plan, trace_seconds=0.0):
        over = threading.Event()

        def poll():     # (the benchmark's own poller runs in a traced run)
            while not trace_seconds and not over.wait(10.0):
                try:
                    poll_flight(self)
                except (OSError, ValueError) as e:
                    self.flight_error = repr(e)

        poller = threading.Thread(target=poll, daemon=True)
        poller.start()
        try:
            await run(self, gen, plan, trace_seconds)
        finally:
            over.set()
        poller.join(30)
        os.makedirs(out, exist_ok=True)
        try:
            anomalies = poll_flight(self)
        except (OSError, ValueError) as e:
            anomalies, self.flight_error = [], repr(e)
        if self.flight_error:
            print("flight poll failed:", self.flight_error, file=sys.stderr)
        with open(os.path.join(out, "anomalies.json"), "w") as f:
            json.dump(anomalies, f)
        recs = [s for _, s in sorted(self.flight.items())
                if self.w0 <= s["ts"] - self.wall_offset < self.w1]
        for name in ("w0", "w1"):
            with open(os.path.join(out, name + ".txt"), "w") as f:
                f.write(self.scrapes[name][1])
        with open(os.path.join(out, "flight.json"), "w") as f:
            json.dump(recs, f)
        table(prom.parse(self.scrapes["w0"][1], self.model),
              prom.parse(self.scrapes["w1"][1], self.model), recs, sys.stderr)
        stalls(anomalies, sys.stderr)

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
    anomalies = []
    if isinstance(flight, dict):    # a /v1/debug/flight answer
        models = flight["models"]
        answer = models[a.model or next(iter(models))]
        flight, anomalies = answer["recent"], answer.get("anomalies", [])
    table(prom.parse(before, a.model), prom.parse(after, a.model), flight)
    stalls(anomalies)


if __name__ == "__main__":
    main()
