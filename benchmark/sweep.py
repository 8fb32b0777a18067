#!/usr/bin/env python3
"""Find an open-loop cell's knee, once: one server, several rates.

    python3 benchmark/sweep.py --workload qwen2-7b.chat --rates 3,4,5,6,7 \
        [--seconds 30] [--seed 1]

For each rate the cell's own traffic runs for ``warm_seconds`` + ``--seconds``
against the same server, and one line gives the requests due in the window,
the requests that completed inside it (whenever they were due: in a steady
state as many complete as arrive), and the tails.  The knee is the highest
swept rate at which completed >= 0.95 of due (ISSUE 24); the cell's fixed
rate, four fifths of it, is then written by hand into
``benchmark/workloads/<cell>.json``.  Not part of a run: ``run.py`` offers
load at the fixed rate and never searches.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as harness  # noqa: E402
from benchmark.lib import load as load_mod  # noqa: E402
from benchmark.lib import manifest, stats  # noqa: E402
from benchmark.lib.server import Server, ServerFailed  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = manifest.cell(args.workload)
    params = cell["params"]
    if args.rehearse:
        params = harness.rehearsal_sizes(params)
    overhead = cell["config"]["serving"]["chat_template_overhead_tokens"]
    os.makedirs(harness.OUT, exist_ok=True)
    srv = Server(ROOT, harness.write_profile(
        cell, args.seed, "sweep", args.rehearse), harness.OUT, "sweep",
        args.rehearse)
    rows = []
    try:
        device, model, ok = harness.boot(srv, cell, params, args.seed,
                                         args.rehearse)
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            p = dict(params, rate_rps=rate, drain_seconds=0)
            plan = cell["generator"].plan(p, args.seed + k, args.seconds)
            ld = load_mod.Load(srv.url, model, args.seed + k,
                               float(p["warm_seconds"]), args.seconds,
                               overhead, float(p.get("temperature", 1.0)))
            load_mod.run_load(ld, cell["generator"], plan)
            due = [r for r in ld.recs if ld.w0 <= r.due < ld.w1]
            inside = [r for r in ld.recs if r.done and ld.w0 <= r.end < ld.w1]
            ttft = [(r.first - r.due) * 1e3 for r in due
                    if r.first is not None]
            tpot = [t for t in (stats.tpot_ms(r.first, r.last, r.n_tokens)
                                for r in inside) if t is not None]
            row = {"rate_rps": rate, "due": len(due),
                   "completed_inside": len(inside),
                   "share": len(inside) / max(len(due), 1),
                   "errors": sum(1 for r in due if r.error),
                   "tokens_per_s": ld.tokens_in_window / args.seconds,
                   "ttft_ms": stats.summary(ttft),
                   "tpot_ms": stats.summary(tpot),
                   "send_lag_p95_ms": stats.percentile(
                       [(r.sent - r.due) * 1e3 for r in due], 95)}
            rows.append(row)
            print(json.dumps(row), flush=True)
            # let the server finish what the cut requests left behind
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                sat = srv.state().get("saturation", {})
                if not sat.get("slots_busy") and not sat.get("queue_depth"):
                    break
                time.sleep(1)
        srv.stop()
    except ServerFailed as e:
        harness.fail(str(e))
    finally:
        srv.kill()
    good = [r["rate_rps"] for r in rows if r["share"] >= 0.95]
    print(json.dumps({"device": device, "probe_ok": ok,
                      "knee_rps": max(good) if good else None,
                      "rule": "highest swept rate with completed_inside >= "
                              "0.95 of due"}), flush=True)


if __name__ == "__main__":
    main()
