#!/usr/bin/env python3
"""One run of ``benchmark/run.py`` with a poller beside its load: every
quarter second (every second once ten seconds into the window) the server's
``/metrics`` against the clients' own token count, and how long the scrape
took.  What ``run.py``'s ``generated_tokens`` check rests on and does not
show: the server's counter is read when the scrape is ANSWERED, so the
tokens the clients receive during a slow scrape at the window's first edge
read as tokens the server never counted (PERF.md section 6, PR 32).

    python3 tools/bench_poll.py <tag> --workload <cell> --seed <n> \
        --seconds <s> --trace 0

The arguments after ``<tag>`` are ``run.py``'s; its lines go to stdout as
ever.  The samples go to ``chiprun_out/poll_<tag>.jsonl`` (first line: the
window's edges and the clients' count at each of ``run.py``'s scrapes), a
table of them to stderr.  Not part of the benchmark: it scrapes forty times
as often as a run does, so its end-to-end numbers are not a cell's.
"""
import asyncio
import json
import os
import runpy
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark.lib import load as load_mod, prom  # noqa: E402

WAIT = "helix_emit_queue_wait_seconds"          # a histogram: sum, count
SERIES = ("helix_generated_tokens_total", WAIT + "_sum", WAIT + "_count",
          "helix_emit_backpressure_total", "helix_moe_experts_touched",
          "helix_moe_expert_load_max_ratio", "helix_decode_slots_busy",
          "helix_queue_depth", "helix_mixed_steps_total")


async def poll(ld, samples):
    while ld.w1 is None:
        await asyncio.sleep(0.005)
    while time.monotonic() < ld.w1:
        t, before = time.monotonic(), ld.tokens_total
        async with ld.session.get(ld.url + "/metrics") as r:
            m = prom.parse(await r.text(), ld.model)
        samples.append({
            "t": round(t - ld.t0, 3), "rtt": round(time.monotonic() - t, 4),
            "client_before": before, "client_after": ld.tokens_total,
            **{k[6:]: m.get(k) for k in SERIES}})
        await asyncio.sleep(0.25 if t < ld.w0 + 10 else 1.0)


def table(samples):
    prev = None
    for s in samples:
        rate = wait = None
        if prev:
            rate = round((s["client_before"] - prev["client_before"])
                         / (s["t"] - prev["t"]))
            n = (s["emit_queue_wait_seconds_count"]
                 - prev["emit_queue_wait_seconds_count"])
            if n:
                wait = round((s["emit_queue_wait_seconds_sum"]
                              - prev["emit_queue_wait_seconds_sum"])
                             / n * 1e3, 1)
        print(f"t={s['t']:7.2f} rtt={s['rtt']:.3f} clients/s={rate} "
              f"received_during_scrape={s['client_after'] - s['client_before']}"
              f" server-clients={s['generated_tokens_total'] - s['client_after']:.0f}"
              f" emit_queue_wait_ms={wait} experts_touched="
              f"{s['moe_experts_touched']} slots_busy={s['decode_slots_busy']}",
              file=sys.stderr)
        prev = s


def main():
    tag = sys.argv[1]
    samples, run = [], load_mod.Load.run

    async def run_polled(self, gen, plan, trace_seconds=0.0):
        side = asyncio.ensure_future(poll(self, samples))
        try:
            await run(self, gen, plan, trace_seconds)
        finally:
            side.cancel()
            out = os.path.join(ROOT, "chiprun_out")
            os.makedirs(out, exist_ok=True)
            with open(os.path.join(out, f"poll_{tag}.jsonl"), "w") as f:
                f.write(json.dumps({
                    "w0": self.w0 - self.t0, "w1": self.w1 - self.t0,
                    "clients_at_scrape": {k: v[0] for k, v in
                                          self.scrapes.items()}}) + "\n")
                for s in samples:
                    f.write(json.dumps(s) + "\n")
            table(samples)

    load_mod.Load.run = run_polled
    sys.argv = [os.path.join(ROOT, "benchmark", "run.py")] + sys.argv[2:]
    runpy.run_path(sys.argv[0], run_name="__main__")


if __name__ == "__main__":
    main()
