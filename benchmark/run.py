#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This parent is plain Python and never imports JAX.  It starts
``python -m helix_tpu serve-node`` with the cell's profile as a child (the
child owns the chip), waits until it serves, checks that it sees the chips
the cell asks for (no chip: exit 3, nothing printed), probes correctness,
warms the shapes the cell's traffic adds, runs the traffic for
``warm_seconds`` unrecorded, measures for ``--seconds``, stops the child and
prints one JSON object as the last line: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``).
With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` a profiler capture of the live server is taken a quarter into
the window and the metrics are the cell's per-layer metrics.

``--rehearse`` walks the same control flow on the CPU with
``profiles/dev-tiny.yaml`` at tiny sizes.  A rehearsal prints
``"correct": false``, no metric, and exits 4.

Everything a cell is made of is found by name from ``BENCHMARK.json``
(see ``benchmark/README.md``).
"""

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import time

T_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import load as load_mod  # noqa: E402
from benchmark.lib import manifest, prom, stats  # noqa: E402
from benchmark.lib.readers import READERS  # noqa: E402
from benchmark.lib.server import (  # noqa: E402
    Server, ServerFailed, device_of, log_seconds, words,
)

OUT = os.path.join(ROOT, ".bench_out")
COLD_LIMIT_S = 1100      # the contract gives a compiling run 1200 s
EXIT_NO_CHIP, EXIT_REHEARSAL = 3, 4


def note(**obj):
    print(json.dumps(obj), flush=True)


def fail(msg, code=1):
    print(f"benchmark: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def write_profile(cell, seed, tag, rehearse):
    """The run's profile: the configuration's template with the seed."""
    if rehearse:
        return os.path.join(ROOT, "profiles", "dev-tiny.yaml")
    with open(cell["profile_template"]) as f:
        text = f.read().replace("__SEED__", str(seed % 2**32))
    path = os.path.join(OUT, f"{tag}.profile.yaml")
    with open(path, "w") as f:
        f.write(text)
    return path


def rehearsal_sizes(params):
    """dev-tiny holds 256 tokens a sequence and two slots."""
    p = dict(params)
    p["prompt_tokens"] = {"dist": "uniform", "min": 24, "max": 60}
    p["max_tokens"] = {"dist": "uniform", "min": 8, "max": 16}
    p["warm_prompt_tokens"] = [40] if p.get("warm_prompt_tokens") else []
    p["warm_seconds"] = 2
    p["clients"] = 4
    p["rate_rps"] = min(float(p.get("rate_rps", 2)), 2.0)
    p["trace_seconds"] = 1
    return p


def probe(srv, model, seed, overhead, prompt_tokens):
    """The same seeded greedy request three times.  The first is prefilled
    cold (flash kernel); the second and third are served from the prefix
    cache the first one filled (paged kernel over history), so those two run
    the same computation and must return the same ids.  The first against
    the second is noted and not judged: on random weights the logits are
    near-flat and the two paths' rounding parts the argmax (PR 22 saw ids
    part at token 2, PR 24 at token 1 in one run of three).  Also checks the
    template overhead the traffic's prompt lengths rest on."""
    text = words(prompt_tokens - overhead, seed % 1000003 + 99)
    runs = [srv.chat_once(model, text, 4, temperature=0.0,
                          return_token_ids=True) for _ in range(3)]
    ids = [r["choices"][0].get("token_ids") or [None] for r in runs]
    usage = runs[0]["usage"]
    ok = (ids[1][0] is not None and ids[1] == ids[2]
          and usage["prompt_tokens"] == prompt_tokens
          and usage["completion_tokens"] == len(ids[0]))
    note(phase="probe", ids=ids, cold_and_cached_agree=ids[0] == ids[1],
         usage=usage, want_prompt_tokens=prompt_tokens, ok=ok)
    return ok


def trace_summary(log_dir):
    """``benchmark.lib.xplane`` in a process of its own (it needs JAX to
    read the trace; this parent must not import it)."""
    out = os.path.join(OUT, "trace_summary.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.setdefault("TPU_LOG_DIR", "disabled")
    r = subprocess.run(
        [sys.executable, "-m", "benchmark.lib.xplane", log_dir, "--out", out],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    if r.returncode != 0:
        note(phase="trace", error=r.stderr[-800:])
        return None
    with open(out) as f:
        return json.load(f)


def flight_means_ms(flight):
    """Mean host phases of the window's engine steps (flight records)."""
    out = {}
    for key in ("host_build_s", "device_wait_s", "emit_s", "idle_gap_s",
                "wall_s"):
        vals = [s[key] for s in flight if key in s]
        if vals:
            out[key[:-2] + "_ms"] = sum(vals) / len(vals) * 1e3
    return out


def breakdown_of(trace):
    """The device operations that took most (self) time, and the longest
    idle gaps by the program before each and what a host thread was in."""
    dev = trace["devices"][0]
    ops = sorted(dev["ops"].items(), key=lambda kv: -kv[1][1])[:10]
    gaps = [[f"after {g['after_program']}; host: {g['host']}"[:200],
             g["dur_s"]] for g in dev["gaps"]]
    return {"device_ops": [[n, v[1]] for n, v in ops], "idle_gaps": gaps}


def boot(srv, cell, params, seed, rehearse):
    """Wait for the server, check its chips, probe, and warm the shapes the
    traffic adds.  Returns (device, model, correct so far); exits 3 where
    the server does not see the chips the cell asks for."""
    chips = cell["entry"]["chips"]
    overhead = cell["config"]["serving"]["chat_template_overhead_tokens"]
    state = srv.wait_running(T_START + COLD_LIMIT_S)
    device = device_of(state)
    if not rehearse and not (
            device["platform"] == "tpu" and device["arch"] == "v5e"
            and device["count"] == chips):
        srv.kill()
        fail(f"the server sees {device}, the cell needs {chips} TPU v5e "
             "chip(s)", EXIT_NO_CHIP)
    model = state["profile"]["models"][0]
    log = srv.log_text()
    note(phase="server", model=model, device=device,
         ready_s=round(srv.ready_s, 2),
         weights_s=log_seconds(log, "weights on device"),
         warmup_s=log_seconds(log, r"warmup\(\)"))
    correct = probe(srv, model, seed, overhead, 60 if rehearse else 260)
    for n in params.get("warm_prompt_tokens", []):
        r = srv.chat_once(model, words(n - overhead, n), 1)
        correct &= r["usage"]["prompt_tokens"] == n
    return device, model, correct


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny sizes; never correct, never exit 0")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(ROOT, "helix_tpu")):
        fail("benchmark/run.py must sit in a helix-tpu checkout "
             "(helix_tpu/ beside benchmark/): there is no system to test", 2)
    try:
        bench = manifest.benchmark_json()
        cell = manifest.cell(args.workload, bench)
    except manifest.ManifestError as e:
        fail(str(e), 2)
    seconds = args.seconds or float(bench["run_seconds"])
    params = cell["params"]
    if args.rehearse:
        params = rehearsal_sizes(params)
    srv_cfg = cell["config"]["serving"]
    overhead = srv_cfg["chat_template_overhead_tokens"]
    # a capture is tens of MB: keep only this run's
    shutil.rmtree(os.path.join(OUT, "profiles"), ignore_errors=True)
    os.makedirs(OUT, exist_ok=True)
    tag = args.workload

    plan = cell["generator"].plan(params, args.seed, seconds)
    # a caller's time limit arrives as SIGTERM: leave no server behind
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    srv = Server(ROOT, write_profile(cell, args.seed, tag, args.rehearse),
                 OUT, tag, args.rehearse)
    try:
        device, model, correct = boot(srv, cell, params, args.seed,
                                      args.rehearse)
        shapes_warm = prom.parse(srv.metrics_text(), model).get(
            "helix_compiled_step_shapes")

        ld = load_mod.Load(srv.url, model, args.seed,
                           float(params["warm_seconds"]), seconds, overhead,
                           float(params.get("temperature", 1.0)))
        trace_s = float(params.get("trace_seconds", 3)) if args.trace else 0.0
        load_mod.run_load(ld, cell["generator"], plan, trace_s)
        setup_s = ld.w0 - T_START
        state = srv.state()
        peak = max((a.get("peak_memory_bytes") or 0)
                   for a in state["accelerators"])
        log = srv.log_text()
        code = srv.stop()
        note(phase="server", sigterm_exit_code=code)
    except ServerFailed as e:
        fail(str(e))
    finally:
        srv.kill()

    # ---- the window ------------------------------------------------------
    scr = {k: prom.parse(text, model) for k, (_, text) in ld.scrapes.items()}
    client_at = {k: before for k, (before, _) in ld.scrapes.items()}
    closed = all(r.due is None for r in ld.recs)
    if closed:
        # closed loop: the requests that ended inside the window
        judged = [r for r in ld.recs
                  if r.end is not None and ld.w0 <= r.end < ld.w1]
    else:
        # open loop: the requests that were due inside the window
        judged = [r for r in ld.recs if ld.w0 <= r.due < ld.w1]
    for r in judged:
        if r.cut and not r.error:
            r.error = "not finished within the drain limit"
    bad = [(r.idx, r.malformed()) for r in judged if r.malformed()]
    attempted, failed = len(judged), len(bad)
    if bad:
        note(phase="window", failed_requests=bad[:10])

    checks = {}
    gen = prom.delta(scr["w0"], scr["w1"], "helix_generated_tokens_total")
    got = client_at["w1"] - client_at["w0"]
    # The server counts a token when the engine emits it and the client when
    # the chunk arrives: at each edge a fused decode window of every slot
    # can be between the two (8 steps x 32 slots, both edges).
    slack = max(1024, 0.03 * max(got, 1))
    checks["generated_tokens"] = {
        "server": gen, "clients": got,
        "ok": gen is not None and abs(gen - got) <= slack}
    # prompt tokens, over the whole traffic period: every request that got a
    # first token was prefilled; none that was not sent was.
    pre = prom.delta(scr["t0"], scr["end"], "helix_prefill_tokens_total")
    hit_pages = prom.delta(scr["t0"], scr["end"],
                           "helix_prefix_cache_hit_pages_total") or 0
    lo = sum(r.prompt_tokens for r in ld.recs if r.first is not None)
    hi = sum(r.prompt_tokens for r in ld.recs if r.sent is not None)
    cached = hit_pages * srv_cfg["page_size"]
    checks["prefill_tokens"] = {
        "server": pre, "clients_low": lo, "clients_high": hi,
        "prefix_cache_hit_tokens": cached,
        "ok": pre is not None and lo - cached <= pre <= hi}
    shapes = [scr[k].get("helix_compiled_step_shapes")
              for k in ("t0", "w0", "w1", "end")]
    checks["no_compile_in_window"] = {
        "compiled_step_shapes": shapes, "after_warm_requests": shapes_warm,
        "ok": shapes[1] is not None and shapes[1] == shapes[2]}
    checks["load_generator"] = {"task_errors": ld.task_errors[:5],
                                "ok": not ld.task_errors}
    correct = bool(correct and attempted > 0 and failed == 0
                   and all(c["ok"] for c in checks.values()))
    note(phase="checks", **checks)

    # ---- end-to-end metrics (host clock, taken here) -----------------------
    done = [r for r in judged if r.done and not r.error]
    ttft = [(r.first - (r.due if r.due is not None else r.sent)) * 1e3
            for r in judged if r.first is not None]
    tpot = [t for t in (stats.tpot_ms(r.first, r.last, r.n_tokens)
                        for r in done) if t is not None]
    values = {
        "tokens_per_s": ld.tokens_in_window / seconds,
        "ttft_mean_ms": sum(ttft) / len(ttft) if ttft else None,
        "tpot_p95_ms": stats.percentile(tpot, 95),
        "setup_s": setup_s,
    }
    note(phase="samples", window_s=seconds, requests_judged=attempted,
         requests_completed=len(done), tokens_in_window=ld.tokens_in_window,
         ttft_ms=stats.summary(ttft), tpot_ms=stats.summary(tpot),
         completed_per_s=len(done) / seconds,
         stopped_early=sum(1 for r in done if r.finish == "stop"),
         preemptions=prom.delta(scr["w0"], scr["w1"],
                                "helix_preemptions_total"),
         mixed_steps=prom.delta(scr["w0"], scr["w1"],
                                "helix_mixed_steps_total"))
    units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
    # a metric split by cell group keeps its quantity's name before the dot
    # (tpot_p95_ms.chat and tpot_p95_ms.saturated are both tpot_p95_ms)
    e2e = {n: {"value": values[n.split(".")[0]], "unit": u}
           for n, u in units.items()
           if values.get(n.split(".")[0]) is not None}

    out_device = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"], "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.trace:
        trace = None
        if ld.trace and ld.trace.get("log_dir"):
            trace = trace_summary(ld.trace["log_dir"])
        in_window = [s for s in ld.flight.values()
                     if ld.w0 <= s["ts"] - ld.wall_offset < ld.w1]
        ctx = {"scrapes": scr, "flight": in_window, "trace": trace,
               "log": log, "recs": judged, "config": cell["config"],
               "device_kind": device["kind"]}
        metrics = {}
        for m in cell["per_layer"]:
            spec = m["reader"]
            v = READERS[spec["reduction"]](ctx, spec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if trace and trace.get("devices"):
            busy = [d["busy_s"] for d in trace["devices"]]
            out_device["busy_s"] = sum(busy) / len(busy)
            out_device["window_s"] = trace["window_s"]
            result["breakdown"] = breakdown_of(trace)
        note(phase="trace", capture=ld.trace, flight_steps=len(in_window),
             flight_step_means=flight_means_ms(in_window),
             host_idle_gauge=scr.get("trace_end", {}).get(
                 "helix_device_idle_ratio"),
             end_to_end_in_this_traced_run=e2e)
        result["metrics"] = metrics
    else:
        result["metrics"] = e2e
    result["device"] = out_device

    if args.rehearse:
        note(rehearsal=True, cpu_values_not_device_metrics={
            "cpu_rehearsal." + k: v["value"]
            for k, v in result["metrics"].items()},
            breakdown=result.get("breakdown"))
        print(json.dumps({"correct": False, "rehearsal": True,
                          "attempted": attempted, "failed": failed,
                          "metrics": {}, "device": out_device}), flush=True)
        sys.exit(EXIT_REHEARSAL)
    if args.trace and "busy_s" not in out_device:
        fail("the traced run read no device operation from the trace")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
