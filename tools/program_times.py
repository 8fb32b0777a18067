#!/usr/bin/env python3
"""Device time of a trace's step programs, a kind at a time, and a kernel's
share of each.

    JAX_PLATFORMS=cpu python3 tools/program_times.py <trace dir or file> \
        [--op '^grouped_matmul_tpu'] [--out programs.json]

For each program name (``jit_step_fn_t128_r32`` ...): how many executions
lay whole inside the capture, their mean, least and longest time, and the
mean self time an execution spent in each operation ``--op`` matches, by the
operation's name and output shape (``grouped_matmul_tpu.55 f32[3072,2048]``:
the shape's rows say whose rows a call multiplied).  A capture's events
carry no ``op_name`` (their stats are three timings; PERF.md section 7 item
8), so a named scope cannot be summed from it: a program against the same
program of another tree, and a kernel's calls by shape, are what it gives.
The answer has two keys: ``programs`` (by program name) and ``launches``
(each ``helix.loop.launch`` span's ``live_rows`` and ``inert_rows`` by
``kind``, as the host counted them: an admission wave's running rows decode
in it, so ``admit`` reads ~31 / ~1 of 32).  ``benchmark/lib/xplane.py`` has
the arithmetic; ``tools/bench_pairs.py`` calls this after a traced run.
"""
import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SHAPE = re.compile(r"= \(?(\w+\[[\d,]*\])")


def main():
    from jax.profiler import ProfileData

    from benchmark.lib import xplane

    ap = argparse.ArgumentParser()
    ap.add_argument("trace")
    ap.add_argument("--op", default="^grouped_matmul_tpu")
    ap.add_argument("--out")
    a = ap.parse_args()
    op_re = re.compile(a.op)
    data = ProfileData.from_file(xplane.find_trace(a.trace))
    programs = {}
    for plane in data.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        by_line = {ln.name: ln for ln in plane.lines}
        if xplane.OPS_LINE not in by_line or (
                xplane.MODULES_LINE not in by_line):
            continue
        ops = [(ev.name, ev.start_ns, ev.duration_ns)
               for ev in by_line[xplane.OPS_LINE].events]
        mods = sorted(
            ((ev.name, ev.start_ns, ev.duration_ns)
             for ev in by_line[xplane.MODULES_LINE].events),
            key=lambda m: m[1])
        first = min(s for _, s, _ in ops)
        last = max(s + d for _, s, d in ops)
        i = 0
        for name, s, d, self_ns in xplane.self_times(ops):
            while i < len(mods) and s >= mods[i][1] + mods[i][2]:
                i += 1
            if i >= len(mods) or s < mods[i][1]:
                continue
            mname, ms, md = mods[i]
            if ms < first or ms + md > last:
                continue        # cut by the capture's edge
            prog = programs.setdefault(
                mname.split("(")[0], {"runs": {}, "op": {}})
            prog["runs"][ms] = md
            if op_re.search(xplane.short(name)):
                shape = SHAPE.search(name)
                key = xplane.short(name) + (
                    " " + shape.group(1) if shape else "")
                prog["op"][key] = prog["op"].get(key, 0) + self_ns
    for prog in programs.values():
        durs = sorted(prog.pop("runs").values())
        n = prog["n"] = len(durs)
        prog["mean_ms"] = sum(durs) / n / 1e6
        prog["min_ms"], prog["max_ms"] = durs[0] / 1e6, durs[-1] / 1e6
        prog["op_ms"] = {
            k: v / n / 1e6 for k, v in sorted(prog.pop("op").items())}
    # the launches as the host counted them (``helix.loop.launch``'s stats),
    # by kind: the state rows each launched live and those that sat out
    launches = {}
    for plane in data.planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name != "helix.loop.launch":
                    continue
                stats = dict(ev.stats)
                rows = launches.setdefault(
                    str(stats.get("kind")), {"live_rows": [], "inert_rows": []})
                for key, seen in rows.items():
                    seen.append(int(stats.get(key, 0)))
    text = json.dumps({"programs": programs, "launches": launches},
                      indent=1, sort_keys=True)
    if a.out:
        with open(a.out, "w") as f:
            f.write(text)
    else:
        print(text)


if __name__ == "__main__":
    main()
