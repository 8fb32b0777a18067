"""Reduce a ``jax.profiler`` trace (``*.xplane.pb``) to what the per-layer
metrics read: for each device plane the traced window, the union of the
intervals in which an operation ran (busy), self time by operation name,
every executed program (XLA module) with the kernel calls inside it, and
the longest idle gaps with what a host thread was doing meanwhile.

Run as ``python -m benchmark.lib.xplane <file-or-dir> [--out json]`` in a
process of its own with ``JAX_PLATFORMS=cpu``: reading a trace needs
``jax.profiler.ProfileData`` and the harness's parent never imports JAX.
The arithmetic (``union_s``, ``self_times``, ``gaps_of``) is plain Python on
(start, duration) pairs and is tested on hand-made lists.
"""

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
N_GAPS = 10


def union_s(intervals):
    """Total length of the union of (start, duration) intervals."""
    total, end = 0, None
    for s, d in sorted(intervals):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def self_times(events):
    """Exclusive time of nested events.  ``events``: (name, start, dur) on
    one line, where a ``while`` or a fusion may enclose the operations it
    runs.  Returns (name, start, dur, self) in start order: an event's
    self time is its duration less that of the events directly inside it."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []         # stack of indexes into out
    for name, s, d in order:
        while stack and s >= out[stack[-1]][1] + out[stack[-1]][2]:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= d
        out.append([name, s, d, d])
        stack.append(len(out) - 1)
    return [tuple(e) for e in out]


def gaps_of(intervals, window):
    """Idle gaps inside ``window`` = (start, end): (start, duration) of
    every stretch that no interval covers, longest first."""
    w0, w1 = window
    gaps, end = [], w0
    for s, d in sorted(intervals):
        if s > end:
            gaps.append((end, min(s, w1) - end))
        end = max(end, s + d)
    if w1 > end:
        gaps.append((end, w1 - end))
    return sorted((g for g in gaps if g[1] > 0), key=lambda g: -g[1])


def find_trace(path):
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return found[-1]


def short(name):
    """An operation's name without its HLO text: ``%fusion.12 = ...`` and
    ``fusion.12`` both give ``fusion.12``."""
    return name.split(" = ")[0].lstrip("%").strip()[:120]


def reduce_trace(path):
    from jax.profiler import ProfileData

    trace_file = find_trace(path)
    planes = list(ProfileData.from_file(trace_file).planes)
    lo, hi = None, None
    host_lines = []
    summary = {"file": os.path.basename(trace_file), "planes": [],
               "devices": []}
    for plane in planes:
        lines = list(plane.lines)
        summary["planes"].append(
            {"name": plane.name, "lines": [ln.name for ln in lines][:40]})
        if not DEVICE_PLANE.match(plane.name):
            host_lines.extend((plane.name, ln) for ln in lines)
    for plane in planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        by_line = {ln.name: ln for ln in plane.lines}
        ops_line = by_line.get(OPS_LINE)
        ops = [(ev.name, ev.start_ns, ev.duration_ns)
               for ev in (ops_line.events if ops_line else ())]
        mods = [(ev.name, ev.start_ns, ev.duration_ns)
                for ev in (by_line[MODULES_LINE].events
                           if MODULES_LINE in by_line else ())]
        if not ops:
            continue
        first = min(s for _, s, _ in ops)
        last = max(s + d for _, s, d in ops)
        lo = first if lo is None else min(lo, first)
        hi = last if hi is None else max(hi, last)
        timed = self_times(ops)
        by_name = {}
        for name, _, d, self_ns in timed:
            c = by_name.setdefault(short(name), [0, 0, 0])
            c[0] += 1
            c[1] += self_ns
            c[2] += d
        # programs, and the operations inside each, by start time
        mods.sort(key=lambda m: m[1])
        inside = [dict() for _ in mods]
        i = 0
        for name, s, d, _ in timed:
            while i < len(mods) and s >= mods[i][1] + mods[i][2]:
                i += 1
            if i < len(mods) and s >= mods[i][1]:
                key = short(name)
                inside[i][key] = inside[i].get(key, 0) + 1
        intervals = [(s, d) for _, s, d in ops]
        summary["devices"].append({
            "plane": plane.name,
            "first_ns": first, "last_ns": last,
            "busy_s": union_s(intervals) / 1e9,
            "ops": {k: [v[0], v[1] / 1e9, v[2] / 1e9]
                    for k, v in by_name.items()},
            "modules": [{"name": n, "start_ns": s, "dur_s": d / 1e9,
                         "ops": inside[j]}
                        for j, (n, s, d) in enumerate(mods)],
            "_intervals": intervals,
        })
    if not summary["devices"]:
        return summary
    # The traced window: from the first to the last device operation of
    # any chip.  (A capture that starts or ends in an idle stretch
    # undercounts that stretch; the host planes carry no event that marks
    # the capture's own edges.)
    summary["window_s"] = (hi - lo) / 1e9
    for dev in summary["devices"]:
        intervals = dev.pop("_intervals")
        gaps = gaps_of(intervals, (lo, hi))[:N_GAPS]
        mods = dev["modules"]
        named = []
        for s, d in gaps:
            # the programs either side of the gap (the sub-millisecond
            # bookkeeping programs between two steps are passed over)
            real = [m for m in mods if m["dur_s"] >= 1e-3]
            before = [m for m in real
                      if m["start_ns"] + m["dur_s"] * 1e9 <= s + 1e3]
            after = [m for m in real if m["start_ns"] >= s + d - 1e3]

            def label(m):
                return f"{m['name']} {m['dur_s'] * 1e3:.1f}ms"

            named.append({"start_ns": s, "dur_s": d / 1e9,
                          "after_program":
                              label(before[-1]) if before else None,
                          "before_program":
                              label(after[0]) if after else None,
                          "host": None})
        dev["gaps"] = named
        dev["idle_s"] = summary["window_s"] - dev["busy_s"]
    # what a host thread was doing in each long gap: the shortest host
    # event that covers at least half of it
    want = [g for dev in summary["devices"] for g in dev["gaps"]]
    best = [None] * len(want)
    for plane_name, ln in host_lines:
        for ev in ln.events:
            s, d = ev.start_ns, ev.duration_ns
            if d <= 0:
                continue
            for k, g in enumerate(want):
                g0, g1 = g["start_ns"], g["start_ns"] + g["dur_s"] * 1e9
                cover = min(s + d, g1) - max(s, g0)
                if cover >= 0.5 * (g1 - g0) and (
                        best[k] is None or d < best[k][0]):
                    best[k] = (d, f"{ln.name}: {ev.name}"[:160])
    for g, b in zip(want, best):
        g["host"] = b[1] if b else None
    return summary


def main(argv):
    out = None
    if "--out" in argv:
        out = argv[argv.index("--out") + 1]
    summary = reduce_trace(argv[1])
    text = json.dumps(summary)
    if out:
        with open(out, "w") as f:
            f.write(text)
    else:
        print(text)


if __name__ == "__main__":
    main(sys.argv)
