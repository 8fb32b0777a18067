#!/usr/bin/env python3
"""Name every long idle gap of a capture's device by the program's own spans
(ISSUE 37): where it lies from the capture's first ``helix.clock`` stamp,
which ``helix.*`` spans (a part of admit or dispatch, ``helix.gc``,
``helix.http.scrape`` ...) cover at least half of it, shortest first, which
collections and scrapes touch it at all, and the shortest event of any other
kind that covers half of it (the runtime's: what ``benchmark/lib/xplane.py``
names a gap by when no span of ours is shorter).

    JAX_PLATFORMS=cpu python3 tools/gap_spans.py <trace dir or file> \
        [--min-ms 20] [--out gaps.json]

``tools/bench_pairs.py`` calls this after a traced run.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

ALWAYS = ("helix.gc", "helix.http.scrape")


def main():
    from jax.profiler import ProfileData

    from benchmark.lib import xplane

    ap = argparse.ArgumentParser()
    ap.add_argument("trace")
    ap.add_argument("--min-ms", type=float, default=20.0)
    ap.add_argument("--out")
    a = ap.parse_args()
    planes = list(ProfileData.from_file(xplane.find_trace(a.trace)).planes)
    host, ops, mods = [], [], []
    for plane in planes:
        device = xplane.DEVICE_PLANE.match(plane.name)
        for ln in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.duration_ns, ln.name,
                    dict(ev.stats) if ev.name.startswith("helix.") else None)
                   for ev in ln.events]
            if not device:
                host.extend(e for e in evs if e[2] > 0 or e[0] == "helix.clock")
            elif ln.name == xplane.OPS_LINE and not ops:
                ops = evs
            elif ln.name == xplane.MODULES_LINE and not mods:
                mods = [e for e in evs if e[2] >= 1e6]
    if not ops:
        sys.exit("no device operation in the trace")
    lo = min(e[1] for e in ops)
    hi = max(e[1] + e[2] for e in ops)
    stamps = sorted(e[1] for e in host if e[0] == "helix.clock")
    out = {"window_ms": (hi - lo) / 1e6,
           "first_op_after_first_stamp_ms":
               (lo - stamps[0]) / 1e6 if stamps else None,
           "gaps": []}
    # the stall watch's captures inside this capture (ISSUE 51)
    out["stalls"] = [
        {"at_ms": (e[1] - stamps[0]) / 1e6 if stamps else None,
         "ms": e[2] / 1e6, **(e[4] or {})}
        for e in host if e[0] == "helix.stall"]
    for g0, dur in xplane.gaps_of([(e[1], e[2]) for e in ops], (lo, hi)):
        if dur < a.min_ms * 1e6:
            break
        g1 = g0 + dur
        before = [m for m in mods if m[1] + m[2] <= g0 + 1e3]
        ours, touch, other = [], [], None
        for name, s, d, line, stats in host:
            cover = min(s + d, g1) - max(s, g0)
            if cover <= 0:
                continue
            if stats is not None:
                span = {"span": name, "thread": line, "ms": d / 1e6,
                        "covers_ms": cover / 1e6,
                        **{k: stats[k] for k in
                           ("kind", "generation", "path", "changed_slots")
                           if k in stats}}
                if cover >= 0.5 * dur:
                    ours.append(span)
                elif name in ALWAYS:
                    touch.append(span)
            elif cover >= 0.5 * dur and (other is None or d < other[0]):
                other = (d, f"{line}: {name}"[:160])
        out["gaps"].append({
            "ms": dur / 1e6,
            "after_first_stamp_ms":
                (g0 - stamps[0]) / 1e6 if stamps else None,
            "after_program": (f"{before[-1][0].split('(')[0]} "
                              f"{before[-1][2] / 1e6:.1f}ms"
                              if before else None),
            "ours": sorted(ours, key=lambda s: s["ms"]),
            "touching": touch,
            "other": {"ms": other[0] / 1e6, "event": other[1]}
            if other else None,
        })
    text = json.dumps(out, indent=1)
    if a.out:
        with open(a.out, "w") as f:
            f.write(text)
    else:
        print(text)


if __name__ == "__main__":
    main()
