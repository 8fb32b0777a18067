"""Open loop: requests go out on a schedule whatever the server does, and
each is timed from when it was due.  The gaps are exponential (a Poisson
process at ``rate_rps``), drawn once from the traffic file's ``pool_seed``
and scaled so that they fill their span exactly: first the unrecorded
traffic before the window, then the window's own sequence.  The schedule is
the same for every run seed, so the tails measure the system and not the
draw (the seed makes the text, the sampling seeds and the weights)."""

import asyncio
import random
import time

from benchmark.lib import lengths


def _sequence(params, n, span, salt):
    """``n`` (gap, prompt_tokens, max_tokens) whose gaps sum to ``span``."""
    rng = random.Random(int(params.get("pool_seed", 0)) * 1000 + 500 + salt)
    gaps = [rng.expovariate(1.0) for _ in range(n)]
    scale = span / sum(gaps)
    return [(g * scale, p, m)
            for g, (p, m) in zip(gaps, lengths.pool(params, n, salt))]


def plan(params, seed, seconds):
    rate, warm = float(params["rate_rps"]), float(params["warm_seconds"])
    before = _sequence(params, max(1, round(rate * warm)), warm, 1)
    window = _sequence(params, max(1, round(rate * float(seconds))),
                      float(seconds), 2)
    requests = []
    for start, seq in ((0.0, before), (warm, window)):
        t = start       # a request is due at the start of its gap: the
        for gap, p, m in seq:   # window's first one exactly at its start
            requests.append({"idx": len(requests), "due_s": t,
                             "prompt_tokens": p, "max_tokens": m})
            t += gap
    return {"drain_seconds": float(params.get("drain_seconds", 30)),
            "requests": requests}


async def drive(plan, load):
    for req in plan["requests"]:
        due = load.t0 + req["due_s"]
        if due >= load.w1:
            break
        await load.sleep_until(due)
        load.spawn(load.send(req, due=due))
    await load.sleep_until(load.w1)


async def drain(plan, load):
    """Wait for what was sent in the window, up to ``drain_seconds``."""
    deadline = time.monotonic() + plan["drain_seconds"]
    while load.tasks and time.monotonic() < deadline:
        await asyncio.wait(list(load.tasks),
                           timeout=max(0.0, deadline - time.monotonic()))
