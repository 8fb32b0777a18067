"""Closed loop: ``clients`` callers, each sends its next request when its
last one completes.  A slow server receives less load; the queue is never
empty when there are more clients than decode slots."""

from benchmark.lib import lengths

POOL = 1024     # requests in a cell's pool, walked in order and again from its start


def plan(params, seed, seconds):
    pool = lengths.pool(params, POOL)     # the same for every seed
    return {"clients": int(params["clients"]),
            "requests": [{"idx": i, "prompt_tokens": p, "max_tokens": m}
                         for i, (p, m) in enumerate(pool)]}


async def drive(plan, load):
    reqs, nxt = plan["requests"], [0]

    async def client():
        while True:
            req = dict(reqs[nxt[0] % len(reqs)], idx=nxt[0])
            nxt[0] += 1
            await load.send(req)

    for _ in range(plan["clients"]):
        load.spawn(client())
    await load.sleep_until(load.w1)


async def drain(plan, load):
    """Nothing to wait for: what is in flight at the window's end is cut."""
