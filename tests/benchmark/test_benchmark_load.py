"""The load generator against a scripted SSE server (ISSUE 24): tokens are
stamped on arrival, an open-loop request is timed from when it was due, the
closed loop sends a client's next request when its last completes, and an
error frame or a bad status is a failed request.  No JAX."""

import asyncio
import json
import os
import sys
import threading
import time

import pytest
from aiohttp import web

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.generators import closed_loop, open_loop_poisson  # noqa: E402
from benchmark.lib.load import Load, run_load  # noqa: E402


class FakeServer:
    """Streams ``max_tokens`` chunks, ``gap`` apart; counts what it sends."""

    def __init__(self, gap=0.002, mode="ok"):
        self.gap, self.mode = gap, mode
        self.generated = 0
        self.prefilled = 0
        self.inflight = self.max_inflight = 0
        self.ready = threading.Event()
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()
        assert self.ready.wait(10)

    async def chat(self, request):
        body = await request.json()
        if self.mode == "503":
            return web.json_response({"error": "busy"}, status=503)
        self.inflight += 1
        self.max_inflight = max(self.max_inflight, self.inflight)
        self.prefilled += len(body["messages"][0]["content"]) + 19
        resp = web.StreamResponse(
            headers={"Content-Type": "text/event-stream"})
        await resp.prepare(request)
        try:
            n = body["max_tokens"]
            for i in range(n):
                await asyncio.sleep(self.gap)
                if self.mode == "error" and i == 2:
                    await resp.write(b'data: {"error": {"message": "x"}}\n\n')
                    break
                fin = "length" if i == n - 1 else None
                chunk = {"choices": [{"index": 0, "delta": {},
                                      "finish_reason": fin}]}
                self.generated += 1
                await resp.write(f"data: {json.dumps(chunk)}\n\n".encode())
            await resp.write(b"data: [DONE]\n\n")
        finally:
            self.inflight -= 1
        return resp

    async def metrics(self, request):
        return web.Response(text=(
            f'helix_generated_tokens_total{{model="m"}} {self.generated}\n'
            f'helix_prefill_tokens_total{{model="m"}} {self.prefilled}\n'))

    def _run(self):
        self.loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self.loop)
        app = web.Application()
        app.router.add_post("/v1/chat/completions", self.chat)
        app.router.add_get("/metrics", self.metrics)
        runner = web.AppRunner(app)
        self.loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", 0)
        self.loop.run_until_complete(site.start())
        self.port = site._server.sockets[0].getsockname()[1]
        self.ready.set()
        self.loop.run_forever()

    @property
    def url(self):
        return f"http://127.0.0.1:{self.port}"


SIZES = {"prompt_tokens": {"dist": "uniform", "min": 30, "max": 60},
         "max_tokens": {"dist": "uniform", "min": 10, "max": 20},
         "pool_seed": 1}


def test_open_loop_times_from_due_and_counts_tokens():
    srv = FakeServer()
    params = dict(SIZES, rate_rps=20.0, warm_seconds=0.5, drain_seconds=5)
    plan = open_loop_poisson.plan(params, 3, 1.5)
    ld = run_load(Load(srv.url, "m", 3, 0.5, 1.5, 19),
                  open_loop_poisson, plan)
    assert len(ld.recs) == len(plan["requests"]) == 40
    for r in ld.recs:
        assert r.malformed() is None and not r.cut
        assert r.n_tokens == r.max_tokens
        assert r.due <= r.sent <= r.first <= r.last <= r.end
        assert r.sent - r.due < 0.25          # the generator kept up
    assert ld.tokens_total == sum(r.n_tokens for r in ld.recs) \
        == srv.generated
    assert 0 < ld.tokens_in_window < ld.tokens_total
    assert sum(r.prompt_tokens for r in ld.recs) == srv.prefilled
    assert set(ld.scrapes) == {"t0", "w0", "w1", "end"}
    # the client's count when a scrape was sent never exceeds the server's
    for before, text in ld.scrapes.values():
        assert before <= float(text.split()[1])


def test_closed_loop_keeps_as_many_in_flight_as_clients():
    srv = FakeServer(gap=0.004)
    params = dict(SIZES, clients=5)
    plan = closed_loop.plan(params, 9, 1.0)
    t0 = time.monotonic()
    ld = run_load(Load(srv.url, "m", 9, 0.2, 1.0, 19), closed_loop, plan)
    assert time.monotonic() - t0 < 3.0       # cut at the window's end
    assert srv.max_inflight == 5
    done = [r for r in ld.recs if r.done]
    cut = [r for r in ld.recs if r.cut]
    assert len(done) > 20 and 1 <= len(cut) <= 5
    assert all(r.end is None for r in cut)
    # a client's next request goes when its last one completes: requests
    # go out in the order of the plan
    assert [r.idx for r in ld.recs] == list(range(len(ld.recs)))
    assert [(r.prompt_tokens, r.max_tokens) for r in ld.recs[:10]] == [
        (q["prompt_tokens"], q["max_tokens"]) for q in plan["requests"][:10]]


@pytest.mark.parametrize("mode,needle", [("503", "HTTP 503"),
                                         ("error", "message")])
def test_a_failed_request_is_recorded_not_dropped(mode, needle):
    srv = FakeServer(mode=mode)
    params = dict(SIZES, rate_rps=10.0, warm_seconds=0.1, drain_seconds=2)
    plan = open_loop_poisson.plan(params, 1, 0.5)
    ld = run_load(Load(srv.url, "m", 1, 0.1, 0.5, 19),
                  open_loop_poisson, plan)
    assert ld.recs and all(needle in (r.malformed() or "") for r in ld.recs)
    assert all(r.end is not None for r in ld.recs)
