"""The load generator: one asyncio thread that streams chat completions
from the server and stamps every token on arrival.  A traffic generator
(``benchmark/generators/<name>.py``) decides which request goes when; this
module sends it, keeps the records and closes the window."""

import asyncio
import dataclasses
import json
import time
from typing import Optional

import aiohttp

from benchmark.lib.server import words


@dataclasses.dataclass
class Rec:
    """One request as the client saw it (times are ``time.monotonic()``)."""
    idx: int
    prompt_tokens: int
    max_tokens: int
    due: Optional[float] = None     # open loop: when it should have gone
    sent: Optional[float] = None
    first: Optional[float] = None   # first streamed token
    last: Optional[float] = None    # last streamed token
    n_tokens: int = 0
    finish: Optional[str] = None
    error: Optional[str] = None
    done: bool = False              # the stream ended with [DONE]
    end: Optional[float] = None     # when it completed or failed
    cut: bool = False               # cancelled by the harness, not a failure

    def malformed(self):
        """Why a finished response is not well-formed, or None."""
        if self.error:
            return self.error
        if not self.done:
            return "stream ended without [DONE]"
        if self.finish not in ("length", "stop"):
            return f"finish_reason {self.finish!r}"
        if not 1 <= self.n_tokens <= self.max_tokens:
            return f"{self.n_tokens} tokens for max_tokens {self.max_tokens}"
        if self.finish == "length" and self.n_tokens != self.max_tokens:
            return f"'length' after {self.n_tokens} of {self.max_tokens}"
        return None


class Load:
    def __init__(self, url, model, seed, warm_s, seconds, template_overhead,
                 temperature=1.0):
        self.url, self.model, self.seed = url, model, seed
        self.warm_s, self.seconds = warm_s, seconds
        self.overhead = template_overhead
        self.temperature = temperature
        self.recs = []
        self.tasks = set()
        self.tokens_total = 0        # every token received since t0
        self.tokens_in_window = 0
        self.scrapes = {}            # name -> (client tokens before, text)
        self.flight = {}             # step number -> flight record
        self.flight_error = None
        self.task_errors = []        # a sender that raised: a harness fault
        self.trace = None            # {"log_dir", "t_start", "t_end", ...}
        self.t0 = self.w0 = self.w1 = None

    # -- one request ------------------------------------------------------
    async def send(self, req, due=None):
        """Stream one chat completion to its end; returns its ``Rec``.
        ``req``: {"idx", "prompt_tokens", "max_tokens"}."""
        rec = Rec(req["idx"], req["prompt_tokens"], req["max_tokens"], due)
        self.recs.append(rec)
        body = {
            "model": self.model, "stream": True,
            "max_tokens": req["max_tokens"],
            "temperature": self.temperature,
            "seed": (self.seed * 1000003 + req["idx"]) % (2**31 - 1),
            "messages": [{"role": "user", "content": words(
                req["prompt_tokens"] - self.overhead,
                self.seed * 7919 + req["idx"])}],
        }
        rec.sent = time.monotonic()
        try:
            async with self.session.post(
                    self.url + "/v1/chat/completions", json=body) as resp:
                if resp.status != 200:
                    rec.error = f"HTTP {resp.status}: " + (
                        await resp.text())[:200]
                    rec.end = time.monotonic()
                    return rec
                async for raw in resp.content:
                    if not raw.startswith(b"data: "):
                        continue
                    now = time.monotonic()
                    if raw.startswith(b"data: [DONE]"):
                        rec.done = True
                        break
                    chunk = json.loads(raw[6:])
                    if "error" in chunk:
                        rec.error = json.dumps(chunk["error"])[:200]
                        break
                    if rec.first is None:
                        rec.first = now
                    rec.last = now
                    rec.n_tokens += 1
                    self.tokens_total += 1
                    if self.w0 <= now < self.w1:
                        self.tokens_in_window += 1
                    rec.finish = chunk["choices"][0]["finish_reason"] \
                        or rec.finish
        except asyncio.CancelledError:
            rec.cut = True
            raise
        except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as e:
            rec.error = f"{type(e).__name__}: {e}"[:200]
        rec.end = time.monotonic()
        return rec

    def spawn(self, coro):
        task = asyncio.ensure_future(coro)
        self.tasks.add(task)
        task.add_done_callback(self.tasks.discard)
        return task

    async def sleep_until(self, t):
        dt = t - time.monotonic()
        if dt > 0:
            await asyncio.sleep(dt)

    # -- the server's own view, at the window's edges ----------------------
    async def scrape(self, name):
        before = self.tokens_total
        async with self.session.get(self.url + "/metrics") as r:
            self.scrapes[name] = (before, await r.text())

    async def _scrape_at(self, t, name):
        await self.sleep_until(t)
        await self.scrape(name)

    async def _poll_flight(self):
        """The flight ring holds 512 steps: poll it through the window."""
        while time.monotonic() < self.w1:
            try:
                async with self.session.get(
                        self.url + "/v1/debug/flight?recent=512") as r:
                    data = await r.json()
                for m in data.get("models", {}).values():
                    for step in m.get("recent", []):
                        self.flight[step["step"]] = step
            except (aiohttp.ClientError, ValueError) as e:
                self.flight_error = repr(e)
            await asyncio.sleep(1.0)

    async def _capture(self, seconds):
        """A profiler capture of the live server a quarter into the window
        (``POST /admin/profiler`` returns when the trace is written, which
        takes several times the capture's own length)."""
        await self.sleep_until(self.w0 + 0.25 * self.seconds)
        await self.scrape("trace_start")
        t_start = time.monotonic()
        try:
            async with self.session.post(
                    self.url + "/admin/profiler",
                    json={"seconds": seconds}) as r:
                out = await r.json()
            self.trace = {"log_dir": out.get("log_dir"),
                          "error": None if r.status == 200 else out,
                          "t_start": t_start, "t_end": time.monotonic(),
                          "asked_s": seconds}
        except (aiohttp.ClientError, ValueError) as e:
            self.trace = {"log_dir": None, "error": repr(e)}
        await self.scrape("trace_end")

    # -- the whole run ------------------------------------------------------
    async def run(self, gen, plan, trace_seconds=0.0):
        timeout = aiohttp.ClientTimeout(total=None, sock_connect=30,
                                        sock_read=120)
        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(
                connector=conn, timeout=timeout) as self.session:
            await self.scrape("t0")
            self.t0 = time.monotonic()
            self.w0 = self.t0 + self.warm_s
            self.w1 = self.w0 + self.seconds
            self.wall_offset = time.time() - time.monotonic()
            side = [asyncio.ensure_future(self._scrape_at(self.w0, "w0")),
                    asyncio.ensure_future(self._scrape_at(self.w1, "w1"))]
            if trace_seconds:
                side.append(asyncio.ensure_future(
                    self._capture(trace_seconds)))
                side.append(asyncio.ensure_future(self._poll_flight()))
            await gen.drive(plan, self)          # returns at w1
            await asyncio.gather(*side)
            await gen.drain(plan, self)
            left = list(self.tasks)
            for t in left:
                t.cancel()
            for res in await asyncio.gather(*left, return_exceptions=True):
                if isinstance(res, Exception):
                    self.task_errors.append(repr(res))
            await self.scrape("end")


def run_load(load, gen, plan, trace_seconds=0.0):
    asyncio.run(load.run(gen, plan, trace_seconds))
    return load
