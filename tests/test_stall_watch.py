"""The program names its own stalls (ISSUE 51).

(a) a step that hangs is caught WHILE it hangs (``obs.flight.StallWatch``
reads the engine thread's marker, ``obs.trace.Mark``): one anomalies entry
whose ``during`` stack names what blocked, one ``helix stall`` log line that
parses, one count, and the two step histograms charged with the step's wall
(off the CPU for a sleep, on it for a spin); the same for a stall between
passes and one of the emission worker; (b) clean steps file nothing and
observe zeros; (c) a slow step that ends inside a tick of the watcher still
logs; (d) the serving event loop's heartbeat observes its lag and a blocked
loop is a stall ``where: http``; (e) the watcher and the heartbeat leave
with the loop and the app; (f) the flight answer is ``json.dumps`` of the
snapshot, letter for letter, with each record serialised once.
"""

import asyncio
import json
import logging
import os
import sys
import threading
import time

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from helix_tpu.engine.engine import Engine, EngineConfig, Request  # noqa: E402
from helix_tpu.engine.sampling import SamplingParams  # noqa: E402
from helix_tpu.models.common import ModelConfig  # noqa: E402
from helix_tpu.models.llama import init_params  # noqa: E402
from helix_tpu.obs import flight as obs_flight  # noqa: E402
from helix_tpu.obs import trace as obs_trace  # noqa: E402
from helix_tpu.obs.flight import WATCH, FlightRecorder  # noqa: E402
from helix_tpu.serving.engine_loop import EngineLoop  # noqa: E402
from helix_tpu.serving.openai_api import OpenAIServer  # noqa: E402
from helix_tpu.serving.registry import ModelRegistry, ServedModel  # noqa: E402
from helix_tpu.serving.tokenizer import ByteTokenizer  # noqa: E402

BLOCK = 1.3


def tiny_engine():
    cfg = ModelConfig.tiny(vocab_size=512, dtype="float32")
    return Engine(cfg, init_params(cfg, jax.random.PRNGKey(3)), EngineConfig(
        max_decode_batch=2, page_size=4, num_pages=256,
        max_pages_per_seq=32, max_prefill_len=16,
        attn_backend="reference", eos_token_ids=ByteTokenizer().eos_ids))


class Sink:
    def __init__(self, on_token=None):
        self.tokens, self.done = [], threading.Event()
        self.on_token = on_token

    def __call__(self, e):
        if e.token_id >= 0:
            self.tokens.append(e.token_id)
            if self.on_token is not None:
                self.on_token(len(self.tokens))
        if e.finished:
            self.done.set()


def submit(loop, rid, max_tokens, on_token=None):
    sink = Sink(on_token)
    loop.submit(Request(
        id=rid, prompt_tokens=list(range(4, 10)),
        sampling=SamplingParams(max_tokens=max_tokens, temperature=0.0),
    ), sink)
    return sink


def snooze(seconds):
    time.sleep(seconds)


def spin(seconds):
    c0 = time.thread_time()
    while time.thread_time() - c0 < seconds:
        pass


class Once:
    """``orig`` with ``block(BLOCK)`` before its ``nth`` call, under the
    name the stack has to show."""

    def __init__(self, orig, block, nth=3):
        self.orig, self.block, self.left = orig, block, nth
        self.blocked = 0.0

    def blocking_call(self, *args, **kw):
        self.left -= 1
        if self.left == 0:
            t0 = time.monotonic()
            self.block(BLOCK)
            self.blocked = time.monotonic() - t0
        return self.orig(*args, **kw)


def stall_lines(caplog):
    out = []
    for r in caplog.records:
        if r.name == "helix.stall":
            msg = r.getMessage()
            assert r.levelno == logging.WARNING
            assert msg.startswith("helix stall {") and len(msg) < 4096
            out.append(json.loads(msg[len("helix stall "):]))
    return out


def stalls_filed(loop):
    return [a for a in loop.flight.snapshot()["anomalies"] if "where" in a]


@pytest.fixture
def short_rule(monkeypatch):
    """Half a second where the program waits a whole one, and a tick of
    50 ms."""
    def shorten():
        monkeypatch.setattr(obs_flight, "STALL_SECONDS", 0.5)
        monkeypatch.setattr(obs_flight, "WATCH_TICK", 0.05)
    return shorten


@pytest.fixture
def started():
    """A started loop whose shapes are compiled, and what it had filed by
    then (a compile on a loaded machine may pass for a stall, as it
    should)."""
    eng = tiny_engine()
    loop = EngineLoop(eng, "stall").start()
    try:
        for i in range(2):      # the request the tests send, twice
            assert submit(loop, f"warm{i}", 24).done.wait(120)
        loop._emit_stage.flush()
        yield loop, eng
    finally:
        loop.stop(join=True)


@pytest.mark.parametrize("site,block", (
    ("fetch", snooze), ("fetch", spin), ("between", snooze),
    ("emit", snooze),
))
def test_a_stall_is_caught_while_it_hangs(
        started, short_rule, caplog, site, block):
    loop, eng = started
    obs = loop.obs
    base = (len(stalls_filed(loop)), obs.stalls.value,
            obs.stall_seconds.sum, obs.stall_offcpu.sum)
    on_token = None
    if site == "fetch":
        once = Once(eng._fetch, block)
        eng._fetch = once.blocking_call
    elif site == "between":
        once = Once(loop._memory_pressure_tick, block, nth=5)
        loop._memory_pressure_tick = once.blocking_call
    else:
        once = Once(lambda n: None, block)
        on_token = once.blocking_call
    short_rule()
    with caplog.at_level(logging.WARNING, logger="helix.stall"):
        caplog.clear()
        sink = submit(loop, "s0", 24, on_token)
        assert sink.done.wait(60)
        loop.stop(join=True)
    assert once.blocked >= BLOCK
    filed = stalls_filed(loop)[base[0]:]
    lines = stall_lines(caplog)
    # one anomalies entry, one log line and one count a stall
    assert len(lines) == len(filed) == obs.stalls.value - base[1]
    where = {"fetch": "helix.loop.", "between": "between", "emit": "emit"}
    mine = [a for a in filed if a["where"].startswith(where[site])]
    assert len(mine) == 1, [a["where"] for a in filed]
    if site != "emit":      # (a blocked subscriber stalls the engine too)
        assert len(filed) == 1
    stall = mine[0]
    during, closed = stall["during"], stall["stall"]
    thread = {"fetch": "engine", "between": "engine", "emit": "emit"}[site]
    assert during["thread"] == thread
    stack = during["threads"][thread]["stack"]
    assert any(f.endswith(":blocking_call") for f in stack), stack
    assert stack[0].endswith(":" + block.__name__)
    assert 1 <= len(stack) <= obs_flight.STACK_FRAMES
    assert set(during["rusage"]) == set(closed["rusage"]) == {
        "nivcsw", "nvcsw", "majflt", "utime", "stime"}
    assert {"loadavg", "gc_open", "inbox_depth", "emit_depth", "ts",
            "t_mono", "since", "stood_s"} <= set(during)
    assert 0.5 <= during["stood_s"] < BLOCK
    assert closed["seen"] is True and closed["watcher_late_s"] < 0.5
    assert abs(closed["wall_s"] - once.blocked) <= 0.1 * once.blocked + 0.05
    line = next(ln for ln in lines if ln["where"] == stall["where"])
    assert line["model"] == "stall" and line["wall_s"] == closed["wall_s"]
    assert line["during"]["threads"][thread]["stack"] == stack
    stalled = obs.stall_seconds.sum - base[2]
    offcpu = obs.stall_offcpu.sum - base[3]
    assert obs.stall_seconds.count == obs.stall_offcpu.count \
        == obs.step_seconds.count
    if site == "fetch":
        # an engine step: the record is the slow step's, with its launch
        assert stall["reason"] == "slow_step" == stall["record"]["anomaly"]
        assert stall["where"] == during["span"]
        assert closed["launch"]["program"].startswith("jit_step_fn_t")
        assert closed["compiled_shapes"][0] == closed["compiled_shapes"][1]
        assert closed["compile_s"] == 0.0
        assert "helix.loop.fetch" in line["phases"]
        assert abs(stalled - once.blocked) <= 0.1 * once.blocked + 0.05
        assert closed["offcpu_s"] == pytest.approx(offcpu, abs=1e-3)
        if block is snooze:
            assert offcpu >= 0.9 * stalled
        else:
            assert offcpu <= 0.5 * stalled
    else:
        assert stall["reason"] == "stall" and stall["step"] is None
        assert "launch" not in closed
        if site == "between":
            assert during["span"] == "helix.loop.pass" == closed["span"]
            assert stalled == 0.0
    # (e) the watcher leaves with the last loop
    assert loop._watched not in WATCH.watched()
    if not WATCH.watched():
        assert not [t for t in threading.enumerate()
                    if t.name == "helix-stallwatch" and t.is_alive()]


def test_clean_steps_file_nothing_and_observe_zeros(caplog):
    loop = EngineLoop(tiny_engine(), "clean")
    with caplog.at_level(logging.WARNING, logger="helix.stall"):
        for i in range(3):      # the compiles, then what is judged
            sinks = [submit(loop, f"c{i}a", 110), submit(loop, f"c{i}b", 110)]
            if i == 1:
                caplog.clear()
                loop.flight = FlightRecorder()
                base = (loop.obs.stall_seconds.count,
                        loop.obs.stall_seconds.sum,
                        loop.obs.step_seconds.count)
            for _ in range(2000):
                if all(s.done.is_set() for s in sinks):
                    break
                assert loop._pass()
    obs = loop.obs
    steps = obs.step_seconds.count - base[2]
    assert steps >= 200
    assert obs.stall_seconds.count - base[0] == steps
    assert obs.stall_seconds.count == obs.stall_offcpu.count
    assert obs.stall_seconds.sum == base[1]
    assert not stall_lines(caplog) and not stalls_filed(loop)
    assert loop.flight.snapshot()["anomalies_total"] == 0


def test_a_slow_step_that_ends_inside_a_tick_still_logs(caplog):
    eng = tiny_engine()
    loop = EngineLoop(eng, "quick")
    loop.flight = FlightRecorder(min_samples=4)
    sink = submit(loop, "q0", 40)
    for _ in range(3):
        assert loop._pass()
    loop.flight.reset_baseline()
    for _ in range(8):
        assert loop._pass()
    base = loop.obs.stalls.value, loop.obs.stall_seconds.sum
    admit = eng._admit
    eng._admit = lambda emitted: (time.sleep(0.4), admit(emitted))[1]
    with caplog.at_level(logging.WARNING, logger="helix.stall"):
        caplog.clear()
        assert loop._pass()
    eng._admit = admit
    (line,) = stall_lines(caplog)
    (stall,) = stalls_filed(loop)
    assert loop.obs.stalls.value - base[0] == 1
    assert stall["reason"] == "slow_step" and stall["during"] is None
    assert line["seen"] is False and "during" not in line
    # nobody saw it hang: the phase that took longest says where
    assert stall["where"] == line["where"] == "helix.loop.admit"
    assert 0.4 <= line["wall_s"] < 0.6
    assert line["offcpu_s"] >= 0.35
    assert loop.obs.stall_seconds.sum - base[1] == pytest.approx(
        line["wall_s"], abs=1e-3)
    assert line["step"] == stall["step"] == stall["record"]["step"]
    for _ in range(400):
        if sink.done.is_set():
            break
        assert loop._pass()


@pytest.mark.parametrize("blocked,stalls", ((0.08, 0), (0.9, 1)))
def test_the_event_loops_heartbeat(short_rule, caplog, blocked, stalls):
    from aiohttp.test_utils import TestClient, TestServer

    loop = EngineLoop(tiny_engine(), "beat").start()
    registry = ModelRegistry()
    registry.register(ServedModel(
        name="beat", loop=loop, tokenizer=ByteTokenizer(),
        context_length=128))
    srv = OpenAIServer(registry)
    lag = loop.obs.http_loop_lag
    seen = {}

    def blocking_call():
        snooze(blocked)

    async def main():
        async with TestClient(TestServer(srv.build_app())) as client:
            await asyncio.sleep(0.35)
            assert srv._beat is not None and WATCH.http.at is not None
            seen["before"] = (lag.count, lag.sum)
            blocking_call()
            await asyncio.sleep(0.25)
            seen["after"] = (lag.count, lag.sum)
            r = await client.get("/v1/debug/flight?recent=512")
            seen["body"] = await r.read()
            seen["type"] = r.headers["Content-Type"]
        seen["left"] = lag.count

    if stalls:
        short_rule()
    try:
        with caplog.at_level(logging.WARNING, logger="helix.stall"):
            caplog.clear()
            asyncio.run(main())
            lines = stall_lines(caplog)
    finally:
        loop.stop(join=True)
    # ten beats a second, each lag small but the one behind the block
    assert seen["before"][0] >= 2
    assert seen["before"][1] <= 0.05 * seen["before"][0]
    late = seen["after"][1] - seen["before"][1]
    assert blocked - 0.1 - 0.02 <= late <= blocked + 0.1
    # (e) cleanup cancels the timer and takes the marker away
    assert srv._beat is None and WATCH.http.at is None
    time.sleep(0.25)
    assert lag.count == seen["left"]
    # (f) the body is what json_response gave
    assert seen["type"] == "application/json; charset=utf-8"
    assert seen["body"] == json.dumps(
        {"models": {"beat": loop.flight.snapshot(recent=512)}}).encode()
    assert len(lines) == len(stalls_filed(loop)) == stalls
    if stalls:
        (stall,) = stalls_filed(loop)
        assert stall["where"] == "http" == lines[0]["where"]
        assert stall["during"]["span"] == "helix.http.beat"
        assert stall["during"]["scrapes_open"] == []
        assert stall["during"]["profiler_capture"] is False
        assert abs(stall["stall"]["wall_s"] - blocked) <= 0.15


def test_the_flight_body_serialises_a_record_once(monkeypatch):
    rec = FlightRecorder(min_samples=4)
    for i in range(40):
        rec.record_step({"step": i, "ts": 1.0 + i, "duration": 0.01 + i / 1e4,
                         "generated_tokens": 1, "phases": {"a": 0.25}})
    rec.record_step({"step": 40, "ts": 50.0, "duration": 3.0,
                     "generated_tokens": 1},
                    where="helix.loop.fetch", stall={"wall_s": 3.0},
                    during={"threads": {"engine": {"stack": ["a:1:f"]}}})
    rec.note_anomaly("quarantine", request_id="r0")
    dumped = []
    dumps = json.dumps

    def counting(obj, *a, **kw):
        if isinstance(obj, obs_flight._Filed):
            dumped.append(obj)
        return dumps(obj, *a, **kw)

    monkeypatch.setattr(obs_flight.json, "dumps", counting)
    first = rec.snapshot_json(recent=64)
    made = len(dumped)
    # 41 step records and the quarantine's own, shared by ring and tails
    assert made == 42
    again = rec.snapshot_json(recent=64)
    assert len(dumped) == made
    monkeypatch.undo()
    assert first == again == json.dumps(rec.snapshot(recent=64))
    rec.record_step({"step": 41, "ts": 51.0, "duration": 0.01,
                     "generated_tokens": 1})
    assert rec.snapshot_json(recent=8) == json.dumps(rec.snapshot(recent=8))
    frozen = rec.snapshot()["anomalies"][0]
    assert list(frozen) == ["reason", "ts", "step", "record", "steps",
                            "where", "stall", "during"]


def test_a_marked_threads_spans_say_where_it_is():
    mark = obs_trace.Mark()
    mark.pass_no, mark.step = 7, 3
    obs_trace.mark_thread(mark)
    try:
        into = obs_trace.Phases()
        with obs_trace.phase("helix.loop.step", step_num=3):
            outer = mark.at
            with obs_trace.phase("helix.loop.launch", into=into, kind="d"):
                inner = mark.at
            assert mark.at is outer
        assert mark.at is None
    finally:
        obs_trace.mark_thread(None)
    assert outer[:3] == (7, 3, "helix.loop.step")
    assert inner[:3] == (7, 3, "helix.loop.launch") and inner[3] >= outer[3]
    assert mark.attrs["helix.loop.launch"] == {"kind": "d"}
    with obs_trace.phase("helix.loop.step", step_num=4):
        assert mark.at is None      # an unmarked thread writes nothing


def test_a_stall_line_stays_under_its_limit():
    frames = [f"serving/engine_loop.py:{1000 + i}:a_function_{i}"
              for i in range(obs_flight.STACK_FRAMES)]
    phases = {f"helix.loop.phase_{i}": 0.123456 for i in range(12)}
    rec = {"model": "m" * 60, "where": "helix.loop.fetch", "wall_s": 2.0,
           "phases": phases, "phases_cpu": phases, "parts": phases,
           "during": {"span": "helix.loop.fetch", "threads": {
               n: {"stack": frames, "cpu_s": 1.0}
               for n in ("engine", "emit", "http")}}}
    line = obs_flight.stall_line(rec)
    assert len(line) <= obs_flight.LOG_LINE_BYTES
    doc = json.loads(line)
    assert doc["wall_s"] == 2.0 and doc["phases"] == phases
    assert doc["during"]["threads"]["engine"]["stack"] == frames[:len(
        doc["during"]["threads"]["engine"]["stack"])]
    assert len(rec["during"]["threads"]["engine"]["stack"]) == 12


@pytest.mark.parametrize("late,captured", (
    (0.0, {"engine", "http"}), (2.0, {"engine"})))
def test_a_watcher_that_stood_still_itself_files_one_stall(late, captured):
    """A process frozen whole thaws with every marker overdue: the watcher
    that was held up with them captures the engine thread's alone."""
    from helix_tpu.obs.metrics import EngineLoopObs

    watch = obs_flight.StallWatch()
    rec = FlightRecorder()
    w = obs_flight.Watched("w", lambda: rec, EngineLoopObs(),
                           lambda: {"threads": {}, "gc_open": False}, watch)
    now = time.monotonic()
    w.marks["engine"].at = (3, 2, "helix.loop.fetch", now - 2.1)
    w.marks["emit"].at = (9, 0, "helix.emit.batch", now - 0.2)
    watch.http.at = (40, 0, "helix.http.beat", now - 2.05)
    watch.http_running = lambda: True
    w.look(now, late)
    assert set(w.during) == captured
    assert w.during["engine"]["watcher_late_s"] == late
    assert w.during["engine"]["span"] == "helix.loop.fetch"
    assert w.during["engine"]["stood_s"] == pytest.approx(2.1, abs=0.01)
    w.look(now + 0.25)          # once a stall: the same markers again
    assert set(w.during) == captured
    assert w.take("engine")["pass"] == 3 and w.take("engine") is None


def test_a_capture_that_lands_after_its_step_closed_flags_no_other_step(
        caplog):
    """A process that thaws ends the overdue step before the watcher has
    read the stacks: the capture belongs to a pass that is over."""
    loop = EngineLoop(tiny_engine(), "late")
    sink = submit(loop, "l0", 12)
    for _ in range(4):
        assert loop._pass()
    stale = {"pass": loop._mark.pass_no, "step": loop.steps,
             "span": "helix.loop.fetch", "since": time.monotonic() - 2.0}
    loop._stalled_pass = loop._mark.pass_no     # its step was filed slow
    with caplog.at_level(logging.WARNING, logger="helix.stall"):
        caplog.clear()
        loop._watched.during["engine"] = dict(stale)    # between passes
        assert loop._pass()
        assert not loop._watched.during
        dispatch = loop.engine.step_dispatch

        def landing_inside_the_next_step():
            loop._watched.during["engine"] = dict(stale)
            return dispatch()

        loop.engine.step_dispatch = landing_inside_the_next_step
        assert loop._pass()
        del loop.engine.step_dispatch
        assert not loop._watched.during
    assert not stall_lines(caplog) and not stalls_filed(loop)
    assert loop.obs.stall_seconds.sum == 0.0 and loop.obs.stalls.value == 0
    for _ in range(400):
        if sink.done.is_set():
            break
        assert loop._pass()


def test_bench_pairs_counts_the_stall_lines_of_the_window():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_pairs", os.path.join(ROOT, "tools", "bench_pairs.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    def at(s, what):
        return f"2026-10-03 17:57:{s:06.3f}".replace(".", ",") + " " + what

    scrape = 'INFO aiohttp.access: 127.0.0.1 "GET /metrics HTTP/1.1" 200 6'
    stall = 'WARNING helix.stall: helix stall {"model":"m"}'
    log = [at(1, scrape), at(1.1, scrape), at(2, stall), at(9, scrape),
           at(20, stall), at(30.5, stall), at(54, scrape), at(54.1, scrape),
           at(54.6, stall)]
    assert mod.stall_lines(log) == (2, 4)
    assert mod.stall_lines(log[2:]) == (0, 4)       # too few scrapes to tell


def test_an_event_loop_that_stopped_without_cleanup_is_not_a_stall():
    """A test's server (or a dying process) stops its loop with the
    heartbeat's marker standing: nobody's tokens wait for that thread."""
    from helix_tpu.obs.metrics import EngineLoopObs

    watch = obs_flight.StallWatch()
    rec = FlightRecorder()
    w = obs_flight.Watched("w", lambda: rec, EngineLoopObs(),
                           lambda: {"threads": {}}, watch)
    aloop = asyncio.new_event_loop()
    watch.http_loop = aloop
    now = time.monotonic()
    watch.http.at = (40, 0, "helix.http.beat", now - 30.0)
    w.look(now)
    aloop.close()
    assert not w.during and not watch.http_running()
