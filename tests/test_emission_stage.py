"""Token delivery off the engine thread in every started loop (ISSUE 26).

A started ``EngineLoop`` —
hands each step's tokens to the emission worker and never runs
``_deliver`` between a completion and the next launch.  (a) the stage
alone: the worker delivers only while the engine thread is parked, a
slice at a time; a full queue blocks ``push`` and is counted; a raising
subscriber does not kill the worker; ``stop()`` delivers what is queued.
(b) ordering on a loop whose stage runs: events arrive in order across
batches, and no terminal event (evict, shed, drain deadline, abort)
overtakes that request's queued tokens.  (c) a started loop end to end:
streams bit-identical to the un-started (inline) path, greedy and
seeded; ``helix_step_emit_seconds`` observed once a step, by the engine
thread only; the six phase means add up to the step's; tokens reach the
client while the request still runs.  (d) every series a
``benchmark/metrics/loop.*.json`` names is in a started loop's
``/metrics``.
"""

import glob
import json
import os
import sys
import threading
import time

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import prom  # noqa: E402
from helix_tpu.engine.engine import Engine, EngineConfig, Request  # noqa: E402
from helix_tpu.engine.sampling import SamplingParams  # noqa: E402
from helix_tpu.models.common import ModelConfig  # noqa: E402
from helix_tpu.models.llama import init_params  # noqa: E402
from helix_tpu.obs import EngineLoopObs  # noqa: E402
from helix_tpu.serving.engine_loop import (  # noqa: E402
    EngineLoop, _EmissionStage,
)

MODEL = "tiny-emit"


@pytest.fixture(scope="module")
def tiny_parts():
    cfg = ModelConfig.tiny(vocab_size=512, dtype="float32")
    return cfg, init_params(cfg, jax.random.PRNGKey(3))


def make_engine(tiny_parts, **extra):
    cfg, params = tiny_parts
    kw = dict(
        max_decode_batch=4, page_size=4, num_pages=128,
        max_pages_per_seq=32, max_prefill_len=8,
        attn_backend="reference",
    )
    kw.update(extra)
    return Engine(cfg, params, EngineConfig(**kw))


def request(rid, prompt, max_tokens=16, temperature=0.0, seed=None):
    return Request(
        id=rid, prompt_tokens=list(prompt),
        sampling=SamplingParams(
            max_tokens=max_tokens, temperature=temperature, seed=seed,
        ),
        stop_token_ids=(),
    )


class Collector:
    """A subscriber: every event, and the threads that brought them."""

    def __init__(self, delay=0.0):
        self.events, self.threads = [], set()
        self.delay = delay
        self.done = threading.Event()

    def __call__(self, ev):
        if self.delay:
            time.sleep(self.delay)
        self.events.append(ev)
        self.threads.add(threading.get_ident())
        if ev.finished:
            self.done.set()

    @property
    def tokens(self):
        return [e.token_id for e in self.events if e.token_id >= 0]


def wait_for(cond, timeout=10.0):
    end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < end, "timed out"
        time.sleep(0.002)


# ---- (a) the stage alone ---------------------------------------------------


@pytest.fixture
def stage():
    got = []
    st = _EmissionStage(got.extend, EngineLoopObs())
    st.HOLD_MAX = 30.0    # a park, or nothing: no timeout in these tests
    st.got = got
    st.start("test")
    yield st
    st.stop()


def test_worker_delivers_nothing_until_the_engine_thread_parks(stage):
    stage.push(list(range(5)))
    time.sleep(0.1)
    assert stage.got == []
    with stage.parked:
        wait_for(lambda: stage.got == list(range(5)))
    assert stage._obs.emit_queue_wait.sum >= 0.1
    assert stage._obs.emit_queue_wait.count == 1
    assert stage._obs.emit_deliver.count == 1


def test_worker_stops_at_a_slice_when_the_park_ends():
    """The engine thread leaving its park mid-batch (here: the sink
    clears the flag, as the engine thread would on its way out of a
    device wait) holds the rest of the batch for the next park."""
    got = []
    st = _EmissionStage(None, EngineLoopObs())
    st.HOLD_MAX = 30.0

    def sink(events):
        got.extend(events)
        st.parked.clear()

    st._sink = sink
    st.start("slice")
    try:
        n = 2 * st.SLICE + 3
        st.parked.set()
        st.push(list(range(n)))
        wait_for(lambda: len(got) == st.SLICE)
        time.sleep(0.05)
        assert len(got) == st.SLICE
        st.parked.set()
        wait_for(lambda: len(got) == 2 * st.SLICE)
        st.parked.set()
        wait_for(lambda: len(got) == n)
        assert got == list(range(n))
        assert st._obs.emit_deliver.count == 1    # one observation a batch
    finally:
        st.stop()


def test_an_engine_thread_that_never_parks_delays_tokens_by_hold_max_only():
    got = []
    st = _EmissionStage(got.extend, EngineLoopObs())
    st.HOLD_MAX = 0.02
    st.start("hold")
    try:
        t0 = time.monotonic()
        st.push(list(range(3 * st.SLICE)))
        wait_for(lambda: len(got) == 3 * st.SLICE)
        assert 0.05 <= time.monotonic() - t0 < 2.0
    finally:
        st.stop()


def test_full_queue_blocks_push_and_counts_backpressure():
    release = threading.Event()
    got = []

    def sink(events):
        release.wait(10)
        got.extend(events)

    obs = EngineLoopObs()
    st = _EmissionStage(sink, obs, depth=2)
    st.start("full")
    try:
        with st.parked:
            st.push([0])              # the worker takes it and blocks
            wait_for(lambda: st.depth() == 0)
            st.push([1])
            st.push([2])              # the queue is full now
        assert obs.emit_backpressure.value == 0
        pushed = threading.Event()

        def push():
            st.push([3])
            pushed.set()

        threading.Thread(target=push, daemon=True).start()
        assert not pushed.wait(0.2), "push did not block on a full queue"
        assert obs.emit_backpressure.value == 1
        # a blocked push is a park: the worker drains without any other
        release.set()
        assert pushed.wait(10)
        st.flush()
        assert got == [0, 1, 2, 3]
        assert obs.emit_backpressure.value == 1
    finally:
        release.set()
        st.stop()


def test_a_sink_that_raises_does_not_kill_the_worker(stage):
    calls = []

    def sink(events):
        calls.append(list(events))
        if len(calls) == 1:
            raise RuntimeError("subscriber bug")

    stage._sink = sink
    stage.push([1])
    stage.push([2])
    stage.flush()
    assert calls == [[1], [2]]
    assert stage._thread.is_alive()


def test_stop_delivers_what_is_queued():
    got = []
    st = _EmissionStage(got.extend, EngineLoopObs())
    st.HOLD_MAX = 30.0
    st.start("stop")
    for i in range(5):
        st.push([i, i + 10])
    assert got == []                   # never parked
    st.stop()
    assert got == [0, 10, 1, 11, 2, 12, 3, 13, 4, 14]
    assert not st.started and not st._thread.is_alive()


def test_push_is_a_direct_call_in_a_stage_never_started():
    got = []
    st = _EmissionStage(got.extend, EngineLoopObs())
    st.push([1, 2])
    assert got == [1, 2] and st.batches == 0
    st.flush()
    st.stop()


# ---- (b) ordering and the terminal events ----------------------------------


def staged_loop(tiny_parts, **loop_kw):
    """A loop whose emission worker runs and whose engine thread is this
    test: every engine-thread method is called from here."""
    loop = EngineLoop(make_engine(tiny_parts), name="staged", **loop_kw)
    loop._emit_stage.HOLD_MAX = 30.0
    loop._emit_stage.start("staged")
    return loop


def push_tokens(loop, req, tokens):
    for t in tokens:
        loop._emit_stage.push(loop._snapshot_events([(req, t)]))


TERMINALS = {
    "evict": lambda loop, req: loop._evict(req, "poisoned"),
    "shed": lambda loop, req: loop._shed_kv_exhausted(req, 9.0),
    "drain_deadline": lambda loop, req: loop._fail_all("drain deadline"),
    "step_failure": lambda loop, req: (
        loop._handle_step_failure(RuntimeError("x"), 0.0, loop._flight_pre()),
        loop._evict(req, "after a failed step"),
    ),
}


@pytest.mark.parametrize("kind", sorted(TERMINALS))
def test_terminal_event_never_overtakes_queued_tokens(tiny_parts, kind):
    loop = staged_loop(tiny_parts)
    try:
        req = request("t-" + kind, range(4, 10))
        col = Collector(delay=0.002)    # a slow subscriber: the queue fills
        loop.engine.add_request(req)
        loop._subscribers[req.id] = col
        loop._admit_order.append(req.id)
        push_tokens(loop, req, range(100, 106))
        assert col.events == []         # the engine thread has not parked
        TERMINALS[kind](loop, req)
        assert [e.token_id for e in col.events] == list(range(100, 106)) + [-1]
        assert col.events[-1].finished and col.events[-1].error
        assert not any(e.finished for e in col.events[:-1])
        assert req.id not in loop._subscribers
        assert req.id not in loop._first_emit
        assert req.id not in loop._last_emit
    finally:
        loop._emit_stage.stop()


def test_abort_waits_for_the_queued_tokens_then_forgets(tiny_parts):
    loop = staged_loop(tiny_parts)
    try:
        req = request("t-abort", range(4, 10))
        col = Collector(delay=0.002)
        loop.engine.add_request(req)
        loop._subscribers[req.id] = col
        push_tokens(loop, req, range(100, 106))
        loop.abort(req.id)
        loop._drain_inbox()
        assert col.tokens == list(range(100, 106))
        assert req.id not in loop._subscribers
        assert req.id not in loop._first_emit
        assert req.id not in loop._last_emit
        # a later batch of the aborted request reaches nobody
        push_tokens(loop, req, [107])
        loop._emit_stage.flush()
        assert col.tokens == list(range(100, 106))
    finally:
        loop._emit_stage.stop()


def test_finish_pops_the_subscriber_on_the_worker(tiny_parts):
    from helix_tpu.engine.engine import FinishReason

    loop = staged_loop(tiny_parts)
    try:
        req = request("t-fin", range(4, 10))
        col = Collector()
        loop._subscribers[req.id] = col
        push_tokens(loop, req, [100, 101])
        req.finished, req.finish_reason = True, FinishReason.LENGTH
        push_tokens(loop, req, [102])
        loop._emit_stage.flush()
        assert [(e.token_id, e.finished) for e in col.events] == [
            (100, False), (101, False), (102, True)]
        assert col.events[-1].finish_reason == "length"
        assert loop._subscribers == {} and loop._first_emit == {}
        assert col.threads == {loop._emit_stage._thread.ident}
    finally:
        loop._emit_stage.stop()


# ---- (c) a started synchronous loop, end to end ----------------------------


def workload(kind):
    if kind == "greedy":
        return [request(f"g{i}", range(4 + i, 12 + 3 * i), max_tokens=24)
                for i in range(5)]
    return [request(f"s{i}", range(5 + i, 14 + 2 * i), max_tokens=20,
                    temperature=0.9, seed=1000 + i) for i in range(5)]


def run_started(tiny_parts, reqs, delay=0.0, **engine_extra):
    loop = EngineLoop(make_engine(tiny_parts, **engine_extra), name="started")
    emit_threads = []
    observe = loop.obs.emit_seconds.observe

    def spy(v):
        emit_threads.append(threading.get_ident())
        observe(v)

    loop.obs.emit_seconds.observe = spy
    # all in the inbox before the engine thread starts: one admission
    # wave, as in run_inline, whatever the machine's load
    cols = {}
    for req in reqs:
        cols[req.id] = col = Collector(delay)
        loop.submit(req, col)
    loop.start()
    try:
        for rid, col in cols.items():
            assert col.done.wait(120), f"{rid} stuck"
    finally:
        engine_thread = loop._thread.ident
        worker = loop._emit_stage._thread.ident
        loop.stop(join=True)
    return loop, cols, emit_threads, engine_thread, worker


def run_inline(tiny_parts, reqs, **engine_extra):
    """The un-started loop: the caller steps the engine and ``_emit``
    delivers on the caller's thread."""
    loop = EngineLoop(make_engine(tiny_parts, **engine_extra), name="inline")
    cols = {}
    for req in reqs:
        cols[req.id] = loop._subscribers[req.id] = Collector()
        loop.engine.add_request(req)
    while loop.engine.has_work():
        loop._emit(loop.engine.step())
    assert not loop._emit_stage.started
    return loop, cols


@pytest.fixture(scope="module")
def started_greedy(tiny_parts):
    return run_started(tiny_parts, workload("greedy"))


def streams(cols):
    return {rid: [(e.token_id, e.finished, e.finish_reason)
                  for e in col.events] for rid, col in cols.items()}


@pytest.mark.parametrize("kind", ["greedy", "seeded"])
def test_streams_bit_identical_to_the_inline_path(tiny_parts, kind):
    """Each path delivers exactly what its engine recorded; and the two
    paths agree.  On a loaded machine the CPU backend's engine does not
    always repeat itself, loop or no loop (PERF.md open question 15:
    ``Engine.step`` alone diverges from its own earlier run), so the
    comparison across engines gets three attempts; the comparison with
    the engine's own record gets one."""
    for attempt in range(3):
        reqs_i, reqs_s = workload(kind), workload(kind)
        _, want = run_inline(tiny_parts, reqs_i)
        loop, got, *_ = run_started(tiny_parts, reqs_s)
        for reqs, cols in ((reqs_i, want), (reqs_s, got)):
            for req in reqs:
                assert cols[req.id].tokens == req.output_tokens, req.id
        if streams(got) == streams(want):
            return
    assert streams(got) == streams(want)


def test_events_arrive_in_order_across_batches(tiny_parts):
    """A slow subscriber keeps several batches queued: each request
    still sees its tokens in the engine's order, one terminal event,
    last."""
    reqs = workload("greedy")
    loop, cols, *_ = run_started(
        tiny_parts, reqs, delay=0.001, decode_steps_per_sync=2)
    assert loop._emit_stage.batches > 10
    for req in reqs:
        col = cols[req.id]
        assert col.tokens == req.output_tokens
        assert [e.finished for e in col.events] == \
            [False] * (len(col.events) - 1) + [True]


def test_delivery_runs_on_the_worker_only(started_greedy):
    loop, cols, _, engine_thread, worker = started_greedy
    assert worker != engine_thread
    for col in cols.values():
        assert col.threads == {worker}
    assert loop.obs.emit_deliver.count == loop._emit_stage.batches > 0
    assert loop.obs.emit_queue_wait.count == loop._emit_stage.batches
    assert loop.obs.emit_backpressure.value == 0
    assert loop._first_emit == {} and loop._last_emit == {}
    assert loop._subscribers == {}


def test_step_emit_seconds_once_a_step_by_the_engine_thread(started_greedy):
    loop, _, emit_threads, engine_thread, _ = started_greedy
    assert loop.steps >= 10
    assert len(emit_threads) == loop.steps
    assert set(emit_threads) == {engine_thread}
    assert loop.obs.emit_seconds.count == loop.obs.step_seconds.count


def test_six_phase_means_add_up_to_the_step_mean(started_greedy):
    loop = started_greedy[0]
    hists = list(loop.obs.step_phases.values()) + [loop.obs.emit_seconds]
    assert len(hists) == 6
    assert all(h.count == loop.steps for h in hists)
    total = sum(h.sum for h in hists)
    step = loop.obs.step_seconds.sum
    assert abs(total - step) <= max(0.05 * step, 1e-3 * loop.steps)


def test_tokens_reach_the_client_while_the_request_runs(tiny_parts):
    """With no timeout to fall back on, delivery needs the engine's
    device waits to park the engine thread (``Engine.device_wait``): the
    idle wait alone would hold every token until the request is over."""
    loop = EngineLoop(make_engine(tiny_parts), name="parks")
    loop._emit_stage.HOLD_MAX = 30.0
    loop.start()
    try:
        req = request("long", range(4, 12), max_tokens=48)
        running_at_delivery = []
        col = Collector()

        def on_event(ev):
            running_at_delivery.append(not req.finished)
            col(ev)

        loop.submit(req, on_event)
        assert col.done.wait(120)
        assert col.tokens == req.output_tokens and len(col.tokens) == 48
        assert sum(running_at_delivery) >= 24
    finally:
        loop.stop(join=True)


def test_drain_deadline_error_comes_after_every_token(tiny_parts):
    loop = EngineLoop(make_engine(tiny_parts), name="drain").start()
    req = request("drained", range(4, 12), max_tokens=100)
    col = Collector(delay=0.002)
    loop.submit(req, col)
    wait_for(lambda: len(col.events) >= 3, timeout=120)
    loop.stop(drain=0.05)
    assert col.done.wait(30)
    assert "drain deadline" in (col.events[-1].error or "")
    assert col.tokens == req.output_tokens[:len(col.tokens)]
    assert len(col.tokens) == len(req.output_tokens) == len(col.events) - 1


def test_a_subscriber_that_raises_loses_only_its_slice(tiny_parts):
    """The worker outlives a subscriber bug, and the other requests'
    streams go on."""
    loop = EngineLoop(make_engine(tiny_parts), name="raises").start()
    try:
        def bad(ev):
            raise RuntimeError("subscriber bug")

        loop.submit(request("bad", range(4, 10), max_tokens=6), bad)
        good = request("good", range(5, 12), max_tokens=30)
        col = Collector()
        loop.submit(good, col)
        assert col.done.wait(120)
        assert loop._emit_stage._thread.is_alive()
        later = Collector()
        loop.submit(request("later", range(6, 12), max_tokens=5), later)
        assert later.done.wait(120)
        assert len(later.tokens) == 5
    finally:
        loop.stop(join=True)


# ---- (d) the benchmark's loop.* series -------------------------------------


def loop_metric_specs():
    out = []
    for path in sorted(glob.glob(
            os.path.join(ROOT, "benchmark", "metrics", "loop.*.json"))):
        with open(path) as f:
            out.append(json.load(f))
    return out


@pytest.fixture(scope="module")
def scraped(tiny_parts):
    from helix_tpu.serving.openai_api import OpenAIServer
    from helix_tpu.serving.registry import ModelRegistry, ServedModel
    from helix_tpu.serving.tokenizer import ByteTokenizer

    loop = EngineLoop(make_engine(tiny_parts), name=MODEL).start()
    try:
        registry = ModelRegistry()
        registry.register(ServedModel(
            name=MODEL, loop=loop, tokenizer=ByteTokenizer(),
            context_length=128,
        ))
        srv = OpenAIServer(registry)
        col = Collector()
        loop.submit(request("m0", range(4, 14), max_tokens=8), col)
        assert col.done.wait(120)
        return srv.obs.render()
    finally:
        loop.stop(join=True)


@pytest.mark.parametrize("spec", loop_metric_specs(), ids=lambda s: s["name"])
def test_loop_metric_series_is_exported_by_a_started_loop(scraped, spec):
    assert spec["source_kind"] == "metrics_delta"
    parsed = prom.parse(scraped, MODEL)
    series = spec["series"]
    assert parsed.get(series + "_count", 0) > 0, series
    zero = {k: 0.0 for k in parsed}
    assert prom.mean_of_histogram_ms(zero, parsed, series) >= 0.0


def test_emission_series_in_metrics(scraped):
    parsed = prom.parse(scraped, MODEL)
    assert parsed["helix_emit_backpressure_total"] == 0
    assert parsed["helix_emit_deliver_seconds_count"] > 0
    assert parsed["helix_emit_queue_wait_seconds_count"] == \
        parsed["helix_emit_deliver_seconds_count"]
    assert f'helix_emit_backpressure_total{{model="{MODEL}"}} 0' in scraped
