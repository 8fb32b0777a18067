"""The measurement inside serve-node (ISSUE 25): engine-step phases,
request stages and step-program kinds as named spans on the profiler's
clock, each with the counter the benchmark reads.

(a) every ``benchmark/metrics/*.json`` that reads a histogram finds its
series in ``/metrics`` with only the ``model`` label; (b) every flight
record carries ``phases`` that add up to its ``wall_s`` and the phase
histograms count one observation a step; (c) the compiled step programs
have distinct ``step_fn...`` names and their lowered text carries the
named scopes; (d) a capture through ``POST /admin/profiler`` holds the
``helix.loop.*`` spans and both ``helix.clock`` stamps, with the Python
tracer off by default; (e) a streamed request's five stages are ordered
and sum to no more than the client's time to first token; (f)
``device_idle_ratio()`` cannot exceed 1; (g) the host's account (ISSUE
37): ``Phases.cpu`` beside wall, the four parts of admit and dispatch in
a sink of their own (``Engine.step_parts``), the three host threads' CPU
and the collector's pauses a step, each a histogram observed once a step
and a field of the flight record.
"""

import asyncio
import glob
import json
import os
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest
import requests

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import prom  # noqa: E402
from helix_tpu.engine import ragged as ragged_meta  # noqa: E402
from helix_tpu.engine.engine import (  # noqa: E402
    Engine, EngineConfig, Request, _build_ragged_step_fn,
)
from helix_tpu.engine.sampling import SamplingParams  # noqa: E402
from helix_tpu.models.common import ModelConfig  # noqa: E402
from helix_tpu.models.llama import init_params  # noqa: E402
from helix_tpu.serving.engine_loop import EngineLoop  # noqa: E402
from helix_tpu.serving.openai_api import OpenAIServer  # noqa: E402
from helix_tpu.serving.registry import ModelRegistry, ServedModel  # noqa: E402
from helix_tpu.serving.tokenizer import ByteTokenizer  # noqa: E402

PORT = 18471
MODEL = "tiny-chat"
LOOP_PHASES = (
    "helix.loop.admit", "helix.loop.prefill_sync", "helix.loop.dispatch",
    "helix.loop.fetch", "helix.loop.reconcile", "helix.loop.emit",
)
STAGES = ("http.pre_submit", "queue", "admit_to_token", "first_token_hold",
          "http.first_write")
SCOPES = ("prefill", "state", "tail", "sample", "attn.qkv", "attn.kernel",
          "attn.out", "mlp.gate_up", "mlp.down", "lm_head")


def histogram_specs():
    out = []
    for path in sorted(glob.glob(
            os.path.join(ROOT, "benchmark", "metrics", "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if spec["reduction"] == "histogram_mean_ms":
            out.append(spec)
    return out


def tiny_engine(vocab_size=512, **extra):
    cfg = ModelConfig.tiny(vocab_size=vocab_size, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(3))
    kw = dict(
        max_decode_batch=2, page_size=4, num_pages=256,
        max_pages_per_seq=32, max_prefill_len=16,
        attn_backend="reference", eos_token_ids=ByteTokenizer().eos_ids,
    )
    kw.update(extra)
    return Engine(cfg, params, EngineConfig(**kw))


def stream_chat(url, text, max_tokens=8):
    """One streamed chat request; returns (trace id, client seconds to
    the first data line, chunks)."""
    t0 = time.monotonic()
    r = requests.post(
        f"{url}/v1/chat/completions",
        json={"model": MODEL, "max_tokens": max_tokens, "temperature": 0,
              "messages": [{"role": "user", "content": text}],
              "stream": True},
        stream=True, timeout=120,
    )
    assert r.status_code == 200, r.text
    first, chunks = None, []
    for line in r.iter_lines():
        if not line:
            continue
        if first is None:
            first = time.monotonic() - t0
        payload = line[len(b"data: "):]
        if payload == b"[DONE]":
            break
        chunks.append(json.loads(payload))
    return r.headers["X-Helix-Trace-Id"], first, chunks


@pytest.fixture(scope="module")
def spine():
    """A tiny model behind the real HTTP surface, after a few requests
    (one prompt long enough to prefill in chunks)."""
    loop = EngineLoop(tiny_engine(), "tiny").start()
    registry = ModelRegistry()
    registry.register(ServedModel(
        name=MODEL, loop=loop, tokenizer=ByteTokenizer(),
        context_length=128,
    ))
    srv = OpenAIServer(registry)   # the process's trace store, as the loop's
    app = srv.build_app()
    started = threading.Event()
    holder = {}

    def run():
        from aiohttp import web

        aloop = asyncio.new_event_loop()
        asyncio.set_event_loop(aloop)
        runner = web.AppRunner(app)
        aloop.run_until_complete(runner.setup())
        aloop.run_until_complete(
            web.TCPSite(runner, "127.0.0.1", PORT).start())
        holder["loop"] = aloop
        started.set()
        aloop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    assert started.wait(10)
    url = f"http://127.0.0.1:{PORT}"
    # the first request compiles; the stages of the later ones are judged
    stream_chat(url, "warm the shapes " * 3)
    r = requests.post(
        f"{url}/v1/chat/completions",
        json={"model": MODEL, "max_tokens": 4, "temperature": 0,
              "messages": [{"role": "user", "content": "hi"}]},
        timeout=120,
    )
    assert r.status_code == 200, r.text
    holder.update(url=url, srv=srv,
                  streamed=[stream_chat(url, "hello there"),
                            stream_chat(url, "warm the shapes " * 3)])
    yield holder
    holder["loop"].call_soon_threadsafe(holder["loop"].stop)
    loop.stop(join=False)


# ---- (a) the benchmark's histogram series ---------------------------------


@pytest.mark.parametrize("spec", histogram_specs(), ids=lambda s: s["name"])
def test_histogram_metric_finds_its_series(spine, spec):
    text = requests.get(f"{spine['url']}/metrics", timeout=10).text
    series = spec["series"]
    parsed = prom.parse(text, MODEL)
    assert parsed.get(series + "_count", 0) > 0, series
    assert series + "_sum" in parsed
    lines = [ln for ln in text.splitlines()
             if re.match(rf"{series}_(count|sum)\b", ln)]
    assert len(lines) == 2
    for ln in lines:
        assert re.match(rf'{series}_\w+{{model="{MODEL}"}} ', ln), ln
    # the reduction itself, over a window that opens before any request
    zero = {k: 0.0 for k in parsed}
    assert prom.mean_of_histogram_ms(zero, parsed, series) >= 0.0


def test_metrics_count_the_joint_pass_programs(spine):
    """``helix_joint_pass_steps_total``: the programs whose prefill rows and
    decode rows shared one pass (every admission here launched one)."""
    text = requests.get(f"{spine['url']}/metrics", timeout=10).text
    parsed = prom.parse(text, MODEL)
    assert parsed["helix_joint_pass_steps_total"] >= 4


@pytest.fixture(scope="module")
def wave_beside_a_row(spine):
    """A request streams forty tokens; once its first is out two short
    completions arrive (a chat template alone is longer than this engine's
    prefill segment: a chat prompt is admitted in chunks, a bare completion
    in a wave).  The first takes the free slot, the second the slot the
    first leaves: each is admitted in a wave in which the streaming row
    decodes a token, the second behind a step in flight.  Returns the
    parsed ``/metrics`` before and after."""
    url = spine["url"]
    before = prom.parse(
        requests.get(f"{url}/metrics", timeout=10).text, MODEL)
    first = threading.Event()

    def long_one():
        r = requests.post(
            f"{url}/v1/chat/completions",
            json={"model": MODEL, "max_tokens": 40, "temperature": 0.9,
                  "seed": 3, "stream": True,
                  "messages": [{"role": "user", "content": "keep going"}]},
            stream=True, timeout=120,
        )
        for line in r.iter_lines():
            if line:
                first.set()

    def short_one(text):
        r = requests.post(
            f"{url}/v1/completions",
            json={"model": MODEL, "prompt": text, "max_tokens": 6,
                  "temperature": 0}, timeout=120)
        assert r.status_code == 200, r.text

    threads = [threading.Thread(target=long_one)]
    threads[0].start()
    assert first.wait(120)
    for text in ("beside it", "and behind"):
        threads.append(threading.Thread(target=short_one, args=(text,)))
        threads[-1].start()
    for t in threads:
        t.join(120)
    after = prom.parse(
        requests.get(f"{url}/metrics", timeout=10).text, MODEL)
    return before, after


def test_metrics_count_the_tokens_decoded_inside_waves(wave_beside_a_row):
    """``helix_wave_decode_tokens_total``: a wave's running rows decode a
    token in its pass; requests served one at a time never move it."""
    before, after = wave_beside_a_row
    assert before["helix_wave_decode_tokens_total"] == 0
    assert after["helix_wave_decode_tokens_total"] >= 1
    assert after["helix_wave_decode_tokens_total"] < (
        after["helix_decode_tokens_total"])


def test_debug_flight_says_how_many_rows_decoded_in_a_steps_waves(
        spine, wave_beside_a_row):
    body = requests.get(
        f"{spine['url']}/v1/debug/flight?model={MODEL}&recent=512",
        timeout=10).json()
    recs = body["models"][MODEL]["recent"]
    assert all("wave_rows" in rec for rec in recs)
    beside = [rec for rec in recs if rec["wave_rows"]]
    assert beside and all(
        rec["joint_pass"] and rec["admissions"]
        and rec["inert_rows"] < rec["joint_pass"] * rec["slots_total"]
        for rec in beside)
    # (a pass that only fills the pipeline leaves no record: the wave
    # that took the free slot is on the counter alone)
    _, after = wave_beside_a_row
    assert sum(rec["wave_rows"] for rec in recs) <= (
        after["helix_wave_decode_tokens_total"])


# ---- (b) flight phases and one observation a step -------------------------


@pytest.fixture(scope="module")
def stepped():
    """Some twenty steps of a tiny engine under a started loop: three
    requests, one with a prompt of several chunks, then one that ends on
    its first token (no decode step to carry that token: the one case
    left where the loop waits for a prefill alone,
    ``helix.loop.prefill_sync``)."""
    loop = EngineLoop(tiny_engine(), "phases").start()
    done = []
    try:
        for i, n in enumerate((6, 40, 9, 5)):
            if i == 3:
                assert all(ev.wait(120) for ev in done)
            ev = threading.Event()
            done.append(ev)

            def on_event(e, ev=ev):
                if e.finished:
                    ev.set()

            loop.submit(Request(
                id=f"p{i}", prompt_tokens=list(range(4, 4 + n)),
                sampling=SamplingParams(
                    max_tokens=1 if i == 3 else 10, temperature=0.0),
            ), on_event)
        for ev in done:
            assert ev.wait(120)
    finally:
        loop.stop(join=True)
    return loop


def test_every_flight_record_carries_phases_that_add_up(stepped):
    recs = stepped.flight.snapshot(recent=512)["recent"]
    assert len(recs) >= 15
    for rec in recs:
        ph = rec["phases"]
        assert set(ph) <= set(LOOP_PHASES) | {"helix.sched.reorder"}
        assert all(v >= 0.0 for v in ph.values())
        inside = sum(v for k, v in ph.items() if k.startswith("helix.loop."))
        assert abs(inside - rec["wall_s"]) <= max(
            0.05 * rec["wall_s"], 1e-3), rec
        assert "t_mono" in rec
    kinds = {k for rec in recs for k in rec["phases"]}
    assert set(LOOP_PHASES) <= kinds


def test_every_flight_record_carries_the_state_segments_query_block(stepped):
    """Plain decode: the state segment's rows are one-token query blocks
    (``ops/paged_kernel.py::query_block`` of the segment's width)."""
    recs = stepped.flight.snapshot(recent=512)["recent"]
    assert recs and {rec["attn_q_block"] for rec in recs} == {1}
    assert stepped.engine.attn_q_block == 1


def test_every_flight_record_carries_the_chunk_segments_query_block(stepped):
    """... and the prefill segment's block in the last launch that had one
    (the page kind's ``query_block``: the paged kernel's own, from the bucket,
    the group and the rows): 0 before any, 16 for a one-row chunk in the
    bucket of 16, 8 where two rows share it or the bucket is 8 or under."""
    recs = stepped.flight.snapshot(recent=512)["recent"]
    eng = stepped.engine
    cfg = eng.model_cfg
    assert {rec["chunk_q_block"] for rec in recs} <= {0, 8, 16}
    assert 16 in {rec["chunk_q_block"] for rec in recs}
    assert recs[-1]["chunk_q_block"] == eng.chunk_q_block
    assert [cfg.page_kind.query_block(cfg, rung, rows) for rung, rows in (
        (16, 1), (16, 2), (8, 1), (4, 2))] == [16, 8, 8, 8]


def test_the_paged_kernels_counters_follow_its_own_block(stepped):
    """``helix_attn_query_blocks_total`` and
    ``helix_attn_page_bytes_read_total`` count what the kernel's block
    function gives: a launch whose rows have history adds, over the
    attention layers, ``ceil(tokens / block)`` programs a row, and each
    walks the row's pages of history once."""
    import types

    import numpy as np

    from helix_tpu.engine.ragged import PrefillPlan
    from helix_tpu.models.mixers import history_pages

    eng = stepped.engine
    cfg = eng.model_cfg
    L, P = eng.model_cfg.num_attn_layers, eng.cache_cfg.page_size
    none = np.zeros(0, np.int64)
    for rung, rows, block in ((16, [(16, 32)], 16), (16, [(9, 5), (7, 8)], 8),
                              (8, [(5, 64)], 8)):
        plan = PrefillPlan(P, eng.cache_cfg.max_pages_per_seq, len(rows))
        plan.rows = [types.SimpleNamespace(rem=n, start=h) for n, h in rows]
        assert cfg.page_kind.query_block(cfg, rung, len(rows)) == block
        assert history_pages(plan.rows, block, none, 0, P) == sum(
            -(-h // P) * -(-n // block) for n, h in rows)
    # a wave of two rows in the bucket of 16: 2 + 1 blocks of 8, and the
    # one-row chunk of 16 over 32 tokens: 1 block over 8 pages
    c = eng.mixer_counts
    assert c["attn_query_blocks"] % L == 0 and c["attn_query_blocks"] > 0
    assert c["attn_page_bytes_read"] > 0


def test_flight_records_carry_the_joint_pass_and_its_inert_rows(stepped):
    """A step that launched a program with a prefill segment (a wave, a
    chunk, a mixed step) says so, and how many state rows rode that pass
    sitting out; a plain decode step says 0 and 0; the counter adds up."""
    recs = stepped.flight.snapshot(recent=512)["recent"]
    eng = stepped.engine
    joint = [rec for rec in recs if rec["joint_pass"]]
    assert joint and all(rec["prefill_tokens"] for rec in joint)
    assert any(rec["joint_pass"] == 0 and rec["inert_rows"] == 0
               and rec["kind"] == "decode" for rec in recs)
    # a wave into an engine with nothing running: every state row sits out
    assert any(rec["inert_rows"] >= len(eng.slots) and not rec["wave_rows"]
               for rec in joint)
    # a wave beside a running row: that row decodes a token in it
    beside = [rec for rec in joint if rec["wave_rows"]]
    assert beside and all(
        rec["inert_rows"] < rec["joint_pass"] * len(eng.slots)
        for rec in beside)
    assert 0 < sum(rec["wave_rows"] for rec in recs) <= (
        eng.num_wave_decode_tokens)
    assert all(rec["wave_rows"] == 0 for rec in recs
               if not rec["joint_pass"])
    # (the ring leaves out the loop's first step, which launched some)
    assert 0 < sum(rec["joint_pass"] for rec in recs) <= (
        eng.num_joint_pass_steps)


@pytest.mark.parametrize("name", LOOP_PHASES)
def test_phase_histogram_counts_one_observation_a_step(stepped, name):
    hists = dict(stepped.obs.step_phases,
                 **{"helix.loop.emit": stepped.obs.emit_seconds})
    assert stepped.steps >= 15
    assert hists[name].count == stepped.steps
    assert stepped.obs.step_seconds.count == stepped.steps


def test_phase_means_add_up_to_the_step_mean(stepped):
    hists = list(stepped.obs.step_phases.values()) + [
        stepped.obs.emit_seconds]
    total = sum(h.sum for h in hists)
    step = stepped.obs.step_seconds.sum
    assert abs(total - step) <= max(0.05 * step, 1e-3 * stepped.steps)


# ---- (c) step programs and operations have stable names -------------------


@pytest.fixture(scope="module")
def warmed():
    # a model of its own: the shape registry is per model, and the other
    # fixtures' traffic compiles shapes warmup() leaves out
    eng = tiny_engine(vocab_size=384, decode_steps_per_sync=4)
    eng.warmup()
    return eng


def test_compiled_step_programs_have_distinct_step_fn_names(warmed):
    eng = warmed
    shapes = ragged_meta.step_shape_set(eng._shape_key)
    names = [ragged_meta.step_program_name(*s[1:]) for s in shapes]
    assert len(set(names)) == len(names) == eng.compiled_step_shapes
    assert all(n.startswith("step_fn") for n in names)
    assert "step_fn_t0" in names
    assert "step_fn_t16_r1_h" in names     # the continuing chunk's program


def test_shape_gauge_reads_what_it_read(warmed):
    eng = warmed
    # the ladder's rungs with and without history at full row capacity,
    # the two single-row chunk shapes, the decode-only program
    assert eng.compiled_step_shapes == 2 * len(eng._token_ladder) + 3


def decode_step_args(eng):
    eng._sync_state()
    return (eng._graft_params(), eng.cache, eng._dstate, (),
            jnp.asarray(eng._zero_drafts), jnp.asarray(eng._zero_rows),
            jnp.int32(1), None)


def built(eng, rung, has_hist, rows):
    return _build_ragged_step_fn(
        eng.model_cfg, eng.cache_cfg.page_size, eng._backend, eng.mesh,
        rung, has_hist, rows, eng._spec_width(), eng._n_tail_max, 0, 0,
        0, 0,
    )


def test_jitted_step_carries_its_shapes_name(warmed):
    eng = warmed
    n = eng.compiled_step_shapes
    assert built(eng, 0, False, 0).__name__ == "step_fn_t0"
    assert built(eng, 16, True, 1).__name__ == "step_fn_t16_r1_h"
    assert eng.compiled_step_shapes == n    # both were compiled by warmup


@pytest.fixture(scope="module")
def lowered_text(warmed):
    """A step with a prefill segment, on the arguments warmup() builds."""
    import joint_pass

    fn, args = joint_pass.step_program(
        warmed, warmed._token_ladder[-1], warmed.cfg.max_decode_batch)
    return fn.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("scope", SCOPES)
def test_lowered_step_carries_the_named_scope(lowered_text, scope):
    assert "jit(step_fn_t16_r2)" in lowered_text
    assert re.search(rf"[/\"]{re.escape(scope)}[/\"]", lowered_text), scope


# ---- (d) a capture through POST /admin/profiler ---------------------------


@pytest.fixture(scope="module")
def capture(spine, tmp_path_factory):
    """A 0.5 s capture of the live server while a request streams."""
    seen = []
    real = jax.profiler.start_trace

    def spy(log_dir, *a, **kw):
        seen.append(kw.get("profiler_options"))
        return real(log_dir, *a, **kw)

    os.environ["HELIX_PROFILER_DIR"] = str(tmp_path_factory.mktemp("prof"))
    jax.profiler.start_trace = spy
    try:
        # traffic for as long as the capture runs, however long the
        # profiler takes to start on a loaded machine
        done = threading.Event()

        def traffic():
            while not done.is_set():
                stream_chat(spine["url"], "during the capture", max_tokens=12)
                time.sleep(0.06)    # a gap the loop spends in helix.loop.idle

        t = threading.Thread(target=traffic)
        t.start()
        try:
            r = requests.post(
                f"{spine['url']}/admin/profiler", json={"seconds": 0.5},
                timeout=300)
        finally:
            done.set()
            t.join(120)
    finally:
        jax.profiler.start_trace = real
        del os.environ["HELIX_PROFILER_DIR"]
    assert r.status_code == 200, r.text
    body = r.json()
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(body["log_dir"], "**", "*.xplane.pb"),
                     recursive=True)[-1]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("helix."):
                    events.setdefault(ev.name, []).append(dict(ev.stats))
    return body, seen, events


@pytest.mark.parametrize("span", (
    "helix.loop.step", "helix.loop.admit", "helix.loop.dispatch",
    "helix.loop.launch", "helix.loop.fetch", "helix.loop.reconcile",
    "helix.loop.emit", "helix.loop.idle",
))
def test_capture_holds_the_loops_spans(capture, span):
    _, _, events = capture
    assert events.get(span), sorted(events)


def test_capture_launch_span_names_the_program(capture):
    _, _, events = capture
    launches = events["helix.loop.launch"]
    assert {"kind", "token_bucket", "prefill_rows", "has_hist", "live_rows",
            "n_extra", "prefill_tokens", "padding_tokens"} <= set(launches[0])
    assert {ln["kind"] for ln in launches} <= {
        "admit", "chunk", "mixed", "spec", "decode"}
    assert any("step_num" in s for s in events["helix.loop.step"])


def test_capture_launch_span_says_whether_the_segments_shared_a_pass(capture):
    _, _, events = capture
    launches = events["helix.loop.launch"]
    assert all({"joint_pass", "inert_rows"} <= set(ln) for ln in launches)
    for ln in launches:
        assert ln["joint_pass"] == int(ln["token_bucket"] > 0)
        if not ln["joint_pass"]:
            assert ln["inert_rows"] == 0
    assert any(ln["joint_pass"] for ln in launches)


def test_capture_launch_span_carries_the_query_block(capture):
    _, _, events = capture
    assert {ln["attn_q_block"] for ln in events["helix.loop.launch"]} == {1}


def test_capture_launch_span_carries_the_chunk_segments_block(capture):
    """A launch with a prefill segment says its paged call's query block (a
    one-row chunk in the bucket of 16: 16; two rows, or a bucket of 8 or
    under: 8) and the programs the kernel ran for it; a decode launch has no
    such segment."""
    _, _, events = capture
    launches = events["helix.loop.launch"]
    with_rows = [ln for ln in launches if ln["prefill_rows"]]
    assert with_rows
    for ln in with_rows:
        alone = ln["prefill_rows"] == 1 and ln["token_bucket"] == 16
        assert ln["chunk_q_block"] == (16 if alone else 8), ln
        assert (ln["attn_query_blocks"] > 0) == bool(ln["has_hist"]), ln
    assert all("chunk_q_block" not in ln and ln["attn_query_blocks"] == 0
               for ln in launches if not ln["prefill_rows"])


def test_program_times_lists_the_launches_beside_the_programs(capture):
    """``tools/program_times.py`` answers with two keys: the device's
    programs by name, and the host's launches by kind (each span's
    ``live_rows`` / ``inert_rows``), which are not programs."""
    import subprocess

    body, _, events = capture
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "program_times.py"),
         body["log_dir"]],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    answer = json.loads(out.stdout)
    assert set(answer) == {"programs", "launches"}
    assert not any(name.startswith("helix.") for name in answer["programs"])
    want: dict = {}
    for ln in events["helix.loop.launch"]:
        want.setdefault(ln["kind"], []).append(
            (ln["live_rows"], ln["inert_rows"]))
    got = {kind: list(zip(rows["live_rows"], rows["inert_rows"]))
           for kind, rows in answer["launches"].items()}
    assert {k: sorted(v) for k, v in got.items()} == {
        k: sorted(v) for k, v in want.items()}


def test_capture_is_stamped_with_the_monotonic_clock(capture):
    body, _, events = capture
    stamps = [s["monotonic_ns"] for s in events["helix.clock"]]
    assert sorted(stamps) == body["clock_ns"] and len(stamps) == 2
    assert 0.4e9 <= stamps[1] - stamps[0] <= 5e9
    assert abs(time.monotonic_ns() - stamps[1]) < 600e9


def test_capture_default_turns_the_python_tracer_off(capture):
    body, seen, _ = capture
    assert body["python_tracer"] is False
    assert len(seen) == 1
    assert seen[0].python_tracer_level == 0
    assert seen[0].host_tracer_level > 0


def test_python_tracer_field_must_be_a_boolean(spine):
    r = requests.post(f"{spine['url']}/admin/profiler",
                      json={"seconds": 0.01, "python_tracer": "yes"},
                      timeout=30)
    assert r.status_code == 400


def test_a_capture_that_is_long_in_the_writing_streams_its_answer(
        spine, monkeypatch, tmp_path):
    """Past ``_PROFILER_QUIET_S`` the answer's headers go out and a space
    every few seconds keeps the connection from idling out (three seconds of
    a busy 40-layer server take two minutes to write); the body is the same
    JSON behind the spaces."""
    from helix_tpu.serving import openai_api

    monkeypatch.setattr(openai_api, "_PROFILER_QUIET_S", 0.05)
    monkeypatch.setenv("HELIX_PROFILER_DIR", str(tmp_path))
    r = requests.post(f"{spine['url']}/admin/profiler",
                      json={"seconds": 0.3}, timeout=300)
    assert r.status_code == 200, r.text
    assert r.text.startswith(" ") and r.headers["Content-Type"].startswith(
        "application/json")
    body = r.json()
    assert body["seconds"] == 0.3 and len(body["clock_ns"]) == 2
    assert glob.glob(os.path.join(body["log_dir"], "**", "*.xplane.pb"),
                     recursive=True)


# ---- (e) request stages ---------------------------------------------------


@pytest.mark.parametrize("which", (0, 1), ids=("short", "chunked"))
def test_streamed_request_stages_are_ordered_and_inside_the_clients_ttft(
        spine, which):
    tid, client_ttft_s, chunks = spine["streamed"][which]
    assert chunks and client_ttft_s is not None
    spans = {}
    for s in spine["srv"].traces.get(tid)["spans"]:
        spans.setdefault(s["name"], s)
    assert set(STAGES) <= set(spans), sorted(spans)
    end = None
    total_ms = 0.0
    for name in STAGES:
        s = spans[name]
        assert s["duration_ms"] >= 0.0, name
        if end is not None:
            # each stage starts where the one before it ended
            assert abs(s["start_unix"] - end) < 2e-3, name
        end = s["start_unix"] + s["duration_ms"] / 1e3
        total_ms += s["duration_ms"]
    assert total_ms <= client_ttft_s * 1e3


# ---- (f) the host-side idle estimate --------------------------------------


def ring(records):
    loop = EngineLoop(tiny_engine(), "idle")
    for wall, gap, at in records:
        loop.flight.record_step({
            "duration": wall, "generated_tokens": 1, "wall_s": wall,
            "idle_gap_s": gap, "t_mono": at, "ts": at,
        })
    return loop


@pytest.mark.parametrize("records,expect", (
    # sparse: a step every five seconds, each charged the gap before it
    ([(0.01, 5.0, 100.0), (0.01, 4.99, 105.0), (0.01, 4.99, 110.0)],
     (0.99, 1.0)),
    # dense: back-to-back 100 ms steps with 40 ms gaps
    ([(0.1, 0.04, 10.0 + 0.1 * i) for i in range(20)], (0.39, 0.41)),
    # one record whose gap reaches back before it started
    ([(0.02, 30.0, 50.0)], (0.0, 1.0)),
    ([], (0.0, 0.0)),
), ids=("sparse", "dense", "single", "empty"))
def test_device_idle_ratio_cannot_exceed_one(records, expect):
    ratio = ring(records).device_idle_ratio()
    assert expect[0] <= ratio <= expect[1] <= 1.0


# ---- (g) the host's account (ISSUE 37) ------------------------------------

import gc  # noqa: E402

from helix_tpu.obs import trace as obs_trace  # noqa: E402
from helix_tpu.obs.flight import FlightRecorder  # noqa: E402

PARTS = ("helix.loop.claim", "helix.loop.plan", "helix.loop.sync_state",
         "helix.loop.launch")
ACCOUNT_FIELDS = ("phases_cpu", "parts", "parts_cpu", "threads_cpu", "gc_s")


def burn(seconds):
    t = time.monotonic()
    while time.monotonic() - t < seconds:
        pass


def test_phases_cpu_of_a_busy_phase_is_its_wall():
    # a busy thread can still be descheduled on a loaded machine: the
    # best of a few tries is judged
    best = 0.0
    for _ in range(8):
        ph = obs_trace.Phases()
        with obs_trace.phase("busy", into=ph):
            burn(0.03)
        assert ph.cpu["busy"] <= ph["busy"] * 1.02
        best = max(best, ph.cpu["busy"] / ph["busy"])
        if best >= 0.8:
            break
    assert best >= 0.8


def test_phases_cpu_of_a_sleeping_phase_is_under_a_tenth_of_its_wall():
    ph = obs_trace.Phases()
    with obs_trace.phase("asleep", into=ph):
        time.sleep(0.05)
    assert ph["asleep"] >= 0.05
    assert ph.cpu["asleep"] < 0.1 * ph["asleep"]


def test_a_nested_phase_is_taken_out_of_wall_and_cpu_alike():
    ph = obs_trace.Phases()
    with obs_trace.phase("outer", into=ph) as outer:
        burn(0.02)
        with obs_trace.phase("inner", into=ph) as inner:
            time.sleep(0.04)
    assert abs(ph["outer"] + ph["inner"] - outer.seconds) < 1e-9
    assert abs(ph["outer"] - (outer.seconds - inner.seconds)) < 1e-9
    # the sleep is the inner phase's, on both clocks
    assert ph.cpu["inner"] < 0.1 * ph["inner"]
    assert ph.cpu["outer"] <= ph["outer"] * 1.02
    assert ph.cpu["outer"] + ph.cpu["inner"] <= outer.seconds
    # a phase with no sink reads no clock of the thread's
    assert set(ph) == set(ph.cpu) == {"outer", "inner"}
    ph.clear()
    assert not ph and not ph.cpu


class _Sink:
    def __init__(self):
        self.tokens, self.done = [], threading.Event()

    def __call__(self, e):
        if e.token_id >= 0:
            self.tokens.append(e.token_id)
        if e.finished:
            self.done.set()


def manual_loop(eng=None, **extra):
    """A loop that was never started: ``_pass()`` runs one pass on the
    test's thread and tokens are delivered inline."""
    return EngineLoop(eng if eng is not None else tiny_engine(**extra),
                      "account")


def submit(loop, rid, n_prompt, max_tokens=6):
    sink = _Sink()
    loop.submit(Request(
        id=rid, prompt_tokens=list(range(4, 4 + n_prompt)),
        sampling=SamplingParams(max_tokens=max_tokens, temperature=0.0),
    ), sink)
    return sink


def run_passes(loop, sinks, each=None, passes=400):
    for _ in range(passes):
        if all(s.done.is_set() for s in sinks):
            return
        assert loop._pass()
        if each is not None:
            each()
    raise AssertionError("requests never finished")


def account_hists(obs):
    return [*obs.step_parts.values(), obs.host_build_cpu,
            *obs.threads_cpu.values(), obs.gc_seconds]


@pytest.fixture(scope="module")
def accounted():
    """Three requests over two slots, one with a prompt of three chunks,
    pass by pass: every kind of step, some left in flight."""
    loop = manual_loop()
    sinks = [submit(loop, "a0", 6, 12), submit(loop, "a1", 40, 8),
             submit(loop, "a2", 9, 8)]
    counts = []

    def each():
        counts.append([h.count for h in account_hists(loop.obs)]
                      + [loop.obs.step_seconds.count])

    run_passes(loop, sinks, each)
    return loop, counts


def test_each_new_histogram_gains_one_observation_a_step(accounted):
    loop, counts = accounted
    assert len(account_hists(loop.obs)) == 9
    assert counts[-1][-1] >= 10
    for row in counts:
        assert set(row) == {row[-1]}, row
    names = {h.name for h in account_hists(loop.obs)}
    assert names == {
        "helix_step_claim_seconds", "helix_step_plan_seconds",
        "helix_step_sync_state_seconds", "helix_step_launch_seconds",
        "helix_step_host_build_cpu_seconds",
        "helix_step_engine_cpu_seconds", "helix_step_emit_cpu_seconds",
        "helix_step_http_cpu_seconds", "helix_step_gc_seconds"}


def test_the_parts_lie_inside_admit_and_dispatch(accounted):
    loop, _ = accounted
    recs = [r for r in loop.flight.snapshot(recent=512)["recent"]
            if "parts" in r]
    assert len(recs) >= 8
    seen = set()
    for rec in recs:
        ph, parts = rec["phases"], rec["parts"]
        assert set(parts) <= set(PARTS) and not set(ph) & set(PARTS)
        seen |= set(parts)
        parents = (ph.get("helix.loop.admit", 0.0)
                   + ph.get("helix.loop.dispatch", 0.0))
        assert sum(parts.values()) <= parents + 1e-5, rec
        for name, sec in parts.items():
            # (a boundary may share a reading up to 20 us old)
            assert -2e-5 <= rec["parts_cpu"][name] <= sec * 1.02 + 4e-5, rec
        for name, sec in ph.items():
            assert -2e-5 <= rec["phases_cpu"][name] <= sec * 1.02 + 4e-5, rec
    assert seen == set(PARTS)
    obs = loop.obs
    assert sum(h.sum for h in obs.step_parts.values()) <= (
        obs.step_phases["helix.loop.admit"].sum
        + obs.step_phases["helix.loop.dispatch"].sum)
    assert obs.host_build_cpu.sum <= obs.host_build.sum * 1.02


def structure(loop):
    return [(r["kind"], sorted(r["phases"]), r["prefill_tokens"],
             r["decode_tokens"])
            for r in loop.flight.snapshot(recent=512)["recent"]]


def test_the_parents_read_what_they_read_with_the_parts_discarded(accounted):
    """The same traffic on an engine whose parts go to no sink: the same
    steps with the same phases, and in both the parents still cover the
    whole of the host's build (a part written to ``step_phases`` would be
    taken out of them)."""
    loop, _ = accounted
    eng = tiny_engine()
    eng._part = lambda name, **attrs: obs_trace.phase(name, **attrs)
    bare = manual_loop(eng)
    run_passes(bare, [submit(bare, "a0", 6, 12), submit(bare, "a1", 40, 8),
                      submit(bare, "a2", 9, 8)])
    assert structure(bare) == structure(loop)
    for name in ("helix.loop.admit", "helix.loop.dispatch"):
        assert (bare.obs.step_phases[name].count
                == loop.obs.step_phases[name].count)
    assert all(h.sum == 0.0 for h in bare.obs.step_parts.values())
    assert all(h.sum > 0.0 for h in loop.obs.step_parts.values())
    for lp in (loop, bare):
        # (the median step: on a loaded machine a thread is descheduled
        # between two phases now and then)
        shares = sorted(
            sum(rec["phases"].get(k, 0.0) for k in (
                "helix.loop.admit", "helix.loop.prefill_sync",
                "helix.loop.dispatch")) / rec["host_build_s"]
            for rec in lp.flight.snapshot(recent=512)["recent"]
            if rec.get("host_build_s"))
        assert 0.9 <= shares[len(shares) // 2] <= 1.0, shares


def test_every_launch_is_under_the_parts_once():
    """A wave's program and the step program behind it: two launches; a
    mixed step: one; a wave admitted while a prompt is chunking: the
    wave's and the mixed step's.  ``num_device_calls`` counts the same."""
    eng = tiny_engine(max_decode_batch=3)
    loop = manual_loop(eng)
    kinds, per_pass = [], []
    orig = eng._part

    def part(name, **attrs):
        if name == "helix.loop.launch":
            kinds.append(attrs["kind"])
        return orig(name, **attrs)

    eng._part = part

    def one_pass():
        calls, n = eng.num_device_calls, len(kinds)
        assert loop._pass()
        assert eng.num_device_calls - calls == len(kinds) - n
        launched = eng.step_parts.get("helix.loop.launch", 0.0)
        assert (launched > 0.0) == (len(kinds) > n)
        per_pass.append(tuple(kinds[n:]))

    sinks = [submit(loop, "w0", 6, 30)]
    one_pass()
    sinks.append(submit(loop, "w1", 40, 4))      # three chunks
    one_pass()
    sinks.append(submit(loop, "w2", 7, 4))       # a wave beside a chunk
    one_pass()
    for _ in range(200):
        if all(s.done.is_set() for s in sinks):
            break
        one_pass()
    assert per_pass[0] == ("admit", "decode")
    assert per_pass[1] == ("mixed",)
    assert per_pass[2] == ("admit", "mixed")
    assert ("decode",) in per_pass


def test_a_forced_collection_lands_in_that_steps_gc_s():
    loop = manual_loop()
    sink = submit(loop, "g0", 6, 12)
    gc.callbacks.append(loop._gc_hook)      # what start() does
    try:
        for _ in range(3):
            assert loop._pass()
        before = loop.obs.gc_seconds.sum
        gc.collect()
        assert loop._pass()
        rec = loop.flight.snapshot(recent=1)["recent"][-1]
        assert rec["gc_s"] > 0.0
        assert loop.obs.gc_seconds.sum - before >= rec["gc_s"] - 1e-6
        assert loop._gc_span is None
        run_passes(loop, [sink])
    finally:
        loop._unhook_gc()
    assert loop._gc_hook not in gc.callbacks


def test_start_installs_the_collector_hook_and_stop_removes_it():
    before = list(gc.callbacks)
    loop = manual_loop().start()
    try:
        assert loop._gc_hook in gc.callbacks
        assert len(gc.callbacks) == len(before) + 1
    finally:
        loop.stop(join=True)
    assert gc.callbacks == before
    assert not loop._thread.is_alive()


@pytest.mark.parametrize("which,work,shows", (
    ("emit", "burn", True), ("emit", "sleep", False), ("http", "burn", True),
))
def test_threads_cpu_reads_the_other_threads_clocks(which, work, shows):
    loop = manual_loop()
    ready, go, done, leave = (threading.Event() for _ in range(4))

    def worker():
        if which == "http":
            sink = submit(loop, "t0", 6, 2)     # submit learns the thread
            assert not sink.done.is_set()
        else:
            loop._emit_stage.cpu_clock = time.pthread_getcpuclockid(
                threading.get_ident())
        ready.set()
        go.wait(30)
        if work == "burn":      # 50 ms of CPU, however long that takes
            c0 = time.thread_time()
            while time.thread_time() - c0 < 0.05:
                pass
        else:
            time.sleep(0.05)
        done.set()
        leave.wait(30)

    t = threading.Thread(target=worker)
    t.start()
    try:
        assert ready.wait(30)
        first = loop._threads_cpu()
        assert first[which] == 0.0      # first seen now
        go.set()
        assert done.wait(30)
        cpu = loop._threads_cpu()
    finally:
        leave.set()
        t.join(30)
    assert not t.is_alive()
    assert set(cpu) == {"engine", "emit", "http"}
    if shows:
        assert 0.04 <= cpu[which] < 0.2
    else:
        assert cpu[which] < 0.01
    other = "http" if which == "emit" else "emit"
    assert cpu[other] == 0.0
    # a thread that has left reads 0 or its last CPU; it never raises
    assert loop._threads_cpu()[which] >= 0.0


def test_flight_records_carry_the_account_and_a_frozen_tail_keeps_it():
    eng = tiny_engine()
    loop = manual_loop(eng)
    loop.flight = FlightRecorder(min_samples=4)
    sink = submit(loop, "f0", 6, 40)
    for _ in range(3):
        assert loop._pass()              # the compiles
    loop.flight.reset_baseline()
    for _ in range(8):
        assert loop._pass()
    orig = eng.step_dispatch

    def slow():
        time.sleep(1.5)
        return orig()

    eng.step_dispatch = slow
    assert loop._pass()
    eng.step_dispatch = orig
    snap = loop.flight.snapshot(recent=64)
    frozen = [a for a in snap["anomalies"] if a["reason"] == "slow_step"]
    assert frozen, [a["reason"] for a in snap["anomalies"]]
    tail = frozen[-1]
    assert len(tail["steps"]) >= 9
    for rec in (*snap["recent"], tail["record"], *tail["steps"]):
        assert set(ACCOUNT_FIELDS) <= set(rec), sorted(rec)
        assert set(rec["threads_cpu"]) == {"engine", "emit", "http"}
        assert set(rec["phases_cpu"]) == set(rec["phases"])
        assert set(rec["parts_cpu"]) == set(rec["parts"])
    # the sleep is wall the thread did not run
    slow_rec = tail["record"]
    ph = slow_rec["phases"]["helix.loop.admit"] + slow_rec["phases"][
        "helix.loop.dispatch"]
    # the parents time the engine's own halves, not what ran around them
    assert slow_rec["host_build_s"] - ph >= 1.4
    run_passes(loop, [sink])
