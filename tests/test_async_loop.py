"""The engine loop runs one step ahead while admission is blocked
(ISSUE 13, finished by ISSUE 33).

The acceptance bar: while a step is on the device the loop admits what
can be admitted, launches that prefill behind the running step, builds
and launches the next step behind both, and only then fetches and
reconciles the running one — across finishes, admission waves and mixed
steps — and every request's tokens are BIT-IDENTICAL to what
``Engine.step()`` (dispatch then complete, nothing in flight) produces:
greedy and seeded temp>0, plain decode, chunked prefill, the mixed
step, prefix-cache hits, a queue that is never empty, ``max_tokens``
finishes inside a fused window, stop tokens found one step late, and a
model with recurrent (conv) state.  The gate is what the loop observes
(queue and slots), not a switch; the conditions that reconcile first
are each held to that.  The chaos lanes re-run the PR 2
step-failure/quarantine and PR 6 preempt-by-swap scenarios: a poisoned
in-flight dispatch must quarantine correctly, not wedge the loop, and a
drain must still export survivors.
"""

import threading
import time

import jax
import numpy as np
import pytest

from helix_tpu.testing import faults


@pytest.fixture(autouse=True)
def _no_leftover_faults():
    faults.disarm()
    yield
    faults.disarm()


@pytest.fixture(scope="module")
def tiny_parts():
    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import init_params

    cfg = ModelConfig.tiny(vocab_size=512, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(3))
    return cfg, params


@pytest.fixture(scope="module")
def conv_parts():
    """A model with recurrent state: conv layers around one attention
    layer, so every row carries a slot of the state pool beside its
    pages."""
    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import init_params

    cfg = ModelConfig.tiny(
        vocab_size=512, dtype="float32", num_layers=3,
        layer_types=("conv", "attn", "conv"), conv_kernel=3,
    )
    params = init_params(cfg, jax.random.PRNGKey(5))
    return cfg, params


@pytest.fixture(scope="module")
def retention_parts():
    """A model whose every mixer is power retention: no page holds a byte,
    every row carries a matrix state a kv head in its slot of the pool."""
    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import init_params

    cfg = ModelConfig.tiny(
        vocab_size=512, dtype="float32", qk_norm=True,
        layer_types=("retention",) * 2,
    )
    params = init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


@pytest.fixture(params=["dense", "conv", "retention"])
def parts(request, tiny_parts, conv_parts, retention_parts):
    return {"dense": tiny_parts, "conv": conv_parts,
            "retention": retention_parts}[request.param]


def _make_engine(parts, **extra):
    from helix_tpu.engine.engine import Engine, EngineConfig

    cfg, params = parts
    kw = dict(
        max_decode_batch=4, page_size=4, num_pages=128,
        max_pages_per_seq=32, max_prefill_len=8,
        attn_backend="reference",
    )
    if cfg.num_retention_layers:
        # a matrix state is not filed: the prefix cache is refused for it
        kw["enable_prefix_cache"] = False
    kw.update(extra)
    return Engine(cfg, params, EngineConfig(**kw))


class _Collector:
    def __init__(self):
        self.events = []
        self.done = threading.Event()

    def __call__(self, ev):
        self.events.append(ev)
        if ev.finished:
            self.done.set()

    @property
    def error(self):
        return next((e.error for e in self.events if e.error), None)

    @property
    def tokens(self):
        return [e.token_id for e in self.events if e.token_id >= 0]


def _req(rid, prompt, max_tokens=16, temperature=0.0, seed=None,
         presence=0.0, frequency=0.0, stop=(1,)):
    from helix_tpu.engine.engine import Request
    from helix_tpu.engine.sampling import SamplingParams

    return Request(
        id=rid, prompt_tokens=list(prompt),
        sampling=SamplingParams(
            max_tokens=max_tokens, temperature=temperature, seed=seed,
            presence_penalty=presence, frequency_penalty=frequency,
        ),
        stop_token_ids=tuple(stop),
    )


def _reference(parts, reqs, engine_extra=None):
    """What ``Engine.step()`` produces: dispatch then complete, nothing
    ever in flight.  Returns ({rid: tokens}, engine)."""
    eng = _make_engine(parts, **(engine_extra or {}))
    rs = reqs()
    for r in rs:
        eng.add_request(r)
    while eng.has_work():
        eng.step()
    return {r.id: list(r.output_tokens) for r in rs}, eng


def _run_workload(parts, reqs, engine_extra=None, timeout=120.0,
                  watch=None):
    """Submit ``reqs`` (a builder) through a started EngineLoop; returns
    ({rid: tokens}, loop stats, engine, loop).  ``watch(engine)`` may
    wrap engine methods before the loop starts."""
    from helix_tpu.serving.engine_loop import EngineLoop

    eng = _make_engine(parts, **(engine_extra or {}))
    if watch is not None:
        watch(eng)
    loop = EngineLoop(eng, name="alp").start()
    try:
        cols = {}
        for req in reqs():
            col = _Collector()
            cols[req.id] = col
            loop.submit(req, col)
        for rid, col in cols.items():
            assert col.done.wait(timeout), f"{rid} stuck"
        for rid, col in cols.items():
            assert col.error is None, f"{rid}: {col.error}"
        stats = loop.stats()
        return {rid: col.tokens for rid, col in cols.items()}, stats, eng, loop
    finally:
        loop.stop(join=True)


def _assert_parity(parts, reqs, engine_extra=None, watch=None):
    want, ref_eng = _reference(parts, reqs, engine_extra)
    got, stats, eng, loop = _run_workload(
        parts, reqs, engine_extra, watch=watch)
    assert got == want, (want, got)
    return want, stats, eng, loop


def _prompt(j, n, vocab=500):
    return [(7 * i + 13 * j) % vocab + 2 for i in range(n)]


def _flight(loop):
    return loop.flight.snapshot(recent=4096)["recent"]


# ---- bit identity against Engine.step() -------------------------------------

FUSED = {"decode_steps_per_sync": 4}


def _case_greedy_prefix_hit(parts):
    """Plain batched decode plus a same-prefix pair (the second request
    admits through the prefix cache), four requests on four slots: no
    slot is free, so steps stay in flight."""
    shared = list(range(4, 9))

    def reqs():
        out = [_req(f"g{j}", [20 + 3 * j + i for i in range(6)],
                    max_tokens=20) for j in range(2)]
        out.append(_req("p1", shared + [40, 41], max_tokens=12))
        out.append(_req("p2", shared + [50, 51], max_tokens=12))
        return out

    out, stats, _, _ = _assert_parity(parts, reqs)
    assert stats["async_loop"]["pipelined_steps"] > 0
    assert all(len(t) >= 1 for t in out.values())


def _case_seeded_penalties(parts):
    """Seeded temp>0 with presence/frequency penalties over a queue: the
    per-slot key stream and the device-resident penalty histograms must
    land byte-for-byte wherever the reconcile happens."""

    def reqs():
        return [_req(f"t{j}", _prompt(j, 6), max_tokens=14 + j,
                     temperature=0.85, seed=100 + j, presence=0.5,
                     frequency=0.3) for j in range(6)]

    _, stats, _, _ = _assert_parity(parts, reqs, FUSED)
    assert stats["async_loop"]["pipelined_steps"] > 0


def _case_chunked_deferred_first_token(parts):
    """Long prompt with the mixed step OFF: the chunk cascade runs
    standalone chunk dispatches and the chunk-final first token is
    DEFERRED into the same-step decode fetch (one host round trip, not
    two) — while short decoders keep emitting."""

    def reqs():
        return [_req("s0", list(range(4, 10)), max_tokens=24),
                _req("long", _prompt(3, 30), max_tokens=10),
                _req("s1", list(range(14, 20)), max_tokens=24)]

    out, _, _, _ = _assert_parity(
        parts, reqs, {"enable_mixed_step": False})
    assert len(out["long"]) == 10


def _case_queue_never_empty(parts):
    """Three times as many requests as slots, submitted together: the
    queue stays non-empty through most of the run, every admission wave
    is launched behind a running window, and its first tokens never come
    to the host alone: they seed the new rows on the device."""

    def reqs():
        return [_req(f"q{j}", _prompt(j, 5 + j % 4), max_tokens=9 + 2 * j,
                     temperature=0.7 if j % 2 else 0.0, seed=7 + j)
                for j in range(12)]

    behind = []

    def watch(eng):
        orig = eng._admit_wave

        def wave(pending):
            n = orig(pending)
            if n:
                behind.append(bool(eng._inflight_out))
            return n

        eng._admit_wave = wave

    _, stats, eng, loop = _assert_parity(parts, reqs, FUSED, watch=watch)
    assert stats["async_loop"]["pipelined_steps"] > 0
    assert any(behind), "no wave was launched behind a running step"
    assert loop.obs.step_phases["helix.loop.prefill_sync"].sum == 0.0


def _case_max_tokens_inside_window(parts):
    """Budgets that end inside a fused window, rows out of step with
    each other: a row the tokens in flight exhaust is launched INACTIVE
    in the next step and finishes at the reconcile, with exactly
    ``max_tokens`` tokens."""

    def reqs():
        return [_req(f"w{j}", _prompt(j, 6), max_tokens=5 + (3 * j) % 7,
                     stop=()) for j in range(10)]

    parked = []

    def watch(eng):
        orig = eng._sync_state

        def sync():
            orig()
            parked.append(sum(
                1 for i in range(len(eng.slots))
                if eng._slot_active(i) and not eng._active_sent[i]))

        eng._sync_state = sync

    out, stats, _, _ = _assert_parity(parts, reqs, FUSED, watch=watch)
    assert stats["async_loop"]["pipelined_steps"] > 0
    assert any(parked), "no row was launched inactive"
    for j in range(10):
        assert len(out[f"w{j}"]) == 5 + (3 * j) % 7


def _case_stop_token_found_late(parts):
    """A stop token sampled inside step N is found at N's reconcile,
    after N+1 was launched with the row live: the overrun is discarded,
    the freed pages go to the next wave (the pool is smaller than what
    the requests need together) and the next owners' outputs are what
    ``Engine.step()`` gives them."""
    extra = dict(FUSED, num_pages=24)

    def base(stop=()):
        return [_req(f"e{j}", _prompt(j, 6), max_tokens=14,
                     temperature=0.9, seed=31 + j, stop=stop)
                for j in range(10)]

    free_run, _ = _reference(parts, base, extra)
    # a token some request samples mid-sequence: every request stops on it
    seq = free_run["e1"]
    stop = (seq[5],)
    assert 10 * ((6 + 14 + 3) // 4) > extra["num_pages"]
    overruns = []

    def watch(eng):
        orig = eng._decode_complete

        def complete(p, emitted):
            orig(p, emitted)
            overruns.extend(
                r.id for _i, r in p.rows
                if r.finished and eng._inflight_out.get(r.id) is None
                and r.finish_reason.value == "stop")

        eng._decode_complete = complete

    out, stats, _, _ = _assert_parity(
        parts, lambda: base(stop), extra, watch=watch)
    assert stats["async_loop"]["pipelined_steps"] > 0
    assert len(out["e1"]) == free_run["e1"].index(stop[0]) + 1
    assert overruns, "no stop-token finish was seen at a reconcile"


def _case_mixed_steps_in_flight(parts):
    """A long prompt chunks beside running decoders while more requests
    queue: mixed steps stay in flight through the whole prompt, the final
    chunk included (its token stays on the device and seeds the slot)."""

    def reqs():
        out = [_req(f"d{j}", _prompt(j, 6), max_tokens=40, stop=())
               for j in range(3)]
        out.append(_req("lng", _prompt(9, 44), max_tokens=8,
                        temperature=0.8, seed=3))
        out += [_req(f"z{j}", _prompt(20 + j, 5), max_tokens=6)
                for j in range(3)]
        return out

    held = []

    def watch(eng):
        orig = eng.step_complete

        def complete(pend, emitted=None):
            # a step completed by a LATER pass than its own was in flight
            held.append((pend.kind, emitted is None))
            return orig(pend, emitted)

        eng.step_complete = complete

    out, _, eng, _ = _assert_parity(parts, reqs, watch=watch)
    assert eng.num_mixed_steps >= 5
    assert ("mixed", True) in held, held
    assert len(out["lng"]) == 8


def _inert_waves(parts, reqs, engine_extra=None):
    """``_reference`` on an engine whose admission waves launch every
    state row sitting out: what a wave was before its running rows
    decoded in it.  Returns {rid: tokens}."""
    eng = _make_engine(parts, **(engine_extra or {}))
    eng._wave_rows = lambda: []
    rs = reqs()
    for r in rs:
        eng.add_request(r)
    while eng.has_work():
        eng.step()
    assert eng.num_wave_decode_tokens == 0
    return {r.id: list(r.output_tokens) for r in rs}


def _case_waves_beside_live_rows(parts):
    """Three times as many requests as slots, sampled and penalised rows
    among them: every wave after the first is launched beside running
    rows, which decode a token in it, most of them behind a step still in
    flight.  The streams are what ``Engine.step()`` gives, and what an
    engine whose waves run alone gives: a wave token reaches the client
    after every earlier token of its request and before the tokens of the
    step that carried it."""

    def reqs():
        return [_req(f"v{j}", _prompt(j, 4 + j % 5), max_tokens=7 + 3 * j,
                     temperature=0.8 if j % 3 == 1 else 0.0, seed=11 + j,
                     presence=0.3 if j % 2 else 0.0, stop=())
                for j in range(12)]

    launched = []

    def watch(eng):
        orig = eng._wave_rows

        def rows():
            out = orig()
            launched.append((len(out), bool(eng._inflight_out)))
            return out

        eng._wave_rows = rows

    want, stats, eng, loop = _assert_parity(parts, reqs, FUSED, watch=watch)
    assert want == _inert_waves(parts, reqs, FUSED)
    assert stats["async_loop"]["pipelined_steps"] > 0
    assert any(n and behind for n, behind in launched), launched
    assert eng.num_wave_decode_tokens == sum(n for n, _b in launched) > 0
    assert sum(r["wave_rows"] for r in _flight(loop)) == (
        eng.num_wave_decode_tokens)
    assert not eng._inflight_out and not eng._pending_waves
    for j in range(12):
        assert len(want[f"v{j}"]) == 7 + 3 * j


CASES = {
    "waves_beside_live_rows": _case_waves_beside_live_rows,
    "greedy_prefix_hit": _case_greedy_prefix_hit,
    "seeded_penalties": _case_seeded_penalties,
    "chunked_deferred_first_token": _case_chunked_deferred_first_token,
    "queue_never_empty": _case_queue_never_empty,
    "max_tokens_inside_window": _case_max_tokens_inside_window,
    "stop_token_found_late": _case_stop_token_found_late,
    "mixed_steps_in_flight": _case_mixed_steps_in_flight,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_bit_identity_against_engine_step(parts, case):
    CASES[case](parts)


class TestBitIdentitySlow:
    @pytest.mark.slow
    def test_spec_decode_parity(self, tiny_parts):
        """Speculative engine (repetitive suffix — real acceptance):
        the loop reconciles around every spec step, and outputs stay
        bit-identical."""
        rep = [5, 9, 7, 3] * 6

        def reqs():
            return [_req("sp0", list(rep), max_tokens=20),
                    _req("sp1", list(range(4, 10)), max_tokens=16)]

        extra = {"enable_spec_decode": True, "spec_tokens": 3}
        _, stats, eng, _ = _assert_parity(tiny_parts, reqs, extra)
        assert eng.num_spec_steps > 0
        assert stats["async_loop"]["pipelined_steps"] == 0

    @pytest.mark.slow
    def test_int8_kv_parity(self, tiny_parts):
        """int8 KV pools: quantize-on-write + in-register dequant with
        steps in flight, greedy and seeded temp>0."""

        def reqs():
            return [_req("i0", list(range(4, 10)), max_tokens=16),
                    _req("i1", list(range(24, 30)), max_tokens=16,
                         temperature=0.8, seed=11, presence=0.4)] + [
                _req(f"i{j}", _prompt(j, 6), max_tokens=12)
                for j in range(2, 6)]

        _assert_parity(tiny_parts, reqs, {"kv_cache_dtype": "int8"})


# ---- the gate, driven pass by pass ------------------------------------------


def _manual_loop(parts, n_reqs, max_tokens=40, engine=None, **extra):
    """An EngineLoop that was never started, stepped with ``_pass()`` on
    the test's thread (tokens are delivered inline), with ``n_reqs``
    submitted.  Returns (loop, engine, {rid: collector})."""
    from helix_tpu.serving.engine_loop import EngineLoop

    eng = engine if engine is not None else _make_engine(parts, **extra)
    loop = EngineLoop(eng, name="alp-gate")
    cols = {}
    for j in range(n_reqs):
        col = cols[f"m{j}"] = _Collector()
        loop.submit(
            _req(f"m{j}", _prompt(j, 6), max_tokens=max_tokens, stop=()),
            col)
    return loop, eng, cols


def _finish(loop, cols, passes=400):
    for _ in range(passes):
        if all(c.done.is_set() for c in cols.values()):
            return
        assert loop._pass()
    raise AssertionError("requests never finished")


@pytest.mark.parametrize("n_reqs,held", [
    (2, False),   # queue empty and a slot free: today's order
    (4, True),    # queue empty, no slot free
    (7, True),    # queue non-empty after admission
])
def test_gate_follows_queue_and_slots(tiny_parts, n_reqs, held):
    loop, eng, cols = _manual_loop(tiny_parts, n_reqs)
    seen = []
    for _ in range(6):
        assert loop._pass()
        seen.append(loop._inflight is not None)
        assert eng.admission_blocked() == held
    assert all(s == held for s in seen), seen
    assert (loop.pipelined_steps > 0) == held
    if held:
        # the step in flight was launched before the one before it was
        # fetched: its pass read no exposed host time
        assert loop._inflight.kind == "decode"
    want, _ = _reference(
        tiny_parts,
        lambda: [_req(f"m{j}", _prompt(j, 6), max_tokens=40, stop=())
                 for j in range(n_reqs)])
    _finish(loop, cols)
    assert {r: c.tokens for r, c in cols.items()} == want


def _in_flight(tiny_parts, **kw):
    loop, eng, cols = _manual_loop(tiny_parts, 6, **kw)
    for _ in range(3):
        assert loop._pass()
    assert loop._inflight is not None
    return loop, eng, cols


def _refuse_abort(loop, eng, cols, seen):
    orig = eng.abort

    def abort(rid):
        seen.append(loop._inflight)
        return orig(rid)

    eng.abort = abort
    loop.abort("m1")
    loop._drain_inbox()
    cols.pop("m1")


def _refuse_import(loop, eng, cols, seen):
    from helix_tpu.engine.engine import SnapshotError

    def import_request(snap):
        seen.append(loop._inflight)
        raise SnapshotError("not a snapshot")

    eng.import_request = import_request
    loop.submit_import(object(), _Collector(), lambda err, code: None)
    loop._drain_inbox()


def _refuse_drain(loop, eng, cols, seen):
    loop._draining = True
    loop._drain_deadline = time.monotonic() + 60
    orig = eng.step_dispatch

    def dispatch():
        seen.append(loop._inflight)
        return orig()

    eng.step_dispatch = dispatch
    assert loop._pass()
    assert loop._inflight is None, "a draining loop keeps nothing in flight"
    loop._draining = False


def _refuse_handoff(loop, eng, cols, seen):
    def on_snapshot(kind, wire):
        seen.append(loop._inflight)

    orig = eng.export_prefill

    def export(rid):
        seen.append(loop._inflight)
        return orig(rid)

    eng.export_prefill = export
    loop.stage_disagg_export("m0", on_snapshot)
    assert loop._pass()


def _refuse_checkpoint(loop, eng, cols, seen):
    due = [True]
    eng.checkpoint_due = lambda: due.pop() if due else False
    eng.checkpoint_tick = lambda sched=None: seen.append(loop._inflight)
    assert loop._pass()


def _refuse_pressure_preempt(loop, eng, cols, seen):
    loop.preempt_stall_seconds = 0.0
    loop._admit_seen = eng.num_admitted
    loop._stall_since = time.monotonic() - 1.0

    def preempt_for_pressure():
        seen.append(loop._inflight)
        return None

    eng.preempt_for_pressure = preempt_for_pressure
    assert eng.waiting
    assert loop._pass()
    loop.preempt_stall_seconds = None


def _refuse_engine(loop, eng, cols, seen):
    """Whatever the engine names (speculation, a parked preemption, a
    tiered row): ``pipeline_ready`` is False."""
    eng.pipeline_ready = lambda: False
    orig = eng.step_dispatch

    def dispatch():
        seen.append(loop._inflight)
        return orig()

    eng.step_dispatch = dispatch
    assert loop._pass()
    assert loop._inflight is None


REFUSALS = {
    "abort": _refuse_abort,
    "import": _refuse_import,
    "drain": _refuse_drain,
    "handoff": _refuse_handoff,
    "checkpoint": _refuse_checkpoint,
    "pressure_preempt": _refuse_pressure_preempt,
    "engine_refuses": _refuse_engine,
}


@pytest.mark.parametrize("refusal", sorted(REFUSALS))
def test_refusals_reconcile_first(tiny_parts, refusal):
    """Each condition that changes or reads what the in-flight prediction
    was built on sees a reconciled engine: nothing in flight at the
    moment it acts; and the streams stay what ``Engine.step()`` gives."""
    loop, eng, cols = _in_flight(tiny_parts)
    seen = []
    REFUSALS[refusal](loop, eng, cols, seen)
    assert seen and all(s is None for s in seen), seen
    want, _ = _reference(
        tiny_parts,
        lambda: [_req(f"m{j}", _prompt(j, 6), max_tokens=40, stop=())
                 for j in range(6)])
    _finish(loop, cols)
    for rid, col in cols.items():
        assert col.tokens == want[rid], rid


@pytest.mark.parametrize("what", ["speculation", "parked", "tiered"])
def test_engine_names_what_reconciles_first(tiny_parts, what):
    extra = {"enable_spec_decode": True} if what == "speculation" else {}
    eng = _make_engine(tiny_parts, **extra)
    eng.add_request(_req("x", list(range(4, 10)), max_tokens=4))
    eng.step()
    if what == "parked":
        eng.preempted.append(object())
    elif what == "tiered":
        eng._tiered[0] = {}
    else:
        assert eng.spec is not None
    assert not eng.pipeline_ready()
    eng.preempted.clear()
    eng._tiered.clear()
    assert eng.pipeline_ready() == (what != "speculation")


def test_plan_leader_looks_ahead_in_steady_decode_only(tiny_parts):
    """The multi-host plan leader keeps ISSUE 13's rule: with requests
    queued (or a chunk, or dirty slots) every pass reconciles first; once
    the queue is empty and the slots are full, steps stay in flight."""
    from helix_tpu.serving.multihost_serving import PlanLeader

    leader = PlanLeader(_make_engine(tiny_parts))
    loop, _, cols = _manual_loop(tiny_parts, 8, max_tokens=12,
                                 engine=leader)
    behind = []
    orig = leader.step_dispatch

    def dispatch():
        behind.append((loop._inflight is not None,
                       bool(leader.engine.waiting)))
        return orig()

    leader.step_dispatch = dispatch
    _finish(loop, cols)
    assert not any(b and queued for b, queued in behind), behind
    assert any(b for b, _q in behind), "the leader never looked ahead"


def test_submit_in_flight_is_drained_without_a_reconcile(tiny_parts):
    """A plain submit only appends to the wait queue: the step in flight
    stays in flight; the arrival is admitted by a later pass and its
    stream, like everyone's, is what ``Engine.step()`` gives."""
    loop, eng, cols = _in_flight(tiny_parts)
    pend = loop._inflight
    late = cols["late"] = _Collector()
    loop.submit(_req("late", _prompt(77, 7), max_tokens=9, seed=5,
                     temperature=0.6), late)
    loop._drain_inbox()
    assert loop._inflight is pend
    assert any(r.id == "late" for r in eng.waiting)

    def reqs():
        return [_req(f"m{j}", _prompt(j, 6), max_tokens=40, stop=())
                for j in range(6)] + [
            _req("late", _prompt(77, 7), max_tokens=9, seed=5,
                 temperature=0.6)]

    want, _ = _reference(tiny_parts, reqs)
    _finish(loop, cols)
    assert {r: c.tokens for r, c in cols.items()} == want


def test_exposed_host_is_zero_behind_a_running_step(tiny_parts):
    """``helix_step_exposed_host_seconds``: one observation a pass; the
    gap from the last completion's return to this step's first launch
    when nothing was queued on the device, 0 for a step launched behind
    a running one."""
    hist = lambda loop: loop.obs.exposed_host   # noqa: E731
    # slots free, queue empty: every step is launched on an idle device
    loop, eng, cols = _manual_loop(tiny_parts, 2, max_tokens=12)
    assert loop._pass()
    first = hist(loop).sum
    time.sleep(0.02)
    for _ in range(3):
        assert loop._pass()
    assert hist(loop).count == 4
    assert hist(loop).sum - first >= 0.02
    gaps = [r["idle_gap_s"] for r in _flight(loop)]
    assert len(gaps) == 4 and all(g > 0 for g in gaps[1:])
    # no slot free: from the second pass on each step is launched behind
    # the one before it and reads 0, however long the host took
    loop, eng, cols = _manual_loop(tiny_parts, 4)
    assert loop._pass()
    before = hist(loop).sum
    for _ in range(4):
        time.sleep(0.01)
        assert loop._pass()
    assert hist(loop).count == 5
    assert hist(loop).sum == before
    assert [r["pipelined"] for r in _flight(loop)][-3:] == [1, 1, 1]


class TestPipelineMechanics:
    def test_idle_ratio_and_time_split_recorded(self, tiny_parts):
        """The flight ring carries the per-step time split and steps
        launched behind a running one charge no idle gap."""

        def reqs():
            return [_req(f"m{j}", [15 + 4 * j + i for i in range(6)],
                         max_tokens=24) for j in range(5)]

        _, stats, _, loop = _run_workload(tiny_parts, reqs)
        al = stats["async_loop"]
        assert al["enabled"] and al["pipelined_steps"] > 0
        assert al["device_idle_ratio"] >= 0.0
        recs = [r for r in _flight(loop) if r.get("pipelined")]
        assert recs and all(r["idle_gap_s"] == 0.0 for r in recs)

    def test_flight_records_have_time_split(self, tiny_parts):
        from helix_tpu.serving.engine_loop import EngineLoop

        eng = _make_engine(tiny_parts)
        loop = EngineLoop(eng, name="alp-ts").start()
        try:
            col = _Collector()
            loop.submit(_req("ts0", list(range(4, 10)), max_tokens=12),
                        col)
            assert col.done.wait(60)
            recs = [
                r for r in _flight(loop) if r.get("kind") == "decode"
            ]
            assert recs, "no decode records"
            for key in ("host_build_s", "device_wait_s", "emit_s",
                        "idle_gap_s", "wall_s", "pipelined"):
                assert key in recs[-1], (key, recs[-1])
            assert loop.device_idle_ratio() >= 0.0
        finally:
            loop.stop(join=False)

    def test_page_allocation_exhaustion_does_not_trip_headroom(
        self, tiny_parts
    ):
        """Regression: a request whose in-flight window advances its
        predicted position exactly to its page allocation (max_len ==
        table capacity here) sits the next step out and finishes at the
        reconcile: no dispatch runs into the headroom-invariant
        RuntimeError."""
        from helix_tpu.serving.engine_loop import EngineLoop

        eng = _make_engine(tiny_parts, max_decode_batch=1, **FUSED)
        loop = EngineLoop(eng, name="alp-cap").start()
        try:
            col = _Collector()
            # prompt 8 + 120 generated = 128 tokens = 32 pages * 4 =
            # the full per-sequence table
            r = _req("cap-1", list(range(4, 12)), max_tokens=120, stop=())
            loop.submit(r, col)
            assert col.done.wait(120)
            assert col.error is None, col.error
            assert len(col.tokens) == 120
            assert loop.step_failures == 0
            assert loop.pipelined_steps > 0
        finally:
            loop.stop(join=False)

    def test_emission_events_snapshot_at_push_time(self, tiny_parts):
        """Regression: TokenEvents are rendered on the engine thread at
        emission time.  A finish discovered at a LATER step's reconcile
        must not retro-stamp an earlier batch's token as terminal (that
        would pop the subscriber and drop the real final tokens), and
        within one batch only a request's LAST entry carries the
        finished flag."""
        from helix_tpu.serving.engine_loop import EngineLoop

        eng = _make_engine(tiny_parts)
        loop = EngineLoop(eng, name="alp-snap")
        req = _req("snap-1", list(range(4, 8)), max_tokens=4)
        # batch A snapshotted while the request is still running...
        events_a = loop._snapshot_events([(req, 7)])
        # ...then a later reconcile finishes it and batch B snapshots
        from helix_tpu.engine.engine import FinishReason

        req.finished = True
        req.finish_reason = FinishReason.STOP
        events_b = loop._snapshot_events([(req, 9)])
        assert events_a[0][1] is False
        assert events_a[0][2].finished is False
        assert events_a[0][2].finish_reason is None
        assert events_b[0][2].finished is True
        assert events_b[0][2].finish_reason == "stop"
        # within-batch: two tokens of a finished request — only the
        # last entry is terminal
        multi = loop._snapshot_events([(req, 11), (req, 12)])
        assert [ev.finished for _r, _f, ev in multi] == [False, True]

    @pytest.mark.parametrize("mixed", [False, True])
    def test_discard_pending_preserves_deferred_first_token(
        self, tiny_parts, mixed
    ):
        """Regression: a completion failure on the step carrying a
        deferred first token (a final chunk's, an admission wave's) must
        NOT lose that token — the prefill succeeded, so the retry
        re-seeds the slot from the handle and the stream still starts at
        token #1."""
        long_p = _prompt(3, 30)

        def reqs():
            return [_req("vic", long_p, max_tokens=6),
                    _req("mate", list(range(4, 10)), max_tokens=12)]

        want, _ = _reference(
            tiny_parts, reqs, {"enable_mixed_step": mixed})
        eng = _make_engine(tiny_parts, enable_mixed_step=mixed)
        rs = reqs()
        for r in rs:
            eng.add_request(r)
        discarded = 0
        emitted_all = []
        while eng.has_work():
            emitted, pend = eng.step_dispatch()
            if pend is not None:
                if discarded < 2 and pend.pending_first:
                    # once for the wave's token, once for the chunk's
                    eng.discard_pending(pend)
                    discarded += 1
                    continue
                eng.step_complete(pend, emitted)
            emitted_all.extend(emitted)
        assert discarded == 2, "workload never exercised the deferred path"
        for r in rs:
            assert r.output_tokens == want[r.id]
            assert [t for q, t in emitted_all if q is r] == want[r.id]
        assert not eng._inflight_out

    @pytest.mark.parametrize("family,what", [
        (family, what)
        for family in ("dense", "conv", "retention")
        for what in ("discard", "abort", "finish", "max_tokens")
        # a discarded launch has already updated a matrix state in place:
        # ``discard_pending`` rolls back the host's mirrors, not the pools
        if (family, what) != ("retention", "discard")])
    def test_between_a_wave_and_its_fetch(
            self, tiny_parts, conv_parts, retention_parts, family, what):
        """Three rows decode, a step is in flight, a fourth request
        arrives: the next dispatch admits it in a wave in which the three
        decode a token, and launches a step behind it.  Before the wave's
        tokens are fetched, ``m1`` meets a failed completion (``discard``:
        the wave's advance is rolled back with the step's), an ``abort``,
        a ``finish`` (the step in flight held its stop token) or its
        ``max_tokens`` (one token left with another in flight: it sits
        the WAVE out, so that its last token cannot reach the host first,
        and decodes it in the step behind).  Positions, the in-flight
        account and every stream come out as a run without the event
        gives them."""
        parts = {"dense": tiny_parts, "conv": conv_parts,
                 "retention": retention_parts}[family]
        plens = {"m0": 6, "m1": 6, "m2": 6, "late": 5}

        def reqs(m1_tokens=30, m1_stop=()):
            return [
                _req("m0", _prompt(0, 6), max_tokens=30, stop=()),
                _req("m1", _prompt(1, 6), max_tokens=m1_tokens,
                     stop=m1_stop),
                _req("m2", _prompt(2, 6), max_tokens=30, stop=()),
                _req("late", _prompt(9, 5), max_tokens=12, stop=()),
            ]

        want, _ = _reference(parts, reqs)
        # m1's tokens: first + one decoded (p1), the third in p2, the
        # fourth in the wave (in p3 where it is the last), the fifth in p3
        kw = {}
        if what == "finish":
            assert want["m1"][2] not in want["m1"][:2]
            kw = dict(m1_stop=(want["m1"][2],))
        elif what == "max_tokens":
            kw = dict(m1_tokens=4)
        eng = _make_engine(parts)
        rs = {r.id: r for r in reqs(**kw)}
        for rid in ("m0", "m1", "m2"):
            eng.add_request(rs[rid])
        eng.step()
        em2, p2 = eng.step_dispatch()          # a step in flight
        eng.add_request(rs["late"])
        em3, p3 = eng.step_dispatch()          # the wave, then a step
        (rows, _sampled), = p3.waves
        live = [rid for rid in ("m0", "m1", "m2")
                if (rid, what) != ("m1", "max_tokens")]
        assert [r.id for _i, r in rows] == live
        assert [r.id for _i, r in p3.rows] == ["m0", "m1", "m2", "late"]
        assert eng._inflight_out == {
            "m0": 3, "m1": 2 if what == "max_tokens" else 3, "m2": 3,
            "late": 2}

        def settled():
            """Nothing in flight: every row's position is its tokens'."""
            assert not eng._inflight_out and not eng._pending_waves
            for i, r in enumerate(eng.slots):
                if r is not None:
                    assert eng._positions[i] == (
                        plens[r.id] + len(r.output_tokens) - 1), r.id

        if what == "abort":
            eng.abort("m1")
        eng.step_complete(p2, em2)
        if what == "discard":
            eng.discard_pending(p3)
            # the first token is put back; the wave's advance is not
            assert eng._inflight_out == {"late": 1}
            assert [eng._positions[i] for i, _r in rows] == [
                6 + 3 - 1] * 3
        else:
            eng.step_complete(p3, em3)
            settled()
        while eng.has_work():
            eng.step()
        settled()
        got = {rid: list(r.output_tokens) for rid, r in rs.items()}
        cut = {"abort": 2, "finish": 3, "max_tokens": 4}.get(what)
        if cut:
            assert got.pop("m1") == want.pop("m1")[:cut]
            assert rs["m1"].finish_reason.value == {
                "abort": "abort", "finish": "stop",
                "max_tokens": "length"}[what]
        assert got == want
        assert eng.num_wave_decode_tokens == len(live)

    @pytest.mark.parametrize("ends", ["length", "stop"])
    @pytest.mark.parametrize("in_flight", [True, False])
    def test_a_wave_holds_no_rows_last_token_ahead_of_a_step_in_flight(
            self, tiny_parts, ends, in_flight):
        """Every running row has ONE token left and the arrival ends on
        its first (``max_tokens=1``: scoring traffic), so no row runs
        behind the wave and no step follows it: what the wave decoded is
        fetched at once (``_flush_pending_first``).  With a step in flight
        that would hand the host each row's last token AHEAD of the step's
        (and, where the last is a stop token, finish the request there and
        drop the step's): the rows sit the wave out and decode in a step
        behind it, which reconciles in order.  With nothing in flight
        they decode in the wave and the flush is in order as it is."""
        names = ("m0", "m1", "m2")

        def reqs(stops=None):
            return [
                _req(rid, _prompt(j, 6), max_tokens=4,
                     stop=(stops[rid],) if stops else ())
                for j, rid in enumerate(names)
            ] + [_req("late", _prompt(9, 5), max_tokens=1, stop=())]

        want, _ = _reference(tiny_parts, reqs)
        stops = None
        if ends == "stop":
            stops = {rid: want[rid][3] for rid in names}
            assert all(stops[rid] not in want[rid][:3] for rid in names)
        eng = _make_engine(tiny_parts)
        rs = {r.id: r for r in reqs(stops)}
        for rid in names:
            eng.add_request(rs[rid])
        seen = eng.step()                      # two tokens a row
        em2, p2 = eng.step_dispatch()          # the third, in flight
        assert [eng._headroom(rs[rid]) for rid in names] == [1, 1, 1]
        if not in_flight:
            seen += eng.step_complete(p2, em2)
        eng.add_request(rs["late"])
        em3, p3 = eng.step_dispatch()
        if in_flight:
            assert not p3.waves and em3 == []
            assert [r.id for _i, r in p3.rows] == list(names)
            assert eng.num_wave_decode_tokens == 0
            seen += eng.step_complete(p2, em2)
            seen += eng.step_complete(p3, em3)
        else:
            assert p3 is None and eng.num_wave_decode_tokens == 3
            seen += em3
        assert not eng.has_work() and not eng._inflight_out
        for rid, r in rs.items():
            assert [t for q, t in seen if q is r] == want[rid], rid
            assert list(r.output_tokens) == want[rid]
            assert r.finish_reason.value == (
                "stop" if stops and rid in stops else "length")

    def test_step_rolls_back_on_completion_failure(
        self, tiny_parts, monkeypatch
    ):
        """Regression: a monolithic ``step()`` whose completion raises
        (real device errors surface at the fetch) must discard the
        pending dispatch — quarantine bisection and lockstep callers
        retry through this wrapper, and a retry against un-rolled-back
        mirrors would silently skip the window's tokens."""
        # reference: unperturbed greedy run
        ref_eng = _make_engine(tiny_parts)
        r_ref = _req("ref", list(range(4, 10)), max_tokens=12)
        ref_eng.add_request(r_ref)
        while ref_eng.has_work():
            ref_eng.step()
        eng = _make_engine(tiny_parts)
        r = _req("vic", list(range(4, 10)), max_tokens=12)
        eng.add_request(r)
        eng.step()   # admission + first token
        orig = eng.step_complete
        state = {"armed": True}

        def boom(pend, emitted=None):
            if state["armed"]:
                state["armed"] = False
                raise RuntimeError("injected completion failure")
            return orig(pend, emitted)

        monkeypatch.setattr(eng, "step_complete", boom)
        with pytest.raises(RuntimeError):
            eng.step()
        while eng.has_work():
            eng.step()
        assert r.output_tokens == r_ref.output_tokens

    def test_requeued_first_token_rides_mixed_step(self, tiny_parts):
        """Regression: a deferred chunk-final first token re-queued by
        a failed completion must be emitted by the NEXT dispatch even
        when that dispatch takes the mixed route (a second long prompt
        started chunking) — token #1 must never trail token #2."""
        long_a = _prompt(3, 30)
        long_b = _prompt(5, 30)

        def reference():
            eng = _make_engine(tiny_parts, enable_mixed_step=True)
            ra = _req("a", long_a, max_tokens=6)
            eng.add_request(ra)
            while eng.has_work():
                eng.step()
            return list(ra.output_tokens)

        ref_tokens = reference()
        eng = _make_engine(tiny_parts, enable_mixed_step=True)
        ra = _req("a", long_a, max_tokens=6)
        eng.add_request(ra)
        discarded = False
        order: list = []
        while eng.has_work():
            emitted, pend = eng.step_dispatch()
            if pend is not None:
                if not discarded and pend.pending_first:
                    # simulated completion failure; then a second long
                    # prompt arrives so the retry goes mixed
                    eng.discard_pending(pend)
                    discarded = True
                    eng.add_request(_req("b", long_b, max_tokens=4))
                    continue
                eng.step_complete(pend, emitted)
            order.extend(t for q, t in emitted if q is ra)
        assert discarded, "workload never exercised the deferred path"
        assert order == ref_tokens
        assert ra.output_tokens == ref_tokens

    def test_rebuild_keeps_the_devices_tokens_and_positions(
        self, tiny_parts
    ):
        """``_rebuild_state`` takes last tokens and positions from the
        host for CHANGED slots only: with a window in flight the host's
        mirror of a surviving row is a window behind, and a rebuild
        (forced here by a finish elsewhere) must not upload it."""
        eng = _make_engine(tiny_parts, **FUSED)
        for j in range(3):
            eng.add_request(_req(f"k{j}", _prompt(j, 6), max_tokens=30,
                                 stop=()))
        eng.step()
        _, pend = eng.step_dispatch()          # a window in flight
        stale = eng._last_token.copy()
        dev = np.asarray(eng._dstate.last_token).copy()
        pos = np.asarray(eng._dstate.positions).copy()
        eng._state_dirty = True                # as a finish would
        eng._changed_slots.add(3)              # an empty slot changed
        eng._sync_state()
        np.testing.assert_array_equal(
            np.asarray(eng._dstate.last_token)[:3], dev[:3])
        np.testing.assert_array_equal(
            np.asarray(eng._dstate.positions)[:3], pos[:3])
        assert (stale[:3] != dev[:3]).any(), "mirror was not behind"
        eng.step_complete(pend)


class TestChaosWithAsyncLoop:
    def test_poisoned_request_quarantined_pipeline_survives(
        self, tiny_parts
    ):
        """PR 2 lane with the pipeline on: innocents decode pipelined,
        a poisoned submission fails the dispatch, the in-flight step's
        tokens are reconciled (not lost), the poison quarantines, and
        the loop keeps serving."""
        from helix_tpu.serving.engine_loop import EngineLoop

        eng = _make_engine(tiny_parts)
        loop = EngineLoop(eng, name="alp-chaos").start()
        try:
            innocents = {}
            for rid in ("keep-1", "keep-2"):
                col = _Collector()
                innocents[rid] = col
                loop.submit(
                    _req(rid, list(range(4, 10)), max_tokens=48), col
                )
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and not all(
                c.tokens for c in innocents.values()
            ):
                time.sleep(0.02)
            assert all(c.tokens for c in innocents.values())

            faults.arm(
                seed=11,
                rules=[{"point": "engine_step",
                        "request_id_contains": "poison"}],
            )
            poison = _Collector()
            loop.submit(
                _req("poison-1", list(range(30, 36)), max_tokens=8),
                poison,
            )
            assert poison.done.wait(60)
            assert "quarantined" in (poison.error or "")
            for rid, col in innocents.items():
                assert col.done.wait(60), f"{rid} stuck"
                assert col.error is None, f"{rid}: {col.error}"
            assert loop.quarantine_evictions == 1
            faults.disarm()
            after = _Collector()
            loop.submit(
                _req("after-1", list(range(40, 46)), max_tokens=4),
                after,
            )
            assert after.done.wait(60)
            assert after.error is None
        finally:
            faults.disarm()
            loop.stop(join=False)

    @pytest.mark.slow
    def test_preempt_by_swap_under_async_loop(self, tiny_parts):
        """PR 6 lane with the pipeline on: KV exhaustion stalls
        admission, the hog is preempted to host RAM and bit-identically
        resumed — the step in flight is reconciled before the swap-out
        and nothing is dispatched ahead while anything is parked."""
        from helix_tpu.engine.engine import Engine, EngineConfig
        from helix_tpu.serving.engine_loop import EngineLoop

        cfg, params = tiny_parts

        def make_engine():
            return Engine(
                cfg, params,
                EngineConfig(
                    max_decode_batch=4, page_size=4, num_pages=33,
                    max_pages_per_seq=24, max_prefill_len=8,
                    attn_backend="reference",
                    host_pool_bytes=1 << 22,
                ),
            )

        hog_prompt = list(range(4, 12))
        med_prompts = [[10 + 7 * i + j for j in range(8)]
                       for i in range(4)]
        # uncontended greedy references, direct-stepped
        ref_eng = make_engine()
        refs = {}
        for rid, prompt, mt in [("hog", hog_prompt, 300)] + [
            (f"med-{i}", p, 40) for i, p in enumerate(med_prompts)
        ]:
            r = _req("ref-" + rid, prompt, max_tokens=mt)
            ref_eng.add_request(r)
            while ref_eng.has_work():
                ref_eng.step()
            refs[rid] = list(r.output_tokens)

        faults.arm(
            seed=13,
            rules=[{"point": "engine_step", "mode": "slow",
                    "delay": 0.005}],
        )
        loop = EngineLoop(
            make_engine(), "alp-pressure",
            admission_timeout=30.0, preempt_stall_seconds=0.05,
        ).start()
        try:
            cols = {}
            reqs = {"hog": _req("hog", hog_prompt, max_tokens=300)}
            for i, p in enumerate(med_prompts):
                reqs[f"med-{i}"] = _req(f"med-{i}", p, max_tokens=40)
            for rid, req in reqs.items():
                col = _Collector()
                cols[rid] = col
                loop.submit(req, col)
            for rid, col in cols.items():
                assert col.done.wait(120), f"{rid} stuck"
            eng = loop.engine
            for rid, col in cols.items():
                if col.error is not None:
                    assert col.error.startswith("kv_exhausted"), (
                        rid, col.error
                    )
                else:
                    assert col.tokens == refs[rid], (
                        f"{rid}: wrong tokens under pressure"
                    )
            assert cols["hog"].error is None
            assert eng.num_preemptions >= 1
            assert eng.num_resumes >= 1
        finally:
            faults.disarm()
            loop.stop(join=False)

    @pytest.mark.slow
    def test_drain_exports_survivors_async(self, tiny_parts):
        """ISSUE 11 drain lane with the pipeline on: the in-flight step
        reconciles before the drain deadline exports, so the snapshot
        captures the sampler state exactly where generation stopped."""
        from helix_tpu.serving.engine_loop import EngineLoop

        eng = _make_engine(tiny_parts)
        # pin per-step wall time so the request demonstrably outlives
        # the drain window however fast the host is (the PR 6 recipe)
        faults.arm(
            seed=7,
            rules=[{"point": "engine_step", "mode": "slow",
                    "delay": 0.01}],
        )
        loop = EngineLoop(eng, name="alp-drain").start()
        shipped = []
        loop.exporter = lambda wire: shipped.append(wire) or "peer-x"
        col = _Collector()
        mig = _req("mig-1", list(range(4, 10)), max_tokens=5000)
        mig.stop_token_ids = ()   # must still be running at the deadline
        loop.submit(mig, col)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and not col.tokens:
            time.sleep(0.02)
        assert col.tokens, "never started emitting"
        loop.stop(drain=0.2)
        assert col.done.wait(30)
        assert "migrated" in (col.error or ""), col.error
        assert len(shipped) == 1
        assert eng.num_snapshots_exported == 1


class TestHostSyncLintContract:
    """tools/lint_metrics.py contract 9: no stray host syncs in
    engine_loop.py (textual scan + marker allowlist)."""

    def _lint(self):
        import os
        import sys

        sys.path.insert(
            0, os.path.join(os.path.dirname(__file__), "..", "tools")
        )
        try:
            import lint_metrics
        finally:
            sys.path.pop(0)
        return lint_metrics

    def _tree(self, tmp_path, loop_src):
        """Minimal tree the host-sync scan runs over."""
        srv = tmp_path / "helix_tpu" / "serving"
        srv.mkdir(parents=True)
        (srv / "engine_loop.py").write_text(loop_src)
        return str(tmp_path)

    def test_violation_fixture_rejected(self, tmp_path):
        lint = self._lint()
        for bad in (
            "x = jax.device_get(handles)\n",
            "jax.block_until_ready(state)\n",
            "tok = int(np.asarray(token)[0])\n",
        ):
            root = self._tree(tmp_path / bad[:6].strip(), bad)
            vs = lint._host_sync_violations(root)
            assert vs and "re-serializes" in vs[0], (bad, vs)

    def test_marker_allowlists_designated_site(self, tmp_path):
        lint = self._lint()
        root = self._tree(
            tmp_path / "ok",
            "x = jax.device_get(h)  # host-sync-ok: reconcile point\n",
        )
        assert lint._host_sync_violations(root) == []

    def test_repo_engine_loop_is_clean(self):
        import os

        lint = self._lint()
        root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        assert lint._host_sync_violations(root) == []
