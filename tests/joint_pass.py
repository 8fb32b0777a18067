"""What every model family's tests ask of a step program with a prefill
segment (``engine/engine.py::_build_ragged_step_fn``): its prefill tokens
and its state rows go through the layers in ONE pass.  Helpers only; the
cases live in each family's test file, at that family's tiny size."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from helix_tpu.engine import engine as engine_mod
from helix_tpu.engine.engine import _build_ragged_step_fn, _host_key
from helix_tpu.engine.ragged import PrefillPlan
from helix_tpu.engine.sampling import SamplingParams, SamplingState


def dummy_plan(eng, rung: int, rows: int, with_hist: bool = False):
    """One row filling the rung against the garbage page, as ``warmup()``
    builds it: nothing real advances."""
    ps, maxP = eng.cache_cfg.page_size, eng.cache_cfg.max_pages_per_seq
    plan = PrefillPlan(ps, maxP, rows)
    plan.add(None, np.zeros((maxP,), np.int32), ps if with_hist else 0,
             rung, [0] * rung, _host_key(0), SamplingParams())
    return plan


def step_program(eng, rung: int = 0, rows: int = 0, with_hist: bool = False):
    """``(jitted step, its arguments)`` for a program of ``rows`` prefill
    rows in the bucket ``rung`` (0: the decode-only program) beside the
    engine's decode rows."""
    eng._sync_state()
    pargs = ()
    if rung:
        a = dummy_plan(eng, rung, rows, with_hist).finalize_device(
            rung, with_state=eng.cache.state is not None)
        pargs = (a["tokens"], a["pos"], a["seg"], a["pages"], a["offsets"],
                 a["t0"], a["qlen"], a["hist"], a["tables"], a["ends"],
                 SamplingState.from_params([SamplingParams()] * rows),
                 a["keys"])
        if eng.cache.state is not None:
            pargs += (a["slots"], a["snaps"])
    n_tail = eng._n_tail_max
    if eng.model_cfg.loop_bodies > 2 and rung:
        n_tail = 0
    fn = _build_ragged_step_fn(
        eng.model_cfg, eng.cache_cfg.page_size, eng._backend, eng.mesh,
        rung, with_hist, rows, eng._spec_width(), n_tail, 0, 0, 0, 0)
    args = (eng._graft_params(), eng.cache, eng._dstate, pargs,
            jnp.asarray(eng._zero_drafts), jnp.asarray(eng._zero_rows),
            jnp.int32(0), None)
    return fn, args


def products_outside_the_tail(fn, args, primitive: str, scope: str) -> int:
    """Equations of ``primitive`` under the named scope ``scope`` in the
    step's jaxpr, the fused tail's own forward left out: how many times
    the program's layer bodies hold that product."""
    def walk(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            stack = str(eqn.source_info.name_stack)
            if (eqn.primitive.name == primitive and scope in stack
                    and "tail" not in stack.split("/")):
                n += 1
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        n += walk(sub)
        return n

    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


def assert_one_forward(eng, rung: int, rows: int, primitive: str,
                       scope: str) -> None:
    """A program with a prefill segment holds the products of ONE forward:
    as many as the decode-only program (the two-call form held twice
    that)."""
    alone = products_outside_the_tail(
        *step_program(eng), primitive, scope)
    both = products_outside_the_tail(
        *step_program(eng, rung, rows), primitive, scope)
    assert alone > 0 and both == alone, (alone, both)


def decode_state_of(eng) -> dict:
    st = eng._dstate
    return {k: np.asarray(getattr(st, k)) for k in
            ("positions", "last_token", "keys", "token_counts")}


def assert_inert_wave_keeps_decode_state(eng, rung: int) -> None:
    """A program whose every state row sits out (an admission wave, a chunk
    without live rows) leaves ``DecodeState`` bit for bit: no key split, no
    count, no position."""
    eng._sync_state()
    before = decode_state_of(eng)
    assert before["positions"].any(), "no slot is decoding"
    n = eng.num_joint_pass_steps
    eng._ragged_step("admit", plan=dummy_plan(eng, rung, 1),
                     draft_len=eng._inert_rows, n_extra=0)
    after = decode_state_of(eng)
    for k in before:
        assert np.array_equal(before[k], after[k]), k
    assert eng.num_joint_pass_steps == n + 1
    assert eng.num_joint_pass_inert_rows >= len(before["positions"])


def run_both_ways(make_engine, make_reqs, watch_id: str):
    """The same requests with the mixed step on (a chunk and the live decode
    rows share one program: one pass) and off (the chunk's program with every
    state row inert, then the decode step alone).  Returns ``{mixed: (tokens
    by request, the watched request's logits by tokens out, engine)}``."""
    out = {}
    for mixed in (True, False):
        eng = make_engine(enable_mixed_step=mixed)
        reqs = make_reqs()
        watch = next(r for r in reqs if r.id == watch_id)
        for r in reqs:
            eng.add_request(r)
        logits = {}
        while eng.has_work():
            eng.step()
            n = len(watch.output_tokens)
            if (n and n not in logits and watch.slot is not None
                    and eng.slots[watch.slot] is watch):
                logits[n] = np.asarray(
                    eng.next_token_logits()[watch.slot])
        out[mixed] = ({r.id: list(r.output_tokens) for r in reqs},
                      logits, eng)
    return out


def assert_mixed_is_chunk_then_decode(make_engine, make_reqs, watch_id: str,
                                      tol: float) -> None:
    """Greedy: a program with a chunk and live decode rows gives the tokens
    of the chunk run alone followed by the decode step alone, and the
    watched request's logits within ``tol``."""
    out = run_both_ways(make_engine, make_reqs, watch_id)
    (tok_m, log_m, eng_m), (tok_s, log_s, eng_s) = out[True], out[False]
    assert eng_m.num_mixed_steps > 0 and eng_s.num_mixed_steps == 0
    assert eng_m.num_joint_pass_steps > 0
    assert tok_m == tok_s
    both = sorted(set(log_m) & set(log_s))
    assert len(both) >= 3, (sorted(log_m), sorted(log_s))
    assert max(np.abs(log_m[n] - log_s[n]).max() for n in both) < tol


# -- an admission wave beside running rows -------------------------------

@contextlib.contextmanager
def watched_programs(seen: list):
    """Every step program launched inside records ``{rung, draft_len,
    before, after}``: the ``DecodeState`` and the state pool it was given
    (after the launch's own state sync) and those it handed back."""
    real = engine_mod._build_ragged_step_fn

    def snapshot(state, cache):
        return ({k: np.asarray(getattr(state, k)) for k in
                 ("positions", "last_token", "keys", "token_counts")},
                jax.tree.map(np.asarray, cache.state))

    def build(*a, **kw):
        fn = real(*a, **kw)

        def run(params, cache, state, pargs, drafts, draft_len, *rest):
            before = snapshot(state, cache)     # both are donated below
            out = fn(params, cache, state, pargs, drafts, draft_len, *rest)
            seen.append(dict(rung=a[4], draft_len=np.asarray(draft_len),
                             before=before, after=snapshot(out[1], out[0])))
            return out
        return run

    engine_mod._build_ragged_step_fn = build
    try:
        yield
    finally:
        engine_mod._build_ragged_step_fn = real


def _same_row(rec, slot: int, pool: bool) -> None:
    """Slot ``slot`` left the program as it entered it, bit for bit: its
    ``DecodeState`` row and (``pool``) its row of every state pool."""
    (st0, pool0), (st1, pool1) = rec["before"], rec["after"]
    for k in st0:
        assert np.array_equal(st0[k][slot], st1[k][slot]), (k, slot)
    if pool:
        for p0, p1 in zip(jax.tree.leaves(pool0), jax.tree.leaves(pool1)):
            assert np.array_equal(p0[:, slot], p1[:, slot]), slot


def run_wave_beside_rows(eng, running, short, late, watch):
    """``running`` requests and ``short`` (two tokens to give) are admitted
    and a decode step is launched and left in flight: it exhausts
    ``short``.  Then ``late`` arrives and the next dispatch admits it in a
    wave beside them.  Returns ``(the wave's record with the requests'
    ``slots`` at its launch, tokens by request, {request: {tokens out:
    next-token logits}} for ``watch``)``."""
    assert short.sampling.max_tokens == 2
    reqs = running + [short, late]
    for r in running + [short]:
        eng.add_request(r)
    em1, p1 = eng.step_dispatch()
    assert eng._headroom(short) == 0 and eng.slots[short.slot] is short
    eng.add_request(late)
    seen: list = []
    assert eng.pipeline_ready()
    with watched_programs(seen):
        em2, p2 = eng.step_dispatch()
    waves = [rec for rec in seen if rec["rung"]]
    assert len(waves) == 1 and late.slot is not None, len(waves)
    waves[0]["slots"] = {r.id: r.slot for r in reqs}
    eng.step_complete(p1, em1)
    assert short.finished and len(short.output_tokens) == 2
    eng.step_complete(p2, em2)
    logits = {r.id: {} for r in watch}
    while eng.has_work():
        eng.step()
        for r in watch:
            n = len(r.output_tokens)
            if (n not in logits[r.id] and r.slot is not None
                    and eng.slots[r.slot] is r):
                logits[r.id][n] = np.asarray(
                    eng.next_token_logits()[r.slot])
    return (waves[0], {r.id: list(r.output_tokens) for r in reqs}, logits)


def assert_wave_is_wave_then_decode(make_engine, make_reqs, tol: float):
    """An admission wave beside running rows is the wave alone followed by
    the decode step alone.  ``make_reqs() -> (running, short, late)``.

    The engine as it is, against one whose waves launch every state row
    sitting out (what a wave was before its rows decoded in it): each
    admitted request gets the same first token and every running row the
    same stream (ids equal, sampled rows included: a row's key stream is
    the one it has without the wave), next-token logits within ``tol``.
    In the wave itself the rows that run advance one token; the slot being
    admitted and the row out of headroom keep ``DecodeState`` bit for bit,
    the latter its rows of the state pools too."""
    out = {}
    for live in (True, False):
        eng = make_engine()
        if not live:
            eng._wave_rows = lambda: []
        running, short, late = make_reqs()
        out[live] = run_wave_beside_rows(
            eng, running, short, late, running[:1] + [late]) + (
                eng, running, short, late)
    (wave, toks, logits, eng, running, short, late) = out[True]
    (wave_ref, toks_ref, logits_ref, eng_ref, *_rest) = out[False]
    # what the wave launched, and what it left alone
    slots = wave["slots"]
    ran = sorted(slots[r.id] for r in running)
    assert list(np.flatnonzero(wave["draft_len"] >= 0)) == ran
    assert (wave_ref["draft_len"] == -1).all()
    assert eng.num_wave_decode_tokens == len(ran)
    assert eng_ref.num_wave_decode_tokens == 0
    (st0, _), (st1, _) = wave["before"], wave["after"]
    for i in ran:
        assert st1["positions"][i] == st0["positions"][i] + 1
        assert not np.array_equal(st0["keys"][i], st1["keys"][i])
        assert st1["token_counts"][i].sum() == st0["token_counts"][i].sum() + 1
    _same_row(wave, slots[short.id], pool=True)
    _same_row(wave, slots[late.id], pool=False)
    for i in range(len(eng_ref.slots)):
        if i != wave_ref["slots"][late.id]:
            _same_row(wave_ref, i, pool=True)
    assert toks == toks_ref, (toks, toks_ref)
    assert all(len(t) == r.sampling.max_tokens
               for r in running + [short, late]
               for t in [toks[r.id]]), toks
    for rid in logits:
        both = sorted(set(logits[rid]) & set(logits_ref[rid]))
        assert len(both) >= 3, (rid, sorted(logits[rid]),
                                sorted(logits_ref[rid]))
        assert max(np.abs(logits[rid][n] - logits_ref[rid][n]).max()
                   for n in both) < tol, rid


@contextlib.contextmanager
def launch_spans():
    """The attributes of every ``helix.loop.launch`` span opened inside."""
    from helix_tpu.obs import trace as obs_trace

    seen, orig = [], obs_trace.phase

    def phase(name, *a, **kw):
        if name == "helix.loop.launch":
            seen.append(kw)
        return orig(name, *a, **kw)

    obs_trace.phase = phase
    try:
        yield seen
    finally:
        obs_trace.phase = orig


def windows_a_chunked_prompt_and_a_reused_slot(eng, req, tokens_of):
    """A kind whose fused window keeps tokens beside its pool, through the
    engine: two rows decode in fused windows; a 37-token prompt arrives and
    its three chunks run beside their decode rows (each a window of one, on
    the state the windows committed); it decodes in windows with them; then
    a fourth request reuses the slot of the first to finish.  ``req(id,
    prompt, tokens out)`` and ``tokens_of(n, seed)`` are the family's.
    Returns every request's tokens."""
    reqs = [req("a", tokens_of(9, 1), 18), req("b", tokens_of(7, 2), 6)]
    late = [req("chunked", tokens_of(37, 3), 9),
            req("reuses", tokens_of(6, 4), 8)]
    for r in reqs:
        eng.add_request(r)
    steps = 0
    while eng.has_work() or late:
        eng.step()
        steps += 1
        if late and (steps == 3 if len(late) == 2 else reqs[1].finished):
            reqs.append(late.pop(0))
            eng.add_request(reqs[-1])
        assert steps < 200
    return {r.id: list(r.output_tokens) for r in reqs}
