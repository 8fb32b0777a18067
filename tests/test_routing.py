"""ISSUE 12 — saturation- and SLO-aware routing, prefix affinity, and
drain-safe autoscaling.

Unit lanes: scored-policy ordering (hard-avoid vs soft-prefer), stale/
missing-saturation neutrality (the 'fresh heartbeat with no saturation
yet looks idle' bugfix), batch-class steering off SLO-burning runners,
affinity-yields-to-saturation, RR parity when the policy is off, the
saturation fault rule, drain-on-assignment, and the lint contract-8
fixtures.

Chaos lane: one runner driven toward KV exhaustion while a scored
router keeps cluster-wide ``kv_exhausted_sheds`` at zero and the RR
baseline sheds under the same load.
"""

from __future__ import annotations

import os
import threading
import time

import pytest

from helix_tpu.control.router import (
    InferenceRouter,
    PrefixAffinity,
    RouterPolicy,
    collect_cp_routing,
    prefix_digest,
    prompt_head,
)


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _router(policy=None, **kw):
    return InferenceRouter(
        clock=FakeClock(),
        policy=policy or RouterPolicy(policy="scored"),
        **kw,
    )


def _hb(router, rid, saturation=None, tenants=None, models=("m",)):
    router.upsert_from_heartbeat(
        rid,
        models=list(models),
        profile_status="running",
        saturation=saturation,
        tenants=tenants,
    )


IDLE = {
    "kv_occupancy": 0.05, "kv_host_occupancy": 0.0,
    "slots_busy": 0, "slots_total": 4, "queue_depth": 0,
    "tokens_per_sec": 10.0, "spec_acceptance_ratio": 0.0,
    "prefill_budget_tokens": 0, "preempted_requests": 0,
    "prefix_hit_rate": 0.0,
}


def _sat(**over):
    return {**IDLE, **over}


class TestScoredRouting:
    def test_soft_prefer_low_queue_and_occupancy(self):
        r = _router()
        _hb(r, "busy", saturation=_sat(kv_occupancy=0.6, queue_depth=8))
        _hb(r, "idle", saturation=_sat())
        for _ in range(4):
            assert r.pick_runner("m").id == "idle"

    def test_hard_avoid_beats_soft_score(self):
        """A runner past the KV avoid threshold loses to ANY un-avoided
        runner, even one with a visibly worse soft score."""
        r = _router()
        _hb(r, "near-full", saturation=_sat(kv_occupancy=0.9))
        _hb(
            r, "loaded",
            saturation=_sat(kv_occupancy=0.5, queue_depth=12,
                            slots_busy=4),
        )
        for _ in range(4):
            assert r.pick_runner("m").id == "loaded"
        assert r.route_hard_avoided > 0

    def test_host_pool_exhaustion_is_an_avoid_signal(self):
        r = _router()
        _hb(r, "host-full", saturation=_sat(kv_host_occupancy=0.95))
        _hb(r, "ok", saturation=_sat(queue_depth=6))
        for _ in range(3):
            assert r.pick_runner("m").id == "ok"

    def test_squeezed_prefill_budget_is_an_avoid_signal(self):
        r = _router()
        # budget floored at 256 = the scheduler's SLO-burn feedback is
        # throttling admission there; 0 means unbudgeted (no signal)
        _hb(r, "throttled", saturation=_sat(prefill_budget_tokens=256))
        _hb(r, "unbudgeted", saturation=_sat(prefill_budget_tokens=0,
                                             queue_depth=5))
        for _ in range(3):
            assert r.pick_runner("m").id == "unbudgeted"

    def test_avoided_runner_is_last_resort_not_unroutable(self):
        r = _router()
        _hb(r, "near-full", saturation=_sat(kv_occupancy=0.9))
        assert r.pick_runner("m").id == "near-full"

    def test_all_full_sheds_at_cp_with_honest_retry_after(self):
        r = _router()
        _hb(r, "a", saturation=_sat(kv_occupancy=0.99, queue_depth=20,
                                    tokens_per_sec=10.0))
        _hb(r, "b", saturation=_sat(kv_occupancy=0.99, queue_depth=20,
                                    tokens_per_sec=10.0))
        assert r.pick_runner("m") is None
        after = r.saturation_retry_after("m")
        # 40 queued tokens-worth over 20 tok/s -> ~3s, clamped [1, 30]
        assert after is not None and 1 <= after <= 30
        assert r.route_saturation_sheds == 1

    def test_one_below_full_means_no_saturation_shed(self):
        r = _router()
        _hb(r, "a", saturation=_sat(kv_occupancy=0.99))
        _hb(r, "b", saturation=_sat(kv_occupancy=0.9))
        assert r.pick_runner("m").id == "b"   # last resort, not a shed
        assert r.saturation_retry_after("m") is None

    def test_rr_policy_never_saturation_sheds(self):
        r = InferenceRouter(clock=FakeClock(), policy=RouterPolicy())
        _hb(r, "a", saturation=_sat(kv_occupancy=0.99))
        assert r.pick_runner("m").id == "a"
        assert r.saturation_retry_after("m") is None


class TestStaleSaturationNeutrality:
    """The satellite bugfix: a runner with a missing or stale saturation
    block must be scored NEUTRAL — it can win against a loaded runner
    but never against one that reports being idle."""

    def test_missing_saturation_never_beats_reported_idle(self):
        r = _router()
        _hb(r, "mute")            # fresh heartbeat, no saturation yet
        _hb(r, "idle", saturation=_sat())
        for _ in range(6):
            assert r.pick_runner("m").id == "idle"
        assert r.route_stale_neutral > 0

    def test_missing_saturation_beats_reported_loaded(self):
        r = _router()
        _hb(r, "mute")
        _hb(
            r, "loaded",
            saturation=_sat(kv_occupancy=0.8, queue_depth=20,
                            slots_busy=4),
        )
        for _ in range(4):
            assert r.pick_runner("m").id == "mute"

    def test_saturation_goes_stale_by_age(self):
        r = _router(policy=RouterPolicy(policy="scored", stale_after=5.0))
        _hb(r, "was-idle", saturation=_sat())
        _hb(r, "idle", saturation=_sat(queue_depth=1))
        # 'was-idle' keeps heartbeating but stops including saturation:
        # its last report ages past stale_after and goes neutral, so the
        # runner that still reports (even slightly loaded) wins
        r.clock.advance(10.0)
        _hb(r, "was-idle")                      # saturation=None: kept
        _hb(r, "idle", saturation=_sat(queue_depth=1))
        for _ in range(4):
            assert r.pick_runner("m").id == "idle"


class TestClassSteering:
    def _two(self):
        r = _router()
        burn = {"top": [{"tenant": "t-hot", "burn_rate_fast": 3.0}]}
        _hb(r, "burning", saturation=_sat(), tenants=burn)
        _hb(r, "calm", saturation=_sat())
        return r

    def test_batch_steered_off_burning_runner(self):
        r = self._two()
        for _ in range(4):
            assert r.pick_runner("m", sched_class="batch").id == "calm"
        assert r.route_class_steered > 0

    def test_interactive_unaffected(self):
        r = self._two()
        picked = {
            r.pick_runner("m", sched_class="interactive").id
            for _ in range(6)
        }
        assert picked == {"burning", "calm"}   # equal scores: RR ties

    def test_steering_is_soft_not_an_avoid(self):
        r = _router()
        burn = {"top": [{"tenant": "t", "burn_rate_fast": 9.0}]}
        _hb(r, "burning", saturation=_sat(), tenants=burn)
        assert r.pick_runner("m", sched_class="batch").id == "burning"


class TestPrefixAffinityRouting:
    def _router(self):
        return _router(
            policy=RouterPolicy(policy="scored", affinity=True)
        )

    def test_affinity_sticks_across_picks(self):
        r = self._router()
        _hb(r, "r1", saturation=_sat())
        _hb(r, "r2", saturation=_sat())
        key = prefix_digest("m", "system:you are helpful")
        first = r.pick_runner("m", affinity_key=key).id
        for _ in range(5):
            assert r.pick_runner("m", affinity_key=key).id == first
        assert r.route_affinity_hits == 5

    def test_affinity_yields_to_saturation(self):
        r = self._router()
        _hb(r, "r1", saturation=_sat())
        _hb(r, "r2", saturation=_sat(queue_depth=2))
        key = prefix_digest("m", "system:shared prompt")
        # seed the hint onto r1 (the better runner right now)
        assert r.pick_runner("m", affinity_key=key).id == "r1"
        # r1 saturates: the hint is a hint, not a pin
        _hb(r, "r1", saturation=_sat(kv_occupancy=0.9))
        assert r.pick_runner("m", affinity_key=key).id == "r2"
        assert r.route_affinity_yields == 1
        # and the map learns the new home
        assert r.pick_runner("m", affinity_key=key).id == "r2"
        assert r.route_affinity_hits >= 1

    def test_affinity_turns_repeats_into_prefix_hits(self):
        """Three shared prompt heads in turn over two runners, each a
        real engine with its prefix cache.  Round robin alternates, so
        an odd number of heads prefills every head on both runners (6
        misses in 15 requests); affinity parks each head on one runner
        and only its first arrival misses (3 in 15)."""
        import jax

        from helix_tpu.engine.engine import Engine, EngineConfig, Request
        from helix_tpu.engine.sampling import SamplingParams
        from helix_tpu.models.common import ModelConfig
        from helix_tpu.models.llama import init_params

        cfg = ModelConfig.tiny(dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(7))
        heads = [
            [(40 * (p + 1) + j) % 250 + 1 for j in range(16)]
            for p in range(3)
        ]

        def serve(policy):
            engines = {
                rid: Engine(cfg, params, EngineConfig(
                    max_decode_batch=2, page_size=4, num_pages=128,
                    max_pages_per_seq=16, max_prefill_len=32,
                    attn_backend="reference", enable_prefix_cache=True,
                ))
                for rid in ("r1", "r2")
            }
            router = InferenceRouter(policy=policy)
            for i in range(15):
                for rid in engines:
                    _hb(router, rid)
                head = heads[i % 3]
                picked = router.pick_runner(
                    "m", affinity_key=prefix_digest("m", str(head))
                )
                eng = engines[picked.id]
                eng.add_request(Request(
                    id=f"q{i}", prompt_tokens=head + [200 + i],
                    sampling=SamplingParams(temperature=0.0, max_tokens=2),
                ))
                while eng.has_work():
                    eng.step()
            return (
                sum(e.prefix_cache_hits for e in engines.values()),
                sum(e.prefix_cache_misses for e in engines.values()),
                router.route_affinity_hits,
            )

        assert serve(RouterPolicy()) == (9, 6, 0)
        assert serve(
            RouterPolicy(policy="scored", affinity=True)
        ) == (12, 3, 12)

    def test_affinity_entry_pruned_with_runner(self):
        r = self._router()
        _hb(r, "r1", saturation=_sat())
        key = prefix_digest("m", "head")
        r.pick_runner("m", affinity_key=key)
        assert len(r._affinity) == 1
        r.remove("r1")
        assert len(r._affinity) == 0

    def test_affinity_off_by_default_ignores_key(self):
        r = _router()   # scored, affinity False
        _hb(r, "r1", saturation=_sat())
        _hb(r, "r2", saturation=_sat())
        key = prefix_digest("m", "head")
        picked = {
            r.pick_runner("m", affinity_key=key).id for _ in range(6)
        }
        assert picked == {"r1", "r2"}
        assert r.route_affinity_hits == 0
        assert len(r._affinity) == 0


class TestPrefixAffinityMap:
    def test_lru_bound(self):
        m = PrefixAffinity(max_entries=2)
        m.put("a", "r1")
        m.put("b", "r1")
        m.get("a")            # refresh: 'b' is now the LRU victim
        m.put("c", "r2")
        assert m.get("a") == "r1"
        assert m.get("b") is None
        assert m.get("c") == "r2"

    def test_forget_runner(self):
        m = PrefixAffinity()
        m.put("a", "r1")
        m.put("b", "r2")
        m.forget_runner("r1")
        assert m.get("a") is None and m.get("b") == "r2"

    def test_digest_and_prompt_head(self):
        chat = {"messages": [{"role": "system", "content": "be brief"},
                             {"role": "user", "content": "hi"}]}
        chat2 = {"messages": [{"role": "system", "content": "be brief"},
                              {"role": "user", "content": "other"}]}
        other = {"messages": [{"role": "system", "content": "be loud"}]}
        k1 = prefix_digest("m", prompt_head(chat))
        assert k1 == prefix_digest("m", prompt_head(chat2))
        assert k1 != prefix_digest("m", prompt_head(other))
        assert k1 != prefix_digest("m2", prompt_head(chat))
        assert prefix_digest("m", prompt_head({"input": "embed"})) is None
        assert prompt_head({"prompt": "tale of"}) == "tale of"


class TestRRParity:
    """Policy off (the default) keeps the seed least-loaded/RR pick
    sequence bit-for-bit, saturation blocks notwithstanding."""

    def test_saturation_ignored_under_rr(self):
        r = InferenceRouter(clock=FakeClock(), policy=RouterPolicy())
        _hb(r, "r1", saturation=_sat(kv_occupancy=0.99, queue_depth=50))
        _hb(r, "r2", saturation=_sat())
        # pure round-robin across both despite r1 reporting saturated
        picks = [r.pick_runner("m").id for _ in range(4)]
        assert picks == ["r1", "r2", "r1", "r2"]

    def test_least_loaded_then_rr_sequence_unchanged(self):
        r = InferenceRouter(clock=FakeClock(), policy=RouterPolicy())
        for rid in ("a", "b", "c"):
            _hb(r, rid, saturation=_sat())
        r.record_dispatch_start("a")   # a now carries one in-flight
        picks = [r.pick_runner("m").id for _ in range(4)]
        # least-loaded = {b, c}; RR cursor walks them
        assert picks == ["b", "c", "b", "c"]

    def test_default_env_policy_is_rr(self):
        assert "HELIX_ROUTER_POLICY" not in os.environ
        assert RouterPolicy.from_env().policy == "rr"
        assert RouterPolicy.from_env().affinity is False


class TestCollectRouting:
    def test_series_render_through_registry(self):
        from helix_tpu import obs

        r = _router(policy=RouterPolicy(policy="scored", affinity=True))
        _hb(r, "r1", saturation=_sat())
        r.pick_runner("m", affinity_key=prefix_digest("m", "x"))
        reg = obs.Registry()
        reg.register_callback(lambda c: collect_cp_routing(c, r))
        text = reg.render()
        assert "helix_cp_route_policy_scored 1" in text
        assert 'helix_cp_route_decisions_total{policy="scored"} 1' in text
        assert "helix_cp_route_affinity_entries 1" in text


class TestSaturationFaultRule:
    def test_override_applied_and_schema_filtered(self):
        from helix_tpu.control.node_agent import NodeAgent
        from helix_tpu.testing import faults

        agent = NodeAgent("r1")
        try:
            faults.arm(rules=[{
                "point": "saturation", "runner": "r1",
                "set": {"kv_occupancy": 0.99, "not_a_key": 5},
            }])
            sat = agent.saturation_summary()
            assert sat["kv_occupancy"] == 0.99
            assert "not_a_key" not in sat
            # rule scoped to r1 only
            other = NodeAgent("r2")
            assert other.saturation_summary()["kv_occupancy"] == 0.0
        finally:
            faults.disarm()
            agent.stop()


class TestDrainOnAssignment:
    def test_drain_request_runs_ladder_then_on_drain(self):
        from helix_tpu.control.node_agent import NodeAgent

        agent = NodeAgent("r1")
        fired = []
        agent.on_drain = lambda: fired.append(True)
        agent._drain_async()
        t = agent._drain_thread
        assert t is not None
        t.join(timeout=10)
        assert agent.draining is True
        assert agent.heartbeat_payload()["draining"] is True
        assert fired == [True]
        # idempotent: a second request must not restart the ladder
        agent._drain_async()
        assert fired == [True]

    def test_graceful_shutdown_idempotent(self):
        from helix_tpu.control.node_agent import NodeAgent

        agent = NodeAgent("r1")
        stats = agent.graceful_shutdown(drain=0.01)
        again = agent.graceful_shutdown(drain=0.01)
        assert stats == again == {}

    def test_assignment_response_carries_drain_flag(self):
        """The cp side of the channel: requesting a drain flips the
        assignment poll's flag; the runner acting on it (heartbeating
        draining=true) clears the request."""
        import asyncio

        from helix_tpu.control.server import ControlPlane

        cp = ControlPlane()
        try:
            cp._request_runner_drain("r9")
            assert "r9" in cp._drain_requested

            async def drive():
                from aiohttp.test_utils import TestClient, TestServer

                app = cp.build_app()
                async with TestClient(TestServer(app)) as client:
                    resp = await client.get(
                        "/api/v1/runners/r9/assignment"
                    )
                    doc = await resp.json()
                    assert doc["drain"] is True
                    # runner announces it is draining -> request served
                    await client.post(
                        "/api/v1/runners/r9/heartbeat",
                        json={"draining": True,
                              "profile": {"models": ["m"],
                                          "status": "running"}},
                    )
                    resp = await client.get(
                        "/api/v1/runners/r9/assignment"
                    )
                    doc = await resp.json()
                    assert doc["drain"] is False

            asyncio.new_event_loop().run_until_complete(drive())
        finally:
            cp.stop()


class TestLintContractRouting:
    def _tree(self, tmp_path, rel_bad: str, extra: str):
        obs = tmp_path / "helix_tpu" / "obs"
        obs.mkdir(parents=True)
        (obs / "flight.py").write_text(
            'SATURATION_KEYS = (\n    "kv_occupancy",\n)\n'
        )
        srv = tmp_path / "helix_tpu" / "serving"
        srv.mkdir(parents=True)
        (srv / "sched.py").write_text(
            'TENANT_QUEUE_FULL = "sched_tenant_queue_full"\n'
            "SCHED_AUDIT_REASONS = (TENANT_QUEUE_FULL,)\n"
        )
        (srv / "migration.py").write_text(
            'MIGRATIONS_EXPORTED = "helix_migrations_exported_total"\n'
        )
        ctl = tmp_path / "helix_tpu" / "control"
        ctl.mkdir(parents=True)
        (ctl / "router.py").write_text(
            'CP_ROUTE_DECISIONS = "helix_cp_route_decisions_total"\n'
        )
        (ctl / "compute.py").write_text(
            'CP_AUTOSCALE_PROVISIONS = '
            '"helix_cp_autoscale_provisions_total"\n'
        )
        bad = tmp_path / rel_bad
        bad.parent.mkdir(parents=True, exist_ok=True)
        bad.write_text(extra)
        return str(tmp_path)

    def test_route_literal_outside_router_rejected(self, tmp_path):
        import tools.lint_metrics as lint

        root = self._tree(
            tmp_path, "helix_tpu/serving/bad.py",
            'N = "helix_cp_route_decisions_total"\n',
        )
        vs = lint.run(root)
        assert any("helix_cp_route_*" in v for v in vs), vs

    def test_autoscale_literal_outside_compute_rejected(self, tmp_path):
        import tools.lint_metrics as lint

        root = self._tree(
            tmp_path, "helix_tpu/control/bad.py",
            'N = "helix_cp_autoscale_drains_total"\n',
        )
        vs = lint.run(root)
        assert any("helix_cp_autoscale_*" in v for v in vs), vs

    def test_server_must_call_both_collectors(self, tmp_path):
        import tools.lint_metrics as lint

        root = self._tree(
            tmp_path, "helix_tpu/control/server.py",
            "# no collector calls here\n",
        )
        vs = lint.run(root)
        assert any("collect_cp_routing" in v for v in vs), vs
        assert any("collect_cp_autoscale" in v for v in vs), vs

    def test_repo_is_clean(self):
        import tools.lint_metrics as lint

        root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        assert lint.run(root) == []


# ---------------------------------------------------------------------------
# chaos lane: graceful degradation under KV pressure (acceptance criteria)
# ---------------------------------------------------------------------------


def _tiny_loop(name, num_pages, admission_timeout=0.3):
    import jax

    from helix_tpu.engine.engine import Engine, EngineConfig
    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import init_params
    from helix_tpu.serving.engine_loop import EngineLoop
    from helix_tpu.serving.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    cfg = ModelConfig.tiny(vocab_size=512, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = Engine(
        cfg, params,
        EngineConfig(
            max_decode_batch=2, page_size=4, num_pages=num_pages,
            max_pages_per_seq=16, max_prefill_len=32,
            attn_backend="reference", eos_token_ids=tok.eos_ids,
        ),
    )
    # no warmup(): the lane only touches two shapes per engine and the
    # slow-step fault makes timing tolerant of first-use compiles; the
    # full rung ladder would double the lane's wall time
    return EngineLoop(
        engine, name, admission_timeout=admission_timeout
    ).start(), tok


@pytest.mark.chaos
class TestRoutingChaosLane:
    """One runner (r1: 8 allocatable KV pages) is driven toward KV
    exhaustion by a pinned hog plus a slow-step fault.  The scored
    router must keep every new dispatch off r1 once it crosses the
    avoid threshold and finish the whole workload with ZERO
    kv_exhausted sheds; the RR baseline dispatches into the exhaustion
    and sheds."""

    HOG_PROMPT = list(range(20, 36))        # 16 tokens = 4 pages
    REQ_PROMPT = list(range(40, 56))        # 16 tokens = 4 pages

    def _run(self, policy: RouterPolicy) -> dict:
        from helix_tpu.engine.engine import Request
        from helix_tpu.engine.sampling import SamplingParams
        from helix_tpu.testing import faults

        r1, tok = _tiny_loop("chaos-r1", num_pages=9)
        r2, _ = _tiny_loop("chaos-r2", num_pages=129)
        loops = {"r1": r1, "r2": r2}
        router = InferenceRouter(policy=policy)

        def beat():
            for rid, loop in loops.items():
                router.upsert_from_heartbeat(
                    rid, models=["m"], profile_status="running",
                    saturation=loop.saturation(),
                )

        outcomes: dict = {}
        done: dict = {}

        def cb_for(rid):
            ev_done = threading.Event()
            done[rid] = ev_done

            def cb(ev):
                if ev.finished:
                    outcomes[rid] = (
                        "error:" + ev.error.split(":")[0]
                        if ev.error else (ev.finish_reason or "stop")
                    )
                    ev_done.set()

            return cb

        picks = []
        try:
            # slow r1's steps so the hog holds its pages long enough
            # for queued requests to age past the admission deadline
            faults.arm(rules=[{
                "point": "engine_step", "engine": "chaos-r1",
                "mode": "slow", "delay": 0.1,
            }])
            # the hog fills r1: 16-token prompt + 14 generated = 30
            # tokens = 8 pages (it FITS — the hog itself must finish;
            # only mis-routed new work can shed)
            r1.submit(
                Request(
                    id="hog", prompt_tokens=list(self.HOG_PROMPT),
                    sampling=SamplingParams(
                        temperature=0.0, max_tokens=14
                    ),
                    stop_token_ids=tok.eos_ids,
                ),
                cb_for("hog"),
            )
            while r1.engine.kv_pages_used < 4:
                time.sleep(0.005)
            # routed traffic: each request needs 5 pages (16 prompt +
            # 2 generated) — it can NEVER fit on r1 beside the hog
            for i in range(4):
                beat()
                st = router.pick_runner("m")
                assert st is not None
                picks.append(st.id)
                rid = f"req-{i}"
                loops[st.id].submit(
                    Request(
                        id=rid,
                        prompt_tokens=list(self.REQ_PROMPT),
                        sampling=SamplingParams(
                            temperature=0.0, max_tokens=2
                        ),
                        stop_token_ids=tok.eos_ids,
                    ),
                    cb_for(rid),
                )
            for rid, ev in done.items():
                assert ev.wait(60), f"stuck request {rid}"
        finally:
            faults.disarm()
            r1.stop(join=False)
            r2.stop(join=False)
        sheds = sum(
            loop.stats()["kv_exhausted_sheds"]
            for loop in loops.values()
        )
        return {
            "picks": picks,
            "outcomes": outcomes,
            "kv_exhausted_sheds": sheds,
        }

    def test_scored_router_zero_sheds_rr_baseline_sheds(self):
        scored = self._run(RouterPolicy(
            policy="scored", kv_avoid_threshold=0.3,
        ))
        # past the avoid threshold r1 receives no new dispatches...
        assert scored["picks"] == ["r2", "r2", "r2", "r2"]
        # ...and the whole workload (hog included) completes cleanly
        assert scored["kv_exhausted_sheds"] == 0
        assert all(
            not o.startswith("error") for o in scored["outcomes"].values()
        ), scored["outcomes"]

        rr = self._run(RouterPolicy())   # the seed baseline
        assert "r1" in rr["picks"]       # RR dispatches into exhaustion
        assert rr["kv_exhausted_sheds"] > 0
        assert any(
            o == "error:kv_exhausted" for o in rr["outcomes"].values()
        ), rr["outcomes"]


@pytest.mark.slow
class TestScaleSoak:
    def test_scale_soak_scenario(self):
        """tools/chaos_soak.py --scenario scale: repeated autoscaler
        scale-downs (graceful drain-then-terminate) under load — zero
        stuck requests, at least one real migration, zero lost tokens
        (combined streams bit-identical to uninterrupted runs)."""
        from tools.chaos_soak import run_scale

        res = run_scale(seconds=8.0, seed=7, scale_every=1.5)
        assert res["stuck"] == []
        assert res["migrated"] >= 1
        assert res["mismatches"] == []
        assert res["lost_tokens"] == 0
        # >= 1 here: the first cycle eats the XLA compile wave on slow
        # hosts; the standalone soak (longer window) shows repetition
        assert res["scale_downs"] >= 1


class TestReviewRegressions:
    """Fixes from the pre-merge review pass."""

    def test_full_excluded_from_ok_pool_under_inverted_thresholds(self):
        # kv_avoid_threshold ABOVE kv_full_threshold: a runner can be
        # full without being avoided — it must still never be picked
        # while an alternative exists, and must shed when alone
        pol = RouterPolicy(
            policy="scored", kv_avoid_threshold=0.995,
            kv_full_threshold=0.98,
        )
        r = _router(policy=pol)
        _hb(r, "full-not-avoided", saturation=_sat(kv_occupancy=0.985))
        _hb(r, "idle", saturation=_sat())
        for _ in range(4):
            assert r.pick_runner("m").id == "idle"
        r2 = _router(policy=pol)
        _hb(r2, "full-not-avoided", saturation=_sat(kv_occupancy=0.985))
        assert r2.pick_runner("m") is None
        assert r2.saturation_retry_after("m") is not None

    def test_rr_affinity_yields_to_load(self):
        # under rr the hint is honoured only while the hinted runner is
        # among the least-loaded — not a pin
        r = InferenceRouter(
            clock=FakeClock(),
            policy=RouterPolicy(affinity=True),
        )
        _hb(r, "r1", saturation=_sat())
        _hb(r, "r2", saturation=_sat())
        key = prefix_digest("m", "popular system prompt")
        first = r.pick_runner("m", affinity_key=key).id
        assert r.pick_runner("m", affinity_key=key).id == first
        # the sticky runner picks up in-flight load: affinity yields
        r.record_dispatch_start(first)
        r.record_dispatch_start(first)
        other = "r2" if first == "r1" else "r1"
        assert r.pick_runner("m", affinity_key=key).id == other
        assert r.route_affinity_yields >= 1


class TestReviewRegressions2:
    def test_multimodal_head_never_serialises_image_bytes(self):
        big = "A" * (4 << 20)   # a base64-image-sized payload
        body = {"messages": [{"role": "user", "content": [
            {"type": "text", "text": "describe this"},
            {"type": "image_url", "image_url": {"url": big}},
        ]}]}
        t0 = time.perf_counter()
        head = prompt_head(body)
        assert time.perf_counter() - t0 < 0.05   # O(1), not O(payload)
        assert "describe this" in head and big[:64] not in head
        # same text+shape, different image bytes -> same affinity key
        body2 = {"messages": [{"role": "user", "content": [
            {"type": "text", "text": "describe this"},
            {"type": "image_url", "image_url": {"url": "B" * 1024}},
        ]}]}
        assert prefix_digest("m", head) == prefix_digest(
            "m", prompt_head(body2)
        )

    def test_token_list_prompt_head_bounded(self):
        head = prompt_head({"prompt": list(range(100_000))})
        assert len(head) <= 512

    def test_stream_path_sheds_kv_saturated(self, monkeypatch):
        """The SSE failover path must answer a fully saturated cluster
        with the typed kv_saturated 503 + honest Retry-After, like the
        non-stream path."""
        import asyncio

        monkeypatch.setenv("HELIX_ROUTER_POLICY", "scored")
        monkeypatch.setenv("HELIX_MIDSTREAM_FAILOVER", "1")
        from helix_tpu.control.server import ControlPlane

        cp = ControlPlane()
        try:
            assert cp.router.policy.policy == "scored"

            async def drive():
                from aiohttp.test_utils import TestClient, TestServer

                app = cp.build_app()
                async with TestClient(TestServer(app)) as client:
                    for rid in ("a", "b"):
                        await client.post(
                            f"/api/v1/runners/{rid}/heartbeat",
                            json={
                                "address": "http://127.0.0.1:1",
                                "profile": {"name": "p",
                                            "status": "running",
                                            "models": ["m"]},
                                "saturation": {"kv_occupancy": 0.99,
                                               "queue_depth": 10,
                                               "tokens_per_sec": 5.0},
                            },
                        )
                    resp = await client.post(
                        "/v1/chat/completions",
                        json={"model": "m", "stream": True,
                              "messages": [{"role": "user",
                                            "content": "hi"}]},
                    )
                    doc = await resp.json()
                    assert resp.status == 503
                    assert doc["error"]["code"] == "kv_saturated", doc
                    assert int(resp.headers["Retry-After"]) >= 1

            asyncio.new_event_loop().run_until_complete(drive())
        finally:
            cp.stop()
