"""Correctness canaries (ISSUE 19): continuous golden-output probing
per runner, federated health, and corruption-aware routing.

The contract under test everywhere: a canary is an OBSERVER with
teeth.  Probes ride the REAL serving path (EngineLoop.submit under the
reserved ``__canary__`` tenant + batch class) but are invisible to
accounting — never in per-tenant series, usage, burn rates or
autoscale inputs.  Only token-level bit-identity failures move the
health rungs (probe sheds/timeouts are capacity events); health
federates over the existing heartbeat with the PR 7 clamp discipline
(malformed blocks degrade, never reject); and the router's avoid
posture can never strand the last runner serving a model.
"""

from __future__ import annotations

import asyncio
import json
import os
import threading
import time

import jax
import pytest
import requests

from helix_tpu.engine.engine import Engine, EngineConfig
from helix_tpu.models.common import ModelConfig
from helix_tpu.models.llama import init_params
from helix_tpu.obs.canary import (
    CANARY_AXES,
    CANARY_FAILING,
    CANARY_OK,
    CANARY_REPROBING,
    CanaryProber,
    canary_failing,
    mint_prompt,
    probe_axes_for,
    validate_canary_block,
)
from helix_tpu.obs.slo import (
    ANON_TENANT,
    CANARY_TENANT,
    AdmissionAudit,
    SLOObserver,
    sanitize_tenant,
)
from helix_tpu.serving.engine_loop import EngineLoop
from helix_tpu.serving.registry import ModelRegistry, ServedModel
from helix_tpu.serving.tokenizer import ByteTokenizer
from helix_tpu.testing import faults

_TOK = ByteTokenizer()


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig.tiny(dtype="float32", name="m1")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(tiny, **over):
    cfg, params = tiny
    kw = dict(
        max_decode_batch=2, page_size=4, num_pages=64,
        max_pages_per_seq=16, max_prefill_len=64,
        attn_backend="reference",
    )
    kw.update(over)
    return Engine(cfg, params, EngineConfig(**kw))


def _served(tiny, loop_name="m1@r1", **over):
    loop = EngineLoop(_engine(tiny, **over), loop_name)
    loop.start()
    return ServedModel(
        name="m1", loop=loop, tokenizer=_TOK, context_length=256
    )


@pytest.fixture()
def clean_faults():
    faults.disarm()
    yield
    faults.disarm()


# ---------------------------------------------------------------------------
# golden minting: deterministic across restarts
# ---------------------------------------------------------------------------


class TestGoldenMinting:
    def test_mint_prompt_deterministic(self):
        a = mint_prompt("m1", "decode", 256)
        b = mint_prompt("m1", "decode", 256)
        assert a == b and len(a) == 8
        assert all(1 <= t < 256 for t in a)
        # a different axis (or model) mints a different stream
        assert mint_prompt("m1", "prefix", 256) != a
        assert mint_prompt("m2", "decode", 256) != a

    def test_spec_axis_repeats_its_head(self):
        toks = mint_prompt("m1", "spec", 256, length=8)
        assert toks[:4] == toks[4:]

    def test_tiny_vocab_stays_in_range(self):
        toks = mint_prompt("m1", "decode", 2)
        assert set(toks) == {1}

    def test_probe_axes_follow_engine_features(self, tiny):
        served = _served(tiny, "m1@axes")
        try:
            axes = probe_axes_for(served.loop)
            assert "decode" in axes
            # resume is opt-in: never minted without HELIX_CANARY_AXES
            assert "resume" not in axes
            assert set(axes) <= set(CANARY_AXES)
        finally:
            served.loop.stop(join=False)

    def test_minting_deterministic_across_restarts(self, tiny):
        """Two probers on two fresh engines built from the same weights
        (a restarted runner) mint identical prompts AND goldens, so a
        restarted runner's canaries are comparable."""
        goldens = []
        for gen in range(2):
            served = _served(tiny, f"m1@restart{gen}")
            prober = CanaryProber(
                runner_id=f"r{gen}", models_fn=lambda s=served: [s],
                interval=9999, failures=2, backoff=9999,
            )
            try:
                assert prober.mint_models([served]) > 0
                with prober._lock:
                    goldens.append({
                        k: (p.prompt, p.golden)
                        for k, p in prober._probes.items()
                    })
            finally:
                served.loop.stop(join=False)
        assert goldens[0] == goldens[1]

    def test_remint_keeps_existing_goldens(self, tiny):
        """A re-apply is idempotent per (model, axis): a hot-swap
        cannot re-baseline around a live corruption."""
        served = _served(tiny, "m1@remint")
        prober = CanaryProber(
            models_fn=lambda: [served], interval=9999, failures=2,
        )
        try:
            n = prober.mint_models([served])
            assert n > 0
            with prober._lock:
                before = {
                    k: id(p) for k, p in prober._probes.items()
                }
            assert prober.mint_models([served]) == 0
            with prober._lock:
                assert {
                    k: id(p) for k, p in prober._probes.items()
                } == before
        finally:
            served.loop.stop(join=False)

    def test_drop_model_forgets_probes(self, tiny):
        served = _served(tiny, "m1@drop")
        prober = CanaryProber(models_fn=lambda: [served], interval=9999)
        try:
            prober.mint_models([served])
            prober.drop_model("m1")
            assert prober.summary().get("probes", 0) == 0
        finally:
            served.loop.stop(join=False)


# ---------------------------------------------------------------------------
# the reserved tenant: unclaimable, invisible to accounting
# ---------------------------------------------------------------------------


class TestReservedTenant:
    def test_canary_tenant_unclaimable_via_header(self):
        # a hostile X-Helix-Tenant can't impersonate the canary and
        # ride the accounting exclusion for free traffic
        assert sanitize_tenant(CANARY_TENANT) == ANON_TENANT
        assert sanitize_tenant("__canary__") == ANON_TENANT

    def test_canary_mismatch_is_a_typed_audit_reason(self):
        assert "canary_mismatch" in AdmissionAudit.REASONS

    def test_slo_observer_drops_canary_at_the_boundary(self):
        obs = SLOObserver()
        obs.note_first_token(CANARY_TENANT, 0.5, 0.1, 8)
        obs.note_tokens(CANARY_TENANT, 8)
        obs.note_shed(CANARY_TENANT)
        obs.note_preemption(CANARY_TENANT)
        roll = obs.rollup()
        assert roll["top"] == [] and roll["tracked"] == 0
        # a real tenant next to it still lands
        obs.note_tokens("acme", 4)
        names = {e["tenant"] for e in obs.rollup()["top"]}
        assert "acme" in names and CANARY_TENANT not in names


# ---------------------------------------------------------------------------
# probe rounds + health rungs on one live engine loop
# ---------------------------------------------------------------------------


class TestProbeRounds:
    @pytest.fixture()
    def rig(self, tiny, clean_faults):
        served = _served(tiny, "m1@rig")
        prober = CanaryProber(
            runner_id="rig", models_fn=lambda: [served],
            interval=9999, failures=2, backoff=9999,
        )
        assert prober.mint_models([served]) > 0
        yield served, prober
        served.loop.stop(join=False)

    def test_clean_round(self, rig):
        served, prober = rig
        steps0 = served.loop.flight.steps_recorded
        res = prober.probe_round()
        assert res["probes"] > 0
        assert res["mismatched"] == 0 and res["errors"] == 0
        assert prober.state == CANARY_OK
        # what a round costs the engine: each probe is one short greedy
        # request, so at most its token budget in engine steps
        steps = served.loop.flight.steps_recorded - steps0
        assert 0 < steps <= res["probes"] * prober.probe_tokens

    def test_corruption_detected_within_bounded_rounds(self, rig):
        served, prober = rig
        faults.arm(rules=[{
            "point": "corrupt_output", "engine": "m1@rig", "offset": 1,
        }])
        flight0 = served.loop.flight.anomalies_total
        rounds = 0
        while prober.state != CANARY_FAILING:
            res = prober.probe_round()
            rounds += 1
            assert res["mismatched"] > 0
            assert rounds <= prober.failures, (
                "corruption not detected within the failure threshold"
            )
        assert rounds == prober.failures
        # the flight-recorder tail froze with the typed reason
        assert served.loop.flight.anomalies_total > flight0
        snap = served.loop.flight.snapshot()
        reasons = {a["reason"] for a in snap["anomalies"]}
        assert "canary_mismatch" in reasons
        # the typed admission-audit record landed with the trace id
        audit = served.loop.slo.audit.snapshot()
        recs = [r for r in audit["recent"]
                if r["reason"] == "canary_mismatch"]
        assert recs
        assert recs[0]["tenant"] == CANARY_TENANT
        assert recs[0]["trace_id"].startswith("__canary__-m1:")
        # recovery: clean rounds walk failing -> reprobing -> ok
        faults.disarm()
        prober.probe_round()
        assert prober.state == CANARY_REPROBING
        for _ in range(prober.failures):
            prober.probe_round()
        assert prober.state == CANARY_OK

    def test_one_bad_round_does_not_flip_health(self, rig):
        """failures=2: a single mismatched round (a transient) keeps
        the runner routable — the rung threshold is the flake guard."""
        served, prober = rig
        faults.arm(rules=[{
            "point": "corrupt_output", "engine": "m1@rig",
            "offset": 3, "times": 1,
        }])
        prober.probe_round()
        assert prober.state == CANARY_OK
        faults.disarm()
        prober.probe_round()
        assert prober.state == CANARY_OK and prober.mismatches >= 1

    def test_probe_errors_never_move_the_rungs(self, rig):
        """A timeout is a CAPACITY event (the saturation plane's job) —
        it must not brand the runner as emitting wrong tokens."""
        served, prober = rig
        prober.probe_timeout = 0.0
        try:
            for _ in range(prober.failures + 1):
                res = prober.probe_round()
                assert res["errors"] > 0 and res["mismatched"] == 0
            assert prober.state == CANARY_OK
            assert prober.probe_errors >= prober.failures + 1
        finally:
            prober.probe_timeout = 120.0
            # drain the aborted probes so later rounds aren't queued
            # behind them
            deadline = time.monotonic() + 30
            while served.loop.engine.has_work():
                assert time.monotonic() < deadline
                time.sleep(0.05)

    def test_probes_absent_from_tenant_accounting(self, rig):
        served, prober = rig
        prober.probe_round()
        roll = served.loop.slo.rollup()
        assert all(
            e["tenant"] != CANARY_TENANT for e in roll["top"]
        )

    def test_summary_empty_before_mint(self):
        p = CanaryProber(models_fn=lambda: [], interval=9999)
        assert p.summary() == {}

    def test_inflight_subtraction_feeds_the_autoscaler_clean(self, rig):
        """The node agent subtracts prober.inflight from the heartbeat
        queue depth; the counter must return to zero after a round so
        the subtraction never goes stale."""
        served, prober = rig
        prober.probe_round()
        assert prober.inflight == 0


# ---------------------------------------------------------------------------
# wire validation: the PR 7 discipline — clamp, never raise
# ---------------------------------------------------------------------------


class TestWireValidation:
    def _block(self, **over):
        base = {
            "state": "ok", "rounds": 3, "probes": 2, "mismatches": 0,
            "probe_errors": 1, "failing_axes": [],
            "last_round_unix": 1700000000.0,
            "last_ttft_seconds": 0.25,
        }
        base.update(over)
        return base

    def test_roundtrip_through_validation(self, tiny):
        served = _served(tiny, "m1@wire")
        prober = CanaryProber(models_fn=lambda: [served], interval=9999)
        try:
            prober.mint_models([served])
            prober.probe_round()
            out = validate_canary_block(prober.summary())
            assert out["state"] == CANARY_OK
            assert out["rounds"] == 1 and out["probes"] >= 1
        finally:
            served.loop.stop(join=False)

    @pytest.mark.parametrize("raw", [
        None, 42, "garbage", [1, 2], {},
        {"state": "evil{label}"}, {"state": 7}, {"state": None},
        {"state": "helix_evil_ \x00"},
    ])
    def test_malformed_degrades_to_absent(self, raw):
        assert validate_canary_block(raw) == {}
        assert not canary_failing(validate_canary_block(raw))

    def test_nan_and_negative_counters_clamp(self):
        out = validate_canary_block(self._block(
            rounds=float("nan"), mismatches=-5,
            probe_errors=float("inf"), probes=True,
            last_round_unix=float("nan"),
            last_ttft_seconds=-1.0,
        ))
        assert out["rounds"] == 0 and out["mismatches"] == 0
        assert out["probe_errors"] == 0 and out["probes"] == 0
        assert out["last_round_unix"] == 0.0
        assert out["last_ttft_seconds"] == 0.0

    def test_axis_bomb_bounded(self):
        out = validate_canary_block(self._block(
            failing_axes=[f"m:{i}" for i in range(500)]
            + ["bad space", "x" * 500, 42, None],
        ))
        assert len(out["failing_axes"]) <= 16
        for a in out["failing_axes"]:
            assert len(a) <= 96 and " " not in a

    def test_failing_states_route_avoid(self):
        assert canary_failing({"state": CANARY_FAILING})
        assert canary_failing({"state": CANARY_REPROBING})
        assert not canary_failing({"state": CANARY_OK})
        assert not canary_failing({})
        assert not canary_failing(None)


# ---------------------------------------------------------------------------
# router: corruption-aware avoid + the last-runner rule
# ---------------------------------------------------------------------------


class TestRouterCanaryAvoid:
    def _router(self, avoid=True):
        from helix_tpu.control.router import (
            InferenceRouter,
            RouterPolicy,
        )

        return InferenceRouter(
            policy=RouterPolicy(canary_avoid=avoid)
        )

    def _beat(self, router, rid, state=None):
        canary = None
        if state is not None:
            canary = {"state": state, "rounds": 1, "probes": 1,
                      "mismatches": 0, "probe_errors": 0,
                      "failing_axes": [], "last_round_unix": 0.0,
                      "last_ttft_seconds": 0.0}
        router.upsert_from_heartbeat(
            rid, models=["m1"], profile_name="p",
            profile_status="running", canary=canary,
        )

    def test_failing_runner_hard_avoided(self):
        router = self._router()
        self._beat(router, "r1", CANARY_OK)
        self._beat(router, "r2", CANARY_FAILING)
        for _ in range(8):
            st = router.pick_runner("m1")
            assert st is not None and st.id == "r1"
        assert router.route_canary_avoided == 8
        assert router.route_canary_served_failing == 0

    def test_reprobing_also_avoided(self):
        router = self._router()
        self._beat(router, "r1", CANARY_OK)
        self._beat(router, "r2", CANARY_REPROBING)
        assert all(router.pick_runner("m1").id == "r1"
                   for _ in range(4))

    def test_last_runner_served_with_warning(self):
        """The satellite-2 rule: avoid must not strand the LAST runner
        for a model — serve, count, log (mirrors all-candidates-full)."""
        router = self._router()
        self._beat(router, "r1", CANARY_FAILING)
        st = router.pick_runner("m1", trace_id="trace-warn-0001")
        assert st is not None and st.id == "r1"
        assert router.route_canary_served_failing == 1
        assert router.route_canary_avoided == 0

    def test_all_failing_still_serves(self):
        router = self._router()
        self._beat(router, "r1", CANARY_FAILING)
        self._beat(router, "r2", CANARY_FAILING)
        assert router.pick_runner("m1") is not None
        assert router.route_canary_served_failing == 1

    def test_never_probed_runner_stays_routable(self):
        router = self._router()
        self._beat(router, "r1", None)   # no canary block at all
        assert router.pick_runner("m1") is not None
        assert router.route_canary_served_failing == 0

    def test_avoid_off_by_default(self):
        router = self._router(avoid=False)
        self._beat(router, "r1", CANARY_FAILING)
        self._beat(router, "r2", CANARY_OK)
        picked = {router.pick_runner("m1").id for _ in range(8)}
        assert picked == {"r1", "r2"}   # rr spreads over both
        assert router.route_canary_avoided == 0

    def test_canary_map_bounded_to_reporting_runners(self):
        router = self._router()
        self._beat(router, "r1", CANARY_OK)
        self._beat(router, "r2", None)
        assert set(router.canary_map()) == {"r1"}


# ---------------------------------------------------------------------------
# the full HTTP spine: two runners + cp, injected corruption on one
# ---------------------------------------------------------------------------


def _serve_app(app, holder):
    started = threading.Event()
    box = {}

    def run():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        from aiohttp import web

        runner = web.AppRunner(app)
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", 0)
        loop.run_until_complete(site.start())
        box["port"] = site._server.sockets[0].getsockname()[1]
        holder.setdefault("loops", []).append(loop)
        started.set()
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    assert started.wait(10)
    return box["port"]


@pytest.fixture(scope="module")
def canarypools(tiny):
    """Two runners serving the same model + a cp with canary-avoid
    routing armed.  Each runner has its OWN CanaryProber (as on real
    hosts): the only way its health reaches the cp is the heartbeat."""
    from helix_tpu.control.server import ControlPlane
    from helix_tpu.serving.openai_api import OpenAIServer

    faults.disarm()
    prior = os.environ.get("HELIX_ROUTER_CANARY_AVOID")
    os.environ["HELIX_ROUTER_CANARY_AVOID"] = "1"
    holder: dict = {}
    sides = {}
    for side in ("r1", "r2"):
        registry = ModelRegistry()
        served = _served(tiny, f"m1@{side}", max_decode_batch=4,
                         num_pages=128, max_pages_per_seq=32)
        registry.register(served)
        prober = CanaryProber(
            runner_id=side, models_fn=lambda s=served: [s],
            interval=9999, failures=2, backoff=9999,
        )
        # golden mint happens at profile apply — BEFORE any corruption
        assert prober.mint_models([served]) > 0
        api = OpenAIServer(registry)
        port = _serve_app(api.build_app(), holder)
        sides[side] = {
            "served": served, "prober": prober, "api": api,
            "url": f"http://127.0.0.1:{port}",
        }
    cp = ControlPlane()
    assert cp.router.policy.canary_avoid
    cp_port = _serve_app(cp.build_app(), holder)
    cp_url = f"http://127.0.0.1:{cp_port}"

    def heartbeat(rid, raw=None):
        side = sides[rid]
        body = {
            "runner_id": rid,
            "address": side["url"],
            "accelerators": [],
            "profile": {"name": "p", "status": "running",
                        "models": ["m1"]},
            "saturation": {},
            "tenants": side["served"].loop.slo.rollup(),
            "canary": (
                raw if raw is not None else side["prober"].summary()
            ),
        }
        r = requests.post(
            f"{cp_url}/api/v1/runners/{rid}/heartbeat",
            data=json.dumps(body, allow_nan=True),
            headers={"Content-Type": "application/json"},
            timeout=10,
        )
        assert r.status_code == 200, r.text
        return r

    heartbeat("r1")
    heartbeat("r2")
    from types import SimpleNamespace

    yield SimpleNamespace(
        sides=sides, cp=cp, cp_url=cp_url, heartbeat=heartbeat,
    )
    faults.disarm()
    if prior is None:
        os.environ.pop("HELIX_ROUTER_CANARY_AVOID", None)
    else:
        os.environ["HELIX_ROUTER_CANARY_AVOID"] = prior
    cp.stop()
    for side in sides.values():
        side["served"].loop.stop(join=False)
    for lp in holder.get("loops", []):
        lp.call_soon_threadsafe(lp.stop)


_MSG = [{"role": "user", "content": "probe the goldens, route around"}]


def _stream(url, tid=""):
    content = []
    headers = {"X-Helix-Trace-Id": tid} if tid else {}
    with requests.post(
        f"{url}/v1/chat/completions",
        json={"model": "m1", "temperature": 0, "max_tokens": 16,
              "stream": True, "messages": _MSG},
        headers=headers, stream=True, timeout=120,
    ) as r:
        assert r.status_code == 200, r.text
        for line in r.iter_lines():
            if not line or not line.startswith(b"data:"):
                continue
            payload = line[5:].strip()
            if payload == b"[DONE]":
                break
            doc = json.loads(payload)
            assert "error" not in doc, doc
            delta = doc["choices"][0]["delta"].get("content", "")
            if delta:
                content.append(delta)
    return "".join(content)


class TestCanaryHTTPSpine:
    def test_corruption_detected_steered_and_bit_identical(
        self, canarypools
    ):
        """The tentpole acceptance: inject silent output corruption on
        one of two runners; the canary detects it within bounded probe
        rounds, the cp status + metrics flip, the router steers
        foreground to the healthy peer, and foreground streams stay
        bit-identical to the healthy runner's output."""
        pools = canarypools
        golden = _stream(pools.sides["r1"]["url"])
        assert golden
        faults.arm(rules=[{
            "point": "corrupt_output", "engine": "m1@r2", "offset": 1,
        }])
        # both probers run their rounds (the node-agent timer, driven
        # by hand for determinism); detection is bounded by the rung
        # threshold
        r2 = pools.sides["r2"]["prober"]
        for n in range(r2.failures):
            assert pools.sides["r1"]["prober"].probe_round()[
                "mismatched"] == 0
            assert r2.probe_round()["mismatched"] > 0
        assert r2.state == CANARY_FAILING
        assert pools.sides["r1"]["prober"].state == CANARY_OK
        pools.heartbeat("r1")
        pools.heartbeat("r2")

        # the cp canary block flips
        doc = requests.get(
            f"{pools.cp_url}/v1/cluster/status", timeout=10
        ).json()
        blk = doc["canary"]
        assert blk["router_avoid"] is True
        assert "r2" in blk["failing"] and "r1" in blk["ok"]
        by_id = {r["id"]: r for r in doc["runners"]}
        assert by_id["r2"]["canary"]["state"] == CANARY_FAILING
        assert by_id["r2"]["canary"]["mismatches"] >= 1

        # the helix_cp_canary_* family renders per runner
        metrics = requests.get(
            f"{pools.cp_url}/metrics", timeout=10
        ).text
        assert 'helix_cp_canary_state{runner="r2"} 2' in metrics
        assert 'helix_cp_canary_state{runner="r1"} 0' in metrics
        assert "helix_cp_canary_failing_runners 1" in metrics
        assert "helix_cp_canary_mismatches_total" in metrics

        # foreground steers to the healthy peer and stays bit-identical
        # (r2 would emit offset tokens — identity proves the steer)
        for _ in range(4):
            assert _stream(pools.cp_url, "trace-canary-0001") == golden
        doc = requests.get(
            f"{pools.cp_url}/v1/cluster/status", timeout=10
        ).json()
        assert doc["canary"]["avoided"] >= 4
        faults.disarm()

    def test_runner_metrics_surface(self, canarypools):
        pools = canarypools
        # the runner surface renders only when a default prober is
        # registered (node-agent start()); register ours for the scrape
        from helix_tpu.obs.canary import set_default_prober

        set_default_prober(pools.sides["r1"]["prober"])
        try:
            text = requests.get(
                f"{pools.sides['r1']['url']}/metrics", timeout=10
            ).text
            for fam in (
                "helix_canary_state",
                "helix_canary_rounds_total",
                "helix_canary_probes_total",
                "helix_canary_mismatches_total",
                "helix_canary_probe_errors_total",
                "helix_canary_last_probe_ttft_seconds",
            ):
                assert fam in text, fam
        finally:
            set_default_prober(None)

    def test_hostile_canary_blocks_degrade_without_500(
        self, canarypools
    ):
        """A compromised runner heartbeats garbage canary health: the
        heartbeat still succeeds, nothing leaks into /metrics or the
        status surface, and garbage can never flip routing."""
        pools = canarypools
        poison = 'helix_evil_{label="x"}'
        for hostile in (
            "junk",
            {"state": poison},
            {"state": float("nan")},
            {"state": "failing", "rounds": float("nan"),
             "mismatches": -3,
             "failing_axes": [poison + " 1"] * 5000},
            {"state": "failing",
             "failing_axes": ["x" * 100000]},
        ):
            pools.heartbeat("r2", raw=hostile)
        metrics = requests.get(
            f"{pools.cp_url}/metrics", timeout=10
        ).text
        assert "helix_evil_" not in metrics
        doc = requests.get(
            f"{pools.cp_url}/v1/cluster/status", timeout=10
        ).json()
        assert poison not in json.dumps(doc)
        # the last hostile block had a VALID state with a bounded axis
        # clamp — counters degraded to 0, axes dropped, still failing
        blk = doc["runners"]
        by_id = {r["id"]: r for r in blk}
        canary = by_id["r2"].get("canary", {})
        if canary:
            assert canary.get("rounds", 0) >= 0
            for a in canary.get("failing_axes", []):
                assert len(a) <= 96
        # restore honest health for later tests
        pools.heartbeat("r2")

    def test_canary_absent_from_usage_and_autoscale_signals(
        self, canarypools
    ):
        """Satellite 1: probe traffic is provably absent from the
        federated per-tenant usage surface and the autoscaler's
        cluster signals."""
        pools = canarypools
        # a real tenant for contrast
        pools.sides["r1"]["served"].loop.slo.note_tokens("acme", 4)
        pools.heartbeat("r1")
        pools.heartbeat("r2")
        usage = requests.get(
            f"{pools.cp_url}/v1/tenants/usage", timeout=10
        ).json()
        names = {e["tenant"] for e in usage["tenants"]}
        assert CANARY_TENANT not in names
        assert "acme" in names
        sig = pools.cp._cluster_signals()
        # probers are idle between rounds: nothing canary-shaped in the
        # queue-depth the autoscaler reads (the node agent additionally
        # subtracts in-flight probes at the source)
        assert sig["queue_depth"] == 0.0
        text = requests.get(
            f"{pools.cp_url}/metrics", timeout=10
        ).text
        assert CANARY_TENANT not in text


# ---------------------------------------------------------------------------
# lint contract 14 fixtures: one minting site for the canary families
# ---------------------------------------------------------------------------


class TestLintContract14:
    _COPIES = (
        "helix_tpu/obs/flight.py",
        "helix_tpu/obs/trace.py",
        "helix_tpu/obs/canary.py",
        "helix_tpu/serving/sched.py",
        "helix_tpu/serving/migration.py",
        "helix_tpu/serving/kv_filestore.py",
        "helix_tpu/serving/engine_loop.py",
        "helix_tpu/serving/openai_api.py",
        "helix_tpu/control/node_agent.py",
        "helix_tpu/control/server.py",
        "helix_tpu/control/router.py",
        "helix_tpu/control/compute.py",
    )

    def _tree(self, tmp_path, rel=None, extra=None, skip=()):
        import shutil

        root = tmp_path
        for sub in ("helix_tpu/obs", "helix_tpu/serving",
                    "helix_tpu/control", "tools"):
            (root / sub).mkdir(parents=True, exist_ok=True)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for f in self._COPIES:
            if f in skip:
                continue
            shutil.copy(os.path.join(repo, f), root / f)
        if rel is not None:
            (root / rel).write_text(extra)
        return str(root)

    def _lint(self, root):
        import importlib.util

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "lint_metrics_canary_test",
            os.path.join(repo, "tools", "lint_metrics.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.run(root)

    def test_runner_canary_literal_outside_module_rejected(
        self, tmp_path
    ):
        root = self._tree(
            tmp_path, "helix_tpu/serving/rogue.py",
            'X = "helix_canary_mismatches_total"\n',
        )
        assert any("correctness-canary" in v for v in self._lint(root))

    def test_cp_canary_literal_outside_module_rejected(self, tmp_path):
        root = self._tree(
            tmp_path, "helix_tpu/control/rogue.py",
            'X = "helix_cp_canary_state"\n',
        )
        assert any("correctness-canary" in v for v in self._lint(root))

    def test_importer_pattern_enforced(self, tmp_path):
        root = self._tree(tmp_path)
        # strip the importer call from the runner /metrics surface
        path = os.path.join(
            root, "helix_tpu", "serving", "openai_api.py"
        )
        with open(path, encoding="utf-8") as f:
            src = f.read()
        with open(path, "w", encoding="utf-8") as f:
            f.write(src.replace("collect_canary_metrics", "c_c_m"))
        assert any("collect_canary_metrics" in v
                   for v in self._lint(root))

    def test_missing_module_rejected(self, tmp_path):
        root = self._tree(tmp_path, skip=("helix_tpu/obs/canary.py",))
        assert any(
            "canary.py: missing" in v for v in self._lint(root)
        )

    def test_repo_is_clean(self):
        import importlib.util

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "lint_metrics_canary_clean",
            os.path.join(repo, "tools", "lint_metrics.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.run(repo) == []
