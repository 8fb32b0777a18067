"""Multi-host plan-broadcast serving: followers execute the leader's
step plans and produce bit-identical state (ISSUE 16).

The real deployment runs one process per host over a global mesh; here
leader and follower engines live in one process (same config + seed),
which exercises exactly the property SPMD lockstep needs: identical
plan sequences produce identical jit sequences and identical tokens —
with every perf feature (spec decode, adapters, WFQ, preemption, the
async pipeline) enabled, because plans pin host decisions as data
instead of forbidding them.
"""

import threading
import time
import zlib

import jax
import jax.numpy as jnp
import pytest

from helix_tpu.engine import ragged as ragged_meta
from helix_tpu.engine.engine import Engine, EngineConfig, Request
from helix_tpu.engine.sampling import SamplingParams
from helix_tpu.models.common import ModelConfig
from helix_tpu.models.llama import init_params
from helix_tpu.serving.multihost_serving import (
    CHECKPOINT_VERSION,
    FOLLOWER_HEALTHY,
    FOLLOWER_LAGGING,
    RESYNC_HANDOFF_MISMATCH,
    RESYNC_LEADER_RESTART,
    RESYNC_RING_OVERFLOW,
    WIRE_VERSION,
    CheckpointError,
    CheckpointStore,
    CommandLog,
    FollowerLoop,
    LagError,
    LocalFeed,
    LockstepLeader,
    PlanLeader,
    ResyncRequired,
    WireVersionError,
    cold_start_leader,
    promote_follower,
    request_from_wire,
    request_to_wire,
)
from helix_tpu.testing import faults


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig.tiny(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def _engine(tiny):
    cfg, params = tiny
    return Engine(
        cfg, params,
        EngineConfig(
            max_decode_batch=2, page_size=4, num_pages=64,
            max_pages_per_seq=16, max_prefill_len=16,
            attn_backend="reference",
        ),
    )


def _drain(leader, max_steps=400):
    steps = 0
    while leader.engine.has_work():
        leader.step()
        steps += 1
        assert steps < max_steps
    return steps


def _replay(follower):
    while follower.run_once():
        pass


class TestWire:
    def test_request_roundtrip_carries_scheduling_fields(self):
        req = Request(
            id="r1", prompt_tokens=[1, 2, 3],
            sampling=SamplingParams(temperature=0.7, top_k=5, seed=9),
            stop_token_ids=(0,),
            tenant="acme", sched_class="batch", adapter="a1",
            max_len=77, trace_id="t" * 8,
        )
        doc = request_to_wire(req)
        assert doc["v"] == WIRE_VERSION
        back = request_from_wire(doc)
        assert back.id == "r1" and back.prompt_tokens == [1, 2, 3]
        assert back.sampling == req.sampling
        assert back.stop_token_ids == (0,)
        # the v1 journal dropped these four; v2 must carry them so the
        # follower's engine charges the same tenant/class/adapter state
        assert back.tenant == "acme"
        assert back.sched_class == "batch"
        assert back.adapter == "a1"
        assert back.max_len == 77
        assert back.trace_id == "t" * 8

    def test_old_wire_version_rejected_typed(self):
        doc = request_to_wire(
            Request(id="r", prompt_tokens=[1],
                    sampling=SamplingParams(max_tokens=2))
        )
        doc["v"] = 1
        with pytest.raises(WireVersionError, match="upgrade the leader"):
            request_from_wire(doc)
        with pytest.raises(WireVersionError):
            request_from_wire({**doc, "v": None})

    def test_old_plan_record_rejected_typed(self, tiny):
        follower = FollowerLoop(_engine(tiny), CommandLog())
        with pytest.raises(WireVersionError, match="plan record version"):
            follower.apply({"v": 1, "kind": "plan", "step": 0, "seq": 1})

    def test_vl_requests_rejected(self):
        req = Request(id="r", prompt_tokens=[1], image_embeds=object())
        with pytest.raises(ValueError, match="multi-host"):
            request_to_wire(req)


class TestPlanBroadcast:
    def test_follower_reproduces_sampled_tokens(self, tiny):
        leader = PlanLeader(_engine(tiny))
        fe = _engine(tiny)
        follower = FollowerLoop(fe, leader.journal)
        # sampled generation WITHOUT explicit seeds: the leader pins them
        reqs = [
            Request(id=f"r{i}", prompt_tokens=[3 + i, 5, 8],
                    sampling=SamplingParams(temperature=0.8, top_k=20,
                                            max_tokens=6))
            for i in range(3)
        ]
        for r in reqs:
            leader.add_request(r)
        steps = _drain(leader)
        _replay(follower)
        assert follower.steps == steps == leader.plans_published
        for r in reqs:
            assert fe._requests[r.id].output_tokens == r.output_tokens
            assert fe._requests[r.id].finished
        # emission digests verified every plan after the first
        assert follower.stats()["digest_checks"] >= steps - 1
        assert follower.stats()["digest_mismatches"] == 0

    def test_plan_size_follows_the_admission_wave_not_the_history(
        self, tiny
    ):
        """What crosses hosts a step: the plan that admits the wave
        carries the requests; a steady decode plan carries no admits
        and no drafts, so five times the generation is five times the
        plans of the same small size, never a larger one."""

        def publish(max_tokens):
            leader = PlanLeader(_engine(tiny))
            for i in range(2):
                leader.add_request(Request(
                    id=f"r{i}", prompt_tokens=[3 + i, 5, 8],
                    sampling=SamplingParams(temperature=0.0,
                                            max_tokens=max_tokens),
                ))
            steps = _drain(leader)
            assert leader.plans_published == steps
            return leader

        short, long = publish(6), publish(30)
        assert long.plans_published > 4 * short.plans_published
        # the admitting plan is the largest, and the same in both runs
        # up to the digits of max_tokens
        assert abs(long.plan_bytes_max - short.plan_bytes_max) <= 4
        steady = (long.plan_bytes_total - long.plan_bytes_max) / (
            long.plans_published - 1
        )
        assert steady < long.plan_bytes_max / 3

    def test_a_follower_replays_a_wave_with_the_leaders_live_rows(
            self, tiny):
        """A wave's running rows decode a token in it: which rows is a
        host decision, so the plan carries each wave's live slots and the
        follower launches those; a plan naming a slot that does not run
        on the replica is a divergence, not a guess."""
        from helix_tpu.serving.multihost_serving import PlanDrive

        leader = PlanLeader(_engine(tiny))
        fe = _engine(tiny)
        follower = FollowerLoop(fe, leader.journal)
        reqs = [
            Request(id=f"r{i}", prompt_tokens=[3 + i, 5, 8],
                    sampling=SamplingParams(temperature=0.8, top_k=20,
                                            max_tokens=5 + 4 * i))
            for i in range(4)
        ]
        for r in reqs:
            leader.add_request(r)
        _drain(leader)
        plans = [rec for rec in leader.journal._records
                 if rec.get("kind") == "plan"]
        waves = [rows for rec in plans for rows in rec["wave_rows"]]
        # two slots: the first wave admits into an idle engine, the later
        # ones beside the one row still running
        assert waves[0] == [] and any(waves[1:]), waves
        assert leader.engine.num_wave_decode_tokens == sum(map(len, waves))
        _replay(follower)
        assert fe.num_wave_decode_tokens == (
            leader.engine.num_wave_decode_tokens)
        for r in reqs:
            assert fe._requests[r.id].output_tokens == r.output_tokens
        assert follower.stats()["digest_mismatches"] == 0
        # the leader's set is replayed, never re-derived: a slot that
        # does not run here is a divergence, and beside a row that runs a
        # wave the plan has no set for (admissions carried over from a
        # discarded plan) launches it sitting out
        for wave_rows, decoded in (([[1]], None), ([], 0), ([[0]], 1)):
            other = _engine(tiny)
            for rid in ("x", "y"):
                other.add_request(Request(
                    id=rid, prompt_tokens=[4, 5, 6],
                    sampling=SamplingParams(temperature=0.0, max_tokens=4)))
                other._plan_drive = PlanDrive(
                    budget=None, queue_blocked=False, drafts=[], resumes=[],
                    cached_tokens={},
                    wave_rows=wave_rows if rid == "y" else [])
                if rid == "y" and decoded is None:
                    with pytest.raises(
                            RuntimeError, match="plan-follow divergence"):
                        other.step()
                else:
                    other.step()
            assert other.num_wave_decode_tokens == (decoded or 0)
        # a set of the leader's that no wave here took is one too
        lost = dict(plans[0], wave_rows=[[], [0]], seq=0, step=0,
                    digest=None, digest_step=None)
        with pytest.raises(Exception, match="admission waves not launched"):
            FollowerLoop(_engine(tiny), leader.journal).apply(lost)

    def test_greedy_bit_identity(self, tiny):
        leader = PlanLeader(_engine(tiny))
        fe = _engine(tiny)
        follower = FollowerLoop(fe, leader.journal)
        req = Request(id="g", prompt_tokens=[2, 4, 6],
                      sampling=SamplingParams(temperature=0.0,
                                              max_tokens=8))
        leader.add_request(req)
        _drain(leader)
        _replay(follower)
        assert fe._requests["g"].output_tokens == req.output_tokens

    def test_abort_replicates_via_ops_record(self, tiny):
        leader = PlanLeader(_engine(tiny))
        fe = _engine(tiny)
        follower = FollowerLoop(fe, leader.journal)
        a = Request(id="a", prompt_tokens=[1, 2],
                    sampling=SamplingParams(max_tokens=50))
        b = Request(id="b", prompt_tokens=[2, 3],
                    sampling=SamplingParams(max_tokens=50))
        leader.add_request(a)
        leader.add_request(b)
        leader.step()
        leader.abort("a")
        _drain(leader)
        _replay(follower)
        assert fe._requests["a"].finished
        assert fe._requests["b"].output_tokens == b.output_tokens
        assert follower.stats()["digest_mismatches"] == 0

    def test_abort_after_final_step_still_reaches_followers(self, tiny):
        """Ops records publish at arrival, not at the next dispatch: an
        abort with no step behind it must still kill the follower's copy
        (the command-replay design leaked exactly this zombie)."""
        leader = PlanLeader(_engine(tiny))
        req = Request(id="tail", prompt_tokens=[5, 6],
                      sampling=SamplingParams(max_tokens=50))
        leader.add_request(req)
        for _ in range(3):
            leader.step()
        leader.abort("tail")      # nothing left to step afterwards
        assert not leader.engine.has_work()
        fe = _engine(tiny)
        follower = FollowerLoop(fe, leader.journal)
        _replay(follower)
        assert fe._requests["tail"].finished

    def test_reaped_waiting_requests_never_broadcast(self, tiny):
        """The reaper scans the waiting queue only; waiting requests are
        never admitted, so followers never hear about them at all."""
        leader = PlanLeader(_engine(tiny))
        a = Request(id="a", prompt_tokens=[1, 2],
                    sampling=SamplingParams(max_tokens=30))
        b = Request(id="b", prompt_tokens=[2, 3],
                    sampling=SamplingParams(max_tokens=30))
        leader.add_request(a)
        leader.add_request(b)
        leader.step()             # a, b admitted (batch of 2)
        c = Request(id="c", prompt_tokens=[4],
                    sampling=SamplingParams(max_tokens=5))
        leader.add_request(c)     # queued behind the full batch
        c.submit_time -= 10_000
        reaped = leader.reap_stuck(1.0)
        assert [r.id for r in reaped] == ["c"]
        _drain(leader)
        fe = _engine(tiny)
        follower = FollowerLoop(fe, leader.journal)
        _replay(follower)
        assert "c" not in fe._requests
        assert fe._requests["a"].output_tokens == a.output_tokens

    def test_background_follower_thread(self, tiny):
        leader = PlanLeader(_engine(tiny))
        fe = _engine(tiny)
        follower = FollowerLoop(fe, leader.journal,
                                poll_timeout=0.2).start()
        req = Request(id="x", prompt_tokens=[1, 2, 3],
                      sampling=SamplingParams(temperature=0.0,
                                              max_tokens=4))
        leader.add_request(req)
        _drain(leader)
        deadline = time.time() + 10
        while time.time() < deadline:
            fr = fe._requests.get("x")
            if fr is not None and fr.finished:
                break
            time.sleep(0.05)
        follower.stop()
        assert fe._requests["x"].output_tokens == req.output_tokens

    def test_legacy_alias_still_importable(self, tiny):
        assert LockstepLeader is PlanLeader


POOL_ECFG = dict(
    max_decode_batch=3, page_size=4, num_pages=64, max_pages_per_seq=16,
    max_prefill_len=32, attn_backend="reference",
    adapter_pool_slots=3, adapter_rank=4,
    enable_spec_decode=True, spec_tokens=3,
    host_pool_bytes=1 << 22,
)


@pytest.fixture(scope="module")
def featureful(tiny):
    """Engine factory with EVERY multi-host-relevant feature on: the
    adapter pool, spec decode, and the host KV tier (preemption-by-swap),
    plus two real (non-zero) published adapters."""
    from helix_tpu.training.lora import LoraConfig, init_lora_params

    cfg, params = tiny

    def adapter(seed):
        lp = init_lora_params(cfg, LoraConfig(rank=4),
                              jax.random.PRNGKey(seed))
        for t in lp:
            # stable per-target fold (str hash() is randomized per
            # process; weight-dependent assertions like "spec decode
            # engaged" must not flip with PYTHONHASHSEED)
            lp[t]["lora_b"] = jax.random.normal(
                jax.random.fold_in(jax.random.PRNGKey(seed),
                                   zlib.crc32(t.encode()) % 97),
                lp[t]["lora_b"].shape, jnp.float32) * 0.05
        return lp

    a1, a2 = adapter(9), adapter(23)

    def make():
        e = Engine(cfg, params, EngineConfig(**POOL_ECFG))
        e.publish_adapter("a1", a1, 2.0)
        e.publish_adapter("a2", a2, 2.0)
        return e

    return make


class TestAllFeaturesLockstep:
    """The acceptance drill: spec decode + adapter pool + WFQ budgets +
    preemption-by-swap SIMULTANEOUSLY live, leader and follower
    bit-identical for greedy and seeded sampled traffic, and the
    follower's compiled step-shape registry exactly the leader's."""

    def _traffic(self):
        return [
            # repeated patterns so the prompt-lookup drafter actually
            # fires; mixed greedy + sampled, two different adapters
            Request(id="g0", prompt_tokens=[5, 6, 7, 5, 6, 7, 5, 6],
                    sampling=SamplingParams(temperature=0.0,
                                            max_tokens=10)),
            Request(id="s1", prompt_tokens=[9, 9, 4, 9, 9, 4, 9, 9],
                    sampling=SamplingParams(temperature=0.8, top_k=20,
                                            max_tokens=10),
                    adapter="a1", tenant="t1"),
            Request(id="s2", prompt_tokens=[2, 3, 2, 3, 2, 3, 2],
                    sampling=SamplingParams(temperature=0.9,
                                            max_tokens=10),
                    adapter="a2", sched_class="batch"),
            Request(id="g3", prompt_tokens=[11, 12, 11, 12, 11],
                    sampling=SamplingParams(temperature=0.0,
                                            max_tokens=8)),
        ]

    def test_spec_adapters_wfq_preemption_bit_identity(self, featureful):
        leader = PlanLeader(featureful())
        leader.prefill_budget = 8              # WFQ-style per-step budget
        leader.victim_policy = lambda c: sorted(c, key=lambda r: r.id)
        assert leader.engine.prefill_budget == 8, "forwarding property"
        reqs = self._traffic()
        for r in reqs:
            leader.add_request(r)
        steps = 0
        preempted = False
        while leader.engine.has_work():
            leader.step()
            steps += 1
            if not preempted and steps == 3:
                active = [r for r in leader.engine.slots if r is not None]
                if active:
                    preempted = leader.preempt(active[0].id)
            assert steps < 300
        assert leader.engine.num_spec_steps > 0, "spec never fired"
        assert leader.engine.num_preemptions >= 1
        assert leader.engine.num_resumes >= 1

        shapes_before = ragged_meta.step_shape_set(
            leader.engine._shape_key
        )
        assert shapes_before
        fe = featureful()
        follower = FollowerLoop(fe, leader.journal)
        _replay(follower)
        for r in reqs:
            assert fe._requests[r.id].output_tokens == r.output_tokens, r.id
            assert fe._requests[r.id].finished
        assert fe.num_spec_steps == leader.engine.num_spec_steps
        assert fe.num_resumes == leader.engine.num_resumes
        assert follower.stats()["digest_mismatches"] == 0
        # the follower drove the SAME compiled step family: the shared
        # module-global registry gained zero entries during replay
        assert fe._shape_key == leader.engine._shape_key
        new = ragged_meta.step_shape_set(fe._shape_key) - shapes_before
        assert not new, f"follower traced NEW step shapes: {new}"

    def test_async_pipelined_leader_replicates(self, tiny):
        """The async EngineLoop arms on a PlanLeader (the old journal
        forced it synchronous) and its pipelined dispatch/complete split
        still publishes replayable plans."""
        from helix_tpu.serving.engine_loop import EngineLoop

        cfg, params = tiny

        def make():
            return Engine(cfg, params, EngineConfig(
                max_decode_batch=2, page_size=4, num_pages=64,
                max_pages_per_seq=16, max_prefill_len=16,
                attn_backend="reference",
            ))

        leader = PlanLeader(make())
        loop = EngineLoop(leader, "mh-async")
        assert loop.async_enabled, "async loop must arm for a PlanLeader"
        loop.start()
        done = {}

        def cb_for(rid):
            done[rid] = threading.Event()

            def cb(ev):
                if ev.finished:
                    done[rid].set()
            return cb

        reqs = [
            Request(id=f"q{i}", prompt_tokens=[3 + i, 5, 8],
                    sampling=SamplingParams(temperature=0.7, top_k=10,
                                            max_tokens=8))
            for i in range(4)
        ]
        try:
            for r in reqs:
                loop.submit(r, cb_for(r.id))
            for r in reqs:
                assert done[r.id].wait(120), f"{r.id} never finished"
        finally:
            loop.stop()
        assert loop.pipelined_steps > 0
        fe = make()
        follower = FollowerLoop(fe, leader.journal)
        _replay(follower)
        for r in reqs:
            assert fe._requests[r.id].output_tokens == r.output_tokens
        assert follower.stats()["digest_mismatches"] == 0


class TestFailureDrills:
    """Recovery drills for the multi-host failure paths: a follower
    killed mid-stream rejoins by replaying the ring; losing the ring or
    a leader restart is loud and operator-actionable; a discarded plan
    is skipped by replaying followers and fatal to live ones."""

    def test_follower_killed_midstream_rejoins_from_ring(self, tiny):
        leader = PlanLeader(_engine(tiny))
        fe_a = _engine(tiny)
        follower_a = FollowerLoop(fe_a, leader.journal)
        reqs = [
            Request(id=f"r{i}", prompt_tokens=[3 + i, 5, 8],
                    sampling=SamplingParams(temperature=0.8, top_k=20,
                                            max_tokens=8))
            for i in range(2)
        ]
        leader.add_request(reqs[0])
        # follower A applies a few records, then is "killed" (dropped)
        for _ in range(3):
            leader.step()
        follower_a.run_once()
        assert follower_a.applied_seq >= 1
        del follower_a
        # leader keeps serving while A is down
        leader.add_request(reqs[1])
        _drain(leader)
        # replacement follower: FRESH engine replica, replays from seq 0
        fe_b = _engine(tiny)
        follower_b = FollowerLoop(fe_b, leader.journal)
        _replay(follower_b)
        assert follower_b.applied_seq == leader.journal._next - 1
        for r in reqs:
            assert fe_b._requests[r.id].output_tokens == r.output_tokens
            assert fe_b._requests[r.id].finished

    def test_rejoin_after_ring_drop_fails_loudly(self, tiny):
        """When the ring no longer retains the journal head, a fresh
        replica CANNOT silently rejoin (it would diverge) — the feed must
        raise instead of returning a partial suffix."""
        journal = CommandLog(capacity=4)
        for _ in range(10):
            journal.publish({"v": WIRE_VERSION, "kind": "plan"})
        fe = _engine(tiny)
        follower = FollowerLoop(fe, journal, poll_timeout=0.1)
        with pytest.raises(LagError, match="fell behind the ring"):
            follower.run_once()

    def test_leader_restart_surfaces_actionable_error(self, tiny):
        """A follower ahead of the journal (leader restarted, sequence
        reset) stops and hands the operator a recovery instruction via
        the on_lost_lockstep hook."""
        journal = CommandLog()
        journal.publish({"v": WIRE_VERSION, "kind": "plan"})
        fe = _engine(tiny)
        surfaced = []
        follower = FollowerLoop(
            fe, journal, poll_timeout=0.1,
            on_lost_lockstep=surfaced.append,
        )
        follower.applied_seq = 57   # state from before the leader restart
        follower.start()
        deadline = time.time() + 10
        while time.time() < deadline and follower.error is None:
            time.sleep(0.02)
        follower.stop()
        assert follower.error is not None
        assert "leader restart" in follower.error
        assert "re-apply the serving profile" in follower.error
        assert surfaced == [follower.error]

    def test_mid_stream_kill_and_rejoin_with_sampled_traffic(self, tiny):
        """End-to-end drill: traffic in flight the whole time, follower
        replaced mid-generation, replacement converges to identical
        outputs without the leader pausing."""
        leader = PlanLeader(_engine(tiny))
        req = Request(id="live", prompt_tokens=[2, 4, 6],
                      sampling=SamplingParams(temperature=0.9,
                                              max_tokens=10))
        leader.add_request(req)
        fe_a = _engine(tiny)
        follower_a = FollowerLoop(fe_a, leader.journal, poll_timeout=0.2)
        follower_a.start()
        leader.step()
        leader.step()
        follower_a.stop()          # kill mid-generation
        _drain(leader)
        fe_b = _engine(tiny)
        follower_b = FollowerLoop(fe_b, leader.journal)
        _replay(follower_b)
        assert fe_b._requests["live"].output_tokens == req.output_tokens

    def test_discarded_plan_skipped_by_replaying_follower(self, tiny):
        """A plan whose device step failed on the leader is marked with a
        discard record; a follower replaying the batch prescans the
        markers and never executes the dead plan, and the retry plan
        re-carries the dead plan's admissions."""
        leader = PlanLeader(_engine(tiny))
        req = Request(id="r", prompt_tokens=[1, 2, 3],
                      sampling=SamplingParams(temperature=0.0,
                                              max_tokens=4))
        leader.add_request(req)
        emitted, pend = leader.step_dispatch()
        assert pend is not None
        leader.discard_pending(pend)   # simulate a failed device step
        _drain(leader)
        records = leader.journal.read_since(0, timeout=0.1)
        kinds = [r.get("kind") for r in records]
        assert "discard" in kinds
        # the retry plan carries the discarded plan's admissions
        retry = next(r for r in records
                     if r.get("kind") == "plan" and r.get("admits"))
        assert [d["id"] for d in retry["admits"]] == ["r"]
        assert any(r.get("digest_reset") for r in records
                   if r.get("kind") == "plan")
        fe = _engine(tiny)
        follower = FollowerLoop(fe, leader.journal)
        _replay(follower)
        assert follower.plans_skipped == 1
        assert fe._requests["r"].output_tokens == req.output_tokens
        assert follower.stats()["digest_mismatches"] == 0

    def test_discard_of_executed_plan_is_fatal_for_live_follower(
        self, tiny
    ):
        """A live follower that already executed the plan the leader then
        discarded has truly diverged (its device ran a step the leader
        rolled back) — restart ladder, not silent continue."""
        from helix_tpu.serving.multihost_serving import DivergenceError

        leader = PlanLeader(_engine(tiny))
        req = Request(id="r", prompt_tokens=[1, 2],
                      sampling=SamplingParams(max_tokens=6))
        leader.add_request(req)
        fe = _engine(tiny)
        follower = FollowerLoop(fe, leader.journal)
        leader.step()
        follower.run_once()        # executes plan 0 live
        emitted, pend = leader.step_dispatch()
        follower.run_once()        # executes plan 1 live too
        leader.discard_pending(pend)
        with pytest.raises(DivergenceError, match="already executed"):
            follower.run_once()


class TestBackoff:
    class _FlakyFeed:
        """Transport that fails N times, then delegates to a journal."""

        def __init__(self, journal, failures):
            self.journal = journal
            self.failures = failures
            self.reconnects = 0

        def read_since(self, since, timeout=1.0):
            if self.failures > 0:
                self.failures -= 1
                self.reconnects += 1
                raise ConnectionError("transient DCN blip")
            return self.journal.read_since(since, timeout)

    def test_transient_feed_errors_backoff_with_jitter(self, tiny,
                                                       monkeypatch):
        monkeypatch.setenv("HELIX_MH_BACKOFF_BASE", "0.01")
        monkeypatch.setenv("HELIX_MH_BACKOFF_CAP", "0.05")
        leader = PlanLeader(_engine(tiny))
        req = Request(id="x", prompt_tokens=[1, 2],
                      sampling=SamplingParams(temperature=0.0,
                                              max_tokens=3))
        leader.add_request(req)
        _drain(leader)
        fe = _engine(tiny)
        feed = self._FlakyFeed(leader.journal, failures=3)
        follower = FollowerLoop(fe, feed, poll_timeout=0.2)
        assert follower.backoff_cap == 0.05
        follower.start()
        deadline = time.time() + 15
        while time.time() < deadline:
            fr = fe._requests.get("x")
            if fr is not None and fr.finished:
                break
            time.sleep(0.02)
        follower.stop()
        st = follower.stats()
        assert fe._requests["x"].output_tokens == req.output_tokens
        assert st["feed_errors"] == 3
        assert 0 < st["backoff_seconds_total"] <= 3 * 0.05
        assert st["reconnects"] == 3
        assert follower.error is None   # transient != lost lockstep


class TestSampleProfiles:
    def test_every_sample_profile_parses(self):
        """Sample profiles double as documentation-as-test fixtures
        (reference: composeparse/sample_profiles_test.go:9-12)."""
        import glob
        import os

        from helix_tpu.control.profile import ServingProfile

        root = os.path.join(os.path.dirname(__file__), "..", "profiles")
        paths = sorted(glob.glob(os.path.join(root, "*.yaml")))
        assert len(paths) >= 5
        by_name = {}
        for p in paths:
            with open(p) as f:
                sp = ServingProfile.from_yaml(f.read())
            assert sp.models, p
            by_name[sp.name] = sp
        leader = by_name["v5e16-2host-llama3"].models[0]
        follower = by_name["v5e16-2host-llama3-follower"].models[0]
        standby = by_name["v5e16-2host-llama3-standby"].models[0]
        assert leader.multihost["role"] == "leader"
        assert follower.multihost["role"] == "follower"
        assert follower.multihost["leader_url"]
        assert standby.multihost["role"] == "follower"
        assert standby.multihost["standby"] is True

    def test_two_host_profile_pair_agrees(self):
        """The leader/follower halves describe ONE global engine: model,
        mesh, KV geometry, quantization and every enabled feature must
        agree or the compiled step shapes (and hence the cross-host
        collectives) diverge."""
        import os

        from helix_tpu.control.profile import ServingProfile

        root = os.path.join(os.path.dirname(__file__), "..", "profiles")

        def load(name):
            with open(os.path.join(root, name)) as f:
                return ServingProfile.from_yaml(f.read()).models[0]

        leader = load("v5e16-2host-llama3.yaml")
        follower = load("v5e16-2host-llama3-follower.yaml")
        standby = load("v5e16-2host-llama3-standby.yaml")
        assert leader.name == follower.name == standby.name
        assert leader.checkpoint == follower.checkpoint
        assert leader.context_length == follower.context_length
        assert leader.mesh == follower.mesh == standby.mesh
        assert leader.quantization == follower.quantization
        # the engine block is the step-shape contract: a verbatim match,
        # not merely overlapping keys (and the standby variant too — it
        # must be able to BECOME the leader without a shape change)
        assert leader.engine == follower.engine == standby.engine
        # and the pair actually exercises the plan-broadcast features
        assert leader.engine.get("enable_spec_decode") is True
        assert leader.engine.get("adapter_pool_slots", 0) >= 2
        assert leader.engine.get("host_pool_bytes", 0) > 0


class TestCommandLog:
    def test_blocking_read_wakes_on_publish(self):
        logj = CommandLog()
        got = []

        def reader():
            got.extend(logj.read_since(0, timeout=5))

        t = threading.Thread(target=reader)
        t.start()
        time.sleep(0.05)
        logj.publish({"step": True})
        t.join(timeout=5)
        assert got and got[0]["seq"] == 1

    def test_ring_overflow_returns_typed_resync_record(self):
        """ISSUE 17 bugfix: overflow is no longer an unconditional fatal
        LagError raised in the transport — the reader gets ONE typed
        ``resync_required`` record whose reason distinguishes "I fell
        behind" from "the leader restarted"."""
        logj = CommandLog(capacity=4)
        for _ in range(10):
            logj.publish({"step": True})
        recs = logj.read_since(1, timeout=0.1)
        assert [r["kind"] for r in recs] == ["resync_required"]
        assert recs[0]["reason"] == RESYNC_RING_OVERFLOW
        assert "fell behind the ring" in recs[0]["error"]
        # seq echoes the reader: its applied_seq must not advance
        assert recs[0]["seq"] == 1
        # a reader inside the retained window still gets real records
        live = logj.read_since(8, timeout=0.1)
        assert live
        assert all(r.get("kind") != "resync_required" for r in live)

    def test_reader_ahead_of_journal_typed_as_leader_restart(self):
        logj = CommandLog()
        logj.publish({"step": True})
        recs = logj.read_since(57, timeout=0.1)
        assert [r["kind"] for r in recs] == ["resync_required"]
        assert recs[0]["reason"] == RESYNC_LEADER_RESTART
        assert "leader restart" in recs[0]["error"]

    def test_publish_throughput_is_flat_when_ring_full(self):
        """The ring is a deque: overflow is an O(1) popleft, so publish
        cost must not grow with how long the ring has been full (the
        old list re-slice made sustained publish quadratic).  Micro-
        assertion: 30k publishes into a full 256-slot ring complete in
        well under a second even on a loaded CI box."""
        logj = CommandLog(capacity=256)
        rec = {"kind": "plan", "admits": [], "step": 0}
        for _ in range(256):
            logj.publish(rec)
        t0 = time.perf_counter()
        for _ in range(30_000):
            logj.publish(rec)
        elapsed = time.perf_counter() - t0
        assert elapsed < 1.0, f"30k publishes took {elapsed:.2f}s"
        assert len(logj._records) == 256
        assert logj.read_since(logj._next - 2, timeout=0.1)


class TestGuardLint:
    """Contract 12 fixtures: a lockstep/multihost feature guard under
    helix_tpu/engine/ or helix_tpu/serving/ fails the build; prose and
    marked transport sites do not."""

    @staticmethod
    def _lint(tmp_path, rel, src):
        import os
        import sys

        sys.path.insert(
            0, os.path.join(os.path.dirname(__file__), "..", "tools")
        )
        import lint_metrics

        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(src)
        return lint_metrics._mh_guard_violations(str(tmp_path))

    def test_journal_sniff_guard_flagged(self, tmp_path):
        out = self._lint(
            tmp_path, "helix_tpu/engine/victim.py",
            "def pick(engine):\n"
            "    if getattr(engine, 'journal', None) is not None:\n"
            "        return None\n",
        )
        assert len(out) == 1 and "journal" in out[0]
        assert "plan-broadcast" in out[0]

    def test_multihost_conditional_flagged(self, tmp_path):
        out = self._lint(
            tmp_path, "helix_tpu/serving/loop2.py",
            "def arm(cfg):\n"
            "    if cfg.multihost:\n"
            "        return False\n",
        )
        assert len(out) == 1 and "lockstep/multihost token" in out[0]

    def test_prose_and_strings_tolerated(self, tmp_path):
        out = self._lint(
            tmp_path, "helix_tpu/serving/loop2.py",
            '"""Docstrings may discuss multihost lockstep freely."""\n'
            "# and so may comments: lockstep, multihost, journal\n"
            "MSG = 'not a multihost leader'\n",
        )
        assert out == []

    def test_marker_escapes_transport_site(self, tmp_path):
        out = self._lint(
            tmp_path, "helix_tpu/serving/feedsrv.py",
            "def feed(engine):\n"
            "    # multihost-ok: transport plumbing, not a feature guard\n"
            "    return getattr(engine, 'journal', None)\n",
        )
        assert out == []

    def test_exempt_module_and_other_trees_ignored(self, tmp_path):
        src = "flag = engine.multihost\n"
        assert self._lint(
            tmp_path, "helix_tpu/serving/multihost_serving.py", src
        ) == []
        assert self._lint(
            tmp_path, "helix_tpu/control/wiring.py", src
        ) == []

    def test_reminted_state_literal_flagged(self, tmp_path):
        """ISSUE 17 fence: quoting a follower-state / resync-reason
        literal under the guarded dirs forks the state machine — import
        FOLLOWER_*/RESYNC_* from multihost_serving instead."""
        out = self._lint(
            tmp_path, "helix_tpu/serving/health2.py",
            "def throttle(st):\n"
            "    return st == 'lagging'\n",
        )
        assert len(out) == 1
        assert "import FOLLOWER_*/RESYNC_*" in out[0]
        # ... unless the site carries the marker (e.g. a wire-format
        # shim that must speak the literal)
        out = self._lint(
            tmp_path, "helix_tpu/serving/health2.py",
            "def throttle(st):\n"
            "    # multihost-ok: wire-format shim\n"
            "    return st == 'ring_overflow'\n",
        )
        assert out == []

    def test_mh_metric_name_fenced_to_module(self, tmp_path):
        """helix_mh_* series may only be minted inside
        multihost_serving.py (the _MH_NAME_RE + _is_mh pair run()
        applies helix_tpu-wide)."""
        import os
        import sys

        sys.path.insert(
            0, os.path.join(os.path.dirname(__file__), "..", "tools")
        )
        import lint_metrics

        assert lint_metrics._MH_NAME_RE.search(
            'c.gauge("helix_mh_follower_lag_steps", 1)'
        )
        assert not lint_metrics._MH_NAME_RE.search(
            "# prose mentioning helix_mh_follower_lag_steps is fine"
        )
        root = str(tmp_path)
        inside = os.path.join(
            root, "helix_tpu", "serving", "multihost_serving.py"
        )
        outside = os.path.join(root, "helix_tpu", "obs", "extra.py")
        assert lint_metrics._is_mh(inside, root)
        assert not lint_metrics._is_mh(outside, root)

    def test_importer_pattern_enforced(self, tmp_path):
        """The consumers named in _MH_IMPORTERS must import their
        symbol from multihost_serving; a present-but-unwired importer
        is a violation, an absent file is skipped (partial trees)."""
        import os
        import sys

        sys.path.insert(
            0, os.path.join(os.path.dirname(__file__), "..", "tools")
        )
        import lint_metrics

        mod = tmp_path / "helix_tpu" / "serving" / "multihost_serving.py"
        mod.parent.mkdir(parents=True, exist_ok=True)
        mod.write_text("def collect_mh_metrics():\n    pass\n")
        api = tmp_path / "helix_tpu" / "serving" / "openai_api.py"
        api.write_text("# no mh import here\n")
        out = lint_metrics._mh_importer_violations(str(tmp_path))
        assert len(out) == 1
        assert "collect_mh_metrics" in out[0]
        api.write_text(
            "from helix_tpu.serving.multihost_serving import "
            "collect_mh_metrics\n"
        )
        assert lint_metrics._mh_importer_violations(str(tmp_path)) == []


class TestHTTPFeedRoute:
    def test_journal_served_over_http(self, tiny):
        import asyncio

        import requests as _requests

        from helix_tpu.serving.engine_loop import EngineLoop
        from helix_tpu.serving.multihost_serving import HTTPFeed
        from helix_tpu.serving.openai_api import OpenAIServer
        from helix_tpu.serving.registry import ModelRegistry, ServedModel
        from helix_tpu.serving.tokenizer import ByteTokenizer

        leader = PlanLeader(_engine(tiny))
        loop_obj = EngineLoop(leader, "plan-leader").start()
        registry = ModelRegistry()
        registry.register(
            ServedModel(name="tiny-mh", loop=loop_obj,
                        tokenizer=ByteTokenizer())
        )
        srv = OpenAIServer(registry)
        started = threading.Event()
        holder = {}

        def run():
            aloop = asyncio.new_event_loop()
            asyncio.set_event_loop(aloop)
            from aiohttp import web

            runner = web.AppRunner(srv.build_app())
            aloop.run_until_complete(runner.setup())
            site = web.TCPSite(runner, "127.0.0.1", 18439)
            aloop.run_until_complete(site.start())
            holder["loop"] = aloop
            started.set()
            aloop.run_forever()

        threading.Thread(target=run, daemon=True).start()
        assert started.wait(10)
        url = "http://127.0.0.1:18439"
        # drive one request through the leader's HTTP surface
        r = _requests.post(
            f"{url}/v1/chat/completions",
            json={"model": "tiny-mh",
                  "messages": [{"role": "user", "content": "hi"}],
                  "max_tokens": 3, "temperature": 0},
            timeout=60,
        )
        assert r.status_code == 200, r.text
        # follower transport reads the plan stream through the route,
        # reusing ONE pooled session across polls
        feed = HTTPFeed(url, "tiny-mh")
        records = feed.read_since(0, timeout=5)
        assert records and any(rec.get("admits") for rec in records)
        assert all(rec["v"] == WIRE_VERSION for rec in records)
        feed.read_since(records[-1]["seq"], timeout=0.2)
        assert feed.reconnects == 0
        assert feed._session is not None
        fe = _engine(tiny)
        follower = FollowerLoop(fe, feed, poll_timeout=1.0)
        follower.run_once()
        assert follower.applied_seq >= 1
        loop_obj.stop(join=False)
        holder["loop"].call_soon_threadsafe(holder["loop"].stop)


class TestFollowerFanout:
    """ISSUE 17: N followers on one leader — per-follower health in the
    leader's registry, the lag ladder throttling admission instead of
    overflowing the ring, and clean rejoin."""

    def test_three_follower_mesh_health_and_bit_identity(self, tiny):
        leader = PlanLeader(_engine(tiny))
        followers = [
            FollowerLoop(_engine(tiny), LocalFeed(leader, f"host-{i}"))
            for i in range(3)
        ]
        reqs = [
            Request(id=f"r{i}", prompt_tokens=[3 + i, 5, 8],
                    sampling=SamplingParams(temperature=0.8, top_k=20,
                                            max_tokens=8))
            for i in range(3)
        ]
        for r in reqs:
            leader.add_request(r)
        _drain(leader)
        for f in followers:
            _replay(f)
        # replays run serially and can outlast the liveness TTL on a
        # slow CPU box; one fresh poll per follower is the real rejoin
        # path (lost -> healthy on the next poll at lag 0)
        for f in followers:
            f.run_once(timeout=0.01)
        health = leader.follower_health()
        assert set(health) == {"host-0", "host-1", "host-2"}
        for st in health.values():
            assert st["state"] == FOLLOWER_HEALTHY
            assert st["lag_steps"] == 0
            assert st["digest_mismatches"] == 0
        # every replica converged to the leader's exact tokens
        for f in followers:
            for r in reqs:
                fr = f.engine._requests[r.id]
                assert fr.output_tokens == r.output_tokens
                assert fr.finished
        ms = leader.mh_stats()
        assert ms["follower_states"][FOLLOWER_HEALTHY] == 3
        assert ms["follower_states"][FOLLOWER_LAGGING] == 0
        assert ms["followers"]["host-1"]["applied_step"] == \
            leader._last_plan_idx

    def test_lagging_follower_throttles_admission_then_rejoins(
        self, tiny, monkeypatch
    ):
        monkeypatch.setenv("HELIX_MH_LAG_STEPS", "4")
        leader = PlanLeader(_engine(tiny))
        assert leader.lag_steps_limit == 4
        long_req = Request(id="bg", prompt_tokens=[2, 4, 6],
                           sampling=SamplingParams(temperature=0.0,
                                                   max_tokens=40))
        leader.add_request(long_req)
        for _ in range(8):
            leader.step()
        # a follower reports far behind (the health path every LocalFeed
        # / HTTPFeed poll drives)
        leader.note_poll("slow-1", 0, applied_step=0)
        assert (leader.follower_health()["slow-1"]["state"]
                == FOLLOWER_LAGGING)
        # while lagging: admission throttled — the queued request stays
        # waiting (budget pinned to 0 for the dispatch), decode continues
        queued = Request(id="q", prompt_tokens=[9, 9],
                         sampling=SamplingParams(temperature=0.0,
                                                 max_tokens=3))
        leader.add_request(queued)
        leader.step()
        assert leader.throttled_steps >= 1
        assert any(r.id == "q" for r in leader.engine.waiting)
        # catch-up past the hysteresis point flips healthy and admission
        # resumes (clean rejoin, no ring overflow, no resync)
        leader.note_poll("slow-1", leader.journal._next - 1,
                         applied_step=leader._last_plan_idx)
        assert (leader.follower_health()["slow-1"]["state"]
                == FOLLOWER_HEALTHY)
        throttled_before = leader.throttled_steps
        _drain(leader)
        assert leader.throttled_steps == throttled_before
        assert leader.engine._requests["q"].finished
        # and a fresh replica replays the whole stream bit-identically
        # (the throttled plan carried budget=0, so no divergence)
        fe = _engine(tiny)
        follower = FollowerLoop(fe, leader.journal)
        _replay(follower)
        assert fe._requests["bg"].output_tokens == long_req.output_tokens
        assert fe._requests["q"].output_tokens == queued.output_tokens
        assert follower.stats()["digest_mismatches"] == 0

    def test_follower_registry_bounded(self, tiny, monkeypatch):
        monkeypatch.setenv("HELIX_MH_MAX_FOLLOWERS", "2")
        leader = PlanLeader(_engine(tiny))
        for i in range(5):
            leader.note_poll(f"f-{i}", 0, applied_step=0)
        assert len(leader.follower_health()) == 2
        assert leader.followers_dropped == 3


class TestCheckpointStore:
    def _state(self, plan_idx, seq):
        return {"version": CHECKPOINT_VERSION, "model": "m",
                "plan_idx": plan_idx, "seq": seq,
                "waiting": [], "snapshots": []}

    def test_round_trip_and_prune(self, tmp_path):
        store = CheckpointStore(str(tmp_path), keep=2)
        for i in range(4):
            ref, nbytes = store.save("m", self._state(i, i + 1))
            assert nbytes > 0
        assert len(store.list_refs("m")) == 2   # keep-newest-K prune
        ref, state = store.load_latest("m")
        assert state["plan_idx"] == 3
        assert state == store.load(ref)          # byte-stable reload

    def test_missing_checkpoint_typed(self, tmp_path):
        store = CheckpointStore(str(tmp_path))
        with pytest.raises(CheckpointError) as ei:
            store.load_latest("nope")
        assert ei.value.code == "checkpoint_missing"

    def test_corrupt_blob_skipped_for_older_good_one(self, tmp_path):
        """One bad write must not take failover down: load_latest skips
        (and counts) the corrupt newest blob and serves the previous
        good one.  Corruption is injected through the deterministic
        fault hook — the same path chaos_soak drives."""
        store = CheckpointStore(str(tmp_path), keep=4)
        store.save("m", self._state(0, 1))
        faults.arm(seed=0, rules=[
            {"point": "checkpoint", "model": "m", "times": 1},
        ])
        try:
            bad_ref, _ = store.save("m", self._state(1, 2))
        finally:
            faults.disarm()
        with pytest.raises(CheckpointError):
            store.load(bad_ref)
        ref, state = store.load_latest("m")
        assert state["plan_idx"] == 0
        assert store.corrupt_rejected >= 1

    def test_version_skew_rejected_typed(self, tmp_path):
        store = CheckpointStore(str(tmp_path))
        blob = __import__("json").dumps(
            {"v": 99, "checksum": "", "payload": "{}"}
        ).encode()
        store.store.write(CheckpointStore.OWNER,
                          "m/ckpt-0000000000000001-0000000000000001.json",
                          blob)
        with pytest.raises(CheckpointError) as ei:
            store.load_latest("m")
        assert ei.value.code == "checkpoint_version"


FAILOVER_ECFG = dict(
    max_decode_batch=2, page_size=4, num_pages=64, max_pages_per_seq=16,
    max_prefill_len=16, attn_backend="reference",
    host_pool_bytes=1 << 22,   # failover parks at the boundary: host tier on
)


def _fo_engine(tiny):
    cfg, params = tiny
    return Engine(cfg, params, EngineConfig(**FAILOVER_ECFG))


class TestLeaderFailover:
    """ISSUE 17 acceptance drill: kill the leader mid-stream, promote a
    digest-verified standby through the filestore checkpoint, and the
    mesh finishes every request bit-identical to an uninterrupted run —
    greedy AND seeded sampled traffic, WFQ budget + spec + adapters on
    for the featureful variant."""

    def _reqs(self):
        return [
            Request(id="g0", prompt_tokens=[5, 6, 7, 5, 6],
                    sampling=SamplingParams(temperature=0.0,
                                            max_tokens=12)),
            Request(id="s1", prompt_tokens=[9, 9, 4, 9],
                    sampling=SamplingParams(temperature=0.8, top_k=20,
                                            max_tokens=12)),
            Request(id="s2", prompt_tokens=[2, 3, 2],
                    sampling=SamplingParams(temperature=0.9,
                                            max_tokens=10)),
        ]

    def _featureful_reqs(self):
        return [
            Request(id="g0", prompt_tokens=[5, 6, 7, 5, 6, 7, 5, 6],
                    sampling=SamplingParams(temperature=0.0,
                                            max_tokens=10)),
            Request(id="s1", prompt_tokens=[9, 9, 4, 9, 9, 4, 9, 9],
                    sampling=SamplingParams(temperature=0.8, top_k=20,
                                            max_tokens=10),
                    adapter="a1", tenant="t1"),
            Request(id="s2", prompt_tokens=[2, 3, 2, 3, 2, 3, 2],
                    sampling=SamplingParams(temperature=0.9,
                                            max_tokens=10),
                    adapter="a2", sched_class="batch"),
        ]

    def _reference(self, make_engine, reqs, budget=None):
        ref = PlanLeader(make_engine())
        if budget is not None:
            ref.prefill_budget = budget
        for r in reqs:
            ref.add_request(r)
        _drain(ref)
        return {r.id: list(r.output_tokens) for r in reqs}

    def _takeover_drill(self, make_engine, reqs_fn, tmp_path,
                        budget=None):
        ref_out = self._reference(make_engine, reqs_fn(), budget=budget)
        store = CheckpointStore(str(tmp_path))
        leader = PlanLeader(make_engine(), checkpoint_store=store,
                            name="m")
        if budget is not None:
            leader.prefill_budget = budget
        standby = FollowerLoop(make_engine(), LocalFeed(leader, "sb-1"),
                               name="m", standby=True,
                               checkpoint_store=store)
        peer = FollowerLoop(make_engine(), LocalFeed(leader, "peer-1"),
                            name="m", checkpoint_store=store)
        reqs = reqs_fn()
        for r in reqs:
            leader.add_request(r)
        steps = 0
        while leader.engine.has_work() and steps < 6:
            leader.step()
            steps += 1
            time.sleep(0.02)
            leader.checkpoint_tick()
        store.flush(10)
        assert store.writes >= 1, "no checkpoint ever landed"
        while standby.run_once(timeout=0.01):
            pass
        while peer.run_once(timeout=0.01):
            pass
        assert leader.engine.has_work(), "traffic ended before the kill"
        # KILL: the old leader publishes nothing further
        new_leader = promote_follower(standby, store=store, name="m")
        assert new_leader.takeovers == 1
        assert new_leader.engine is standby.engine
        # surviving peer re-points and crosses the handoff seamlessly
        peer.feed.retarget(new_leader)
        while new_leader.engine.has_work():
            new_leader.step()
        while peer.run_once(timeout=0.01):
            pass
        got = {rid: list(new_leader.engine._requests[rid].output_tokens)
               for rid in ref_out}
        assert got == ref_out, "takeover diverged from uninterrupted run"
        assert peer.handoffs == 1
        assert peer.digest_mismatches == 0
        peer_got = {rid: list(peer.engine._requests[rid].output_tokens)
                    for rid in ref_out}
        assert peer_got == ref_out
        for rid in ref_out:
            assert new_leader.engine._requests[rid].finished
        return store, new_leader, peer

    def test_takeover_bit_identity(self, tiny, monkeypatch):
        monkeypatch.setenv("HELIX_MH_CHECKPOINT_SECONDS", "0.01")
        import tempfile

        with tempfile.TemporaryDirectory() as tmp:
            store, new_leader, peer = self._takeover_drill(
                lambda: _fo_engine(tiny), self._reqs, tmp
            )
            # fresh follower bootstraps from the handoff checkpoint
            fresh = FollowerLoop(_fo_engine(tiny),
                                 LocalFeed(new_leader, "fresh-1"),
                                 name="m", checkpoint_store=store)
            while fresh.run_once(timeout=0.01):
                pass
            assert fresh.handoffs == 1
            assert fresh.digest_mismatches == 0
            assert fresh._applied_step == new_leader._last_plan_idx
            ms = new_leader.mh_stats()
            assert ms["follower_states"][FOLLOWER_HEALTHY] >= 2

    def test_takeover_bit_identity_all_features(self, featureful,
                                                monkeypatch):
        """WFQ budget + spec decode + two live adapters through the
        kill: the checkpoint carries budget/spec EMAs/adapter refs and
        the promoted leader finishes bit-identical anyway."""
        monkeypatch.setenv("HELIX_MH_CHECKPOINT_SECONDS", "0.01")
        import tempfile

        with tempfile.TemporaryDirectory() as tmp:
            _store, new_leader, _peer = self._takeover_drill(
                featureful, self._featureful_reqs, tmp, budget=8
            )
            assert new_leader.engine.prefill_budget == 8
            assert new_leader.engine.num_spec_steps > 0

    def test_corrupt_checkpoint_rejected_before_any_mutation(
        self, tiny, monkeypatch
    ):
        """Validate-before-mutate: when every checkpoint blob fails its
        checksum, promotion refuses typed and the standby's allocator
        is untouched (it can keep running as a follower)."""
        monkeypatch.setenv("HELIX_MH_CHECKPOINT_SECONDS", "0.01")
        import tempfile

        with tempfile.TemporaryDirectory() as tmp:
            store = CheckpointStore(tmp)
            leader = PlanLeader(_fo_engine(tiny), checkpoint_store=store,
                                name="m")
            standby = FollowerLoop(_fo_engine(tiny),
                                   LocalFeed(leader, "sb-1"),
                                   name="m", standby=True,
                                   checkpoint_store=store)
            req = Request(id="r", prompt_tokens=[2, 4, 6],
                          sampling=SamplingParams(temperature=0.0,
                                                  max_tokens=30))
            leader.add_request(req)
            faults.arm(seed=0, rules=[
                {"point": "checkpoint", "model": "m", "p": 1.0},
            ])
            try:
                for _ in range(4):
                    leader.step()
                    time.sleep(0.02)
                    leader.checkpoint_tick()
                store.flush(10)
            finally:
                faults.disarm()
            assert store.writes >= 1
            while standby.run_once(timeout=0.01):
                pass
            active_before = [r.id for r in standby.engine.slots
                             if r is not None]
            assert active_before, "nothing active at the boundary"
            with pytest.raises(CheckpointError):
                promote_follower(standby, store=store, name="m")
            assert [r.id for r in standby.engine.slots
                    if r is not None] == active_before
            assert standby.engine.num_preemptions == 0

    def test_takeover_past_overflowed_ring_typed_fallback(
        self, tiny, monkeypatch
    ):
        """A standby that fell off the ring cannot silently become
        leader (it would re-decide steps the mesh already executed):
        promotion refuses with the typed ring_overflow reason and the
        operator lands on today's full-resync ladder."""
        monkeypatch.setenv("HELIX_MH_CHECKPOINT_SECONDS", "0.01")
        import tempfile

        with tempfile.TemporaryDirectory() as tmp:
            store = CheckpointStore(tmp)
            leader = PlanLeader(_fo_engine(tiny),
                                journal=CommandLog(capacity=4),
                                checkpoint_store=store, name="m")
            standby = FollowerLoop(_fo_engine(tiny),
                                   LocalFeed(leader, "sb-1"),
                                   name="m", standby=True,
                                   checkpoint_store=store)
            req = Request(id="r", prompt_tokens=[2, 4, 6],
                          sampling=SamplingParams(temperature=0.0,
                                                  max_tokens=40))
            leader.add_request(req)
            leader.step()
            standby.run_once(timeout=0.01)   # applies the head
            assert standby._applied_step >= 0
            # leader runs FAR ahead of the 4-slot ring, checkpointing
            for _ in range(10):
                leader.step()
                time.sleep(0.02)
                leader.checkpoint_tick()
            store.flush(10)
            assert store.writes >= 1
            with pytest.raises(ResyncRequired) as ei:
                promote_follower(standby, store=store, name="m")
            assert ei.value.reason == RESYNC_RING_OVERFLOW
            assert standby.engine.num_preemptions == 0

    def test_handoff_mismatch_peer_gets_typed_resync(self, tiny,
                                                     monkeypatch):
        """A non-standby peer behind the takeover boundary cannot cross
        the handoff (its replica diverges from the parked boundary) —
        it fails typed with handoff_mismatch and restarts fresh."""
        monkeypatch.setenv("HELIX_MH_CHECKPOINT_SECONDS", "0.01")
        import tempfile

        with tempfile.TemporaryDirectory() as tmp:
            store = CheckpointStore(tmp)
            leader = PlanLeader(_fo_engine(tiny), checkpoint_store=store,
                                name="m")
            standby = FollowerLoop(_fo_engine(tiny),
                                   LocalFeed(leader, "sb-1"),
                                   name="m", standby=True,
                                   checkpoint_store=store)
            laggard = FollowerLoop(_fo_engine(tiny),
                                   LocalFeed(leader, "lag-1"),
                                   name="m", checkpoint_store=store)
            req = Request(id="r", prompt_tokens=[2, 4, 6],
                          sampling=SamplingParams(temperature=0.0,
                                                  max_tokens=40))
            leader.add_request(req)
            leader.step()
            laggard.run_once(timeout=0.01)   # applies step 0, then stalls
            behind = laggard._applied_step
            for _ in range(5):
                leader.step()
                time.sleep(0.02)
                leader.checkpoint_tick()
            store.flush(10)
            while standby.run_once(timeout=0.01):
                pass
            new_leader = promote_follower(standby, store=store, name="m")
            assert new_leader._last_plan_idx > behind
            laggard.feed.retarget(new_leader)
            with pytest.raises(ResyncRequired) as ei:
                laggard.run_once(timeout=0.01)
            assert ei.value.reason == RESYNC_HANDOFF_MISMATCH
            assert laggard.resync_reason == RESYNC_HANDOFF_MISMATCH

    def test_cold_start_leader_finishes_waiting_work(self, tiny,
                                                     monkeypatch):
        """Last-resort rung: a FRESH process resumes from the newest
        checkpoint alone.  Requests still waiting (never admitted) at
        the checkpoint finish — delivery for them is exactly-once even
        here, since no step ever ran them before the crash."""
        monkeypatch.setenv("HELIX_MH_CHECKPOINT_SECONDS", "0.01")
        import tempfile

        with tempfile.TemporaryDirectory() as tmp:
            store = CheckpointStore(tmp)
            leader = PlanLeader(_fo_engine(tiny), checkpoint_store=store,
                                name="m")
            active = [
                Request(id=f"a{i}", prompt_tokens=[3 + i, 5],
                        sampling=SamplingParams(temperature=0.0,
                                                max_tokens=30))
                for i in range(2)
            ]
            for r in active:
                leader.add_request(r)
            leader.step()            # fills both decode slots
            queued = Request(id="q", prompt_tokens=[8, 9],
                             sampling=SamplingParams(temperature=0.0,
                                                     max_tokens=4))
            leader.add_request(queued)   # waits behind the full batch
            leader.step()
            time.sleep(0.02)
            leader.checkpoint_tick()
            store.flush(10)
            assert store.writes >= 1
            # leader dies; a fresh process cold-starts from the store
            new_leader = cold_start_leader(_fo_engine(tiny), store,
                                           name="m")
            assert new_leader.takeovers == 1
            _drain(new_leader)
            assert new_leader.engine._requests["q"].finished
            assert len(new_leader.engine._requests["q"].output_tokens) > 0


class TestPlanFeedFaults:
    """Satellite: the plan-feed fault family (testing/faults.py) proves
    the _pump seq discipline repairs duplicated/reordered transports and
    a dropped record re-reads from the ring instead of diverging."""

    def test_duplicate_and_reorder_are_repaired(self, tiny):
        leader = PlanLeader(_engine(tiny), name="m")
        req = Request(id="r", prompt_tokens=[2, 4, 6],
                      sampling=SamplingParams(temperature=0.7, top_k=9,
                                              max_tokens=8))
        leader.add_request(req)
        _drain(leader)
        fe = _engine(tiny)
        follower = FollowerLoop(fe, leader.journal, name="m")
        faults.arm(seed=3, rules=[
            {"point": "plan_feed", "model": "m", "action": "duplicate",
             "p": 0.5},
            {"point": "plan_feed", "model": "m", "action": "reorder",
             "p": 0.3},
        ])
        try:
            _replay(follower)
        finally:
            faults.disarm()
        assert fe._requests["r"].output_tokens == req.output_tokens
        assert follower.stats()["digest_mismatches"] == 0
        assert follower.records_duplicate > 0, "faults never fired"

    def test_dropped_records_rereads_from_ring(self, tiny):
        leader = PlanLeader(_engine(tiny), name="m")
        req = Request(id="r", prompt_tokens=[1, 3, 5],
                      sampling=SamplingParams(temperature=0.0,
                                              max_tokens=8))
        leader.add_request(req)
        _drain(leader)
        fe = _engine(tiny)
        follower = FollowerLoop(fe, leader.journal, name="m")
        faults.arm(seed=11, rules=[
            {"point": "plan_feed", "model": "m", "action": "drop",
             "p": 0.4},
        ])
        try:
            for _ in range(200):
                if not follower.run_once(timeout=0.01):
                    # drained AND nothing dropped on the final pass?
                    if fe._requests.get("r") is not None and \
                            fe._requests["r"].finished:
                        break
        finally:
            faults.disarm()
        _replay(follower)          # clean tail read
        assert fe._requests["r"].output_tokens == req.output_tokens
