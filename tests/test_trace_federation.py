"""Cluster-wide trace federation (ISSUE 18): one stitched timeline per
request across dispatch, the disagg handoff, migration, and the
multihost plan plane.

The contract under test everywhere: span federation is an OBSERVER.
Runner spans ride the existing heartbeat (no new connection, no new
timer); a hostile or malformed span batch degrades to nothing ingested
and can never reject a heartbeat, 500 a debug endpoint, or leak an
unbounded string into /metrics.  On the happy path one trace id
resolves on the control plane to every host's spans in one
skew-corrected, monotone timeline — including the leader/follower plan
plane, correlated by plan seq.
"""

from __future__ import annotations

import asyncio
import json
import math
import os
import threading
import time

import jax
import pytest
import requests

from helix_tpu.engine.engine import Engine, EngineConfig, Request
from helix_tpu.engine.sampling import SamplingParams
from helix_tpu.models.common import ModelConfig
from helix_tpu.models.llama import init_params
from helix_tpu.obs.trace import (
    TraceFederation,
    TraceStore,
    validate_span_batch,
)
from helix_tpu.serving.engine_loop import EngineLoop
from helix_tpu.serving.multihost_serving import (
    FollowerLoop,
    PlanLeader,
    plan_trace_id,
)
from helix_tpu.serving.tokenizer import ByteTokenizer

_TOK = ByteTokenizer()

# a nice wall-clock base well in the past so shifted copies stay positive
_T0 = 1700000000.0


def _wire(tid="trace-0000000a", name="work", start=_T0, dur=0.01,
          plane="runner", **attrs):
    return {
        "trace_id": tid, "name": name, "plane": plane,
        "start_unix": start, "end_unix": start + dur,
        "attrs": {k: str(v) for k, v in attrs.items()},
    }


# ---------------------------------------------------------------------------
# wire validation: the PR 7 discipline — clamp, never raise
# ---------------------------------------------------------------------------


class TestWireValidation:
    def test_recorded_span_roundtrips_through_validation(self):
        st = TraceStore()
        st.enable_export(cap=16)
        t = time.monotonic()
        st.record("trace-roundtrip-1", "prefill", t, t + 0.25,
                  plane="engine", request_id="r1")
        batch = {"spans": st.drain_export()}
        spans, rejected = validate_span_batch(batch)
        assert rejected == 0 and len(spans) == 1
        s = spans[0]
        assert s["trace_id"] == "trace-roundtrip-1"
        assert s["name"] == "prefill"
        assert s["end_unix"] >= s["start_unix"]
        assert s["attrs"]["request_id"] == "r1"

    @pytest.mark.parametrize("raw", [
        "not a dict", 42, [1, 2], {"spans": "nope"}, {"spans": 7},
    ])
    def test_malformed_batch_degrades_counted(self, raw):
        spans, rejected = validate_span_batch(raw)
        assert spans == [] and rejected >= 1

    def test_none_and_empty_are_free(self):
        assert validate_span_batch(None) == ([], 0)
        assert validate_span_batch({}) == ([], 0)
        assert validate_span_batch({"spans": []}) == ([], 0)

    @pytest.mark.parametrize("doc", [
        "not-a-span",
        {},
        {"trace_id": "x", "name": "n", "start_unix": 1, "end_unix": 2},
        _wire(tid="bad id with spaces"),
        _wire(tid="trace-ok-000001", name="rm -rf \x00"),
        {**_wire(), "start_unix": float("nan")},
        {**_wire(), "end_unix": float("inf")},
        {**_wire(), "start_unix": "soon"},
    ])
    def test_hostile_span_rejected_not_raised(self, doc):
        spans, rejected = validate_span_batch({"spans": [doc]})
        assert spans == [] and rejected == 1

    def test_oversized_batch_clamped(self):
        items = [_wire(tid=f"trace-over-{i:04d}") for i in range(40)]
        spans, rejected = validate_span_batch(
            {"spans": items}, max_spans=16
        )
        assert len(spans) == 16 and rejected == 24

    def test_attr_bomb_clamped(self):
        doc = _wire()
        doc["attrs"] = {f"k{i}" * 40: "v" * 10000 for i in range(50)}
        spans, _ = validate_span_batch({"spans": [doc]})
        (s,) = spans
        assert len(s["attrs"]) <= 8
        for k, v in s["attrs"].items():
            assert len(k) <= 64 and len(v) <= 256

    def test_backwards_span_clamped_to_zero_duration(self):
        doc = _wire()
        doc["end_unix"] = doc["start_unix"] - 5.0
        spans, _ = validate_span_batch({"spans": [doc]})
        assert spans[0]["end_unix"] == spans[0]["start_unix"]


# ---------------------------------------------------------------------------
# the runner-side export ring
# ---------------------------------------------------------------------------


class TestExportRing:
    def test_export_off_by_default_and_retroactive_spans_stay_local(self):
        st = TraceStore()
        t = time.monotonic()
        st.record("trace-local-0001", "a", t, t + 0.01)
        assert st.drain_export() == []
        st.enable_export(cap=16)
        assert st.drain_export() == []  # not exported retroactively
        st.record("trace-local-0001", "b", t, t + 0.01)
        assert [s["name"] for s in st.drain_export()] == ["b"]

    def test_overflow_drops_oldest_and_counts(self):
        st = TraceStore()
        st.enable_export(cap=16)
        t = time.monotonic()
        for i in range(20):
            st.record("trace-ring-00001", f"s{i}", t, t + 0.01)
        assert st.export_dropped == 4
        names = [s["name"] for s in st.drain_export(limit=100)]
        assert names[0] == "s4" and names[-1] == "s19"

    def test_drain_respects_batch_limit(self):
        st = TraceStore()
        st.enable_export(cap=64)
        t = time.monotonic()
        for i in range(10):
            st.record("trace-batch-0001", f"s{i}", t, t + 0.01)
        assert len(st.drain_export(limit=3)) == 3
        assert len(st.drain_export(limit=100)) == 7

    def test_per_trace_cap_rings_out_oldest(self):
        st = TraceStore(max_spans_per_trace=4)
        t = time.monotonic()
        for i in range(6):
            st.record("trace-cap-000001", f"s{i}", t + i, t + i + 0.5)
        doc = st.get("trace-cap-000001")
        assert doc["dropped_spans"] == 2
        # the RECENT spans survive (the part being debugged)
        assert [s["name"] for s in doc["spans"]] == [
            "s2", "s3", "s4", "s5"
        ]


# ---------------------------------------------------------------------------
# the control-plane federation store
# ---------------------------------------------------------------------------


class TestFederationStore:
    def _fed(self, **kw):
        return TraceFederation(local=TraceStore(), **kw)

    def test_stitch_applies_causality_skew(self):
        fed = self._fed()
        tid = "trace-skew-00001"
        # cp anchor: the dispatch span exists before any runner span
        m0 = time.monotonic()
        fed.local.record(tid, "dispatch_attempt", m0, m0 + 0.05,
                         plane="control")
        base = time.time()
        # r-skewed's wall clock runs 120 s slow
        fed.ingest("r-skewed", {"spans": [
            _wire(tid=tid, name="prefill", start=base - 120.0, dur=0.2),
            _wire(tid=tid, name="emit", start=base - 119.5, dur=0.1),
        ]})
        fed.ingest("r-true", {"spans": [
            _wire(tid=tid, name="migrate import", start=base + 0.4,
                  dur=0.05),
        ]})
        doc = fed.stitched(tid)
        assert set(doc["hosts"]) == {
            "control-plane", "r-skewed", "r-true"
        }
        shift = doc["clock_skew_applied_s"]["r-skewed"]
        assert shift > 100.0
        assert "r-true" not in doc.get("clock_skew_applied_s", {})
        starts = [s["start_unix"] for s in doc["spans"]]
        assert starts == sorted(starts)
        # causality restored: nothing precedes the dispatch anchor
        cp_start = min(
            s["start_unix"] for s in doc["spans"]
            if s["host"] == "control-plane"
        )
        assert starts[0] >= cp_start - 1e-9

    def test_chrome_trace_one_pid_per_host(self):
        fed = self._fed()
        tid = "trace-chrome-001"
        m0 = time.monotonic()
        fed.local.record(tid, "dispatch_attempt", m0, m0 + 0.01,
                         plane="control")
        base = time.time()
        fed.ingest("r-a", {"spans": [_wire(tid=tid, start=base + 1)]})
        fed.ingest("r-b", {"spans": [_wire(tid=tid, start=base + 2)]})
        doc = fed.chrome_trace(tid)
        pids = {e["pid"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert len(pids) == 3
        names = {
            e["args"]["name"] for e in doc["traceEvents"]
            if e["ph"] == "M"
        }
        assert names == {"helix:control-plane", "helix:r-a", "helix:r-b"}

    def test_prune_runner_drops_its_spans_only(self):
        fed = self._fed()
        tid = "trace-prune-0001"
        base = time.time()
        fed.ingest("r-dead", {"spans": [_wire(tid=tid, start=base)]})
        fed.ingest("r-live", {"spans": [
            _wire(tid=tid, name="other", start=base + 1)
        ]})
        fed.ingest("r-dead", {"spans": [
            _wire(tid="trace-prune-0002", start=base)
        ]})
        fed.prune_runner("r-dead")
        doc = fed.stitched(tid)
        assert doc["hosts"] == ["r-live"]
        assert fed.stitched("trace-prune-0002") is None
        assert "trace-prune-0002" not in fed.ids()
        fed.prune_runner("r-dead")  # idempotent
        fed.prune_runner("never-seen")

    def test_lru_retention_bounded(self):
        fed = self._fed(max_traces=8)
        base = time.time()
        for i in range(20):
            fed.ingest("r1", {"spans": [
                _wire(tid=f"trace-lru-{i:05d}", start=base)
            ]})
        assert len(fed) == 8
        assert fed.stitched("trace-lru-00000") is None
        assert fed.stitched("trace-lru-00019") is not None

    def test_per_trace_cap_counts_and_marks_doc(self):
        fed = self._fed(max_spans_per_trace=4)
        base = time.time()
        tid = "trace-full-0001"
        fed.ingest("r1", {"spans": [
            _wire(tid=tid, name=f"s{i}", start=base + i)
            for i in range(6)
        ]})
        assert fed.ingest_dropped == 2
        doc = fed.stitched(tid)
        assert len(doc["spans"]) == 4 and doc["dropped_spans"] == 2

    @pytest.mark.parametrize("raw", [
        None, {}, "garbage", {"spans": [float("nan")]},
        {"spans": [{"trace_id": "trace-bad-00001",
                    "name": "ok", "start_unix": float("nan"),
                    "end_unix": 1.0}]},
    ])
    def test_ingest_never_raises(self, raw):
        fed = self._fed()
        fed.ingest("r1", raw)  # must not raise — heartbeat-safe

    def test_ids_union_local_first(self):
        fed = self._fed()
        m0 = time.monotonic()
        fed.local.record("trace-local-0009", "a", m0, m0 + 0.01)
        fed.ingest("r1", {"spans": [
            _wire(tid="trace-fed-000009", start=time.time())
        ]})
        ids = fed.ids()
        assert ids.index("trace-local-0009") < ids.index(
            "trace-fed-000009"
        )


# ---------------------------------------------------------------------------
# tools/trace_report.py — the terminal renderer (satellite 5)
# ---------------------------------------------------------------------------


class TestTraceReport:
    def _report(self):
        import importlib.util

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "trace_report_test",
            os.path.join(repo, "tools", "trace_report.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def _doc(self):
        return {
            "trace_id": "trace-report-001",
            "hosts": ["control-plane", "r-dec", "r-pre"],
            "clock_skew_applied_s": {"r-pre": 119.8},
            "spans": [
                {"host": "control-plane", "name": "dispatch_attempt",
                 "plane": "control", "start_unix": _T0,
                 "duration_ms": 50.0, "attrs": {}},
                {"host": "r-pre", "name": "prefill", "plane": "engine",
                 "start_unix": _T0 + 0.05, "duration_ms": 400.0,
                 "attrs": {}},
                {"host": "r-pre", "name": "disagg ship",
                 "plane": "runner", "start_unix": _T0 + 0.45,
                 "duration_ms": 100.0, "attrs": {}},
                {"host": "r-dec", "name": "migrate import",
                 "plane": "runner", "start_unix": _T0 + 0.55,
                 "duration_ms": 50.0, "attrs": {}},
                # a fat uncovered gap before resume
                {"host": "r-dec", "name": "migrate resume",
                 "plane": "runner", "start_unix": _T0 + 2.0,
                 "duration_ms": 700.0, "attrs": {}},
            ],
        }

    def test_render_full_story(self):
        mod = self._report()
        out = mod.render(self._doc(), width=48)
        assert "trace trace-report-001" in out
        assert "5 span(s)" in out and "3 host(s)" in out
        assert "clock skew: r-pre shifted +119.800s" in out
        for host in ("[control-plane]", "[r-pre]", "[r-dec]"):
            assert host in out
        assert "critical path" in out
        assert "largest gap" in out
        assert "migrate import" in out and "migrate resume" in out
        # the gap is > 25% of the trace — the callout fires
        assert "uninstrumented" in out
        # hosts ordered by first activity: cp dispatches first
        assert out.index("[control-plane]") < out.index("[r-pre]")
        assert out.index("[r-pre]") < out.index("[r-dec]")

    def test_render_dropped_warning(self):
        mod = self._report()
        doc = self._doc()
        doc["dropped_spans"] = 7
        assert "7 span(s) dropped" in mod.render(doc)

    def test_render_degenerate_docs(self):
        mod = self._report()
        assert "(no spans)" in mod.render({"trace_id": "t"})
        assert "(no spans)" in mod.render({})
        # hostile spans (missing fields) are skipped, not raised
        out = mod.render({"trace_id": "x", "spans": [
            {"name": "half"}, "junk",
            {"host": "h", "name": "ok", "plane": "p",
             "start_unix": _T0, "duration_ms": 1.0, "attrs": {}},
        ]})
        assert "1 span(s)" in out

    def test_main_reads_file(self, tmp_path, capsys):
        mod = self._report()
        p = tmp_path / "trace.json"
        p.write_text(json.dumps(self._doc()))
        assert mod.main([str(p), "--width", "40"]) == 0
        assert "critical path" in capsys.readouterr().out

    def test_main_rejects_garbage(self, tmp_path, capsys):
        mod = self._report()
        p = tmp_path / "bad.json"
        p.write_text("not json")
        assert mod.main([str(p)]) == 1
        p2 = tmp_path / "list.json"
        p2.write_text("[1, 2]")
        assert mod.main([str(p2)]) == 1


# ---------------------------------------------------------------------------
# multihost plan plane: leader and follower correlate by plan seq
# ---------------------------------------------------------------------------


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


class TestMultihostPlanCorrelation:
    def _pair(self, tiny):
        """Leader + follower, each with its OWN store — two hosts."""
        leader = PlanLeader(_engine(tiny))
        leader._trace = ls = TraceStore()
        follower = FollowerLoop(_engine(tiny), leader.journal,
                                follower_id="f1")
        follower._trace = fs = TraceStore()
        return leader, ls, follower, fs

    def test_publish_apply_digest_share_plan_seq(self, tiny):
        leader, ls, follower, fs = self._pair(tiny)
        leader.add_request(Request(
            id="r1", prompt_tokens=[3, 5, 8],
            sampling=SamplingParams(temperature=0.0, max_tokens=6),
        ))
        _drain(leader)
        _replay(follower)
        ptid = leader.plan_trace_id
        assert ptid == plan_trace_id("") == follower.plan_trace_id
        pub = [s for s in ls.get(ptid)["spans"]
               if s["name"] == "mh plan publish"]
        app = [s for s in fs.get(ptid)["spans"]
               if s["name"] == "mh plan apply"]
        dig = [s for s in fs.get(ptid)["spans"]
               if s["name"] == "mh digest verify"]
        assert pub and app and dig
        pub_seqs = {s["attrs"]["seq"] for s in pub}
        # every applied plan's seq names a published plan's seq
        assert {s["attrs"]["seq"] for s in app} <= pub_seqs
        assert len(app) == len(pub)
        for s in dig:
            assert s["attrs"]["outcome"] == "ok"
        # steps line up pairwise too
        assert ([s["attrs"]["step"] for s in app]
                == [s["attrs"]["step"] for s in pub])

    def test_plan_spans_federate_to_one_stitched_timeline(self, tiny):
        leader, ls, follower, fs = self._pair(tiny)
        ls.enable_export(cap=512)
        fs.enable_export(cap=512)
        leader.add_request(Request(
            id="r1", prompt_tokens=[2, 4, 6],
            sampling=SamplingParams(temperature=0.0, max_tokens=4),
        ))
        _drain(leader)
        _replay(follower)
        fed = TraceFederation(local=TraceStore())
        fed.ingest("host-leader", {"spans": ls.drain_export(limit=512)})
        fed.ingest("host-follower",
                   {"spans": fs.drain_export(limit=512)})
        doc = fed.stitched(leader.plan_trace_id)
        assert set(doc["hosts"]) == {"host-leader", "host-follower"}
        by_seq = {}
        for s in doc["spans"]:
            if s["name"] in ("mh plan publish", "mh plan apply"):
                by_seq.setdefault(s["attrs"]["seq"], set()).add(
                    s["host"]
                )
        # at least one plan seq shows both hosts on the same timeline
        assert any(hosts == {"host-leader", "host-follower"}
                   for hosts in by_seq.values())

    def test_op_record_carries_request_trace_through_follower(self, tiny):
        leader, ls, follower, fs = self._pair(tiny)
        tid = "trace-abort-0001"
        leader.add_request(Request(
            id="victim", prompt_tokens=[1, 2, 3],
            sampling=SamplingParams(temperature=0.0, max_tokens=64),
            trace_id=tid,
        ))
        for _ in range(3):
            leader.step()
        leader.abort("victim")
        _replay(follower)
        pub = [s for s in (ls.get(tid) or {"spans": []})["spans"]
               if s["name"] == "mh op publish"]
        assert pub and pub[0]["attrs"]["op"] == "abort"
        app = [s for s in (fs.get(tid) or {"spans": []})["spans"]
               if s["name"] == "mh op apply"]
        assert app and app[0]["attrs"]["request_id"] == "victim"
        assert app[0]["attrs"]["follower"] == "f1"

    def test_untraced_request_publishes_no_op_span(self, tiny):
        leader, ls, follower, fs = self._pair(tiny)
        leader.add_request(Request(
            id="plain", prompt_tokens=[1, 2],
            sampling=SamplingParams(temperature=0.0, max_tokens=64),
        ))
        for _ in range(3):
            leader.step()
        leader.abort("plain")
        _replay(follower)
        for store in (ls, fs):
            for tid in store.ids():
                for s in store.get(tid)["spans"]:
                    assert s["name"] not in (
                        "mh op publish", "mh op apply"
                    ), "fabricated a trace id for an untraced request"


# ---------------------------------------------------------------------------
# what one request costs in spans at the engine plane
# ---------------------------------------------------------------------------


class TestSpansARequest:
    """The engine plane records a fixed handful of spans a request,
    whatever its length: the HTTP planes stack theirs on top."""

    PROMPT = list(range(3, 27))
    SAMPLING = SamplingParams(temperature=0.0, max_tokens=16)

    @pytest.mark.parametrize("flow", ["plain", "migrated"])
    def test_engine_plane_span_names(self, tiny, flow):
        from helix_tpu.serving import migration

        tid = f"spans-{flow}-0001"
        loop = EngineLoop(_engine(tiny), name=f"spans-{flow}")
        loop._trace = store = TraceStore()   # a host of its own
        loop.start()
        done = threading.Event()

        def cb(e):
            if e.finished:
                done.set()

        req = Request(id="one", prompt_tokens=list(self.PROMPT),
                      sampling=self.SAMPLING, trace_id=tid)
        want = ["queue", "prefill", "admit_to_token", "first_token_hold",
                "decode"]
        try:
            if flow == "plain":
                loop.submit(req, cb)
            else:
                # cut mid-decode on another engine, through the wire
                # format, and finished here: the import span and then
                # the same five
                src = _engine(tiny)
                src.add_request(req)
                while len(req.output_tokens) < 4:
                    src.step()
                wire = migration.snapshot_to_wire(
                    src.export_request("one"))
                imported = threading.Event()
                loop.submit_import(
                    migration.wire_to_snapshot(wire), cb,
                    on_result=lambda err, code: imported.set(),
                )
                assert imported.wait(60)
                want = ["engine import admit"] + want
            assert done.wait(60)
        finally:
            loop.stop(join=True)
        assert [s["name"] for s in store.get(tid)["spans"]] == want


# ---------------------------------------------------------------------------
# the full HTTP spine: cp + two pool runners, three hosts on one trace
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
def fedpools(tiny):
    """A prefill runner + a decode runner + a cp with disagg armed —
    each runner holding its OWN trace store (as on real hosts), so the
    only way its spans reach the cp is the heartbeat push."""
    from helix_tpu.control.server import ControlPlane
    from helix_tpu.serving.openai_api import OpenAIServer
    from helix_tpu.serving.registry import ModelRegistry, ServedModel

    import dataclasses

    cfg, params = tiny
    # the snapshot wire names the ENGINE's model; it must match the
    # served name or the ship finds no target
    cfg = dataclasses.replace(cfg, name="m1")
    prior = os.environ.get("HELIX_POOL_DISAGG")
    os.environ["HELIX_POOL_DISAGG"] = "1"
    holder: dict = {}
    sides = {}
    for side in ("r-pre", "r-dec"):
        store = TraceStore()
        store.enable_export(cap=2048)
        registry = ModelRegistry()
        engine = Engine(
            cfg, params,
            EngineConfig(
                max_decode_batch=4, page_size=4, num_pages=64,
                max_pages_per_seq=32, max_prefill_len=64,
                attn_backend="reference",
                eos_token_ids=tuple(_TOK.eos_ids),
            ),
        )
        loop = EngineLoop(engine, f"{side}-m1")
        loop._trace = store   # this "host"'s engine-plane spans
        loop.start()
        registry.register(
            ServedModel(name="m1", loop=loop, tokenizer=_TOK,
                        context_length=256)
        )
        api = OpenAIServer(registry, trace_store=store)
        port = _serve_app(api.build_app(), holder)
        sides[side] = {
            "loop": loop, "api": api, "store": store,
            "url": f"http://127.0.0.1:{port}",
        }
    cp = ControlPlane()
    cp_port = _serve_app(cp.build_app(), holder)
    cp_url = f"http://127.0.0.1:{cp_port}"

    def heartbeat(rid, role, traces=None):
        body = {
            "runner_id": rid,
            "address": sides[rid]["url"] if rid in sides else
            "http://127.0.0.1:1",
            "accelerators": [],
            "profile": {"name": "p", "status": "running",
                        "models": ["m1"]},
            "saturation": {},
            "role": role,
        }
        if traces is not None:
            body["traces"] = traces
        r = requests.post(
            f"{cp_url}/api/v1/runners/{rid}/heartbeat",
            json=body, timeout=10,
        )
        assert r.status_code == 200, r.text
        return r

    heartbeat("r-pre", "prefill")
    heartbeat("r-dec", "decode")
    from types import SimpleNamespace

    yield SimpleNamespace(
        sides=sides, cp=cp, cp_url=cp_url, heartbeat=heartbeat,
    )
    if prior is None:
        os.environ.pop("HELIX_POOL_DISAGG", None)
    else:
        os.environ["HELIX_POOL_DISAGG"] = prior
    cp.stop()
    for side in sides.values():
        side["loop"].stop(join=False)
    for lp in holder.get("loops", []):
        lp.call_soon_threadsafe(lp.stop)


_MSG = [{"role": "user", "content": "stitch the hosts, keep the spans"}]


def _stream_via_cp(url, tid):
    content = []
    with requests.post(
        f"{url}/v1/chat/completions",
        json={"model": "m1", "temperature": 0, "max_tokens": 24,
              "stream": True, "messages": _MSG},
        headers={"X-Helix-Trace-Id": tid},
        stream=True, timeout=120,
    ) as r:
        assert r.status_code == 200, r.text
        assert r.headers.get("X-Helix-Trace-Id") == tid
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


def _drain_for(store, tid, deadline=10.0):
    """All exported wire spans of one trace (spans complete shortly
    after the stream does — poll briefly)."""
    out, others = [], []
    t_end = time.monotonic() + deadline
    while time.monotonic() < t_end:
        for s in store.drain_export(limit=4096):
            (out if s["trace_id"] == tid else others).append(s)
        if out:
            break
        time.sleep(0.05)
    return out


class TestFederationHTTPSpine:
    def test_disagg_request_stitches_three_hosts(self, fedpools):
        """The tentpole acceptance: one trace id, pushed over real
        heartbeats from two runners, resolves on the cp to a
        skew-corrected monotone timeline spanning dispatch -> disagg
        handoff -> decode resume across >= 3 hosts."""
        tid = "fedspine-disagg-0001"
        content = _stream_via_cp(fedpools.cp_url, tid)
        assert content
        pre = _drain_for(fedpools.sides["r-pre"]["store"], tid)
        dec = _drain_for(fedpools.sides["r-dec"]["store"], tid)
        assert pre, "prefill runner recorded no spans for the trace"
        assert dec, "decode runner recorded no spans for the trace"
        # r-pre's wall clock runs 2 minutes slow: shift its spans back
        # so only causality correction can restore the timeline
        for s in pre:
            s["start_unix"] -= 120.0
            s["end_unix"] -= 120.0
        fedpools.heartbeat("r-pre", "prefill", traces={"spans": pre})
        fedpools.heartbeat("r-dec", "decode", traces={"spans": dec})

        r = requests.get(
            f"{fedpools.cp_url}/v1/debug/traces/{tid}", timeout=10
        )
        assert r.status_code == 200, r.text
        doc = r.json()
        assert len(doc["hosts"]) >= 3
        assert {"control-plane", "r-pre", "r-dec"} <= set(doc["hosts"])
        names_by_host = {}
        for s in doc["spans"]:
            names_by_host.setdefault(s["host"], set()).add(s["name"])
        assert "dispatch_attempt" in names_by_host["control-plane"]
        assert any("disagg" in n for n in names_by_host["r-pre"])
        assert "migrate import" in names_by_host["r-dec"]
        assert "migrate resume" in names_by_host["r-dec"]
        # skew-corrected: monotone, r-pre shifted forward ~120 s, and
        # nothing precedes the dispatch anchor
        starts = [s["start_unix"] for s in doc["spans"]]
        assert starts == sorted(starts)
        assert all(math.isfinite(t) for t in starts)
        assert doc["clock_skew_applied_s"]["r-pre"] > 100.0
        cp_start = min(s["start_unix"] for s in doc["spans"]
                       if s["host"] == "control-plane")
        assert starts[0] >= cp_start - 1e-6
        # the trace id is listed cluster-wide
        listed = requests.get(
            f"{fedpools.cp_url}/v1/debug/traces", timeout=10
        ).json()["traces"]
        assert tid in listed

    def test_chrome_export_renders_hosts_as_processes(self, fedpools):
        tid = "fedspine-disagg-0001"  # stitched by the test above
        r = requests.get(
            f"{fedpools.cp_url}/v1/debug/traces/{tid}?format=chrome",
            timeout=10,
        )
        assert r.status_code == 200
        doc = r.json()
        assert "traceEvents" in doc
        pids = {e["pid"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert len(pids) >= 3
        for e in doc["traceEvents"]:
            if e["ph"] == "X":
                assert e["dur"] >= 1.0

    def test_hostile_span_batch_degrades_without_500(self, fedpools):
        """A compromised runner pushes garbage: the heartbeat still
        succeeds (rejecting would TTL-evict a healthy runner), nothing
        hostile reaches the debug surface or /metrics."""
        poison = "helix_evil_{label=\"x\"} 1"
        hostile = {"spans": [
            "junk",
            {"trace_id": "trace-hostile-01", "name": poison,
             "start_unix": 1.0, "end_unix": 2.0},
            {"trace_id": "trace-hostile-01", "name": "ok span",
             "start_unix": 1e308, "end_unix": 1e309},  # end -> inf
            {"trace_id": "x", "name": "short-id", "start_unix": 1,
             "end_unix": 2},
            {"trace_id": "trace-hostile-01", "name": "attr bomb",
             "start_unix": 1.0, "end_unix": 2.0,
             "attrs": {("k" * 500): "v" * 99999}},
        ] + [{"trace_id": f"trace-flood-{i:06d}", "name": "flood",
              "start_unix": 1.0, "end_unix": 2.0}
             for i in range(5000)]}
        # raw-serialize with allow_nan so the non-finite timestamp
        # actually reaches the wire as ``Infinity`` (requests' own
        # encoder would refuse to send it)
        body = {
            "runner_id": "r-dec",
            "address": fedpools.sides["r-dec"]["url"],
            "accelerators": [],
            "profile": {"name": "p", "status": "running",
                        "models": ["m1"]},
            "saturation": {}, "role": "decode", "traces": hostile,
        }
        r = requests.post(
            f"{fedpools.cp_url}/api/v1/runners/r-dec/heartbeat",
            data=json.dumps(body, allow_nan=True),
            headers={"Content-Type": "application/json"},
            timeout=10,
        )
        assert r.status_code == 200, r.text
        # rejected counted, nothing leaked into exposition
        metrics = requests.get(
            f"{fedpools.cp_url}/metrics", timeout=10
        ).text
        assert "helix_cp_trace_ingest_rejected_total" in metrics
        rej = [ln for ln in metrics.splitlines()
               if ln.startswith("helix_cp_trace_ingest_rejected_total")]
        assert rej and float(rej[0].split()[-1]) >= 1
        assert "helix_evil_" not in metrics
        # the debug endpoints stay healthy
        r = requests.get(
            f"{fedpools.cp_url}/v1/debug/traces", timeout=10
        )
        assert r.status_code == 200
        r = requests.get(
            f"{fedpools.cp_url}/v1/debug/traces/trace-hostile-01",
            timeout=10,
        )
        assert r.status_code in (200, 404)
        if r.status_code == 200:
            assert poison not in json.dumps(r.json().get("hosts", []))

    def test_trace_metric_families_on_both_planes(self, fedpools):
        run = requests.get(
            f"{fedpools.sides['r-pre']['url']}/metrics", timeout=10
        ).text
        assert "helix_trace_dropped_spans_total" in run
        cp = requests.get(f"{fedpools.cp_url}/metrics", timeout=10).text
        for fam in (
            "helix_cp_traces_stored",
            "helix_cp_trace_ingest_spans_total",
            "helix_cp_trace_ingest_dropped_total",
            "helix_cp_trace_ingest_rejected_total",
        ):
            assert fam in cp, fam

    def test_runner_eviction_prunes_federated_spans(self, fedpools):
        tid = "fedspine-evict-001"
        fedpools.heartbeat("r-ghost", "decode", traces={"spans": [
            _wire(tid=tid, name="orphan", start=time.time()),
        ]})
        r = requests.get(
            f"{fedpools.cp_url}/v1/debug/traces/{tid}", timeout=10
        )
        assert r.status_code == 200
        fedpools.cp.router.remove("r-ghost")
        r = requests.get(
            f"{fedpools.cp_url}/v1/debug/traces/{tid}", timeout=10
        )
        assert r.status_code == 404


# ---------------------------------------------------------------------------
# lint contract 13 fixtures: one minting site for the trace families
# ---------------------------------------------------------------------------


class TestLintContract13:
    def _tree(self, tmp_path, rel, extra):
        import shutil

        root = tmp_path
        for sub in ("helix_tpu/obs", "helix_tpu/serving",
                    "helix_tpu/control", "tools"):
            (root / sub).mkdir(parents=True, exist_ok=True)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for f in (
            "helix_tpu/obs/flight.py",
            "helix_tpu/obs/trace.py",
            "helix_tpu/serving/sched.py",
            "helix_tpu/serving/migration.py",
            "helix_tpu/serving/kv_filestore.py",
            "helix_tpu/serving/engine_loop.py",
            "helix_tpu/serving/openai_api.py",
            "helix_tpu/control/node_agent.py",
            "helix_tpu/control/server.py",
            "helix_tpu/control/router.py",
            "helix_tpu/control/compute.py",
        ):
            shutil.copy(os.path.join(repo, f), root / f)
        (root / rel).write_text(extra)
        return str(root)

    def _lint(self, root):
        import importlib.util

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "lint_metrics_trace_test",
            os.path.join(repo, "tools", "lint_metrics.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.run(root)

    def test_runner_trace_literal_outside_module_rejected(self, tmp_path):
        root = self._tree(
            tmp_path, "helix_tpu/serving/rogue.py",
            'X = "helix_trace_dropped_spans_total"\n',
        )
        assert any("trace-federation series" in v for v in self._lint(root))

    def test_cp_trace_literal_outside_module_rejected(self, tmp_path):
        root = self._tree(
            tmp_path, "helix_tpu/control/rogue.py",
            'X = "helix_cp_trace_ingest_spans_total"\n',
        )
        assert any("trace-federation series" in v for v in self._lint(root))

    def test_importer_pattern_enforced(self, tmp_path):
        root = self._tree(
            tmp_path, "helix_tpu/control/rogue.py", "X = 1\n"
        )
        # strip the importer call from the cp surface
        path = os.path.join(root, "helix_tpu", "control", "server.py")
        with open(path, encoding="utf-8") as f:
            src = f.read()
        with open(path, "w", encoding="utf-8") as f:
            f.write(src.replace("collect_cp_trace_ingest", "cp_tr_ing"))
        assert any("collect_cp_trace_ingest" in v
                   for v in self._lint(root))

    def test_repo_is_clean(self):
        import importlib.util

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "lint_metrics_trace_clean",
            os.path.join(repo, "tools", "lint_metrics.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.run(repo) == []
