"""OpenAI- and Anthropic-compatible HTTP surface over the engine.

Mirrors the reference's public inference surface exactly (the routes its
inference-proxy forwards: ``/v1/chat/completions``, ``/v1/completions``,
``/v1/embeddings``, ``/v1/models`` — ``api/pkg/inferenceproxy/proxy.go:
94-120`` — plus the native Anthropic ``/v1/messages`` proxy surface,
``api/pkg/anthropic/anthropic_proxy.go:32-40``), so a reference control
plane can point at this server the way it points at a vLLM container.

SSE framing follows OpenAI: ``data: {json}\n\n`` chunks, closing
``data: [DONE]``; Anthropic streaming emits the event-typed frames
(message_start / content_block_delta / message_stop).
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import threading
import time
import uuid
from typing import Optional

from aiohttp import web

from helix_tpu import obs
from helix_tpu.engine.engine import Request, SnapshotError
from helix_tpu.engine.sampling import SamplingParams
from helix_tpu.obs.canary import collect_canary_metrics, default_prober
from helix_tpu.obs.flight import WATCH
from helix_tpu.obs.slo import ANON_TENANT, TENANT_HEADER, sanitize_tenant
from helix_tpu.engine.adapters import (
    ADAPTER_SEP,
    MAX_LISTED_ADAPTERS,
    collect_adapter_metrics,
    sanitize_adapter_id,
    split_model_adapter,
)
from helix_tpu.serving.sched import CLASS_HEADER, sanitize_class
from helix_tpu.obs.trace import (
    TRACE_HEADER,
    adopt_trace_id,
    clock_stamp,
    collect_trace_metrics,
    is_trace_id,
    phase,
)
from helix_tpu.serving.engine_loop import (
    KV_EXHAUSTED,
    QUEUE_FULL,
    SHUTTING_DOWN,
)
from helix_tpu.serving.context_cache import (
    collect_ctx_metrics,
    context_cache_for,
)
from helix_tpu.serving.kv_filestore import collect_filestore_kv, kv_filestore_dir
from helix_tpu.serving.multihost_serving import collect_mh_metrics
from helix_tpu.serving.migration import (
    DISAGG_HEADER,
    DISAGG_PEER_ADDR_HEADER,
    DISAGG_PEER_ID_HEADER,
    MIGRATED,
    ImportedStream,
    ImportedStreams,
    XferConfig,
    collect_runner_migration,
    collect_xfer,
    make_chunk,
    migrated_error,
    migration_timeout,
    wire_to_snapshot,
)
from helix_tpu.serving.registry import ModelRegistry
from helix_tpu.serving.tokenizer import IncrementalDetokenizer, _content_text


def _now() -> int:
    return int(time.time())


# seconds a profiler capture's answer may stay silent before it is streamed
_PROFILER_QUIET_S = 45.0
# the serving event loop's heartbeat: ten observations of its lag a second
_HEARTBEAT_S = 0.1
_LONGPOLL_POOL = None


def _longpoll_pool():
    """Dedicated pool for multi-host journal long-polls (they park a
    thread for tens of seconds each)."""
    global _LONGPOLL_POOL
    if _LONGPOLL_POOL is None:
        import concurrent.futures

        _LONGPOLL_POOL = concurrent.futures.ThreadPoolExecutor(
            max_workers=16, thread_name_prefix="mh-longpoll"
        )
    return _LONGPOLL_POOL


def _error(status: int, message: str, etype: str = "invalid_request_error",
           headers: Optional[dict] = None, trace_id: str = "",
           request_id: str = "", code: str = ""):
    """Structured error body.  When a trace id is known it rides both the
    body and the response header, so a failing request can be correlated
    from the client straight to runner logs and /v1/debug/traces."""
    err: dict = {"message": message, "type": etype}
    if code:
        err["code"] = code
    if trace_id:
        err["trace_id"] = trace_id
        headers = {**(headers or {}), TRACE_HEADER: trace_id}
    if request_id:
        err["request_id"] = request_id
    return web.json_response({"error": err}, status=status, headers=headers)


class EngineRequestError(Exception):
    """A request the engine rejected or failed mid-flight; surfaces as a
    structured 4xx/5xx instead of a dead stream."""

    def __init__(self, message: str, request_id: str = ""):
        super().__init__(message)
        self.request_id = request_id


def _engine_error_response(e: Exception, trace_id: str = ""):
    """Map an engine error onto its HTTP shape: shed load is a clean 429
    with Retry-After, drain is 503, engine timeouts are 504, everything
    else stays the seed's 400."""
    msg = str(e)
    rid = getattr(e, "request_id", "")
    if msg.startswith(QUEUE_FULL):
        return _error(429, msg, "overloaded_error",
                      headers={"Retry-After": "1"}, trace_id=trace_id,
                      request_id=rid)
    if msg.startswith(KV_EXHAUSTED):
        # typed KV-exhaustion shed (ISSUE 6): the engine is out of KV
        # pages and the request outwaited (or would outwait) the
        # admission deadline — clean 503 + Retry-After, code kv_exhausted
        return _error(503, msg, "overloaded_error",
                      headers={"Retry-After": "2"}, trace_id=trace_id,
                      request_id=rid, code="kv_exhausted")
    if msg.startswith(SHUTTING_DOWN):
        return _error(503, msg, "overloaded_error",
                      headers={"Retry-After": "5"}, trace_id=trace_id,
                      request_id=rid)
    if msg.startswith(MIGRATED):
        # the request was exported to a peer at the drain deadline
        # (ISSUE 11): the control plane's mid-stream failover resumes
        # SSE streams in place; non-stream callers get a typed retry
        return _error(503, msg, "overloaded_error",
                      headers={"Retry-After": "1"}, trace_id=trace_id,
                      request_id=rid, code="migrated")
    if msg.startswith("inter_token_timeout"):
        return _error(504, msg, "timeout_error", trace_id=trace_id,
                      request_id=rid)
    return _error(400, msg, trace_id=trace_id, request_id=rid)


def _sse_error_frame(e: Exception, trace_id: str = "") -> dict:
    """In-band SSE error payload with correlation ids (a quarantined
    request's client error names the trace/request the runner logged)."""
    err: dict = {"message": str(e)}
    if trace_id:
        err["trace_id"] = trace_id
    rid = getattr(e, "request_id", "")
    if rid:
        err["request_id"] = rid
    return {"error": err}


class OpenAIServer:
    def __init__(self, registry: ModelRegistry, metrics=None,
                 inter_token_timeout: Optional[float] = None,
                 obs_registry: Optional[obs.Registry] = None,
                 trace_store: Optional[obs.TraceStore] = None):
        import os
        from helix_tpu.serving.logbuf import install as install_logbuf

        self.registry = registry
        self.metrics = metrics
        self.started = time.monotonic()
        self.logbuf = install_logbuf()
        # shared metrics registry (obs): every runner-side series renders
        # through it — engine counters/gauges attach per model at scrape
        # time, latency histograms come from each EngineLoop's obs bundle
        self.obs = obs_registry or obs.Registry()
        self.obs.register_callback(self._collect_metrics)
        # identity check, not truthiness: an EMPTY TraceStore is falsy
        # (__len__ == 0) but still the caller's store
        self.traces = (trace_store if trace_store is not None
                       else obs.default_store())
        self._profiler_lock = threading.Lock()
        # the /metrics and flight renders open now, by path (the stall
        # watch asks at a capture), and the event loop's heartbeat
        self._scrapes: list = []
        self._beat: Optional[asyncio.TimerHandle] = None
        self._beat_due = 0.0
        # migrated-in requests awaiting their resumed stream (ISSUE 11):
        # the peer engine may start generating before the control plane
        # attaches, so token events buffer here until /v1/migrate/resume
        # claims them (or the migration timeout aborts the orphan)
        self._imported = ImportedStreams()
        # context-caching registry (ISSUE 20): shared with the node
        # agent's heartbeat block via the per-root singleton; persisted
        # through the PR 14 filestore root when one is armed
        self.ctx_cache = context_cache_for(kv_filestore_dir())
        # max seconds between consecutive engine events for one request
        # before the server gives up on it (wedged engine watchdog)
        self.inter_token_timeout = (
            inter_token_timeout
            if inter_token_timeout is not None
            else float(os.environ.get("HELIX_INTER_TOKEN_TIMEOUT", "300"))
        )

    # ------------------------------------------------------------------
    def build_app(self) -> web.Application:
        app = web.Application()
        app.router.add_get("/healthz", self.healthz)
        app.router.add_get("/metrics", self.prometheus_metrics)
        app.router.add_get("/logs", self.tail_logs)
        app.router.add_post("/admin/prefetch", self.prefetch_model)
        app.router.add_get("/v1/models", self.list_models)
        # multi-LoRA registry surface (ISSUE 15): publish a trained
        # LoRA checkpoint for `model@adapter` serving — no restart, no
        # hot-swap, no recompile (the pool shape compiled at warmup)
        app.router.add_post("/v1/adapters", self.publish_adapter)
        # context-caching API (ISSUE 20): persist a prompt prefix once
        # (prefilled + adopted into the residency ladder), reference it
        # from chat/completions via context_id — the cached span's
        # prefill is skipped on every reuse
        app.router.add_post("/v1/context", self.create_context)
        app.router.add_get("/v1/context", self.list_contexts)
        app.router.add_post("/v1/chat/completions", self.chat_completions)
        app.router.add_post("/v1/completions", self.completions)
        app.router.add_post("/v1/embeddings", self.embeddings)
        app.router.add_post("/v1/messages", self.anthropic_messages)
        # request tracing + on-demand device profiling (obs)
        app.router.add_get("/v1/debug/traces", self.debug_traces_list)
        app.router.add_get(
            "/v1/debug/traces/{trace_id}", self.debug_trace
        )
        # engine flight recorder: per-step saturation ring + frozen
        # anomaly snapshots (ISSUE 4)
        app.router.add_get("/v1/debug/flight", self.debug_flight)
        # admission-decision audit trail: every shed / quarantine /
        # preemption with its tenant + trace id (ISSUE 7)
        app.router.add_get("/v1/debug/admissions", self.debug_admissions)
        # cross-runner migration (ISSUE 11): a peer ships a request
        # snapshot in; the control plane re-attaches the client stream
        app.router.add_post("/v1/migrate/import", self.migrate_import)
        app.router.add_post("/v1/migrate/resume", self.migrate_resume)
        app.router.add_post("/admin/profiler", self.profiler_capture)
        # multi-host step-plan feed (followers long-poll over DCN;
        # see serving/multihost_serving.py).  The route keeps its
        # historical name — followers of either wire version find it,
        # and the version field inside each record does the rejecting.
        app.router.add_get("/multihost/commands", self.multihost_commands)
        app.on_startup.append(self._start_heartbeat)
        app.on_cleanup.append(self._stop_heartbeat)
        return app

    # -- the event loop's heartbeat (ISSUE 51) ------------------------------

    async def _start_heartbeat(self, app) -> None:
        """Every ``_HEARTBEAT_S`` a timer on the serving event loop
        observes how late it ran (``helix_http_loop_lag_seconds``: what a
        token's ``call_soon_threadsafe``, a handler and an SSE write wait
        for this thread) and stamps the marker the stall watch reads: a
        loop silent past the stall rule is a stall ``where: http``."""
        WATCH.http_state = self._http_state
        loop = WATCH.http_loop = asyncio.get_running_loop()
        self._beat_due = loop.time() + _HEARTBEAT_S
        self._beat = loop.call_later(_HEARTBEAT_S, self._heartbeat, loop)

    def _heartbeat(self, loop) -> None:
        now = loop.time()
        WATCH.beat(max(0.0, now - self._beat_due))
        self._beat_due = now + _HEARTBEAT_S
        self._beat = loop.call_later(_HEARTBEAT_S, self._heartbeat, loop)

    async def _stop_heartbeat(self, app) -> None:
        if self._beat is not None:
            self._beat.cancel()
            self._beat = None
        WATCH.http.at = WATCH.http_loop = None
        WATCH.http_state = dict

    def _http_state(self) -> dict:
        return {"profiler_capture": self._profiler_lock.locked(),
                "scrapes_open": list(self._scrapes)}

    @contextlib.contextmanager
    def _scrape(self, path: str):
        """A render of ``path`` as the span ``helix.http.scrape``, on the
        thread that makes it: it holds the GIL, beside the engine
        thread's."""
        self._scrapes.append(path)
        try:
            with phase("helix.http.scrape", path=path):
                yield
        finally:
            self._scrapes.remove(path)

    async def multihost_commands(self, request):
        """Leader-side plan feed for follower hosts."""
        import asyncio as _asyncio

        from helix_tpu.serving.multihost_serving import LagError

        model = request.query.get("model", "")
        served = self.registry.get(model)
        if served is None or served.loop is None:
            return _error(404, f"model '{model}' is not served here")
        # multihost-ok: transport plumbing (serving the PlanLeader's
        # ring), not a feature guard
        journal = getattr(served.loop.engine, "journal", None)
        if journal is None:
            # multihost-ok: the word is in the client's error message
            return _error(
                400, f"model '{model}' is not running as a multihost "
                "leader"
            )
        since = int(request.query.get("since", 0))
        timeout = min(float(request.query.get("timeout", 25)), 55.0)
        # per-follower registration + health (ISSUE 17): HTTPFeed sends
        # the follower's identity and applied position as query params;
        # the leader's bounded registry drives the lag ladder and the
        # helix_mh_follower_* family.  multihost-ok: transport plumbing.
        note = getattr(served.loop.engine, "note_poll", None)
        fid = request.query.get("follower_id", "")
        if note is not None and fid:
            def _qint(key):
                v = request.query.get(key)
                try:
                    return int(v) if v is not None else None
                except ValueError:
                    return None

            try:
                apply_ms = float(request.query.get("apply_ms", ""))
            except ValueError:
                apply_ms = None
            note(
                fid[:128], since,
                applied_step=_qint("applied_step"),
                apply_ms=apply_ms,
                digest_checks=_qint("digest_checks"),
                digest_mismatches=_qint("digest_mismatches"),
                standby=request.query.get("standby", "0")
                in ("1", "true"),
            )
        try:
            # long-polls park a thread for up to ``timeout`` — keep them
            # out of the shared default executor or a few followers
            # would starve every other run_in_executor call
            records = await _asyncio.get_running_loop().run_in_executor(
                _longpoll_pool(), journal.read_since, since, timeout
            )
        except LagError as e:
            return web.json_response({"lagged": True, "error": str(e)})
        return web.json_response({"records": records})

    # ------------------------------------------------------------------
    async def healthz(self, request):
        return web.json_response(
            {"status": "ok", "models": self.registry.names()}
        )

    async def prometheus_metrics(self, request):
        """Prometheus text surface, rendered by the shared obs registry.
        Runs in an executor: scrape-time collectors take live locks (the
        residency manager's stats() lock is held across whole model
        builds) and must never block the event loop."""
        def render():
            with self._scrape("/metrics"):
                return self.obs.render()

        text = await asyncio.get_running_loop().run_in_executor(
            None, render
        )
        return web.Response(text=text)

    def _collect_metrics(self, c: "obs.Collector") -> None:
        """Scrape-time collection from every live engine (per-model
        labels) + the residency manager.  Counter/gauge values are plain
        GIL-atomic int reads off the engine thread's state."""
        c.gauge(
            "helix_uptime_seconds", time.monotonic() - self.started,
            help="Runner process uptime",
        )
        # KV-transfer outcomes (ISSUE 14): process-wide (drain shippers
        # and disagg handoffs share one ledger), minted ONLY by
        # serving/migration.py (lint contract 10)
        collect_xfer(c)
        # trace-loss series (ISSUE 18): spans lost to the per-trace cap
        # or the federation export ring, minted ONLY by obs/trace.py
        # (lint contract 13)
        collect_trace_metrics(c, self.traces)
        # correctness-canary series (ISSUE 19): health rung + probe /
        # mismatch counters from the node agent's prober, minted ONLY
        # by obs/canary.py (lint contract 14); no-op until one starts
        collect_canary_metrics(c, default_prober())
        # context-caching registry (ISSUE 20): handle/token gauges and
        # create/hit/miss/quota counters, minted ONLY by
        # serving/context_cache.py (lint contract 15)
        collect_ctx_metrics(c, self.ctx_cache)
        for m in self.registry.list():
            if m.loop is None:
                continue
            eng = m.loop.engine
            lbl = {"model": m.name}
            c.counter("helix_engine_steps", m.loop.steps, lbl)
            c.counter(
                "helix_prefill_tokens_total", eng.num_prefill_tokens, lbl
            )
            c.counter(
                "helix_decode_tokens_total", eng.num_decode_tokens, lbl
            )
            # ragged mixed steps: chunk prefill + decode in ONE call
            c.counter(
                "helix_mixed_steps_total",
                getattr(eng, "num_mixed_steps", 0), lbl,
            )
            # programs whose prefill rows and decode rows went through
            # the layers in one pass (each weight streamed once)
            c.counter(
                "helix_joint_pass_steps_total",
                getattr(eng, "num_joint_pass_steps", 0), lbl,
            )
            # tokens the running rows decoded inside admission waves
            c.counter(
                "helix_wave_decode_tokens_total",
                getattr(eng, "num_wave_decode_tokens", 0), lbl,
            )
            # MoE prefill routing assignments dropped to expert-capacity
            # overflow (rode the residual stream instead)
            c.counter(
                "helix_moe_dropped_tokens_total",
                getattr(eng, "moe_dropped_tokens", 0), lbl,
            )
            if getattr(eng.model_cfg, "num_experts", 0):
                # the routing load, from the small array each MoE step
                # returns with its tokens: (token, choice) assignments
                # routed; of the last step read, the busiest expert's
                # tokens over the mean and the distinct experts touched
                # (mean over the MoE layers: it decides a decode step's
                # weight bytes).  A program with a prefill segment runs
                # ONE product over its prefill tokens and decode rows, so
                # its ratio, experts touched and tile fill are of both
                # together
                c.counter(
                    "helix_moe_routed_tokens_total",
                    getattr(eng, "moe_routed_tokens", 0), lbl,
                )
                c.gauge(
                    "helix_moe_expert_load_max_ratio",
                    getattr(eng, "moe_expert_load_max_ratio", 0.0), lbl,
                )
                c.gauge(
                    "helix_moe_experts_touched",
                    getattr(eng, "moe_experts_touched", 0.0), lbl,
                )
                # the dropless grouped product's rows routed over rows
                # walked by its (row tile, expert) visits, same step
                c.gauge(
                    "helix_moe_tile_fill_ratio",
                    getattr(eng, "moe_tile_fill_ratio", 0.0), lbl,
                )
            if getattr(eng.model_cfg, "held_experts", None):
                # one expert-parallel rank: assignments to the experts held
                # here (computed) and to experts elsewhere (not); their
                # ratio is how near this rank's share is to its 1 / ranks
                c.counter(
                    "helix_moe_held_tokens_total",
                    getattr(eng, "moe_routed_tokens", 0), lbl,
                )
                c.counter(
                    "helix_moe_away_tokens_total",
                    getattr(eng, "moe_away_tokens", 0), lbl,
                )
            # the engine's series by kind: what the record of the kind of the
            # model's pages and that of its state kind (``models/mixers.py``)
            # show of the page pool and the state pool, from the host's
            # account of the launches
            kinds = getattr(eng, "kinds", ())
            values = eng.mixer_values() if kinds else {}
            for kind in kinds:
                if kind is eng.mixer and kind.snapshots:
                    # a second kind of state beside the pages: boundary
                    # states kept for the prefix cache, states written into
                    # admitted hits' slots, hits cut back for want of a state
                    c.counter(
                        "helix_state_snapshots_total",
                        getattr(eng, "num_state_snapshots", 0), lbl,
                    )
                    c.counter(
                        "helix_state_restores_total",
                        getattr(eng, "num_state_restores", 0), lbl,
                    )
                    c.counter(
                        "helix_prefix_hits_shortened_total",
                        getattr(eng, "prefix_hits_shortened", 0), lbl,
                    )
                for sr in kind.series:
                    getattr(c, sr.kind)(
                        sr.name, values[sr.value], {**lbl, **dict(sr.labels)})
            # speculative decoding (ISSUE 5): host-drafted tokens, the
            # subset the verify pass accepted, lifetime acceptance, and
            # slots the per-request EMA currently benches
            c.counter(
                "helix_spec_drafted_tokens_total",
                getattr(eng, "num_spec_drafted_tokens", 0), lbl,
            )
            c.counter(
                "helix_spec_accepted_tokens_total",
                getattr(eng, "num_spec_accepted_tokens", 0), lbl,
            )
            c.gauge(
                "helix_spec_acceptance_ratio",
                getattr(eng, "spec_acceptance_ratio", 0.0), lbl,
            )
            spec_disabled = getattr(eng, "spec_disabled_slots", None)
            c.gauge(
                "helix_spec_disabled_slots",
                spec_disabled() if callable(spec_disabled) else 0, lbl,
            )
            c.gauge("helix_waiting_requests", len(eng.waiting), lbl)
            c.gauge(
                "helix_active_slots",
                sum(1 for s in eng.slots if s is not None), lbl,
            )
            c.gauge("helix_free_pages", eng.allocator.free_pages, lbl)
            # robustness spine: step failure/retry/quarantine/shed
            # accounting (ISSUE 2)
            c.counter(
                "helix_step_failures_total",
                getattr(m.loop, "step_failures", 0), lbl,
            )
            c.counter(
                "helix_step_retries_total",
                getattr(m.loop, "step_retries", 0), lbl,
            )
            c.counter(
                "helix_quarantine_evictions_total",
                getattr(m.loop, "quarantine_evictions", 0), lbl,
            )
            c.counter(
                "helix_shed_requests_total",
                getattr(m.loop, "shed_requests", 0), lbl,
            )
            # asynchronous pipelined loop (ISSUE 13): how often the loop
            # dispatched step N+1 while step N was still executing, and
            # a host-side estimate of the share of serving time the
            # device had nothing dispatched (the sync loop's build+emit
            # shadow shows up here); the device's idle share is read
            # from a trace
            c.counter(
                "helix_pipelined_steps_total",
                getattr(m.loop, "pipelined_steps", 0), lbl,
            )
            if hasattr(m.loop, "device_idle_ratio"):
                c.gauge(
                    "helix_device_idle_ratio",
                    round(m.loop.device_idle_ratio(), 4), lbl,
                    help="Flight-window idle gaps over the time the "
                         "window spans: a host-side estimate; the "
                         "device's idle share is read from a trace",
                )
            # latency histograms (TTFT / queue wait / inter-token / step
            # duration) observed by the engine loop itself
            loop_obs = getattr(m.loop, "obs", None)
            if loop_obs is not None:
                loop_obs.collect(c, lbl)
            # saturation / capacity-efficiency gauges (ISSUE 4): how full
            # the machine is and where the capacity goes
            self._collect_saturation(c, m, eng, lbl)
            # per-tenant SLO series (ISSUE 7): bounded top-K + __other__
            # accounting and burn-rate gauges — obs/slo.py is the ONLY
            # legal emitter of tenant-labelled samples (lint contract 4)
            slo = getattr(m.loop, "slo", None)
            if slo is not None:
                slo.collect(c, lbl)
            # scheduler policy series (ISSUE 9): helix_sched_* samples
            # are minted ONLY by serving/sched.py (lint contract 5)
            sched = getattr(m.loop, "sched", None)
            if sched is not None:
                sched.collect(c, lbl)
            # cross-runner migration series (ISSUE 11): minted ONLY by
            # serving/migration.py (lint contract 6)
            collect_runner_migration(c, m.loop, lbl)
            # persistent filestore KV tier (ISSUE 14): minted ONLY by
            # serving/kv_filestore.py (lint contract 10)
            collect_filestore_kv(c, m.loop, lbl)
            # continuous multi-LoRA serving (ISSUE 15): helix_adapter_*
            # series are minted ONLY by engine/adapters.py (lint
            # contract 11)
            collect_adapter_metrics(c, m.loop, lbl)
            # N-follower mesh health + failover accounting (ISSUE 17):
            # helix_mh_* series are minted ONLY by
            # serving/multihost_serving.py (lint contract 12)
            collect_mh_metrics(c, m.loop, lbl)
            pc = getattr(eng, "prefix_cache", None)
            if pc is not None:
                st = pc.stats
                c.gauge("helix_prefix_cache_pages", st["pages"], lbl)
                c.counter(
                    "helix_prefix_cache_hit_pages_total", st["hits"], lbl
                )
                c.counter(
                    "helix_prefix_cache_miss_pages_total", st["misses"], lbl
                )
                # request-level hit/miss + eviction pressure (ISSUE 4)
                c.counter(
                    "helix_prefix_cache_hits_total",
                    getattr(eng, "prefix_cache_hits", 0), lbl,
                )
                c.counter(
                    "helix_prefix_cache_misses_total",
                    getattr(eng, "prefix_cache_misses", 0), lbl,
                )
                c.counter(
                    "helix_prefix_cache_evicted_pages_total",
                    st.get("evicted_pages", 0), lbl,
                )
        mgr = self._residency_manager()
        if mgr is not None:
            st = mgr.stats()
            c.counter("helix_residency_loads_total", st["loads"])
            c.counter("helix_residency_evictions_total", st["evictions"])
            c.gauge("helix_residency_used_bytes", st["used_bytes"])
            c.gauge(
                "helix_residency_budget_bytes", st.get("budget_bytes", 0)
            )
            for name, secs in sorted(st["swap_seconds"].items()):
                c.gauge(
                    "helix_model_swap_seconds", secs, {"model": name}
                )
            for name, secs in sorted(st["load_seconds"].items()):
                c.gauge(
                    "helix_model_load_seconds", secs, {"model": name}
                )

    def _collect_saturation(self, c, m, eng, lbl: dict) -> None:
        """Per-model capacity gauges: KV occupancy + high-water mark,
        decode-slot utilization, queue depth/queued tokens, goodput
        tokens/s, padding waste, and an MFU estimate when a peak-FLOPs
        figure is known.  All values are GIL-atomic host reads."""
        sat = m.loop.saturation()
        used = getattr(eng, "kv_pages_used", 0)
        cap = getattr(eng, "kv_pages_capacity", 1)
        c.gauge("helix_kv_pages_used", used, lbl)
        c.gauge("helix_kv_pages_capacity", cap, lbl)
        c.gauge(
            "helix_kv_pages_used_peak",
            getattr(eng.allocator, "peak_used", 0), lbl,
        )
        c.gauge("helix_kv_occupancy_ratio", sat["kv_occupancy"], lbl)
        c.gauge("helix_decode_slots_busy", sat["slots_busy"], lbl)
        c.gauge("helix_decode_slots_capacity", sat["slots_total"], lbl)
        c.gauge(
            "helix_decode_slot_utilization",
            sat["slots_busy"] / max(1, sat["slots_total"]), lbl,
        )
        c.gauge("helix_queue_depth", sat["queue_depth"], lbl)
        c.gauge("helix_queued_tokens", m.loop.queued_tokens(), lbl)
        c.counter(
            "helix_generated_tokens_total",
            getattr(eng, "num_generated_tokens", 0), lbl,
        )
        c.counter(
            "helix_prefill_padding_tokens_total",
            getattr(eng, "num_prefill_padding_tokens", 0), lbl,
        )
        # ragged unification (ISSUE 10): the shape-zoo collapse made
        # observable — distinct compiled device-step entry points per
        # model, and padding / (padding + useful prefill) over the
        # flight-recorder window
        c.gauge(
            "helix_compiled_step_shapes",
            getattr(eng, "compiled_step_shapes", 0), lbl,
        )
        c.gauge(
            "helix_prefill_padding_ratio", m.loop.padding_ratio(), lbl
        )
        c.gauge(
            "helix_goodput_tokens_per_second", sat["tokens_per_sec"], lbl
        )
        c.gauge(
            "helix_prefix_cache_hit_ratio", sat["prefix_hit_rate"], lbl
        )
        c.counter(
            "helix_flight_anomalies_total",
            m.loop.flight.anomalies_total, lbl,
        )
        # KV tiering + preemption-by-swap (ISSUE 6): host-tier traffic
        # and fullness, swap-out/swap-in counts, parked decoders, typed
        # kv_exhausted sheds, cumulative restore time
        hp = getattr(eng, "host_pool", None)
        if hp is not None:
            c.counter("helix_kv_spilled_pages_total", hp.spilled_pages, lbl)
            c.counter(
                "helix_kv_restored_pages_total", hp.restored_pages, lbl
            )
            c.counter(
                "helix_kv_host_evicted_pages_total", hp.evicted_pages, lbl
            )
            c.counter(
                "helix_kv_host_corrupt_pages_total", hp.corrupt_pages, lbl
            )
            c.counter(
                "helix_kv_host_alloc_failures_total", hp.alloc_failures,
                lbl,
            )
            c.gauge("helix_kv_host_pool_pages", hp.pages, lbl)
            c.gauge("helix_kv_host_pool_used_bytes", hp.used_bytes, lbl)
            c.gauge(
                "helix_kv_host_pool_budget_bytes", hp.budget_bytes, lbl
            )
            c.gauge("helix_kv_host_occupancy_ratio", hp.occupancy, lbl)
            c.counter(
                "helix_kv_restore_seconds_total",
                getattr(eng, "restore_seconds", 0.0), lbl,
            )
        c.counter(
            "helix_preemptions_total",
            getattr(eng, "num_preemptions", 0), lbl,
        )
        c.counter(
            "helix_resumes_total", getattr(eng, "num_resumes", 0), lbl
        )
        c.gauge(
            "helix_preempted_requests",
            len(getattr(eng, "preempted", ())), lbl,
        )
        c.counter(
            "helix_kv_exhausted_sheds_total",
            getattr(m.loop, "kv_exhausted_sheds", 0), lbl,
        )
        peak = self._peak_flops()
        if peak > 0:
            from helix_tpu.engine.residency import model_param_count

            # decode-side MFU estimate: each generated token moves ~2
            # FLOPs per active parameter through the MXU
            c.gauge(
                "helix_mfu_estimate",
                sat["tokens_per_sec"] * 2 * model_param_count(eng.model_cfg)
                / peak,
                lbl,
            )

    @staticmethod
    def _peak_flops() -> float:
        """Peak accelerator FLOP/s for the MFU denominator:
        ``HELIX_PEAK_FLOPS`` when the operator sets it, else the published
        bf16 peak of this ``device_kind`` (``device/peaks.py``; an unknown
        kind raises — a share of a guessed peak is not a utilisation).
        0 on a CPU backend: no device, no utilisation, gauge omitted."""
        import os

        v = os.environ.get("HELIX_PEAK_FLOPS", "")
        if v:
            try:
                return float(v)
            except ValueError:
                return 0.0
        import jax

        dev = jax.devices()[0]
        if dev.platform == "cpu":
            return 0.0
        from helix_tpu.device.peaks import peak_flops

        return peak_flops(dev.device_kind)

    # -- tracing + profiling ---------------------------------------------
    @staticmethod
    def _require_runner_token(request):
        """Debug surfaces carry request metadata / cost serving latency:
        when the node has a shared runner token configured, callers must
        present it (``X-Runner-Token``).  Without one (dev, unix-socket,
        behind-the-tunnel deployments) they stay open like /logs."""
        import hmac
        import os

        token = os.environ.get("HELIX_RUNNER_TOKEN", "")
        if token and not hmac.compare_digest(
            request.headers.get("X-Runner-Token", ""), token
        ):
            return _error(403, "requires the runner token")
        return None

    async def debug_traces_list(self, request):
        denied = self._require_runner_token(request)
        if denied is not None:
            return denied
        return web.json_response({"traces": self.traces.ids()[-100:]})

    async def debug_trace(self, request):
        denied = self._require_runner_token(request)
        if denied is not None:
            return denied
        tid = request.match_info["trace_id"]
        if request.query.get("format") == "chrome":
            doc = self.traces.chrome_trace(tid)
        else:
            doc = self.traces.get(tid)
        if doc is None:
            return _error(404, f"unknown trace {tid!r}")
        return web.json_response(doc)

    async def debug_flight(self, request):
        """Engine flight recorder: the per-step saturation ring (batch
        composition, KV occupancy, padding waste, step wall time) plus
        the frozen snapshots of the last N anomalies (slow step,
        zero-progress step, step failure, quarantine).  Runner-token
        gated like the other debug surfaces; ``?model=`` filters to one
        engine, ``?recent=`` bounds the live-ring tail returned."""
        denied = self._require_runner_token(request)
        if denied is not None:
            return denied
        want = request.query.get("model", "")
        try:
            recent = max(1, min(int(request.query.get("recent", 64)), 512))
        except ValueError:
            return _error(400, "recent must be an integer")
        def collect():
            # off the event loop: registry.list() on a residency-backed
            # runner blocks on the build-holding ResidencyManager lock
            # (same rule as the /metrics render above), and the body is
            # joined here too, from each record's JSON as it was made the
            # first time it was served: what json_response would write,
            # letter for letter
            snap = {}
            with self._scrape("/v1/debug/flight"):
                for m in self.registry.list():
                    if m.loop is None or (want and m.name != want):
                        continue
                    fl = getattr(m.loop, "flight", None)
                    if fl is None:
                        continue
                    snap[m.name] = fl.snapshot_json(recent=recent)
                if want and not snap:
                    return None
                return ('{"models": {' + ", ".join(
                    json.dumps(name) + ": " + body
                    for name, body in snap.items()) + "}}").encode()

        body = await asyncio.get_running_loop().run_in_executor(
            None, collect
        )
        if body is None:
            return _error(
                404, f"model {want!r} has no engine flight recorder"
            )
        return web.Response(
            body=body, content_type="application/json", charset="utf-8")

    async def debug_admissions(self, request):
        """The admission-decision audit trail: a bounded ring per model
        of every 429 shed, typed kv_exhausted shed, quarantine eviction
        and preemption-by-swap — ``(tenant, trace_id, reason, queue
        state)`` at the moment of the decision.  Runner-token gated like
        ``/v1/debug/flight``; ``?model=`` filters, ``?recent=`` bounds
        the tail returned."""
        denied = self._require_runner_token(request)
        if denied is not None:
            return denied
        want = request.query.get("model", "")
        try:
            recent = max(1, min(int(request.query.get("recent", 64)), 256))
        except ValueError:
            return _error(400, "recent must be an integer")

        def collect():
            # off the event loop: registry.list() on a residency-backed
            # runner blocks on the build-holding lock (debug_flight rule)
            snap = {}
            for m in self.registry.list():
                if m.loop is None or (want and m.name != want):
                    continue
                slo = getattr(m.loop, "slo", None)
                if slo is None:
                    continue
                snap[m.name] = slo.audit.snapshot(recent=recent)
            return snap

        out = await asyncio.get_running_loop().run_in_executor(
            None, collect
        )
        if want and not out:
            return _error(404, f"model {want!r} has no admission audit")
        return web.json_response({"models": out})

    # -- cross-runner migration (ISSUE 11) --------------------------------

    def _sweep_imports(self) -> None:
        """Abort imported requests whose stream was never claimed within
        the migration timeout — a peer must not generate into the void
        because the control plane that planned to resume went away."""
        for stream in self._imported.sweep():
            served = self.registry.get(stream.model)
            if served is not None and served.loop is not None:
                served.loop.abort(stream.request_id)
                served.loop.migration_failures += 1

    async def migrate_import(self, request):
        """Accept one request snapshot from a peer runner (the drain
        ladder's ship step).  Runner-token gated — migration is
        cluster-internal traffic.  The snapshot is decoded, then
        re-admitted on the engine thread where EVERY page checksum is
        verified before any allocator mutation; a corrupt or
        incompatible snapshot fails typed (422) and touches nothing.
        On success the request parks until resources free (a full
        engine queues it behind admission) and its token events buffer
        until ``/v1/migrate/resume`` attaches."""
        denied = self._require_runner_token(request)
        if denied is not None:
            return denied
        self._sweep_imports()
        t0 = time.monotonic()
        try:
            body = await request.json()
        except Exception:  # noqa: BLE001 — client error
            return _error(400, "invalid JSON body")
        try:
            snap = wire_to_snapshot(body)
        except SnapshotError as e:
            return _error(422, str(e), "invalid_request_error",
                          code=e.code)
        # adopt the CALLER's trace (ISSUE 18): the shipping peer
        # forwards X-Helix-Trace-Id (PeerShipper bugfix) and the wire
        # snapshot carries trace_id — prefer the header, fall back to
        # the snapshot, never mint (an untraced import stays untraced)
        hdr_tid = request.headers.get(TRACE_HEADER)
        trace_id = hdr_tid if is_trace_id(hdr_tid) else (
            snap.trace_id if is_trace_id(snap.trace_id) else ""
        )

        def _span(outcome: str) -> None:
            self.traces.record(
                trace_id, "migrate import", t0, time.monotonic(),
                plane="runner", request_id=snap.request_id,
                model=snap.model, outcome=outcome,
                prior_tokens=len(snap.output_tokens),
            )
        served, err = await self._lookup(snap.model)
        if err is not None:
            return err
        err = self._require_loop(served, snap.model)
        if err is not None:
            return err
        stream = ImportedStream(
            snap.request_id, snap.model, snap.output_tokens,
            stop=tuple(snap.sampling.get("stop") or ()),
            trace_id=trace_id,
        )
        if not self._imported.register(stream):
            return _error(
                429, "too many unclaimed imported requests",
                "overloaded_error", headers={"Retry-After": "2"},
            )
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()

        def on_result(err_msg, code):
            def settle():
                if not fut.done():
                    fut.set_result((err_msg, code))

            loop.call_soon_threadsafe(settle)

        served.loop.submit_import(snap, stream.on_event,
                                  on_result=on_result)
        try:
            err_msg, code = await asyncio.wait_for(
                fut, timeout=migration_timeout()
            )
        except asyncio.TimeoutError:
            # the source treats 504 as a failed ship and may re-ship
            # elsewhere — abort the (possibly later-admitted) request so
            # an unregistered orphan can never keep generating here
            self._imported.discard(snap.request_id)
            served.loop.abort(snap.request_id)
            _span("timeout")
            return _error(
                504, "import was not admitted in time", "timeout_error"
            )
        if err_msg is not None:
            self._imported.discard(snap.request_id)
            status = 503 if code == "shutting_down" else 422
            _span(code or "snapshot_invalid")
            return _error(
                status, err_msg, "invalid_request_error",
                code=code or "snapshot_invalid",
            )
        _span("admitted")
        return web.json_response(
            {
                "ok": True,
                "request_id": snap.request_id,
                "model": snap.model,
                "prior_tokens": len(snap.output_tokens),
            }
        )

    async def migrate_resume(self, request):
        """Attach the client stream to a migrated-in request.

        The control plane calls this after a clean source drain: the
        body names the engine request id and how many characters of
        generated text the CLIENT has already received.  The response
        is a neutral SSE delta stream — first the catch-up slice (text
        the source engine emitted but the client never saw), then live
        deltas — which the control plane re-wraps in the client's
        original chunk shape.  Exactly-once: the snapshot's prior
        tokens seed the detokenizer, so character arithmetic against
        ``emitted_chars`` is exact."""
        denied = self._require_runner_token(request)
        if denied is not None:
            return denied
        self._sweep_imports()
        try:
            body = await request.json()
        except Exception:  # noqa: BLE001 — client error
            return _error(400, "invalid JSON body")
        rid = str(body.get("request_id", ""))
        try:
            emitted_chars = max(0, int(body.get("emitted_chars", 0) or 0))
        except (TypeError, ValueError):
            return _error(400, "'emitted_chars' must be an integer")
        stream = self._imported.get(rid)
        if stream is None:
            return _error(
                404, f"no imported request {rid!r} awaiting resume"
            )
        served, err = await self._lookup(stream.model)
        if err is not None:
            return err
        loop = asyncio.get_running_loop()
        q: asyncio.Queue = asyncio.Queue()
        if not stream.attach(loop, q):
            return _error(409, f"request {rid!r} was already resumed")
        self._imported.discard(rid)
        # the resume leg of the migrated timeline (ISSUE 18): stream
        # attach through catch-up-slice sent, under the trace id the
        # import adopted from the shipping peer
        resume_tid = getattr(stream, "trace_id", "")
        t_resume = time.monotonic()
        detok = IncrementalDetokenizer(served.tokenizer)
        prior = ""
        for t in stream.prior_tokens:
            if t not in served.tokenizer.eos_ids:
                prior += detok.push(t)
        resp = web.StreamResponse(
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
            }
        )
        await resp.prepare(request)

        async def send(obj):
            await resp.write(f"data: {json.dumps(obj)}\n\n".encode())

        finished = False
        stops = stream.stop
        full = prior                      # everything generated so far
        sent = min(emitted_chars, len(prior))   # chars the client has

        def stop_hit(scan_from: int):
            """Earliest stop-string index at/after ``scan_from`` (a
            stop may SPAN the migration point, so matches straddle the
            prior/resumed boundary)."""
            hit = None
            for s in stops:
                idx = full.find(s, max(0, scan_from - len(s)))
                if idx >= 0:
                    hit = idx if hit is None else min(hit, idx)
            return hit

        try:
            # stop already completed in the prior text (defensive: the
            # source's HTTP handler normally catches this pre-export)
            hit = stop_hit(0)
            if hit is not None:
                finished = True
                served.loop.abort(rid)
                await send(
                    {"request_id": rid, "delta": full[sent:hit],
                     "finish_reason": "stop"}
                )
            elif len(full) > sent:
                # catch-up: text the source engine emitted that the
                # client never saw
                await send(
                    {"request_id": rid, "delta": full[sent:],
                     "catchup": True, "finish_reason": None}
                )
                sent = len(full)
            self.traces.record(
                resume_tid, "migrate resume", t_resume,
                time.monotonic(), plane="runner", request_id=rid,
                catchup_chars=max(0, sent - emitted_chars),
            )
            while not finished:
                try:
                    ev = await asyncio.wait_for(
                        q.get(), timeout=self.inter_token_timeout
                    )
                except asyncio.TimeoutError:
                    await send(
                        {"request_id": rid,
                         "error": {"message": "inter_token_timeout on "
                                              "resumed stream"}}
                    )
                    break
                if ev.error:
                    finished = True
                    await send(
                        {"request_id": rid,
                         "error": {"message": ev.error}}
                    )
                    break
                is_eos = ev.token_id in served.tokenizer.eos_ids
                prev = len(full)
                delta = "" if is_eos else detok.push(ev.token_id)
                full += delta
                hit = stop_hit(prev)
                if hit is not None:
                    # serving-level stop string: truncate exactly like
                    # the ordinary stream handler would have
                    finished = True
                    served.loop.abort(rid)
                    await send(
                        {"request_id": rid,
                         "delta": full[min(sent, hit):hit],
                         "finish_reason": "stop"}
                    )
                    break
                await send(
                    {
                        "request_id": rid,
                        "delta": full[sent:],
                        "finish_reason": (
                            ev.finish_reason if ev.finished else None
                        ),
                    }
                )
                sent = len(full)
                if ev.finished:
                    finished = True
            await resp.write(b"data: [DONE]\n\n")
            await resp.write_eof()
        finally:
            if not finished and served.loop is not None:
                served.loop.abort(rid)
        return resp

    async def profiler_capture(self, request):
        """On-demand ``jax.profiler`` capture against the live runner:
        POST {"seconds": 2} starts a device+host trace and returns the
        directory to feed TensorBoard/XProf.  One capture at a time; the
        capture runs in an executor so serving traffic keeps flowing
        while it records.  The host side of the capture is the program's
        own ``helix.*`` spans (``obs.trace.phase``); the Python tracer,
        which slows the server it measures by a tenth, is off unless the
        body says ``"python_tracer": true``.  The capture's first and
        last event is a ``helix.clock`` stamp (``time.monotonic_ns()``),
        returned as ``clock_ns``: it lays flight records and request
        spans, which are on the monotonic clock, onto the trace.

        Trust model: captures are expensive (real serving-latency cost)
        and write to disk, so when ``HELIX_RUNNER_TOKEN`` is set the
        caller must present it (``X-Runner-Token``) — the same shared
        secret the node uses on the control loop.  Capture directories
        are always minted under the system temp dir (or the operator's
        ``HELIX_PROFILER_DIR``); clients never choose the path."""
        import os

        denied = self._require_runner_token(request)
        if denied is not None:
            return denied
        try:
            body = await request.json() if request.can_read_body else {}
        except Exception:  # noqa: BLE001 — client error
            return _error(400, "invalid JSON body")
        if not isinstance(body, dict):
            return _error(400, "body must be a JSON object")
        try:
            seconds = min(max(float(body.get("seconds", 2.0)), 0.01), 60.0)
        except (TypeError, ValueError):
            return _error(400, "'seconds' must be a number")
        python_tracer = body.get("python_tracer", False)
        if not isinstance(python_tracer, bool):
            return _error(400, "'python_tracer' must be true or false")
        if not self._profiler_lock.acquire(blocking=False):
            return _error(
                409, "a profiler capture is already running",
                "overloaded_error",
            )

        def capture():
            # the CAPTURE THREAD owns the lock release: if the client
            # disconnects and the awaiting handler is cancelled, the
            # capture still runs to completion — releasing in the
            # handler would let a retry call start_trace concurrently
            try:
                import tempfile
                import jax

                base = os.environ.get("HELIX_PROFILER_DIR") or None
                d = tempfile.mkdtemp(prefix="helix-jax-profile-", dir=base)
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = int(python_tracer)
                options.host_tracer_level = 2
                jax.profiler.start_trace(d, profiler_options=options)
                try:
                    stamps = [clock_stamp()]
                    time.sleep(seconds)
                    stamps.append(clock_stamp())
                finally:
                    jax.profiler.stop_trace()
                return d, stamps
            finally:
                self._profiler_lock.release()

        try:
            fut = asyncio.get_running_loop().run_in_executor(None, capture)
        except Exception:   # submission failed: the thread never runs
            self._profiler_lock.release()
            raise
        # A long capture of a busy server takes minutes to WRITE (three
        # seconds of Laguna-XS.2's steps: 110-130 s), and a client that
        # reads nothing for two minutes gives the connection up.  Past
        # ``_PROFILER_QUIET_S`` the answer is therefore streamed: the
        # headers now, a space every few seconds (JSON allows it before
        # the value), the same body at the end.  A capture that is done by
        # then answers as it always has, its failure a 501.
        stream = None
        try:
            while True:
                try:
                    d, stamps = await asyncio.wait_for(
                        asyncio.shield(fut),
                        _PROFILER_QUIET_S if stream is None else 5.0)
                    break
                except asyncio.TimeoutError:
                    if stream is None:
                        stream = web.StreamResponse(
                            headers={"Content-Type": "application/json"})
                        await stream.prepare(request)
                    await stream.write(b" ")
        except asyncio.CancelledError:
            raise   # capture thread finishes + releases on its own
        except Exception as e:  # noqa: BLE001 — profiler not available
            if stream is None:
                return _error(501, f"jax profiler capture failed: {e}")
            await stream.write(json.dumps({"error": {
                "message": f"jax profiler capture failed: {e}"}}).encode())
            await stream.write_eof()
            return stream
        body = {"log_dir": d, "seconds": seconds,
                "python_tracer": python_tracer, "clock_ns": stamps}
        if stream is None:
            return web.json_response(body)
        await stream.write(json.dumps(body).encode())
        await stream.write_eof()
        return stream

    def _residency_manager(self):
        """The ResidencyManager behind the registry, if hot-swap is on."""
        for cand in (self.registry, getattr(self.registry, "inner", None)):
            if cand is not None and hasattr(cand, "prefetch"):
                return cand
        return None

    async def prefetch_model(self, request):
        """Stage a model's weights in the background ahead of traffic (the
        async half of hot-swap; helix_model_swap_seconds in /metrics
        shows the payoff)."""
        try:
            body = await request.json()
        except Exception:  # noqa: BLE001 — client error, not server fault
            return _error(400, "invalid JSON body")
        name = body.get("model", "")
        mgr = self._residency_manager()
        if mgr is None:
            return _error(
                409, "no residency manager: profile has no residency block"
            )
        if name not in mgr.names():
            return _error(404, f"unknown model {name!r}")
        # executor: prefetch() takes the manager lock (see /metrics note)
        started = await asyncio.get_running_loop().run_in_executor(
            None, lambda: bool(mgr.prefetch(name))
        )
        return web.json_response(
            {"model": name, "prefetch": "started" if started else "declined"}
        )

    async def tail_logs(self, request):
        """Node log tail for the admin UI (hydra logbuf analogue)."""
        try:
            n = max(1, min(int(request.query.get("tail", 200)), 2000))
        except ValueError:
            return _error(400, "tail must be an integer")
        return web.json_response({"logs": self.logbuf.tail(n)})

    async def list_models(self, request):
        def build():
            # runs in an executor: AdapterStore.ids walks the
            # filestore directory, which may be a slow/remote mount —
            # never on the event loop
            data = []
            for m in self.registry.list():
                data.append(
                    {
                        "id": m.name,
                        "object": "model",
                        "created": m.created,
                        "owned_by": m.owned_by,
                        **(
                            {"context_length": m.context_length}
                            if m.context_length
                            else {}
                        ),
                    }
                )
                # published multi-LoRA adapters (ISSUE 15): bounded
                # `base@adapter` entries, addressable through the same
                # chat/completions surface
                store = getattr(
                    getattr(getattr(m, "loop", None), "engine", None),
                    "adapter_store", None,
                )
                if store is not None:
                    for aid in store.ids(MAX_LISTED_ADAPTERS):
                        data.append(
                            {
                                "id": f"{m.name}{ADAPTER_SEP}{aid}",
                                "object": "model",
                                "created": m.created,
                                "owned_by": m.owned_by,
                                "parent": m.name,
                            }
                        )
            return data

        data = await asyncio.get_running_loop().run_in_executor(
            None, build
        )
        return web.json_response({"object": "list", "data": data})

    async def publish_adapter(self, request):
        """POST /v1/adapters (runner-token gated): publish a LoRA SFT
        checkpoint for ``model@name`` serving.  Body: ``{"model":
        base, "name": adapter_id, "checkpoint": dir[, "scale": f]}``.
        The checkpoint restores off the event loop, is validated
        against the base model's geometry, and lands on the residency
        ladder (host tier + filestore write-through) — servable
        immediately, warmup already covered the pool shape."""
        denied = self._require_runner_token(request)
        if denied is not None:
            return denied
        try:
            body = await request.json()
        except Exception:
            return _error(400, "invalid JSON body")
        base = body.get("model", "")
        adapter_id = sanitize_adapter_id(body.get("name", ""))
        ckpt = body.get("checkpoint", "")
        if not adapter_id:
            return _error(
                400,
                "'name' must be a bounded [A-Za-z0-9._-] adapter id",
            )
        if not ckpt or not isinstance(ckpt, str):
            return _error(400, "'checkpoint' directory is required")
        served, err = await self._lookup(base)
        if err is not None:
            return err
        eng = getattr(served.loop, "engine", None)
        store = getattr(eng, "adapter_store", None)
        if store is None:
            return _error(
                409,
                f"model '{base}' serves without an adapter pool "
                "(engine.adapter_pool_slots)",
            )
        scale = body.get("scale")
        try:
            spec = await asyncio.get_running_loop().run_in_executor(
                None, store.publish_checkpoint, adapter_id, ckpt,
                float(scale) if scale is not None else None,
            )
        except FileNotFoundError as e:
            return _error(404, str(e))
        except (ValueError, TypeError, KeyError) as e:
            # KeyError: a valid orbax checkpoint that is not a LoRA
            # checkpoint (no lora_params tree) — a caller error, not a
            # server fault
            return _error(400, f"adapter rejected: {e}")
        return web.json_response(
            {
                "id": f"{base}{ADAPTER_SEP}{adapter_id}",
                "object": "model",
                "parent": base,
                "rank": spec.rank,
                "scale": spec.scale,
                "bytes": spec.nbytes,
            }
        )

    # ------------------------------------------------------------------
    async def _lookup(self, model: str):
        """Resolve a model, faulting it in off the event loop (the registry
        may be a ResidencyManager that loads weights on demand).  Returns
        (served, error_response)."""
        try:
            served = await asyncio.get_running_loop().run_in_executor(
                None, self.registry.get, model
            )
        except MemoryError as e:
            return None, _error(503, str(e), "overloaded_error")
        if served is None:
            return None, _error(
                404,
                f"model '{model}' not found; available: {self.registry.names()}",
                "model_not_found",
            )
        return served, None

    async def _lookup_generation(self, model: str):
        """Resolve a generation target, including ``base@adapter``
        multi-LoRA addressing (ISSUE 15): the base model faults in
        through the ordinary registry path, the adapter id is sanitised
        and must be published on the engine's residency ladder (404
        otherwise — a hostile id never reaches a metrics label or a
        filestore path), and its filestore->host prefetch is kicked so
        a cold adapter overlaps loading with everything that follows.
        Returns ``(served, adapter_id, error_response)``."""
        base, adapter, ok = split_model_adapter(model)
        if ADAPTER_SEP in (model or "") and model:
            # a model whose LITERAL registered name contains '@' keeps
            # resolving by exact name — adapter addressing never breaks
            # a pre-existing registration
            lit = await asyncio.get_running_loop().run_in_executor(
                None, self.registry.get, model
            )
            if lit is not None:
                return lit, "", None
        if not ok:
            return None, "", _error(
                404, f"model '{model}' not found (invalid adapter id)",
                "model_not_found",
            )
        served, err = await self._lookup(base)
        if err is not None:
            return None, "", err
        if adapter:
            loop = served.loop
            eng = getattr(loop, "engine", None)
            pool = getattr(eng, "adapter_pool", None)
            store = getattr(eng, "adapter_store", None)
            if pool is None or store is None:
                return None, "", _error(
                    404,
                    f"model '{base}' does not serve adapters "
                    "(engine.adapter_pool_slots is off)",
                    "model_not_found",
                )
            # contains and the 404's listing both touch the filestore
            # directory — off the event loop (the mount may be remote);
            # prefetch itself does no caller-thread I/O by contract
            aio = asyncio.get_running_loop()
            known = pool.resident(adapter) or await aio.run_in_executor(
                None, store.contains, adapter
            )
            if not known:
                available = await aio.run_in_executor(
                    None, store.ids, MAX_LISTED_ADAPTERS
                )
                return None, "", _error(
                    404,
                    f"adapter '{adapter}' is not published for model "
                    f"'{base}'; available: {available}",
                    "model_not_found",
                )
            if not pool.resident(adapter):
                store.prefetch(adapter)
        return served, adapter, None

    @staticmethod
    def _require_loop(served, model: str):
        """Generation needs a live engine loop; embedding-only workers
        and multi-host FOLLOWERS (journal replay, no local traffic) have
        loop=None and must answer with a clean error, not a 500."""
        if served.loop is not None:
            return None
        if served.follower is not None:
            return _error(
                409,
                f"'{model}' is a multi-host follower replica on this "
                "host; send traffic to the leader",
            )
        return _error(
            404, f"'{model}' does not serve generation", "model_not_found"
        )

    @staticmethod
    def _precheck_admission(served, prompt_ids, trace_id: str = "",
                            tenant: str = ANON_TENANT):
        """Shed before committing response headers: streaming handlers
        prepare() the SSE response before the first engine event, so a
        queue_full discovered after submit can only surface as an in-band
        error frame — this pre-check turns it into a real 429/503.  The
        tenant rides along so the shed lands in that tenant's accounting
        and the admission audit ring."""
        check = getattr(served.loop, "check_admission", None)
        if check is None:
            return None
        err = check(
            len(prompt_ids), count_shed=True, tenant=tenant,
            trace_id=trace_id,
        )
        if err is None:
            return None
        return _engine_error_response(
            EngineRequestError(err), trace_id=trace_id
        )

    def _trace_id(self, request) -> str:
        """The request's end-to-end trace identity: adopt the control
        plane's (header, shape-validated) or mint one at this endpoint."""
        return adopt_trace_id(request.headers.get(TRACE_HEADER))

    @staticmethod
    def _tenant(request) -> str:
        """The request's tenant identity: the control plane resolves it
        at dispatch and forwards ``X-Helix-Tenant``.  The runner is an
        internal surface (same trust model as /logs and /metrics), so a
        direct caller's header is trusted like its prompts; the
        sanitiser bounds the SHAPE — malformed values and claims on the
        ``__other__`` fold bucket land under ``anonymous`` — and the
        top-K accounting bounds the series count."""
        return sanitize_tenant(request.headers.get(TENANT_HEADER, ""))

    @staticmethod
    def _sched_class(request) -> str:
        """The request's priority class (``X-Helix-Class``): forwarded
        by the control plane for authenticated callers, sanitised to
        the known class names; "" defers to the serving profile's
        default class (stamped by the engine loop at submit)."""
        return sanitize_class(request.headers.get(CLASS_HEADER, ""))

    def _sampling_from_body(self, body: dict) -> SamplingParams:
        stop = body.get("stop") or []
        if isinstance(stop, str):
            stop = [stop]
        return SamplingParams(
            temperature=float(body.get("temperature", 1.0)),
            top_p=float(body.get("top_p", 1.0)),
            top_k=int(body.get("top_k", 0)),
            presence_penalty=float(body.get("presence_penalty", 0.0)),
            frequency_penalty=float(body.get("frequency_penalty", 0.0)),
            max_tokens=int(
                body.get("max_tokens")
                or body.get("max_completion_tokens")
                or 256
            ),
            stop=tuple(stop),
            seed=body.get("seed"),
        )

    def _request_stage(self, served, trace_id: str, name: str,
                       hist: str, start: float, end: float) -> None:
        """One HTTP-side stage of a request's way to its first token: a
        span in the request's trace and an observation of the engine
        loop's histogram ``hist`` (beside ``helix_queue_wait_seconds``,
        so it carries the model's label)."""
        histogram = getattr(getattr(served.loop, "obs", None), hist, None)
        if histogram is not None:
            histogram.observe(max(0.0, end - start))
        self.traces.record(trace_id, name, start, end, plane="runner")

    async def _generate(self, served, prompt_ids, sampling, extra=None,
                        trace_id: str = "", tenant: str = ANON_TENANT,
                        sched_class: str = "", stages=None):
        """Submit to the engine; yields (delta_text, token_id, finished,
        finish_reason).  ``extra`` carries multimodal Request fields;
        ``trace_id`` and ``tenant`` ride the Request into engine-level
        spans and the per-tenant accounting; ``sched_class`` is the
        scheduler priority class ("" = profile default).  ``stages``
        (``{"t_entry": handler entry}``) records ``http.pre_submit`` and
        receives the Request under ``"req"``, whose ``first_emit_time``
        the handler's ``http.first_write`` starts from."""
        loop = asyncio.get_running_loop()
        q: asyncio.Queue = asyncio.Queue()

        def on_event(ev):
            loop.call_soon_threadsafe(q.put_nowait, ev)

        req = Request(
            id=f"req-{uuid.uuid4().hex[:12]}",
            prompt_tokens=list(prompt_ids),
            sampling=sampling,
            stop_token_ids=tuple(served.tokenizer.eos_ids),
            trace_id=trace_id,
            tenant=tenant,
            sched_class=sched_class,
            **(extra or {}),
        )
        if stages is not None:
            stages["req"] = req
            self._request_stage(
                served, trace_id, "http.pre_submit", "http_pre_submit",
                stages["t_entry"], req.submit_time,
            )
        served.loop.submit(req, on_event)
        detok = IncrementalDetokenizer(served.tokenizer)
        emitted_len = 0
        try:
            while True:
                try:
                    ev = await asyncio.wait_for(
                        q.get(), timeout=self.inter_token_timeout
                    )
                except asyncio.TimeoutError:
                    # never leak a raw TimeoutError (dead stream / bare
                    # 500): abort the engine request and surface a typed
                    # error the handlers map to 504 / an SSE error event
                    served.loop.abort(req.id)
                    raise EngineRequestError(
                        f"inter_token_timeout: no engine event for "
                        f"{self.inter_token_timeout:.0f}s; request "
                        f"{req.id} aborted", request_id=req.id,
                    ) from None
                if ev.error:
                    raise EngineRequestError(ev.error, request_id=req.id)
                is_eos = ev.token_id in served.tokenizer.eos_ids
                delta = "" if is_eos else detok.push(ev.token_id)
                # serving-level stop strings
                hit_stop = None
                for s in sampling.stop:
                    idx = detok._emitted.find(s, max(0, emitted_len - len(s)))
                    if idx >= 0:
                        hit_stop = idx
                        break
                if hit_stop is not None:
                    keep = detok._emitted[:hit_stop]
                    final_delta = keep[emitted_len:]
                    served.loop.abort(req.id)
                    yield final_delta, ev.token_id, True, "stop"
                    return
                emitted_len = len(detok._emitted)
                yield delta, ev.token_id, ev.finished, ev.finish_reason
                if ev.finished:
                    return
        finally:
            if not req.finished:
                served.loop.abort(req.id)

    # ------------------------------------------------------------------
    async def _disagg_prefill(self, request, served, model, prompt_ids,
                              sampling, kind, http_id, created,
                              trace_id, tenant, sched_class,
                              adapter: str = ""):
        """Disaggregated prefill/decode handoff (ISSUE 14), runner side.

        Submits the request like an ordinary stream, but stages an
        export-at-prefill-completion with the engine loop: the moment
        the first token exists, the engine thread snapshots the request
        (pages + device-evolved sampler state) and hands the wire dict
        back HERE, where the ship to the control-plane-named decode
        peer runs off the engine thread with the full
        ``HELIX_XFER_*`` retry/backoff/deadline discipline.

        Degrade-to-local by design — every rung falls back one step and
        none can produce a stuck or wrong-token stream:

        - ship CONFIRMED: the local request aborts and the response is
          a single ``migrated ... peer=<id>`` SSE frame the control
          plane resumes on the peer (the PR 11 clean-drain contract,
          exactly-once via prior-token catch-up);
        - ship FAILED (peer unreachable / corrupt-rejected / slow past
          the deadline): the local request never stopped decoding —
          the stream serves from HERE, colocated, bit-identical;
        - export unavailable or prefill deadline exceeded: same local
          path;
        - the request finished before the export fired (short
          generation): the buffered events replay as a normal stream.
        """
        import os

        from helix_tpu.serving.migration import PeerShipper

        peer_id = request.headers.get(DISAGG_PEER_ID_HEADER, "")
        peer_addr = request.headers.get(DISAGG_PEER_ADDR_HEADER, "")
        loop = asyncio.get_running_loop()
        q: asyncio.Queue = asyncio.Queue()

        def on_event(ev):
            loop.call_soon_threadsafe(q.put_nowait, ("ev", ev))

        def on_export(kind2, wire):
            loop.call_soon_threadsafe(
                q.put_nowait, ("export", kind2, wire)
            )

        req = Request(
            id=f"req-{uuid.uuid4().hex[:12]}",
            prompt_tokens=list(prompt_ids),
            sampling=sampling,
            stop_token_ids=tuple(served.tokenizer.eos_ids),
            trace_id=trace_id,
            tenant=tenant,
            sched_class=sched_class,
            adapter=adapter,
        )
        t_plan = time.monotonic()
        if peer_addr:
            served.loop.stage_disagg_export(req.id, on_export)
        served.loop.submit(req, on_event)
        # the handoff-plan leg of the federated timeline (ISSUE 18):
        # which decode peer the control plane named, staged or not
        self.traces.record(
            trace_id, "disagg handoff plan", t_plan, time.monotonic(),
            plane="runner", request_id=req.id,
            peer=peer_id or peer_addr or "(none)",
            staged=bool(peer_addr),
        )
        xfer = XferConfig()
        deadline = loop.time() + xfer.deadline
        last_event = loop.time()
        buffered: list = []
        t_wait = time.monotonic()
        outcome = ("local", None) if not peer_addr else None
        try:
            while outcome is None:
                remaining = deadline - loop.time()
                if remaining <= 0:
                    # prefill did not complete inside the transfer
                    # deadline (engine under load): serve locally —
                    # never a stuck handoff
                    served.loop.unstage_disagg_export(req.id)
                    outcome = ("local", None)
                    break
                try:
                    item = await asyncio.wait_for(
                        q.get(),
                        timeout=min(remaining, self.inter_token_timeout),
                    )
                except asyncio.TimeoutError:
                    if loop.time() - last_event < self.inter_token_timeout:
                        # the TRANSFER deadline cut this wait short, not
                        # a wedged engine: a slow prefill that colocated
                        # serving would have tolerated must not become
                        # an error just because disagg was attempted —
                        # withdraw the handoff and serve locally (the
                        # colocated tail keeps its own inter-token
                        # discipline)
                        served.loop.unstage_disagg_export(req.id)
                        outcome = ("local", None)
                        break
                    served.loop.unstage_disagg_export(req.id)
                    served.loop.abort(req.id)
                    raise EngineRequestError(
                        f"inter_token_timeout: no engine event for "
                        f"{self.inter_token_timeout:.0f}s; request "
                        f"{req.id} aborted", request_id=req.id,
                    ) from None
                last_event = loop.time()
                if item[0] == "export":
                    _tag, k2, wire = item
                    if k2 == "snapshot":
                        outcome = ("snapshot", wire)
                    elif k2 == "completed":
                        outcome = ("completed", None)
                    elif k2 == "gone":
                        return _error(
                            502,
                            f"request {req.id} vanished before the "
                            "prefill handoff",
                            "overloaded_error", code="disagg_failed",
                            trace_id=trace_id,
                        )
                    else:   # "local": export unavailable — serve here
                        outcome = ("local", None)
                    continue
                ev = item[1]
                if ev.error:
                    served.loop.unstage_disagg_export(req.id)
                    raise EngineRequestError(
                        ev.error, request_id=req.id
                    )
                buffered.append(ev)
                if ev.finished:
                    served.loop.unstage_disagg_export(req.id)
                    outcome = ("completed", None)
        except EngineRequestError as e:
            return _engine_error_response(e, trace_id=trace_id)
        except asyncio.CancelledError:
            served.loop.unstage_disagg_export(req.id)
            served.loop.abort(req.id)
            raise
        if peer_addr:
            self.traces.record(
                trace_id, "disagg prefill wait", t_wait,
                time.monotonic(), plane="runner", request_id=req.id,
                outcome=outcome[0],
            )

        if outcome[0] == "snapshot":
            # the ship spends only what is LEFT of the one transfer
            # deadline (HELIX_XFER_DEADLINE covers prefill wait + all
            # ship attempts + backoffs, as config_reference documents);
            # an exhausted budget fails the first remaining-time check
            # inside the shipper and degrades to local serving
            shipper = PeerShipper(
                runner_token=os.environ.get("HELIX_RUNNER_TOKEN", ""),
                targets=[{
                    "id": peer_id or peer_addr,
                    "address": peer_addr,
                    "models": [model],
                }],
                config=XferConfig(
                    attempt_timeout=xfer.attempt_timeout,
                    max_attempts=xfer.max_attempts,
                    backoff_base=xfer.backoff_base,
                    backoff_cap=xfer.backoff_cap,
                    deadline=max(0.0, deadline - loop.time()),
                ),
                prefill=True,
            )
            peer = None
            ship_err = ""
            t_ship = time.monotonic()
            try:
                peer = await loop.run_in_executor(
                    None, shipper, outcome[1]
                )
            except Exception as e:  # noqa: BLE001 — degrade to local serving
                ship_err = str(e)
            self.traces.record(
                trace_id, "disagg ship", t_ship, time.monotonic(),
                plane="runner", request_id=req.id,
                peer=peer or peer_id or peer_addr,
                outcome="confirmed" if peer is not None else "failed",
            )
            if peer is not None:
                # handoff confirmed: tear the local request down and
                # hand the stream to the control plane's resume path
                served.loop.abort(req.id)
                self.traces.record(
                    trace_id, "disagg migrated frame",
                    time.monotonic(), time.monotonic(),
                    plane="runner", request_id=req.id, peer=peer,
                )
                resp = web.StreamResponse(
                    headers={
                        "Content-Type": "text/event-stream",
                        "Cache-Control": "no-cache",
                        TRACE_HEADER: trace_id,
                    }
                )
                await resp.prepare(request)
                err: dict = {
                    "message": migrated_error(req.id, peer),
                    "request_id": req.id,
                }
                if trace_id:
                    err["trace_id"] = trace_id
                await resp.write(
                    f"data: {json.dumps({'error': err})}\n\n".encode()
                )
                await resp.write(b"data: [DONE]\n\n")
                await resp.write_eof()
                return resp
            # ship failed: the local request never stopped decoding —
            # degrade to colocated serving (strictly never worse than
            # not having attempted the handoff)
            import logging as _logging

            _logging.getLogger(__name__).warning(
                "disagg ship for request %s to %s failed (%s): "
                "serving locally", req.id, peer_id or peer_addr,
                ship_err[:200],
            )

        if peer_addr and outcome[0] != "completed":
            # a fallback rung was taken: the handoff was attempted but
            # this request is now serving colocated — name the rung so
            # the timeline explains WHY the decode peer never appears
            self.traces.record(
                trace_id, "disagg fallback rung", time.monotonic(),
                time.monotonic(), plane="runner", request_id=req.id,
                rung=(
                    "ship_failed" if outcome[0] == "snapshot"
                    else "prefill_local"
                ),
            )

        # -- colocated tail: stream buffered + live events ----------------
        resp = web.StreamResponse(
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                TRACE_HEADER: trace_id,
            }
        )
        await resp.prepare(request)
        detok = IncrementalDetokenizer(served.tokenizer)
        template = {"id": http_id, "model": model, "created": created}
        emitted_len = 0
        first = True
        idx = 0
        finished = False
        try:
            while not finished:
                if idx < len(buffered):
                    ev = buffered[idx]
                    idx += 1
                elif outcome[0] == "completed":
                    break   # defensive: finish event should be last
                else:
                    try:
                        item = await asyncio.wait_for(
                            q.get(), timeout=self.inter_token_timeout
                        )
                    except asyncio.TimeoutError:
                        served.loop.abort(req.id)
                        await resp.write(
                            f"data: {json.dumps(_sse_error_frame(EngineRequestError('inter_token_timeout on disagg-local stream', req.id), trace_id))}\n\n"
                            .encode()
                        )
                        break
                    if item[0] == "export":
                        continue   # stale sentinel: we already chose local
                    ev = item[1]
                if ev.error:
                    await resp.write(
                        f"data: {json.dumps(_sse_error_frame(EngineRequestError(ev.error, req.id), trace_id))}\n\n"
                        .encode()
                    )
                    break
                is_eos = ev.token_id in served.tokenizer.eos_ids
                delta = "" if is_eos else detok.push(ev.token_id)
                hit_stop = None
                for s in sampling.stop:
                    j = detok._emitted.find(
                        s, max(0, emitted_len - len(s))
                    )
                    if j >= 0:
                        hit_stop = j
                        break
                if hit_stop is not None:
                    keep = detok._emitted[:hit_stop]
                    served.loop.abort(req.id)
                    await resp.write(
                        f"data: {json.dumps(make_chunk(template, kind, keep[emitted_len:], 'stop', first=first))}\n\n"
                        .encode()
                    )
                    finished = True
                    break
                emitted_len = len(detok._emitted)
                fr = ev.finish_reason if ev.finished else None
                if delta or fr or first:
                    await resp.write(
                        f"data: {json.dumps(make_chunk(template, kind, delta, fr, first=first))}\n\n"
                        .encode()
                    )
                    first = False
                if ev.finished:
                    finished = True
            await resp.write(b"data: [DONE]\n\n")
            await resp.write_eof()
        finally:
            if not req.finished:
                served.loop.abort(req.id)
        return resp

    # ------------------------------------------------------------------
    # -- context-caching API (ISSUE 20) --------------------------------
    def _resolve_context(self, body: dict, trace_id: str = ""):
        """Resolve a request's ``context_id`` to its cached token span.
        Returns ``(prefix_ids, error_response)`` — ``([], None)`` when
        the request references no context.  An unknown or unreadable
        handle is a clean 404 (typed miss), never silent recompute of a
        prefix the caller believes is pinned."""
        ctx_id = body.get("context_id", "")
        if not ctx_id:
            return [], None
        if not isinstance(ctx_id, str):
            return [], _error(
                400, "'context_id' must be a string", trace_id=trace_id
            )
        cached = self.ctx_cache.get(ctx_id)
        if cached is None:
            return [], _error(
                404, f"context '{ctx_id}' not found (expired, evicted, "
                "or never created on this runner)",
                "invalid_request_error", code="context_not_found",
                trace_id=trace_id,
            )
        return cached, None

    async def create_context(self, request):
        """``POST /v1/context``: prefill a prompt prefix once and pin it
        behind a content-addressed handle.  The prefix runs through the
        engine as an ordinary one-token request with ``ctx_pin`` set —
        fully resident even on a tiered engine, so the prefix-cache
        adoption and the filestore write-through fire exactly as for any
        resident prompt — then the handle registers in the (tenant-
        quota'd, filestore-persisted) registry.  Requests that later
        carry ``context_id`` prepend the span and the residency ladder
        serves its pages without recomputing prefill."""
        from helix_tpu.serving.context_cache import context_handle

        try:
            body = await request.json()
        except Exception:
            return _error(400, "invalid JSON body")
        tid = self._trace_id(request)
        tenant = self._tenant(request)
        model = body.get("model", "")
        served, adapter, err = await self._lookup_generation(model)
        if err is not None:
            return err
        if served.kind == "embedding":
            return _error(404, f"model '{model}' is an embedding model",
                          "model_not_found", trace_id=tid)
        err = self._require_loop(served, model)
        if err is not None:
            return err
        messages = body.get("messages")
        prompt = body.get("prompt")
        if messages:
            # no generation prompt: this span is a PREFIX later
            # requests extend, not a turn awaiting an answer
            prompt_ids = served.tokenizer.apply_chat_template(
                messages, add_generation_prompt=False
            )
        elif isinstance(prompt, list) and all(
            isinstance(t, int) for t in prompt
        ):
            prompt_ids = list(prompt)
        elif isinstance(prompt, str) and prompt:
            prompt_ids = served.tokenizer.encode(prompt)
        else:
            return _error(
                400, "'messages' or 'prompt' is required", trace_id=tid
            )
        if not prompt_ids:
            return _error(400, "context prefix is empty", trace_id=tid)
        handle = context_handle(prompt_ids)
        if self.ctx_cache.contains(handle):
            # content-addressed: the prefix is already pinned — answer
            # without paying another prefill and without a new charge
            return web.json_response({
                "id": handle, "object": "context", "created": _now(),
                "model": model, "tokens": len(prompt_ids),
                "cached": True,
            }, headers={TRACE_HEADER: tid})
        if not self.ctx_cache.admit(tenant, len(prompt_ids)):
            return _error(
                429,
                f"tenant '{tenant}' is over its context-cache token "
                f"quota ({self.ctx_cache.tenant_token_cap} tokens)",
                "overloaded_error", code="context_quota_exceeded",
                trace_id=tid,
            )
        shed = self._precheck_admission(
            served, prompt_ids, trace_id=tid, tenant=tenant
        )
        if shed is not None:
            return shed
        # prefill-once: one greedy token forces the full prefix through
        # the engine; ctx_pin keeps it fully device-resident so every
        # page adopts into the prefix cache (and writes through to the
        # filestore tier when armed)
        sampling = SamplingParams(temperature=0.0, max_tokens=1)
        extra = {"ctx_pin": True}
        if adapter:
            extra["adapter"] = adapter
        t0 = time.monotonic()
        try:
            async for _delta, _tok, finished, _reason in self._generate(
                served, prompt_ids, sampling, extra, trace_id=tid,
                tenant=tenant,
            ):
                if finished:
                    break
        except EngineRequestError as e:
            return _engine_error_response(e, trace_id=tid)
        handle = self.ctx_cache.put(prompt_ids, tenant=tenant)
        self.traces.record(
            tid, "context create", t0, time.monotonic(),
            plane="runner", model=model, prompt_tokens=len(prompt_ids),
            handle=handle, tenant=tenant,
        )
        return web.json_response({
            "id": handle, "object": "context", "created": _now(),
            "model": model, "tokens": len(prompt_ids),
            "cached": False,
        }, headers={TRACE_HEADER: tid})

    async def list_contexts(self, request):
        return web.json_response({
            "object": "list", "data": self.ctx_cache.entries(),
        })

    async def chat_completions(self, request):
        stages = {"t_entry": time.monotonic()}
        try:
            body = await request.json()
        except Exception:
            return _error(400, "invalid JSON body")
        tid = self._trace_id(request)
        tenant = self._tenant(request)
        sclass = self._sched_class(request)
        t_req = time.monotonic()
        model = body.get("model", "")
        served, adapter, err = await self._lookup_generation(model)
        if err is not None:
            return err
        if served.kind == "embedding":
            return _error(404, f"model '{model}' is an embedding model",
                          "model_not_found", trace_id=tid)
        err = self._require_loop(served, model)
        if err is not None:
            return err
        messages = body.get("messages")
        if not messages:
            return _error(400, "'messages' is required", trace_id=tid)
        sampling = self._sampling_from_body(body)
        t_admit = time.monotonic()
        has_images = any(
            isinstance(m.get("content"), list)
            and any(
                p.get("type") in ("image_url", "image")
                for p in m["content"]
            )
            for m in messages
        )
        extra = None
        if has_images:
            if served.vision is None:
                return _error(
                    400, f"model '{model}' does not accept image input",
                    trace_id=tid,
                )
            try:
                extra = await asyncio.get_running_loop().run_in_executor(
                    None, served.vision.prepare, messages, served.tokenizer
                )
            except Exception as e:  # noqa: BLE001 — bad image data etc.
                return _error(
                    400, f"image processing failed: {e}", trace_id=tid
                )
            prompt_ids = extra.pop("prompt_tokens")
        else:
            prompt_ids = served.tokenizer.apply_chat_template(
                messages, add_generation_prompt=True
            )
        if adapter:
            # `model@adapter` requests ride the batched multi-LoRA
            # path: the engine resolves the id to an HBM pool slot at
            # admission (ISSUE 15)
            extra = {**(extra or {}), "adapter": adapter}
        # context-cache reference (ISSUE 20): prepend the pinned span —
        # the prefix-cache ladder serves its pages, so prefill covers
        # only the NEW tokens
        ctx_prefix, ctx_err = self._resolve_context(body, trace_id=tid)
        if ctx_err is not None:
            return ctx_err
        if ctx_prefix:
            prompt_ids = list(ctx_prefix) + list(prompt_ids)
        shed = self._precheck_admission(
            served, prompt_ids, trace_id=tid, tenant=tenant
        )
        self.traces.record(
            tid, "admit", t_admit, time.monotonic(), plane="runner",
            model=model, prompt_tokens=len(prompt_ids),
            shed=shed is not None, tenant=tenant,
        )
        if shed is not None:
            return shed
        rid = f"chatcmpl-{uuid.uuid4().hex[:16]}"
        created = _now()

        # disaggregated prefill handoff (ISSUE 14): the control plane
        # marked this dispatch prefill-only and named a decode peer.
        # VL requests (device-resident image state) and non-stream
        # bodies ignore the header and serve colocated — the control
        # plane handles an ordinary stream transparently.
        if (
            request.headers.get(DISAGG_HEADER)
            and body.get("stream")
            and not has_images
            and self._require_runner_token(request) is None
            and hasattr(served.loop, "stage_disagg_export")
        ):
            # adapter requests hand off too: the snapshot carries the
            # adapter id and the decode peer re-resolves it against ITS
            # residency ladder (an unpublished adapter there is a typed
            # import rejection -> the ordinary colocated fallback)
            return await self._disagg_prefill(
                request, served, model, prompt_ids, sampling,
                kind="chat", http_id=rid, created=created,
                trace_id=tid, tenant=tenant, sched_class=sclass,
                adapter=adapter,
            )

        if body.get("stream"):
            resp = web.StreamResponse(
                headers={
                    "Content-Type": "text/event-stream",
                    "Cache-Control": "no-cache",
                    TRACE_HEADER: tid,
                }
            )
            await resp.prepare(request)

            async def send(obj):
                await resp.write(f"data: {json.dumps(obj)}\n\n".encode())

            first = True
            finish_reason = None
            ntokens = 0
            t_emit = None
            try:
              async for delta, tok, finished, reason in self._generate(
                served, prompt_ids, sampling, extra, trace_id=tid,
                tenant=tenant, sched_class=sclass, stages=stages,
              ):
                if t_emit is None:
                    t_emit = time.monotonic()
                ntokens += 1
                chunk_delta = {}
                first_write = first
                if first:
                    chunk_delta["role"] = "assistant"
                    first = False
                if delta:
                    chunk_delta["content"] = delta
                finish_reason = reason if finished else None
                await send(
                    {
                        "id": rid,
                        "object": "chat.completion.chunk",
                        "created": created,
                        "model": model,
                        "choices": [
                            {
                                "index": 0,
                                "delta": chunk_delta,
                                "finish_reason": finish_reason,
                            }
                        ],
                    }
                )
                if first_write and stages["req"].first_emit_time is not None:
                    self._request_stage(
                        served, tid, "http.first_write", "http_first_write",
                        stages["req"].first_emit_time, time.monotonic(),
                    )
                if finished:
                    break
            except EngineRequestError as e:
                await send(_sse_error_frame(e, tid))
            await resp.write(b"data: [DONE]\n\n")
            await resp.write_eof()
            end = time.monotonic()
            self.traces.record(
                tid, "emit", t_emit if t_emit is not None else end, end,
                plane="runner", tokens=ntokens, stream=True,
            )
            self.traces.record(
                tid, "request", t_req, end, plane="runner",
                endpoint=request.path, model=model, http_id=rid,
            )
            return resp

        text_parts = []
        token_ids = []
        finish_reason = "stop"
        ntokens = 0
        t_emit = None
        try:
          async for delta, tok, finished, reason in self._generate(
            served, prompt_ids, sampling, extra, trace_id=tid,
            tenant=tenant, sched_class=sclass, stages=stages,
          ):
            if t_emit is None:
                t_emit = time.monotonic()
            text_parts.append(delta)
            token_ids.append(int(tok))
            ntokens += 1
            if finished:
                finish_reason = reason or "stop"
                break
        except EngineRequestError as e:
            return _engine_error_response(e, trace_id=tid)
        end = time.monotonic()
        self.traces.record(
            tid, "emit", t_emit if t_emit is not None else end, end,
            plane="runner", tokens=ntokens, stream=False,
        )
        self.traces.record(
            tid, "request", t_req, end, plane="runner",
            endpoint=request.path, model=model, http_id=rid,
        )
        return web.json_response(
            {
                "id": rid,
                "object": "chat.completion",
                "created": created,
                "model": model,
                "choices": [
                    {
                        "index": 0,
                        "message": {
                            "role": "assistant",
                            "content": "".join(text_parts),
                        },
                        "finish_reason": finish_reason,
                        # vLLM's extension: the sampled ids themselves, for
                        # callers that compare runs below the detokenizer
                        **(
                            {"token_ids": token_ids}
                            if body.get("return_token_ids") else {}
                        ),
                    }
                ],
                "usage": {
                    "prompt_tokens": len(prompt_ids),
                    "completion_tokens": ntokens,
                    "total_tokens": len(prompt_ids) + ntokens,
                },
            },
            headers={TRACE_HEADER: tid},
        )

    # ------------------------------------------------------------------
    async def completions(self, request):
        try:
            body = await request.json()
        except Exception:
            return _error(400, "invalid JSON body")
        tid = self._trace_id(request)
        tenant = self._tenant(request)
        sclass = self._sched_class(request)
        t_req = time.monotonic()
        model = body.get("model", "")
        served, adapter, err = await self._lookup_generation(model)
        if err is not None:
            return err
        err = self._require_loop(served, model)
        if err is not None:
            return err
        extra = {"adapter": adapter} if adapter else None
        prompt = body.get("prompt", "")
        if isinstance(prompt, list):
            prompt = prompt[0] if prompt else ""
        sampling = self._sampling_from_body(body)
        t_admit = time.monotonic()
        prompt_ids = served.tokenizer.encode(prompt)
        # context-cache reference (ISSUE 20) — see chat_completions
        ctx_prefix, ctx_err = self._resolve_context(body, trace_id=tid)
        if ctx_err is not None:
            return ctx_err
        if ctx_prefix:
            prompt_ids = list(ctx_prefix) + list(prompt_ids)
        shed = self._precheck_admission(
            served, prompt_ids, trace_id=tid, tenant=tenant
        )
        self.traces.record(
            tid, "admit", t_admit, time.monotonic(), plane="runner",
            model=model, prompt_tokens=len(prompt_ids),
            shed=shed is not None, tenant=tenant,
        )
        if shed is not None:
            return shed
        rid = f"cmpl-{uuid.uuid4().hex[:16]}"
        created = _now()

        # disaggregated prefill handoff (ISSUE 14) — see chat_completions
        if (
            request.headers.get(DISAGG_HEADER)
            and body.get("stream")
            and self._require_runner_token(request) is None
            and hasattr(served.loop, "stage_disagg_export")
        ):
            return await self._disagg_prefill(
                request, served, model, prompt_ids, sampling,
                kind="completions", http_id=rid, created=created,
                trace_id=tid, tenant=tenant, sched_class=sclass,
                adapter=adapter,
            )

        if body.get("stream"):
            resp = web.StreamResponse(
                headers={
                    "Content-Type": "text/event-stream",
                    TRACE_HEADER: tid,
                }
            )
            await resp.prepare(request)
            n = 0
            t_emit = None
            try:
              async for delta, tok, finished, reason in self._generate(
                served, prompt_ids, sampling, extra, trace_id=tid,
                tenant=tenant, sched_class=sclass,
              ):
                if t_emit is None:
                    t_emit = time.monotonic()
                n += 1
                await resp.write(
                    f"data: {json.dumps({'id': rid, 'object': 'text_completion', 'created': created, 'model': model, 'choices': [{'index': 0, 'text': delta, 'finish_reason': reason if finished else None}]})}\n\n".encode()
                )
                if finished:
                    break
            except EngineRequestError as e:
                await resp.write(
                    f"data: {json.dumps(_sse_error_frame(e, tid))}\n\n"
                    .encode()
                )
            await resp.write(b"data: [DONE]\n\n")
            await resp.write_eof()
            end = time.monotonic()
            self.traces.record(
                tid, "emit", t_emit if t_emit is not None else end, end,
                plane="runner", tokens=n, stream=True,
            )
            self.traces.record(
                tid, "request", t_req, end, plane="runner",
                endpoint=request.path, model=model, http_id=rid,
            )
            return resp

        parts = []
        finish_reason = "stop"
        n = 0
        t_emit = None
        try:
          async for delta, tok, finished, reason in self._generate(
            served, prompt_ids, sampling, extra, trace_id=tid,
            tenant=tenant, sched_class=sclass,
          ):
            if t_emit is None:
                t_emit = time.monotonic()
            parts.append(delta)
            n += 1
            if finished:
                finish_reason = reason or "stop"
                break
        except EngineRequestError as e:
            return _engine_error_response(e, trace_id=tid)
        end = time.monotonic()
        self.traces.record(
            tid, "emit", t_emit if t_emit is not None else end, end,
            plane="runner", tokens=n, stream=False,
        )
        self.traces.record(
            tid, "request", t_req, end, plane="runner",
            endpoint=request.path, model=model, http_id=rid,
        )
        return web.json_response(
            {
                "id": rid,
                "object": "text_completion",
                "created": created,
                "model": model,
                "choices": [
                    {
                        "index": 0,
                        "text": "".join(parts),
                        "finish_reason": finish_reason,
                    }
                ],
                "usage": {
                    "prompt_tokens": len(prompt_ids),
                    "completion_tokens": n,
                    "total_tokens": len(prompt_ids) + n,
                },
            },
            headers={TRACE_HEADER: tid},
        )

    # ------------------------------------------------------------------
    async def embeddings(self, request):
        try:
            body = await request.json()
        except Exception:
            return _error(400, "invalid JSON body")
        model = body.get("model", "")
        served, err = await self._lookup(model)
        if err is not None:
            return err
        if served.kind not in ("embedding", "vision-embedding"):
            return _error(
                404, f"'{model}' is not an embedding model", "model_not_found"
            )
        inputs = body.get("input", [])
        if isinstance(inputs, (str, dict)):
            inputs = [inputs]
        bad = [
            x for x in inputs if isinstance(x, dict) and "image" not in x
        ]
        if bad:
            return _error(
                400,
                "dict inputs must be {\"image\": <url/base64>}; got keys "
                f"{sorted(bad[0])}",
            )
        has_images = any(
            isinstance(x, dict) and "image" in x for x in inputs
        )
        if has_images:
            # vision-RAG: image entries ({"image": url/b64}) pool through
            # the vision tower into the same space as text (reference:
            # Qwen3-VL-Embedding pooling runner)
            if served.kind != "vision-embedding":
                return _error(
                    400,
                    f"'{model}' cannot embed images; serve a "
                    "vision-embedding model",
                )
            embed = served.embedder.embed_mixed
        else:
            embed = served.embedder.embed_texts
        vectors = await asyncio.get_running_loop().run_in_executor(
            None, embed, inputs
        )
        text_tokens = sum(
            len(served.tokenizer.encode(t))
            for t in inputs
            if isinstance(t, str)
        )
        return web.json_response(
            {
                "object": "list",
                "model": model,
                "data": [
                    {"object": "embedding", "index": i, "embedding": list(map(float, v))}
                    for i, v in enumerate(vectors)
                ],
                "usage": {
                    "prompt_tokens": text_tokens,
                    "total_tokens": text_tokens,
                },
            }
        )

    # ------------------------------------------------------------------
    async def anthropic_messages(self, request):
        """Native Anthropic /v1/messages surface (reference:
        ``api/pkg/anthropic/anthropic_proxy.go``)."""
        try:
            body = await request.json()
        except Exception:
            return _error(400, "invalid JSON body")
        tid = self._trace_id(request)
        tenant = self._tenant(request)
        sclass = self._sched_class(request)
        t_req = time.monotonic()
        model = body.get("model", "")
        served, adapter, err = await self._lookup_generation(model)
        if err is not None:
            return err
        err = self._require_loop(served, model)
        if err is not None:
            return err
        extra = {"adapter": adapter} if adapter else None
        messages = list(body.get("messages", []))
        if body.get("system"):
            messages = [{"role": "system", "content": body["system"]}] + messages
        sampling = SamplingParams(
            temperature=float(body.get("temperature", 1.0)),
            top_p=float(body.get("top_p", 1.0)),
            top_k=int(body.get("top_k", 0)),
            max_tokens=int(body.get("max_tokens", 256)),
            stop=tuple(body.get("stop_sequences", []) or []),
        )
        t_admit = time.monotonic()
        prompt_ids = served.tokenizer.apply_chat_template(
            messages, add_generation_prompt=True
        )
        shed = self._precheck_admission(
            served, prompt_ids, trace_id=tid, tenant=tenant
        )
        self.traces.record(
            tid, "admit", t_admit, time.monotonic(), plane="runner",
            model=model, prompt_tokens=len(prompt_ids),
            shed=shed is not None, tenant=tenant,
        )
        if shed is not None:
            return shed
        rid = f"msg_{uuid.uuid4().hex[:20]}"

        if body.get("stream"):
            resp = web.StreamResponse(
                headers={
                    "Content-Type": "text/event-stream",
                    TRACE_HEADER: tid,
                }
            )
            await resp.prepare(request)

            async def ev(name, obj):
                await resp.write(
                    f"event: {name}\ndata: {json.dumps(obj)}\n\n".encode()
                )

            await ev(
                "message_start",
                {
                    "type": "message_start",
                    "message": {
                        "id": rid,
                        "type": "message",
                        "role": "assistant",
                        "model": model,
                        "content": [],
                        "usage": {"input_tokens": len(prompt_ids), "output_tokens": 0},
                    },
                },
            )
            await ev(
                "content_block_start",
                {
                    "type": "content_block_start",
                    "index": 0,
                    "content_block": {"type": "text", "text": ""},
                },
            )
            n = 0
            stop_reason = "end_turn"
            t_emit = None
            try:
              async for delta, tok, finished, reason in self._generate(
                served, prompt_ids, sampling, extra, trace_id=tid,
                tenant=tenant, sched_class=sclass,
              ):
                if t_emit is None:
                    t_emit = time.monotonic()
                n += 1
                if delta:
                    await ev(
                        "content_block_delta",
                        {
                            "type": "content_block_delta",
                            "index": 0,
                            "delta": {"type": "text_delta", "text": delta},
                        },
                    )
                if finished:
                    stop_reason = (
                        "max_tokens" if reason == "length" else "end_turn"
                    )
                    break
            except EngineRequestError as e:
                await ev("error", {"type": "error",
                                   "error": _sse_error_frame(e, tid)["error"]})
            await ev(
                "content_block_stop", {"type": "content_block_stop", "index": 0}
            )
            await ev(
                "message_delta",
                {
                    "type": "message_delta",
                    "delta": {"stop_reason": stop_reason},
                    "usage": {"output_tokens": n},
                },
            )
            await ev("message_stop", {"type": "message_stop"})
            await resp.write_eof()
            end = time.monotonic()
            self.traces.record(
                tid, "emit", t_emit if t_emit is not None else end, end,
                plane="runner", tokens=n, stream=True,
            )
            self.traces.record(
                tid, "request", t_req, end, plane="runner",
                endpoint=request.path, model=model, http_id=rid,
            )
            return resp

        parts = []
        n = 0
        stop_reason = "end_turn"
        t_emit = None
        try:
          async for delta, tok, finished, reason in self._generate(
            served, prompt_ids, sampling, extra, trace_id=tid,
            tenant=tenant, sched_class=sclass,
          ):
            if t_emit is None:
                t_emit = time.monotonic()
            parts.append(delta)
            n += 1
            if finished:
                stop_reason = "max_tokens" if reason == "length" else "end_turn"
                break
        except EngineRequestError as e:
            return _engine_error_response(e, trace_id=tid)
        end = time.monotonic()
        self.traces.record(
            tid, "emit", t_emit if t_emit is not None else end, end,
            plane="runner", tokens=n, stream=False,
        )
        self.traces.record(
            tid, "request", t_req, end, plane="runner",
            endpoint=request.path, model=model, http_id=rid,
        )
        return web.json_response(
            {
                "id": rid,
                "type": "message",
                "role": "assistant",
                "model": model,
                "content": [{"type": "text", "text": "".join(parts)}],
                "stop_reason": stop_reason,
                "usage": {
                    "input_tokens": len(prompt_ids),
                    "output_tokens": n,
                },
            },
            headers={TRACE_HEADER: tid},
        )


def run_server(registry: ModelRegistry, host="0.0.0.0", port=8000):
    server = OpenAIServer(registry)
    web.run_app(server.build_app(), host=host, port=port, print=None)
