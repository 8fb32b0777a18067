"""Engine driver thread: bridges async HTTP handlers to the step loop.

The reference streams SSE chunks from vLLM through hydra and a NATS response
queue back to the waiting HTTP handler (``SURVEY.md`` §3.2).  In-process the
same shape holds with cheaper parts: one dedicated thread owns the Engine
(all JAX dispatch stays single-threaded), handlers submit via a thread-safe
inbox and receive per-request events through callbacks marshalled onto their
asyncio loop.

Robustness (ISSUE 2): a failing ``engine.step()`` no longer aborts every
in-flight request.  The loop retries the step once (transient faults), then
quarantines the not-yet-emitting requests and bisects them back in to find
the poisoned one(s) — only the culprit gets an error event, everything else
keeps generating.  Admission is bounded (queue depth / queued-token budget)
so overload sheds immediately with a clean ``queue_full`` error (HTTP 429)
instead of rotting toward the queue timeout, and ``stop(drain=...)`` drains
in-flight work before the thread exits.
"""

from __future__ import annotations

import dataclasses
import gc
import logging
import queue
import threading
import time
from typing import Callable, Optional

from helix_tpu.engine.engine import (
    Engine,
    FinishReason,
    Request,
    SnapshotError,
)
from helix_tpu.engine.ragged import step_program_name
from helix_tpu.models.mixers import flight_fields
from helix_tpu.obs import EngineLoopObs, FlightRecorder, RateTracker
from helix_tpu.obs import flight as obs_flight
from helix_tpu.obs import trace as obs_trace
from helix_tpu.obs.flight import SATURATION_KEYS
from helix_tpu.obs.slo import ANON_TENANT, SLOObserver
from helix_tpu.serving.sched import (
    PREEMPT_VICTIM,
    SHED_VICTIM,
    TENANT_QUEUE_FULL,
    make_scheduler,
)
from helix_tpu.testing import faults

log = logging.getLogger("helix.engine")

# error-message prefixes the HTTP layer maps onto statuses (429 / 503);
# keep in sync with openai_api._engine_error_response
QUEUE_FULL = "queue_full"
SHUTTING_DOWN = "shutting_down"
# typed KV-exhaustion shed (ISSUE 6): a request that cannot claim pages
# within the admission deadline — or arrives while admission has already
# been KV-starved longer than the deadline — gets a clean 503 +
# Retry-After instead of silently aging in the queue
KV_EXHAUSTED = "kv_exhausted"


def _rounded(phases: dict) -> dict:
    """A step's phase seconds as the flight record files them."""
    return {name: round(sec, 6) for name, sec in phases.items()}


@dataclasses.dataclass
class TokenEvent:
    request_id: str
    token_id: int
    finished: bool
    finish_reason: Optional[str] = None
    error: Optional[str] = None


class _Parked(threading.Event):
    """Set while the engine thread is blocked (on the device, or on the
    emission stage itself): ``with stage.parked:`` around the blocking
    call.  Not re-entrant: the engine thread parks in one place at a
    time."""

    def __enter__(self):
        self.set()

    def __exit__(self, *exc):
        self.clear()
        return False


class _EmissionStage:
    """Bounded, ordered token emission off the engine thread (ISSUE 13;
    on in every started loop since ISSUE 26).

    The loop hands each step's emitted batch to this stage so SSE
    subscriber callbacks and per-tenant SLO accounting never sit between
    a device completion and the next dispatch.  One worker thread keeps
    per-request event order; the bounded queue applies backpressure (a
    full queue blocks the engine thread, so the engine never runs more
    than ``depth`` batches ahead of the slowest subscriber).

    The engine thread comes first on the GIL: the worker delivers only
    while the engine thread is ``parked`` (blocked on the device, or
    on this stage in a full ``push`` or a ``flush``, which every idle
    wait and terminal event takes first), a slice of
    ``SLICE`` events at a time, so its Python lands in the step's device
    waits and not in the admission and dispatch that follow a
    completion.  A slice waits for a park at most ``HOLD_MAX`` seconds:
    an engine thread that never parks (a compile, a long export) delays
    tokens by that much and no more.

    When not started (a loop that was never started: unit tests,
    quarantine bisection's ``_emit``), ``push`` is a direct call on the
    caller's thread."""

    SLICE = 16
    HOLD_MAX = 0.05

    def __init__(self, sink: Callable, obs: EngineLoopObs, depth: int = 8,
                 watched: Optional[obs_flight.Watched] = None):
        self._sink = sink
        self._obs = obs
        # the worker says where it is ("delivering batch n since t") for
        # the stall watch, and closes a stall of its own itself (a stage
        # on its own, with no loop around it, is watched by nobody)
        self._watched = watched
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._thread: Optional[threading.Thread] = None
        self.started = False
        self.batches = 0
        self.parked = _Parked()
        # the worker's CPU clock, for the engine thread to read
        # (time.clock_gettime); taken by the worker itself as it starts
        self.cpu_clock: Optional[int] = None

    def start(self, name: str = "emit") -> None:
        self._thread = threading.Thread(
            target=self._run, name=f"helix-emit-{name}", daemon=True
        )
        self.started = True
        self._thread.start()

    def push(self, emitted) -> None:
        if not emitted:
            return
        if not self.started:
            self._sink(emitted)
            return
        item = (time.monotonic(), emitted)
        try:
            self._q.put_nowait(item)
        except queue.Full:
            # bounded backpressure: the slowest subscriber stalls the
            # engine, and the stall is a park like any other
            self._obs.emit_backpressure.inc()
            with self.parked:
                self._q.put(item)
        self.batches += 1

    def flush(self) -> None:
        """Block until every pushed batch has been delivered — THE
        ordering barrier the engine thread takes before any terminal
        event (evict/shed/drain/quarantine), so an error frame can never
        overtake that request's queued tokens."""
        if self.started:
            with self.parked:
                self._q.join()

    def stop(self) -> None:
        if not self.started:
            return
        self._q.put(None)
        self.started = False
        # what is queued is delivered: nothing is left to come first
        with self.parked:
            if self._thread is not None:
                self._thread.join(timeout=10)

    def depth(self) -> int:
        return self._q.qsize()

    def _run(self) -> None:
        self.cpu_clock = time.pthread_getcpuclockid(threading.get_ident())
        watched = self._watched
        mark = watched.marks["emit"] if watched else obs_trace.Mark()
        obs_trace.mark_thread(mark)
        while True:
            item = self._q.get()
            try:
                if item is None:
                    return
                pushed_at, batch = item
                mark.pass_no += 1
                t0, c0 = time.monotonic(), time.thread_time()
                mark.at = (mark.pass_no, 0, "helix.emit.batch", t0)
                spent = 0.0
                for i in range(0, len(batch), self.SLICE):
                    self.parked.wait(self.HOLD_MAX)
                    if i == 0:
                        self._obs.emit_queue_wait.observe(
                            time.monotonic() - pushed_at
                        )
                    with obs_trace.phase("helix.emit.deliver") as span:
                        try:
                            self._sink(batch[i:i + self.SLICE])
                        except Exception:  # noqa: BLE001 — a subscriber bug must not kill emission
                            log.exception("emission stage sink failed")
                    spent += span.seconds
                self._obs.emit_deliver.observe(spent)
                mark.at = None
                during = watched.take("emit") if watched else None
                if during is not None:
                    watched.end(
                        "emit", during, time.monotonic() - t0,
                        time.thread_time() - c0, batch=mark.pass_no)
            finally:
                self._q.task_done()


@dataclasses.dataclass
class _ImportItem:
    """An inbox entry carrying a migrated-in request snapshot (ISSUE 11):
    ``engine.import_request`` must run on the engine thread, so the HTTP
    handler enqueues here like a submit.  ``on_result(err, code)`` fires
    once validation settles (None = accepted) so the import endpoint can
    answer with a typed status instead of a blind 200."""

    snapshot: object
    on_event: Callable[[TokenEvent], None]
    on_result: Optional[Callable] = None


class EngineLoop:
    def __init__(self, engine: Engine, name: str = "engine",
                 max_queue_seconds: float = 600.0,
                 max_queue_depth: Optional[int] = None,
                 max_queued_tokens: Optional[int] = None,
                 admission_timeout: Optional[float] = None,
                 preempt_stall_seconds: Optional[float] = None,
                 slo_targets: Optional[dict] = None,
                 tenant_top_k: Optional[int] = None,
                 burn_windows: Optional[tuple] = None,
                 sched_config=None):
        self.engine = engine
        self.name = name
        self.max_queue_seconds = max_queue_seconds
        # admission bounds: None = unbounded (seed behaviour).  Depth
        # counts requests waiting for a slot (inbox + engine wait queue);
        # tokens bound the queued prefill work so one burst of 32k
        # prompts can't hide behind a small depth bound.
        self.max_queue_depth = max_queue_depth
        self.max_queued_tokens = max_queued_tokens
        # KV-pressure degradation ladder (ISSUE 6), rungs from mildest:
        # spill (engine-internal, always on with a host tier) ->
        # preempt-by-swap after admission has stalled preempt_stall_
        # seconds -> typed kv_exhausted shed once a request has waited
        # admission_timeout (and fast-fail of NEW arrivals while the
        # engine is that starved).  None disables a rung.
        self.admission_timeout = admission_timeout
        self.preempt_stall_seconds = preempt_stall_seconds
        self._stall_since: Optional[float] = None
        self._admit_seen = 0            # num_admitted at last progress
        self._last_preempt_at = 0.0
        self.kv_exhausted_sheds = 0     # typed 503s issued
        self._inbox: "queue.Queue" = queue.Queue()
        self._pending = 0          # submitted, not yet drained to the engine
        self._pending_tokens = 0
        # RLock: submit holds it across check+enqueue so the draining
        # flag flip in stop() can be made atomic against in-flight submits
        self._admission_lock = threading.RLock()
        self._subscribers: dict[str, Callable[[TokenEvent], None]] = {}
        self._admit_order: list[str] = []   # request ids, admission order
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._draining = False
        self._drain_deadline = 0.0
        self._thread: Optional[threading.Thread] = None
        self._last_reap = time.monotonic()
        self._consec_failures = 0
        self._barren_rounds = 0   # quarantine rounds that found no culprit
        # serving metrics (scraped by /metrics)
        self.steps = 0
        self.step_failures = 0
        self.step_retries = 0
        self.quarantine_evictions = 0
        self.shed_requests = 0
        self.started_at = time.monotonic()
        # latency histograms (TTFT / queue wait / inter-token / step) —
        # standalone obs families; the runner's /metrics folds them in
        # with a model label at scrape time
        self.obs = EngineLoopObs()
        # flight recorder: bounded per-step ring + anomaly watchdog
        # (host-side counter deltas only — nothing enters the jitted
        # path), served at GET /v1/debug/flight
        self.flight = FlightRecorder()
        # what the process's stall watch reads of this loop (ISSUE 51):
        # where the engine thread is (the spans it opens write the
        # marker; ``_at`` for the places with no span), and the watcher's
        # capture of a stall under way until the thread that ends it
        # closes the record
        self._watched = obs_flight.Watched(
            name, lambda: self.flight, self.obs, self._probe,
            obs_flight.WATCH)
        self._mark = self._watched.marks["engine"]
        self._engine_clock: Optional[int] = None
        # as the step in progress began: seconds of XLA compilation so
        # far, compiled step shapes
        self._compiled0 = (0.0, 0)
        self._stalled_pass = -1     # the pass whose step was last closed
        # goodput tokens/s over a trailing window (scraped by /metrics
        # and the heartbeat saturation summary)
        self._tps = RateTracker()
        # per-tenant SLO observability (ISSUE 7): bounded top-K tenant
        # accounting (+ __other__ fold), multi-window burn rates against
        # the profile-declared SLO targets, and the admission audit ring
        # served at GET /v1/debug/admissions
        self.slo = SLOObserver(
            targets=slo_targets, top_k=tenant_top_k, windows=burn_windows
        )
        self._trace = obs_trace.default_store()
        self._first_emit: dict[str, float] = {}   # req id -> first-token t
        self._last_emit: dict[str, float] = {}    # req id -> last-token t
        # the scheduler (ISSUE 9, serving/sched.py): owns every
        # ordering / per-tenant-bound / victim decision.  The FIFO
        # baseline (no sched_config, or policy: fifo) preserves the
        # pre-scheduler semantics exactly.  Multihost leaders run the
        # scheduler like any engine: its decisions (budget, victim
        # order, admission order) replicate as step-plan data.
        self.sched = make_scheduler(sched_config)
        self._sched_active = self.sched.active
        # per-tenant inbox depth (admission lock); the per-tenant bound
        # adds the engine-side wait-queue count on demand
        self._pending_by_tenant: dict[str, int] = {}
        # the loop runs one step ahead (ISSUE 13, finished by ISSUE 33):
        # while admission is blocked it dispatches step N+1 against
        # predicted post-step state before it fetches step N, so the
        # host's admit and dispatch run under the device's time.  Needs
        # the engine's dispatch/complete split; the gate is what the loop
        # observes each pass (``_run``), not a setting.  Multihost leaders
        # look ahead too: plan N+1 publishes at dispatch, so the broadcast
        # rides the same overlap.
        self.async_enabled = hasattr(engine, "step_dispatch")
        # the at-most-one dispatched-but-not-reconciled step
        self._inflight = None
        self.pipelined_steps = 0    # steps dispatched while one was in flight
        self._emit_stage = _EmissionStage(
            self._deliver, self.obs, watched=self._watched)
        self._phases = obs_trace.Phases()   # the step in progress
        self._parts = obs_trace.Phases()    # the parts of its admit and dispatch
        # the host's account (ISSUE 37).  The CPU clock of the thread
        # that submits (the HTTP event loop), taken by that thread in
        # ``submit``; what the engine thread last read of each host
        # thread's clock, ``{name: (clock, seconds)}``; collector pauses
        # so far (added by ``_gc_hook`` on whichever thread collects) and
        # as of the last step
        self._http_ident: Optional[int] = None
        self._http_clock: Optional[int] = None
        self._cpu_seen: dict = {}
        self._gc_total = 0.0
        self._gc_seen = 0.0
        self._gc_span: Optional[obs_trace.phase] = None
        # host-side device-busy watermark: the last completion's return
        # time.  A dispatch that happens with nothing in flight charges
        # the gap since this watermark as device idle (idle_gap_s).
        self._device_busy_until = 0.0
        # cross-runner migration (ISSUE 11): when set, requests still
        # unfinished at the drain deadline are snapshotted and handed to
        # this callable (wire dict -> accepting peer id; raises on
        # failure) instead of shed — the node agent wires a PeerShipper
        # here during graceful shutdown, tests wire a direct stub
        self.exporter = None
        self.migration_failures = 0   # failed exports/ships/imports
        # disaggregated prefill/decode (ISSUE 14): request ids staged
        # for export-at-prefill-completion -> callback(kind, wire).
        # Written by HTTP handler threads (stage/unstage), consumed on
        # the engine thread (_disagg_tick) — dict ops are GIL-atomic.
        self._disagg_cb: dict = {}
        self.disagg_exports = 0       # prefill snapshots handed to a shipper
        engine.on_admit = self._note_admit
        engine.device_wait = self._emit_stage.parked
        if self._sched_active:
            engine.victim_policy = self.sched.preempt_order

    # -- called from any thread --------------------------------------------

    def check_admission(
        self, prompt_len: int, count_shed: bool = False,
        tenant: str = ANON_TENANT, trace_id: str = "",
        request_id: str = "",
    ) -> Optional[str]:
        """Would a submit of this size be shed right now?  Returns the
        error string (``queue_full: ...`` / ``shutting_down: ...``) or
        None.  HTTP handlers pre-check so streaming requests get a clean
        429/503 status instead of an SSE error frame; callers that act on
        the verdict (actually shed the request) pass ``count_shed=True``
        so the metric — and the per-tenant accounting + admission audit
        entry — is owned here, in one place."""
        hit = self._check_admission(prompt_len, tenant)
        if hit is None:
            return None
        reason, err = hit
        if count_shed:
            self.shed_requests += 1
            kv = reason == "kv_exhausted"
            if kv:
                self.kv_exhausted_sheds += 1
            if reason == TENANT_QUEUE_FULL:
                # a scheduler decision: the flooding tenant overflowed
                # ITS bounded queue — everyone else keeps admitting
                self.sched.note_tenant_shed()
            self.slo.note_shed(tenant, kv_exhausted=kv)
            self._audit(
                reason, tenant=tenant, trace_id=trace_id,
                request_id=request_id, detail=err,
            )
        return err

    def _audit(self, reason: str, tenant: str = ANON_TENANT,
               trace_id: str = "", request_id: str = "",
               detail: str = "") -> None:
        """One admission-decision audit record, stamped with the queue
        state at the moment of the decision.  O(1) reads only — sheds
        spike exactly when the node is saturated, so the rejection path
        must not walk the wait queue per record."""
        eng = self.engine
        self.slo.audit.record(
            reason, tenant=tenant, trace_id=trace_id,
            request_id=request_id, detail=detail,
            queue_depth=self.queue_depth(),
            kv_pages_free=eng.allocator.free_pages,
            slots_busy=sum(1 for s in eng.slots if s is not None),
            preempted_parked=len(getattr(eng, "preempted", ())),
        )

    def queue_depth(self) -> int:
        """Requests awaiting a slot (inbox + engine wait queue) — THE
        queue-depth formula: the admission bound, audit records,
        saturation summary and flight records all read this one helper
        (the ``queued_tokens()`` treatment).  O(1) GIL-atomic reads,
        safe from any thread."""
        return self._pending + len(self.engine.waiting)

    def queued_tokens(self) -> int:
        """Prompt tokens awaiting admission (inbox + engine wait queue)
        — the quantity ``max_queued_tokens`` bounds and the
        ``helix_queued_tokens`` gauge reports.  Finished (aborted while
        queued) requests no longer hold KV work, so they don't count.
        GIL-atomic reads, safe from any thread."""
        return self._pending_tokens + sum(
            len(r.prompt_tokens)
            for r in list(self.engine.waiting)
            if not r.finished
        )

    def _tenant_depth(self, tenant: str) -> int:
        """Queued requests for ONE tenant (inbox + engine wait queue).
        Only computed when the scheduler's per-tenant bound is
        configured — an O(queue) walk like ``queued_tokens``."""
        return self._pending_by_tenant.get(tenant, 0) + sum(
            1
            for r in list(self.engine.waiting)
            if not r.finished
            and getattr(r, "tenant", ANON_TENANT) == tenant
        )

    def _check_admission(
        self, prompt_len: int, tenant: str = ANON_TENANT,
    ) -> Optional[tuple]:
        """(audit_reason, error_string) when a submit of this size would
        be shed right now, else None."""
        if self._draining or self._stop.is_set():
            return (
                "shutting_down",
                f"{SHUTTING_DOWN}: engine '{self.name}' is draining",
            )
        # KV-starved fast-fail: when admission has already been stalled
        # longer than the deadline, a new arrival would only age out the
        # same way — reject it NOW, before the HTTP layer commits SSE
        # headers, so the client gets a real 503 + Retry-After
        # (_stall_since is written by the engine thread; a float read is
        # GIL-atomic)
        stall_since = self._stall_since
        if (
            self.admission_timeout is not None
            and stall_since is not None
            and time.monotonic() - stall_since > self.admission_timeout
        ):
            return (
                "kv_exhausted",
                f"{KV_EXHAUSTED}: engine '{self.name}' admission has been "
                f"KV-starved for {time.monotonic() - stall_since:.1f}s "
                f"(admission_timeout={self.admission_timeout}s)",
            )
        # the engine-side sums are read without the admission lock (list
        # copies are GIL-atomic; the bound is advisory by one request
        # anyway), so overloaded submitters don't serialize on an O(n)
        # walk of the wait queue
        depth = self.queue_depth()
        if (
            self.max_queue_depth is not None
            and depth >= self.max_queue_depth
        ):
            return (
                "queue_full",
                f"{QUEUE_FULL}: {depth} request(s) already queued "
                f"(max_queue_depth={self.max_queue_depth})",
            )
        # bounded per-tenant queues (scheduler policy): one flooding
        # tenant overflows ITS queue and gets per-tenant 429s instead
        # of filling the global bound and starving the cluster
        if self.sched.cfg.max_tenant_queue_depth is not None:
            td = self._tenant_depth(tenant)
            if self.sched.tenant_overflow(tenant, td):
                return (
                    TENANT_QUEUE_FULL,
                    f"{QUEUE_FULL}: tenant '{tenant}' already has {td} "
                    f"request(s) queued (max_tenant_queue_depth="
                    f"{self.sched.cfg.max_tenant_queue_depth})",
                )
        if self.max_queued_tokens is not None:
            queued = self.queued_tokens()
            if queued + prompt_len > self.max_queued_tokens:
                return (
                    "queue_full",
                    f"{QUEUE_FULL}: {queued} tokens queued + "
                    f"{prompt_len} requested exceeds "
                    f"max_queued_tokens={self.max_queued_tokens}",
                )
        return None

    def submit(self, req: Request, on_event: Callable[[TokenEvent], None]):
        # resolve the priority class once, at the edge: a stamped class
        # passes through, everything else gets the profile default
        if not getattr(req, "sched_class", ""):
            req.sched_class = self.sched.cfg.default_class
        ident = threading.get_ident()
        if ident != self._http_ident:
            # the submitting thread's CPU clock, taken while it is
            # certainly alive: by itself
            self._http_clock = time.pthread_getcpuclockid(ident)
            self._http_ident = ident
        # reject unservable requests on the caller's thread with a clean
        # event — the engine thread must never die on bad input
        err = self.engine.validate_request(req) or self.check_admission(
            len(req.prompt_tokens), count_shed=True,
            tenant=getattr(req, "tenant", ANON_TENANT),
            trace_id=req.trace_id, request_id=req.id,
        )
        if err:
            on_event(
                TokenEvent(
                    request_id=req.id, token_id=-1, finished=True,
                    finish_reason="error", error=err,
                )
            )
            return
        if getattr(req, "adapter", ""):
            # cold-adapter overlap starts NOW, on the submitter's
            # thread: the engine's ONE readiness gate (thread-safe —
            # pool/store take their own locks) kicks the async
            # filestore->host prefetch as a side effect, so the load
            # rides the queue wait and a still-cold adapter defers
            # admission instead of stalling a step
            ready = getattr(self.engine, "_adapter_ready", None)
            if ready is not None:
                ready(req)
        with self._admission_lock:
            # re-check under the lock: stop() flips _draining inside the
            # same lock, so a submit can never slip its request into the
            # inbox after the engine thread's terminal sweep
            if self._draining or self._stop.is_set():
                self.shed_requests += 1
                self.slo.note_shed(getattr(req, "tenant", ANON_TENANT))
                self._audit(
                    "shutting_down",
                    tenant=getattr(req, "tenant", ANON_TENANT),
                    trace_id=req.trace_id, request_id=req.id,
                    detail="draining",
                )
                on_event(
                    TokenEvent(
                        request_id=req.id, token_id=-1, finished=True,
                        finish_reason="error",
                        error=f"{SHUTTING_DOWN}: engine '{self.name}' "
                              "is draining",
                    )
                )
                return
            self._pending += 1
            self._pending_tokens += len(req.prompt_tokens)
            t = getattr(req, "tenant", ANON_TENANT)
            self._pending_by_tenant[t] = (
                self._pending_by_tenant.get(t, 0) + 1
            )
            self._inbox.put((req, on_event))
        self._wake.set()

    def abort(self, request_id: str):
        self._inbox.put((request_id, None))
        self._wake.set()

    @property
    def draining(self) -> bool:
        """Shutdown-ladder state for metrics/heartbeats (GIL-atomic)."""
        return self._draining or self._stop.is_set()

    def submit_import(self, snapshot, on_event, on_result=None):
        """Enqueue a migrated-in request snapshot (any thread).

        Validation and re-admission happen on the engine thread
        (``engine.import_request`` — every checksum checked before any
        allocator mutation); ``on_result(err, code)`` reports the
        outcome.  A KV-carrying snapshot parks on the preempted list and
        re-admits when a slot + pages free up, so an import landing on a
        FULL engine queues behind admission instead of wedging — and the
        ordinary admission deadline sheds it (typed) if capacity never
        comes."""
        with self._admission_lock:
            if self._draining or self._stop.is_set():
                if on_result is not None:
                    on_result(
                        f"{SHUTTING_DOWN}: engine '{self.name}' is "
                        "draining",
                        "shutting_down",
                    )
                return
            self._inbox.put(
                (_ImportItem(snapshot, on_event, on_result), None)
            )
        self._wake.set()

    def _handle_import(self, item: _ImportItem) -> None:
        """Engine-thread half of submit_import."""
        rid = getattr(item.snapshot, "request_id", "")
        t0 = time.monotonic()
        try:
            req = self.engine.import_request(item.snapshot)
        except SnapshotError as e:
            self.migration_failures += 1
            self.flight.note_anomaly(
                "import_rejected", request_id=rid, detail=str(e)[:200]
            )
            log.warning(
                "engine '%s' rejected snapshot import request_id=%s: %s",
                self.name, rid, e,
            )
            if item.on_result is not None:
                item.on_result(str(e), e.code)
            return
        except Exception as e:  # noqa: BLE001 — thread must survive
            self.migration_failures += 1
            log.exception(
                "engine '%s' snapshot import failed request_id=%s",
                self.name, rid,
            )
            if item.on_result is not None:
                item.on_result(str(e), "snapshot_invalid")
            return
        self._subscribers[req.id] = item.on_event
        self._admit_order.append(req.id)
        # the engine-side admit leg of a migrated/disagg timeline
        # (ISSUE 18): checksum-verified page import through admission
        self._trace.record(
            getattr(item.snapshot, "trace_id", ""),
            "engine import admit", t0, time.monotonic(),
            plane="engine", request_id=req.id,
            prior_tokens=len(req.output_tokens),
            pages=len(getattr(item.snapshot, "pages", ())),
        )
        log.info(
            "engine '%s' imported request_id=%s (%d prior token(s), "
            "%d page(s))",
            self.name, req.id, len(req.output_tokens),
            len(getattr(item.snapshot, "pages", ())),
        )
        if item.on_result is not None:
            item.on_result(None, None)

    def stage_disagg_export(self, request_id: str, on_snapshot) -> None:
        """Register a disaggregated prefill export (ISSUE 14): the
        moment ``request_id`` has completed its prefill (first token
        sampled), the engine thread snapshots it via
        ``engine.export_prefill`` and fires ``on_snapshot(kind, wire)``
        exactly once, where kind is:

        - ``"snapshot"`` — wire dict attached; the request KEEPS
          decoding locally until the caller confirms the ship and
          aborts it (a failed ship degrades to local serving);
        - ``"completed"`` — the request finished before the export
          fired (short generation): serve the buffered stream locally;
        - ``"local"`` — export unavailable/failed (VL, lockstep, host
          page lost): the request keeps generating here, colocated;
        - ``"gone"`` — the request vanished (aborted) before export.

        Call BEFORE ``submit`` so the first token cannot race the
        staging."""
        self._disagg_cb[request_id] = on_snapshot

    def unstage_disagg_export(self, request_id: str) -> None:
        """Withdraw a staged export (handler timed out / chose local)."""
        self._disagg_cb.pop(request_id, None)

    def _handoff_work(self) -> bool:
        """True when a staged disagg export is actionable — the gate
        that forces a reconcile before ``_disagg_tick`` runs (export
        gathers pages + syncs sampler state, so no step may be in
        flight).  O(staged), GIL-atomic reads."""
        if not self._disagg_cb:
            return False
        for rid in list(self._disagg_cb):
            req = self.engine.get_request(rid)
            if req is None or req.finished or req.output_tokens:
                return True
        return False

    def _disagg_tick(self) -> None:
        """Engine-thread half of the disaggregated handoff: export every
        staged request whose prefill completed and hand the wire dict to
        its callback (the HTTP handler ships it OFF this thread — a slow
        peer must never stall the engine).  Export mutates nothing; the
        request keeps decoding until the ship is confirmed."""
        from helix_tpu.serving.migration import snapshot_to_wire

        for rid, cb in list(self._disagg_cb.items()):
            req = self.engine.get_request(rid)
            if req is None:
                self._disagg_cb.pop(rid, None)
                cb("gone", None)
                continue
            if req.finished:
                self._disagg_cb.pop(rid, None)
                cb("completed", None)
                continue
            if not req.output_tokens:
                continue   # still queued / prefilling
            self._disagg_cb.pop(rid, None)
            t0 = time.monotonic()
            export = getattr(self.engine, "export_prefill", None)
            snap = None
            if export is not None:
                try:
                    snap = export(rid)
                except Exception:  # noqa: BLE001 — degrade to local serving
                    log.exception(
                        "engine '%s' prefill export failed for "
                        "request_id=%s", self.name, rid,
                    )
            if snap is None:
                cb("local", None)
                continue
            try:
                wire = snapshot_to_wire(snap)
            except Exception:  # noqa: BLE001 — degrade to local serving
                log.exception(
                    "engine '%s' prefill snapshot encode failed for "
                    "request_id=%s", self.name, rid,
                )
                cb("local", None)
                continue
            self.disagg_exports += 1
            # the engine-side export leg (ISSUE 18): prefill snapshot
            # gather + wire encode, before the HTTP handler ships it
            self._trace.record(
                getattr(req, "trace_id", ""), "disagg export",
                t0, time.monotonic(), plane="engine", request_id=rid,
                pages=len(wire.get("pages") or ()),
            )
            cb("snapshot", wire)

    def _export_survivors(self) -> int:
        """Drain-deadline migration: snapshot every still-unfinished
        request and ship it to a peer via ``self.exporter`` instead of
        shedding.  Runs on the engine thread after the last drain step,
        so the captured sampler state is exactly where generation
        stopped.  Requests that cannot export (VL, ship failure) are
        left for the ``_fail_all`` that follows."""
        self._emit_stage.flush()   # no error frame may overtake tokens
        if self.exporter is None:
            return 0
        from helix_tpu.serving.migration import (
            migrated_error,
            snapshot_to_wire,
        )

        shipped = 0
        for req in self._active_by_recency():
            try:
                snap = self.engine.export_request(req.id)
            except Exception:  # noqa: BLE001 — degrade to shed
                log.exception(
                    "engine '%s' export failed for request_id=%s",
                    self.name, req.id,
                )
                snap = None
            if snap is None:
                self.migration_failures += 1
                continue
            t0 = time.monotonic()
            try:
                peer = self.exporter(snapshot_to_wire(snap))
            except Exception as e:  # noqa: BLE001 — degrade to shed
                self.migration_failures += 1
                self._trace.record(
                    getattr(req, "trace_id", ""), "migrate export ship",
                    t0, time.monotonic(), plane="engine",
                    request_id=req.id, outcome="failed",
                )
                log.warning(
                    "engine '%s' could not ship snapshot for "
                    "request_id=%s: %s",
                    self.name, req.id, e,
                )
                continue
            shipped += 1
            # the drain-ladder ship leg (ISSUE 18): snapshot encode +
            # accepted POST to the peer that now owns the request
            self._trace.record(
                getattr(req, "trace_id", ""), "migrate export ship",
                t0, time.monotonic(), plane="engine",
                request_id=req.id, outcome="shipped", peer=peer,
            )
            msg = migrated_error(req.id, peer)
            self.engine.abort(req.id)
            self._forget_request(req.id)
            log.info(
                "engine '%s' migrated request_id=%s to peer %s at "
                "drain deadline",
                self.name, req.id, peer,
            )
            cb = self._subscribers.pop(req.id, None)
            if cb:
                cb(
                    TokenEvent(
                        request_id=req.id, token_id=-1, finished=True,
                        finish_reason="error", error=msg,
                    )
                )
        return shipped

    def stats(self) -> dict:
        """Counter snapshot for /metrics (reads of plain ints are atomic
        under the GIL, so no lock against the engine thread is needed)."""
        eng = self.engine
        return {
            "steps": self.steps,
            "step_failures": self.step_failures,
            "step_retries": self.step_retries,
            "quarantine_evictions": self.quarantine_evictions,
            "shed_requests": self.shed_requests,
            "prefill_tokens": eng.num_prefill_tokens,
            "decode_tokens": eng.num_decode_tokens,
            "generated_tokens": getattr(eng, "num_generated_tokens", 0),
            "prefill_padding_tokens": getattr(
                eng, "num_prefill_padding_tokens", 0
            ),
            # ragged unification (ISSUE 10): distinct compiled device-
            # step entry points + padding over the flight window
            "compiled_step_shapes": getattr(
                eng, "compiled_step_shapes", 0
            ),
            "prefill_padding_ratio": self.padding_ratio(),
            "mixed_steps": getattr(eng, "num_mixed_steps", 0),
            "moe_dropped_tokens": getattr(eng, "moe_dropped_tokens", 0),
            "moe_routed_tokens": getattr(eng, "moe_routed_tokens", 0),
            "spec_steps": getattr(eng, "num_spec_steps", 0),
            "spec_drafted_tokens": getattr(
                eng, "num_spec_drafted_tokens", 0
            ),
            "spec_accepted_tokens": getattr(
                eng, "num_spec_accepted_tokens", 0
            ),
            "waiting": len(eng.waiting),
            "active_slots": sum(1 for s in eng.slots if s is not None),
            "free_pages": eng.allocator.free_pages,
            "kv_pages_used": getattr(eng, "kv_pages_used", 0),
            "kv_pages_peak": getattr(eng.allocator, "peak_used", 0),
            "flight_anomalies": self.flight.anomalies_total,
            "kv_cache_dtype": eng.cache_cfg.dtype,
            # KV tiering + preemption-by-swap (ISSUE 6)
            "preemptions": getattr(eng, "num_preemptions", 0),
            "resumes": getattr(eng, "num_resumes", 0),
            "preempted_parked": len(getattr(eng, "preempted", ())),
            "kv_exhausted_sheds": self.kv_exhausted_sheds,
            "host_pool": (
                eng.host_pool.stats()
                if getattr(eng, "host_pool", None) is not None
                else None
            ),
            # cross-runner migration (ISSUE 11): snapshots out/in +
            # ship/import failures + the drain-ladder state
            "migration": {
                "exported": getattr(eng, "num_snapshots_exported", 0),
                "imported": getattr(eng, "num_snapshots_imported", 0),
                "failures": self.migration_failures,
                "draining": self.draining,
                # disaggregated prefill handoffs (ISSUE 14)
                "prefill_exports": getattr(
                    eng, "num_prefill_exports", 0
                ),
                "disagg_exports": self.disagg_exports,
            },
            # persistent filestore KV tier (ISSUE 14): None = tier off
            "filestore": (
                eng.kv_filestore.stats()
                if getattr(eng, "kv_filestore", None) is not None
                else None
            ),
            # per-tenant SLO observability (ISSUE 7): pooled totals +
            # top-K bounding introspection
            "tenants": self.slo.stats(),
            # scheduler policy + per-class admission/victim counters
            # (ISSUE 9)
            "sched": self.sched.stats(),
            # asynchronous pipelined loop (ISSUE 13)
            "async_loop": {
                "enabled": self.async_enabled,
                "pipelined_steps": self.pipelined_steps,
                "device_idle_ratio": round(self.device_idle_ratio(), 4),
                "emit_queue_depth": self._emit_stage.depth(),
            },
            # continuous multi-LoRA serving (ISSUE 15): HBM pool +
            # host/filestore residency ladder; None = pool off
            "adapters": (
                {
                    **eng.adapter_pool.stats(),
                    "store": (
                        eng.adapter_store.stats()
                        if getattr(eng, "adapter_store", None)
                        is not None else None
                    ),
                }
                if getattr(eng, "adapter_pool", None) is not None
                else None
            ),
            # N-follower mesh health + failover accounting (ISSUE 17):
            # None except on a plan-broadcast leader.  multihost-ok:
            # duck-typed stats surfacing, not a feature guard.
            "multihost": (
                eng.mh_stats()
                if callable(getattr(eng, "mh_stats", None))
                else None
            ),
        }

    def device_idle_ratio(self) -> float:
        """A host-side estimate of the share of recent serving time the
        device had NOTHING dispatched: the flight window's summed
        ``idle_gap_s`` over the time its records span.  A gap is charged
        from the previous completion's return to the next dispatch
        whenever no step was in flight in between (pipelined dispatches
        therefore charge zero), so it understates idle when a fetch
        returned after the device actually finished.  The device's idle
        share is read from a trace (``POST /admin/profiler``)."""
        return self.flight.idle_share()

    def tokens_per_sec(self) -> float:
        """Goodput: generated tokens/s over the trailing rate window."""
        return self._tps.rate(getattr(self.engine, "num_generated_tokens", 0))

    def padding_ratio(self) -> float:
        """Prefill padding / (padding + useful prefill) over the flight
        window — the ragged unification's waste gauge (one formula,
        fed by the engine's single ``_charge_padding`` site)."""
        return self.flight.window_ratio(
            "padding_tokens", ("padding_tokens", "prefill_tokens")
        )

    def saturation(self) -> dict:
        """The compact saturation summary (``obs.flight.SATURATION_KEYS``
        schema) this engine contributes to the node heartbeat and the
        runner's capacity gauges.  Plain GIL-atomic reads, safe from any
        thread."""
        eng = self.engine
        used = getattr(eng, "kv_pages_used", 0)
        cap = getattr(eng, "kv_pages_capacity", 1)
        pc = getattr(eng, "prefix_cache", None)
        hits = getattr(pc, "hits", 0) if pc is not None else 0
        misses = getattr(pc, "misses", 0) if pc is not None else 0
        denom = hits + misses
        hp = getattr(eng, "host_pool", None)
        out = {
            "kv_occupancy": round(used / cap, 4),
            "slots_busy": sum(1 for s in eng.slots if s is not None),
            "slots_total": len(eng.slots),
            "queue_depth": self.queue_depth(),
            "tokens_per_sec": round(self.tokens_per_sec(), 2),
            "prefix_hit_rate": round(hits / denom, 4) if denom else 0.0,
            "spec_acceptance_ratio": round(
                getattr(eng, "spec_acceptance_ratio", 0.0), 4
            ),
            # host KV tier fullness (0 with the tier off) + decoders
            # currently swapped out awaiting resume
            "kv_host_occupancy": round(
                hp.occupancy if hp is not None else 0.0, 4
            ),
            "preempted_requests": len(getattr(eng, "preempted", ())),
            # scheduler prefill-admission budget this engine is running
            # under (0 = unbudgeted — FIFO baseline or no cap declared)
            "prefill_budget_tokens": int(
                getattr(eng, "prefill_budget", None) or 0
            ),
            # multi-LoRA adapters resident in the HBM pool (0 = pool
            # off) — the control plane's adapter-affinity signal
            "adapters_resident": (
                eng.adapter_pool.stats()["resident"]
                if getattr(eng, "adapter_pool", None) is not None
                else 0
            ),
            # tiered KV residency (ISSUE 20): cold-middle pages demoted
            # to host RAM — how much admitted context lives past HBM
            "kv_cold_pages": int(getattr(eng, "kv_cold_pages", 0)),
        }
        # schema lockstep: this summary IS the per-engine instance of the
        # shared heartbeat schema — emit exactly its key set
        return {k: out[k] for k in SATURATION_KEYS}

    def start(self):
        gc.callbacks.append(self._gc_hook)
        obs_flight.WATCH.attach(self._watched)
        self._emit_stage.start(self.name)
        self._thread = threading.Thread(
            target=self._run, name=f"helix-engine-{self.name}", daemon=True
        )
        self._thread.start()
        return self

    def stop(self, join: bool = True, drain: float = 0.0):
        """Stop the engine thread.  With ``drain > 0`` new submissions are
        shed (``shutting_down`` -> 503) while in-flight requests keep
        stepping for up to ``drain`` seconds; anything still unfinished at
        the deadline gets a clean error event before the thread exits.
        ``join=False`` + drain leaves the thread to finish the drain on
        its own (it exits once idle or at the deadline)."""
        if drain > 0 and self._thread is not None and self._thread.is_alive():
            # deadline must be visible before the flag: the engine thread
            # checks the deadline as soon as it sees _draining
            self._drain_deadline = time.monotonic() + drain
            with self._admission_lock:
                self._draining = True
            self._wake.set()
            if not join:
                return   # thread self-terminates when drained
            self._thread.join(timeout=drain + 30)
        self._stop.set()
        self._wake.set()
        if join and self._thread is not None:
            self._thread.join(timeout=30)
        self._unhook_gc()
        self._unwatch()

    def _gc_hook(self, event: str, info: dict) -> None:
        """``gc.callbacks``: a collection as the span ``helix.gc``, on
        whichever thread triggered it (a pause stalls them all: the
        collector holds the GIL).  Collections do not nest, so one open
        span is all there is."""
        if event == "start":
            self._gc_span = obs_trace.phase(
                "helix.gc", generation=info["generation"])
            self._gc_span.__enter__()
        elif self._gc_span is not None:
            span, self._gc_span = self._gc_span, None
            span.__exit__(None, None, None)
            self._gc_total += span.seconds

    def _unhook_gc(self) -> None:
        if self._gc_hook in gc.callbacks:
            gc.callbacks.remove(self._gc_hook)

    # -- the stall watch (obs.flight.StallWatch) ----------------------------

    def _probe(self) -> dict:
        """This loop's account of itself for a stall capture, read by the
        WATCHER's thread: each host thread's ident, CPU clock and CPU
        seconds as of the last step's end (``_threads_cpu``'s reading),
        whether a collection is open, and what waits in the inbox and
        before the emission worker."""
        stage, seen = self._emit_stage, self._cpu_seen
        threads = {}
        for name, thread, clock in (
            ("engine", self._thread, self._engine_clock),
            ("emit", stage._thread, stage.cpu_clock),
            ("http", self._http_ident, self._http_clock),
        ):
            ident = getattr(thread, "ident", thread)
            if ident is not None and clock is not None:
                threads[name] = (ident, clock, seen.get(name, (0, None))[1])
        return {
            "threads": threads,
            "gc_open": self._gc_span is not None,
            "inbox_depth": self._inbox.qsize(),
            "emit_depth": stage.depth(),
        }

    def _at(self, name: Optional[str]) -> None:
        """The engine thread is at ``name`` from now (a place with no
        span; None: nowhere)."""
        mark = self._mark
        mark.at = name and (
            mark.pass_no, mark.step, name, time.monotonic())

    def _unwatch(self) -> None:
        """Leave the stall watch; a stall still under way is closed as it
        stands, marked ``unfinished``."""
        obs_flight.WATCH.detach(self._watched)
        now = time.monotonic()
        for where in list(self._watched.during):
            during = self._watched.take(where)
            if during is not None:
                self._watched.end(
                    where if where != "engine" else "between", during,
                    now - during["since"], span=during["span"],
                    unfinished=True)

    def _stall_of(self, duration: float, wall: float,
                  cpu: Optional[float]) -> Optional[dict]:
        """Whether the step that just ended is a stall, asked BEFORE its
        observation is made: the watcher caught it overdue, or its
        ``duration`` passes the recorder's slow-step rule (the record
        that follows is then filed ``slow_step``).  ``wall``: the seconds
        the stall is charged; ``cpu``: the engine thread's CPU seconds
        inside them, where known."""
        during = self._watched.take("engine")
        if during is not None and during["pass"] != self._mark.pass_no:
            # a capture that landed after its own pass had closed its step
            # (a process that thaws ends the step before the watcher has
            # read the stacks): that step is filed, this one did not hang
            during = None
        if during is None and not self.flight.slow(duration):
            return None
        self._stalled_pass = self._mark.pass_no
        return {"wall": wall, "cpu": cpu, "during": during}

    def _close_stall(self, stall: dict, rec: Optional[dict] = None) -> dict:
        """Close a stalled step's record with what the engine loop alone
        knows (the launch record, the step's own flight record, compile
        seconds and shapes over the step), log and count it; returns
        what the recorder files with the anomaly.  ``where`` is the span
        the engine thread was caught in, or, for a step that ended
        inside a tick of the watcher, the phase that took longest."""
        during, rec = stall["during"], rec or {}
        phases = rec.get("phases") or _rounded(self._phases)
        where = during["span"] if during is not None else max(
            phases, key=phases.get, default="helix.loop.step")
        launch = self._mark.attrs.get("helix.loop.launch")
        if launch is not None:
            launch = {
                # (rings' and cold chunks' suffixes are not among the
                # span's attributes)
                "program": "jit_" + step_program_name(
                    launch["token_bucket"], launch["has_hist"],
                    launch["prefill_rows"]),
                **{k: launch[k] for k in (
                    "kind", "token_bucket", "prefill_rows", "live_rows")},
            }
        compile0, shapes0 = self._compiled0
        closed = self._watched.closed(
            where, during, stall["wall"], stall["cpu"],
            step=self.steps, launch=launch,
            **{k: rec[k] for k in (
                "kind", "phases", "phases_cpu", "parts", "threads_cpu",
                "gc_s", "device_wait_s") if k in rec},
            compiled_shapes=[shapes0, getattr(
                self.engine, "compiled_step_shapes", 0)],
            compile_s=round(
                obs_flight.WATCH.compile_seconds - compile0, 6),
        )
        return self._watched.file(closed)

    def _close_between(self) -> None:
        """A stall of the engine thread outside any step (the inbox
        drain, a reconcile point, a ladder between passes), closed as
        soon as the pass is past it; its CPU is the thread's since the
        last step's end."""
        during = self._watched.take("engine")
        if during is None or during["pass"] == self._stalled_pass:
            # (a capture that landed as its step closed: filed already)
            return
        seen = self._cpu_seen.get("engine")
        self._watched.end(
            "between", during, time.monotonic() - during["since"],
            time.thread_time() - seen[1] if seen else None,
            span=during["span"])

    # -- engine thread ------------------------------------------------------

    def _drain_inbox(self):
        """Apply what arrived.  A plain submit only appends to the wait
        queue, which no step in flight reads; an abort or an import
        changes slots the in-flight prediction was built on, so the step
        in flight is reconciled first (whether or not that succeeds,
        nothing is in flight afterwards)."""
        while True:
            try:
                item, on_event = self._inbox.get_nowait()
            except queue.Empty:
                return
            if isinstance(item, _ImportItem) or on_event is None:
                self._reconcile_or_fail()
            if isinstance(item, _ImportItem):  # migrated-in snapshot
                self._handle_import(item)
                continue
            if on_event is None:  # abort
                # barrier: the emission worker may still be delivering
                # this request's queued tokens — its bookkeeping and the
                # forget below must not interleave
                self._emit_stage.flush()
                self.engine.abort(item)
                self._subscribers.pop(item, None)
                self._forget_request(item)
            else:
                with self._admission_lock:
                    self._pending = max(0, self._pending - 1)
                    self._pending_tokens = max(
                        0, self._pending_tokens - len(item.prompt_tokens)
                    )
                    t = getattr(item, "tenant", ANON_TENANT)
                    n = self._pending_by_tenant.get(t, 0) - 1
                    if n > 0:
                        self._pending_by_tenant[t] = n
                    else:
                        self._pending_by_tenant.pop(t, None)
                try:
                    self.engine.add_request(item)
                    self._subscribers[item.id] = on_event
                    self._admit_order.append(item.id)
                except Exception as e:  # noqa: BLE001 — thread must survive
                    on_event(
                        TokenEvent(
                            request_id=item.id, token_id=-1, finished=True,
                            finish_reason="error", error=str(e),
                        )
                    )

    def _note_admit(self, req) -> None:
        """Engine admission-confirm hook (fires on the engine thread
        inside ``_try_claim``): feeds the scheduler's class counters and
        the per-tenant fair-share account — charging only on CONFIRMED
        admissions is what keeps the DRR ledger honest when a reorder
        pass couldn't be acted on (resource block)."""
        try:
            self.sched.note_admitted(req)
        except Exception:  # noqa: BLE001 — bookkeeping must never fail admission
            log.exception("scheduler note_admitted failed")

    def _observe_emit(self, req: Request, finished: bool) -> None:
        """Feed the latency histograms + engine-level spans from one
        emitted token (queue/prefill on the first token, decode span on
        finish).  ``finished`` is the emission-time snapshot — the live
        ``req.finished`` may already reflect a LATER step's reconcile
        when delivery runs on the emission worker."""
        now = time.monotonic()
        rid = req.id
        tenant = getattr(req, "tenant", ANON_TENANT)
        last = self._last_emit.get(rid)
        if rid not in self._first_emit:
            self._first_emit[rid] = now
            # what the HTTP handler measures http.first_write from
            req.first_emit_time = now
            admitted = req.admitted_time or now
            # the engine has the token / the token is emitted: apart by
            # the decode window the first token travels through
            have = min(max(req.first_token_time or now, admitted), now)
            self.obs.queue_wait.observe(max(0.0, admitted - req.submit_time))
            self.obs.admit_to_first_token.observe(have - admitted)
            self.obs.first_token_hold.observe(now - have)
            self.obs.ttft.observe(max(0.0, now - req.submit_time))
            self.slo.note_first_token(
                tenant,
                max(0.0, now - req.submit_time),
                max(0.0, admitted - req.submit_time),
                len(req.prompt_tokens),
            )
            if req.trace_id:
                self._trace.record(
                    req.trace_id, "queue", req.submit_time, admitted,
                    plane="engine", request_id=rid,
                )
                self._trace.record(
                    req.trace_id, "prefill", admitted, now,
                    plane="engine", request_id=rid,
                    prompt_tokens=len(req.prompt_tokens),
                    cached_tokens=req.cached_tokens,
                )
                self._trace.record(
                    req.trace_id, "admit_to_token", admitted, have,
                    plane="engine", request_id=rid,
                )
                self._trace.record(
                    req.trace_id, "first_token_hold", have, now,
                    plane="engine", request_id=rid,
                )
        elif last is not None:
            self.obs.inter_token.observe(max(0.0, now - last))
        self._last_emit[rid] = now
        if finished:
            t_first = self._first_emit.pop(rid, now)
            self._last_emit.pop(rid, None)
            if req.trace_id:
                self._trace.record(
                    req.trace_id, "decode", t_first, now,
                    plane="engine", request_id=rid,
                    output_tokens=len(req.output_tokens),
                    finish_reason=(
                        req.finish_reason.value if req.finish_reason else None
                    ),
                )

    def _forget_request(self, request_id: str) -> None:
        """Drop per-request emit bookkeeping (abort/evict paths where no
        finished token event flows through _emit)."""
        self._first_emit.pop(request_id, None)
        self._last_emit.pop(request_id, None)

    def _emit(self, emitted) -> None:
        """Snapshot + deliver in one call (a loop that was never
        started, and quarantine bisection, which flushes first).  A
        started loop snapshots on the engine thread at push time and
        delivers on the emission worker."""
        self._deliver(self._snapshot_events(emitted))

    def _snapshot_events(self, emitted) -> list:
        """Render ``[(req, token), ...]`` into delivery-ready events —
        ENGINE-THREAD ONLY, at emission time.  ``req.finished`` keeps
        evolving after the push (the next step's reconcile may finish
        this request before the worker delivers), so a delivery-time
        read would stamp an EARLIER token as terminal, pop the
        subscriber, and drop the real final tokens.  Within one batch
        the finishing token is always a request's LAST entry (the
        engine discards post-finish overruns), so only the last
        occurrence carries the finished flag."""
        # chaos (ISSUE 19): a corrupt_output rule models a host silently
        # computing wrong logits — offset every emitted token id (mod
        # vocab) at emission time.  Requests still complete, latency is
        # untouched; only the canary's bit-identity check can see it.
        offset = 0
        inj = faults.active()
        if inj is not None:
            corrupt = inj.corrupt_output(self.name)
            if corrupt:
                offset = int(corrupt.get("offset", 1))
        vocab = getattr(
            getattr(self.engine, "model_cfg", None), "vocab_size", 0
        )
        last: dict = {}
        for idx, (req, _token) in enumerate(emitted):
            last[req.id] = idx
        events = []
        for idx, (req, token) in enumerate(emitted):
            if offset and token >= 0 and vocab:
                token = (token + offset) % vocab
            fin = req.finished and last[req.id] == idx
            events.append((
                req, fin,
                TokenEvent(
                    request_id=req.id,
                    token_id=token,
                    finished=fin,
                    finish_reason=(
                        req.finish_reason.value
                        if fin and req.finish_reason else None
                    ),
                ),
            ))
        return events

    def _deliver(self, events) -> None:
        # per-tenant token counts batched to ONE accounting call per
        # tenant per step (not per token) — the accounting lock is
        # shared with /metrics scrapes and must stay off the hot path
        tenant_tokens: dict = {}
        for req, fin, ev in events:
            self._observe_emit(req, fin)
            t = getattr(req, "tenant", ANON_TENANT)
            tenant_tokens[t] = tenant_tokens.get(t, 0) + 1
            cb = self._subscribers.get(req.id)
            if cb is None:
                continue
            cb(ev)
            if fin:
                self._subscribers.pop(req.id, None)
        for t, n in tenant_tokens.items():
            self.slo.note_tokens(t, n)

    def _shed_kv_exhausted(self, req, waited: float) -> None:
        """Terminal typed shed for one request that outwaited the
        admission deadline (queued or parked-preempted)."""
        self._emit_stage.flush()   # no error frame may overtake tokens
        msg = (
            f"{KV_EXHAUSTED}: request waited {waited:.1f}s for KV pages "
            f"(admission_timeout={self.admission_timeout}s) — the engine "
            "is out of KV capacity; retry later"
        )
        self.engine.abort(req.id)
        self.kv_exhausted_sheds += 1
        self.shed_requests += 1
        tenant = getattr(req, "tenant", ANON_TENANT)
        self.slo.note_shed(tenant, kv_exhausted=True)
        self._audit(
            "kv_exhausted", tenant=tenant, trace_id=req.trace_id,
            request_id=req.id, detail=msg,
        )
        log.warning(
            "engine '%s' shedding request_id=%s trace_id=%s: %s",
            self.name, req.id, req.trace_id or "-", msg,
            extra={"trace_id": req.trace_id or "", "request_id": req.id},
        )
        self._forget_request(req.id)
        cb = self._subscribers.pop(req.id, None)
        if cb:
            cb(
                TokenEvent(
                    request_id=req.id, token_id=-1, finished=True,
                    finish_reason="error", error=msg,
                )
            )

    def _memory_pressure_tick(self) -> None:
        """The graceful-degradation ladder, walked once per loop pass.

        Tracks how long admission has been KV-starved (queue non-empty
        with no admissions or resumes landing).  Past
        ``preempt_stall_seconds``, swap out the newest/largest decoder
        (``Engine.preempt_for_pressure``) so the starved queue gets its
        pages — bounded to one preemption per stall window.  Past
        ``admission_timeout``, requests stop aging silently: queued and
        parked requests over the deadline get the typed ``kv_exhausted``
        shed."""
        eng = self.engine
        now = time.monotonic()
        progress = eng.num_admitted + getattr(eng, "num_resumes", 0)
        waiting = list(eng.waiting)
        if progress != self._admit_seen:
            self._admit_seen = progress
            self._stall_since = now if waiting else None
        elif not waiting:
            self._stall_since = None
        elif self._stall_since is None:
            self._stall_since = now
        if self.admission_timeout is not None:
            # queued sheds require the STALL ITSELF to have outlived the
            # deadline (same criterion as the fast-fail path): a request
            # aging in a merely throughput-bound queue — admissions still
            # landing, so the stall clock keeps resetting — is ordinary
            # latency, not KV exhaustion, and labelling it kv_exhausted
            # would misdirect both the client's retry and the operator's
            # capacity read
            if (
                self._stall_since is not None
                and now - self._stall_since > self.admission_timeout
            ):
                over = [
                    r for r in waiting
                    if not r.finished
                    and now - r.submit_time > self.admission_timeout
                ]
                if self._sched_active and len(over) > 1:
                    # every over-deadline request sheds, but in the
                    # policy's victim order (lowest class first) so the
                    # audit trail reflects the ladder
                    over = self.sched.preempt_order(over)
                for r in over:
                    self._shed_kv_exhausted(r, now - r.submit_time)
            # a parked decoder that cannot re-acquire pages IS KV
            # pressure by construction (resume is retried every step),
            # so its deadline is unconditional
            for st in list(getattr(eng, "preempted", ())):
                waited = now - st.preempted_at
                if not st.req.finished and waited > self.admission_timeout:
                    self._shed_kv_exhausted(st.req, waited)
        if (
            self.preempt_stall_seconds is not None
            and self._stall_since is not None
            and now - self._stall_since > self.preempt_stall_seconds
            and now - self._last_preempt_at > self.preempt_stall_seconds
        ):
            # a swap-out reads the victim's sampler state and pages where
            # its last step left them
            self._reconcile_or_fail()
            victim = self.engine.preempt_for_pressure()
            if victim is not None:
                self._last_preempt_at = now
                vreq = self.engine.get_request(victim)
                tenant = getattr(vreq, "tenant", ANON_TENANT)
                self.slo.note_preemption(tenant)
                if self._sched_active and vreq is not None:
                    # a scheduler decision: the victim came from the
                    # policy ladder (lowest class, most-over-fair-share
                    # tenant, newest) — audited under its own reason
                    self.sched.note_preempt_victim(vreq)
                    preempt_reason = PREEMPT_VICTIM
                else:
                    preempt_reason = "preempt_by_swap"
                self._audit(
                    preempt_reason, tenant=tenant,
                    trace_id=getattr(vreq, "trace_id", ""),
                    request_id=victim,
                    detail=f"admission KV-starved "
                           f"{now - self._stall_since:.1f}s",
                )
                log.warning(
                    "engine '%s' admission KV-starved for %.1fs: "
                    "preempted request_id=%s (swap-to-host)",
                    self.name, now - self._stall_since, victim,
                )

    def _deliver_resume_failures(self) -> None:
        """Typed error events for parked requests whose swap-in failed
        verification (corrupt host copy) — detected inside the engine,
        surfaced to the subscriber here."""
        drain = getattr(self.engine, "drain_resume_failures", None)
        if drain is None:
            return
        if self._resume_failures_pending():
            self._emit_stage.flush()
        for req, msg in drain():
            log.warning(
                "engine '%s' resume failed for request_id=%s: %s",
                self.name, req.id, msg,
                extra={"trace_id": req.trace_id or "",
                       "request_id": req.id},
            )
            self.flight.note_anomaly(
                "resume_corrupt", request_id=req.id, detail=msg[:200]
            )
            self._forget_request(req.id)
            cb = self._subscribers.pop(req.id, None)
            if cb:
                cb(
                    TokenEvent(
                        request_id=req.id, token_id=-1, finished=True,
                        finish_reason="error", error=msg,
                    )
                )

    def _fault_gate(self) -> None:
        """The (normally disabled) fault-injection hook so chaos tests
        can poison specific requests — shared by the synchronous step
        and the async dispatch."""
        from helix_tpu.testing import faults

        inj = faults.active()
        if inj is not None:
            ids = [r.id for r in self.engine.slots if r is not None] + [
                r.id for r in self.engine.waiting
            ]
            inj.maybe_fail_step(self.name, self.steps, ids)

    def _step_once(self):
        """One full synchronous engine step (quarantine bisection uses
        this — no pipelining)."""
        self._fault_gate()
        return self.engine.step()

    def _dispatch_once(self):
        """Host phase of one engine step.  An engine without the
        dispatch/complete split runs its monolithic ``step()`` and
        returns no pending, so the loop behaves exactly synchronously.
        Multihost leaders implement the split themselves (publishing the
        step plan at dispatch), so they pipeline like any engine."""
        self._fault_gate()
        if not hasattr(self.engine, "step_dispatch"):
            return self.engine.step(), None
        return self.engine.step_dispatch()

    def _handle_step_failure(
        self, e: Exception, dt_step: float, flight_pre: tuple,
    ) -> None:
        """The step-failure ladder (shared by the sync and async paths):
        record, retry once on the exact same state, then quarantine."""
        self._emit_stage.flush()
        stall = self._stall_of(
            dt_step, dt_step, sum(self._phases.cpu.values()))
        self._observe_step(dt_step, self._phases, stall=stall)
        self._flight_record(
            dt_step, flight_pre, generated=0, failed=str(e), stall=stall
        )
        self.step_failures += 1
        self._consec_failures += 1
        scheduled = [
            r.id for r in self.engine.slots if r is not None
        ]
        log.warning(
            "engine '%s' step %d failed (consecutive=%d, "
            "scheduled request_ids=%s): %s",
            self.name, self.steps, self._consec_failures,
            scheduled, e,
        )
        if self._consec_failures == 1:
            # transient faults (preemption, a runtime hiccup) clear on
            # an immediate retry of the exact same state
            self.step_retries += 1
            return
        import traceback

        traceback.print_exc()
        self._quarantine(e)
        self._consec_failures = 0

    # -- step phases (obs.trace.phase) ---------------------------------------

    def _new_phases(self) -> obs_trace.Phases:
        """The phase seconds of the step that starts now.  The engine
        owns the dict its own phases write to (a wrapper's attribute
        passthrough reaches it); an engine without one leaves the loop's
        phases alone in a dict of the loop's."""
        ph = getattr(self.engine, "step_phases", None)
        if ph is None:
            ph = obs_trace.Phases()
        ph.clear()
        self._phases = ph
        self._mark.attrs.clear()    # the launch record is this step's
        self._compiled0 = (
            obs_flight.WATCH.compile_seconds,
            getattr(self.engine, "compiled_step_shapes", 0))
        parts = getattr(self.engine, "step_parts", None)
        if parts is not None:
            parts.clear()
            self._parts = parts
        return ph

    def _push_emit(self, emitted, ph: obs_trace.Phases) -> float:
        """Hand one step's tokens to the emission stage, as the span
        ``helix.loop.emit``; returns the seconds it took: the snapshot
        and the enqueue (and any block on a full queue) in a started
        loop, the delivery itself in one that was never started.  What
        delivery costs on the worker is ``helix.emit.deliver``."""
        with obs_trace.phase(
            "helix.loop.emit", hist=self.obs.emit_seconds, into=ph
        ) as span:
            self._emit_stage.push(self._snapshot_events(emitted))
        return span.seconds

    def _observe_step(self, seconds: float, ph: obs_trace.Phases,
                      exposed: float = 0.0, build_cpu: float = 0.0,
                      stall: Optional[dict] = None) -> dict:
        """One observation a step of the step histogram and of every
        phase histogram (0 where the phase did not run), so the phase
        means add up to the step's; of the host time the device
        waited out before this step's launch (0 for a step launched
        behind a running one); and of the host's account: the parts of
        admit and dispatch, the engine thread's CPU while it built the
        step, the three host threads' CPU and the collector's pauses
        since the step before; and of the step's wall and of the part of
        it off the CPU if the step is a ``stall`` (``_stall_of``), 0 if
        not.  Returns the account as the flight record files it."""
        obs = self.obs
        obs.step_seconds.observe(seconds)
        wall, cpu = (stall["wall"], stall["cpu"] or 0.0) if stall else (0, 0)
        obs.stall_seconds.observe(wall)
        obs.stall_offcpu.observe(max(0.0, wall - cpu))
        obs.exposed_host.observe(exposed)
        obs.step_context_tokens.observe(
            getattr(self.engine, "step_context_tokens", 0))
        for name, hist in (*obs.step_phases.items(),
                           *obs.state_phases.items()):
            hist.observe(ph.get(name, 0.0))
        parts = self._parts
        for name, hist in obs.step_parts.items():
            hist.observe(parts.get(name, 0.0))
        obs.host_build_cpu.observe(build_cpu)
        threads = self._threads_cpu()
        for name, hist in obs.threads_cpu.items():
            hist.observe(threads[name])
        gc_total = self._gc_total
        gc_s, self._gc_seen = gc_total - self._gc_seen, gc_total
        obs.gc_seconds.observe(gc_s)
        return {
            "phases_cpu": _rounded(ph.cpu),
            "parts": _rounded(parts),
            "parts_cpu": _rounded(parts.cpu),
            "threads_cpu": _rounded(threads),
            "gc_s": round(gc_s, 6),
        }

    def _threads_cpu(self) -> dict:
        """CPU seconds the engine thread (the caller), the emission
        worker and the submitting thread used since the last call; 0 for
        a thread first seen now, or gone."""
        reads = {"engine": (None, obs_trace.thread_cpu())}
        for name, clock in (("emit", self._emit_stage.cpu_clock),
                            ("http", self._http_clock)):
            if clock is None:
                continue
            try:
                reads[name] = (clock, time.clock_gettime(clock))
            except OSError:     # the thread has exited
                pass
        seen, self._cpu_seen = self._cpu_seen, reads
        out = {}
        for name in ("engine", "emit", "http"):
            now, last = reads.get(name), seen.get(name)
            same = now is not None and last is not None and now[0] == last[0]
            out[name] = max(0.0, now[1] - last[1]) if same else 0.0
        return out

    # -- flight recorder (host-side counter deltas only) --------------------

    def _flight_pre(self) -> tuple:
        """Counter snapshot taken just before a step so the per-step
        record carries deltas, not lifetime totals."""
        eng = self.engine
        hp = getattr(eng, "host_pool", None)
        return (
            eng.num_prefill_tokens,
            getattr(eng, "num_prefill_padding_tokens", 0),
            eng.num_decode_tokens,
            getattr(eng, "num_admitted", 0),
            self.quarantine_evictions,
            getattr(eng, "num_spec_drafted_tokens", 0),
            getattr(eng, "num_spec_accepted_tokens", 0),
            hp.spilled_pages if hp is not None else 0,
            hp.restored_pages if hp is not None else 0,
            getattr(eng, "num_preemptions", 0),
            getattr(eng, "num_resumes", 0),
            getattr(eng, "num_ctx_stream_chunks", 0),
            getattr(eng, "num_joint_pass_steps", 0),
            getattr(eng, "num_joint_pass_inert_rows", 0),
            getattr(eng, "num_wave_decode_tokens", 0),
            dict(getattr(eng, "mixer_counts", {})),
        )

    def _resume_failures_pending(self) -> bool:
        return bool(getattr(self.engine, "_resume_failures", None))

    def _flight_record(
        self, duration: float, pre: tuple, generated: int,
        failed: Optional[str] = None, timing: Optional[dict] = None,
        stall: Optional[dict] = None,
    ) -> None:
        eng = self.engine
        (p0, pad0, d0, a0, q0, sd0, sa0, sp0, rs0, pe0, re0,
         cs0, jp0, ji0, wr0, mixer0) = pre
        hp = getattr(eng, "host_pool", None)
        prefill = eng.num_prefill_tokens - p0
        decode = eng.num_decode_tokens - d0
        if failed is not None:
            kind = "failed"
        elif prefill and decode:
            kind = "mixed"
        elif prefill:
            kind = "prefill"
        elif decode:
            kind = "decode"
        else:
            kind = "idle"
        rec = {
            "step": self.steps,
            "ts": time.time(),
            # the same instant on the clock request spans and the
            # profiler's helix.clock stamps are on
            "t_mono": time.monotonic(),
            "duration": duration,
            "kind": kind,
            "slots_busy": sum(1 for s in eng.slots if s is not None),
            "slots_total": len(eng.slots),
            "queue_depth": self.queue_depth(),
            "kv_pages_used": getattr(eng, "kv_pages_used", 0),
            "kv_pages_free": eng.allocator.free_pages,
            # of the last MoE step the host has read (0 for dense models):
            # the busiest expert's tokens over the mean, and the distinct
            # experts a step touched (mean over the MoE layers), the dropless
            # grouped product's rows routed over rows walked
            "moe_expert_load_max_ratio": getattr(
                eng, "moe_expert_load_max_ratio", 0.0),
            "moe_experts_touched": getattr(eng, "moe_experts_touched", 0.0),
            "moe_tile_fill_ratio": getattr(eng, "moe_tile_fill_ratio", 0.0),
            # tokens in a query block of the state segment's attention
            # call: 1 for plain decode, 8 under speculation
            "attn_q_block": getattr(eng, "attn_q_block", 0),
            # ... and of the prefill segment's paged call in the last launch
            # that had one (the page kind's ``query_block``; 0 before any)
            "chunk_q_block": getattr(eng, "chunk_q_block", 0),
            # this step's programs in which prefill rows and state rows
            # shared one pass over the layers (1 for a wave, a chunk or a
            # mixed step; a step of several waves counts each), and the
            # state rows that rode those passes sitting out; the state
            # rows that decoded a token inside the step's admission waves
            "joint_pass": getattr(eng, "num_joint_pass_steps", 0) - jp0,
            "inert_rows": getattr(eng, "num_joint_pass_inert_rows", 0) - ji0,
            "wave_rows": getattr(eng, "num_wave_decode_tokens", 0) - wr0,
            # what each kind's record, of state and of page, shows of this
            # step's programs (``models/mixers.py``: a count since the step
            # began, a level as it stands; 0 for a kind the model has not):
            # layers whose state is fixed arrays a slot and what moved in the
            # state pool, the K/V bytes of the pages the dense paged kernel
            # walked, what a sparse-attention indexer scored, chose and
            # fetched
            **flight_fields(
                getattr(eng, "kinds", ()),
                {**getattr(eng, "mixer_values", dict)(),
                 **getattr(eng, "mixer_gauges", dict)()}, mixer0),
            # experts of the routed set whose weights are on this chip (0:
            # dense, or every expert is here)
            "held_experts": (
                getattr(eng.model_cfg, "num_held_experts", 0)
                if getattr(eng.model_cfg, "held_experts", None) else 0),
            "attn_layers": getattr(
                eng.model_cfg, "num_attn_layers",
                getattr(eng.model_cfg, "num_layers", 0)),
            # the live tokens the rows of the step's last launch attended
            # over (from the host's mirrors)
            "context_tokens": getattr(eng, "step_context_tokens", 0),
            "prefill_tokens": prefill,
            "padding_tokens": (
                getattr(eng, "num_prefill_padding_tokens", 0) - pad0
            ),
            # distinct compiled device-step entry points live for this
            # model at step time: flat after warmup = the shape ladder
            # is doing its job; climbing under traffic = a caller is
            # minting new trace shapes (the pre-unification zoo smell)
            "compiled_shapes": getattr(eng, "compiled_step_shapes", 0),
            "decode_tokens": decode,
            "generated_tokens": generated,
            "admissions": getattr(eng, "num_admitted", 0) - a0,
            "evictions": self.quarantine_evictions - q0,
            # speculative decoding gains: drafts proposed/accepted this
            # step (0/0 on non-speculative steps)
            "spec_drafted": (
                getattr(eng, "num_spec_drafted_tokens", 0) - sd0
            ),
            "spec_accepted": (
                getattr(eng, "num_spec_accepted_tokens", 0) - sa0
            ),
            # KV tiering this step: pages demoted/promoted across the
            # host tier, decoders swapped out/in, host-pool fullness
            "spilled_pages": (
                (hp.spilled_pages - sp0) if hp is not None else 0
            ),
            "restored_pages": (
                (hp.restored_pages - rs0) if hp is not None else 0
            ),
            "preemptions": getattr(eng, "num_preemptions", 0) - pe0,
            "resumes": getattr(eng, "num_resumes", 0) - re0,
            "host_pool_pages": hp.pages if hp is not None else 0,
            # tiered KV residency (ISSUE 20): cold chunks streamed
            # through attention this step, and the cold-page gauge
            "ctx_stream_chunks": (
                getattr(eng, "num_ctx_stream_chunks", 0) - cs0
            ),
            "kv_cold_pages": int(getattr(eng, "kv_cold_pages", 0)),
            # the scheduler's prefill-admission budget in force this
            # step (0 = unbudgeted)
            "prefill_budget_tokens": int(
                getattr(eng, "prefill_budget", None) or 0
            ),
            # distinct tenants sharing this step's decode batch: the
            # noisy-neighbour axis (1 = single-tenant step, >1 = a slow
            # step taxed every tenant listed)
            "distinct_tenants": len({
                getattr(s, "tenant", ANON_TENANT)
                for s in eng.slots if s is not None
            }),
            # distinct multi-LoRA adapters sharing this step's batch
            # (ISSUE 15): >1 = a genuinely mixed-adapter device call —
            # the batched gather-matmul packing the wave that merged
            # per-tenant model copies never could
            "distinct_adapters": len({
                getattr(s, "adapter", "")
                for s in eng.slots
                if s is not None and getattr(s, "adapter", "")
            }),
        }
        if timing:
            # per-step time split (ISSUE 13): host build / device wait /
            # emit, plus the device-idle gap this step charged — the
            # numerators of helix_device_idle_ratio
            rec.update(timing)
        if failed is not None:
            rec["anomaly"] = "step_failure"
            rec["error"] = failed[:200]
        elif stall is not None:
            rec["anomaly"] = "slow_step"
        # a stall's record is closed (one log line, one count) and filed
        # with the anomaly the recorder freezes for it
        self.flight.record_step(
            rec, **(self._close_stall(stall, rec) if stall else {}))
        # bank a goodput sample while the engine works (throttled inside
        # the tracker): keeps the rate anchor within ~one window of now,
        # so sparse external scrapes can't understate a recent burst
        self._tps.rate(getattr(eng, "num_generated_tokens", 0))

    def _complete(self, pend):
        """One step's reconcile: the fetch + every host-visible effect,
        stamping the device-busy watermark the exposed-host accounting
        reads."""
        emitted = self.engine.step_complete(pend)
        self._device_busy_until = time.monotonic()
        return emitted

    def _reconcile_or_fail(self) -> bool:
        """Reconcile point outside the main step path (aborts, imports,
        drain, idle, preemption): complete the in-flight step and drain
        the emission stage.  False = the completion failed and the
        failure ladder ran — restart the loop pass."""
        was = self._mark.at
        try:
            self._at("helix.loop.park")
            return self._park()
        finally:
            self._mark.at = was

    def _park(self) -> bool:
        if self._inflight is None:
            self._emit_stage.flush()
            return True
        pend, self._inflight = self._inflight, None
        pre = self._flight_pre()
        ph = self._new_phases()
        t0 = time.monotonic()
        try:
            emitted = self._complete(pend)
        except Exception as e:  # noqa: BLE001 — fail requests, not the loop
            self.engine.discard_pending(pend)
            self._handle_step_failure(e, time.monotonic() - t0, pre)
            return False
        dt_wait = time.monotonic() - t0
        dt_emit = self._push_emit(emitted, ph)
        wall = time.monotonic() - t0
        # the step's own pass recorded its dispatch only: its tokens and
        # its device wait land here, or the burst's last step vanishes
        # from the flight window
        self._flight_record(
            dt_wait, pre, generated=len(emitted),
            timing={
                "host_build_s": 0.0,
                "device_wait_s": round(dt_wait, 6),
                "emit_s": round(dt_emit, 6),
                "idle_gap_s": 0.0,
                "wall_s": round(wall, 6),
                "pipelined": 1,
                "phases": _rounded(ph),
                "phases_cpu": _rounded(ph.cpu),
            },
            stall=self._stall_of(dt_wait, wall, sum(ph.cpu.values())),
        )
        self._emit_stage.flush()
        return True

    def _run(self):
        self._engine_clock = time.pthread_getcpuclockid(
            threading.get_ident())
        obs_trace.mark_thread(self._mark)
        while not self._stop.is_set() and self._pass():
            if self._watched.during:
                self._close_between()
        self._at(None)
        # a step still in flight at shutdown: reconcile so its tokens
        # reach subscribers before the terminal sweep
        if self._inflight is not None:
            pend, self._inflight = self._inflight, None
            try:
                self._emit_stage.push(
                    self._snapshot_events(self._complete(pend))
                )
            except Exception:  # noqa: BLE001 — best-effort at shutdown
                self.engine.discard_pending(pend)
        self._unhook_gc()
        self._emit_stage.stop()
        self._unwatch()
        log.info(
            "engine '%s' emission stage stopped: %d batch(es) delivered "
            "off the engine thread, %d push(es) found the queue full",
            self.name, self._emit_stage.batches,
            self.obs.emit_backpressure.value,
        )
        # terminal sweep: anything still in the inbox (raced a shutdown)
        # gets a clean error event instead of a 300s client hang
        while True:
            try:
                item, on_event = self._inbox.get_nowait()
            except queue.Empty:
                break
            if on_event is not None:
                on_event(
                    TokenEvent(
                        request_id=item.id, token_id=-1, finished=True,
                        finish_reason="error",
                        error=f"{SHUTTING_DOWN}: engine '{self.name}' "
                              "stopped",
                    )
                )

    def _pass(self) -> bool:
        """One pass of the engine thread: apply the inbox, walk the
        ladders that need a reconciled engine (drain, hand-off,
        checkpoint, idle), then one engine step.  False: the drain is
        over and the thread leaves."""
        mark = self._mark
        mark.pass_no += 1
        mark.step = self.steps
        self._at("helix.loop.inbox")
        self._drain_inbox()
        self._at("helix.loop.pass")
        if self._draining:
            if not self._reconcile_or_fail():
                return True
            if not self.engine.has_work():
                return False
            if time.monotonic() > self._drain_deadline:
                # migrate instead of shed (ISSUE 11): with an
                # exporter wired, the drain ladder is
                # finish -> snapshot+ship -> shed — _fail_all only
                # sees what could not be exported
                shipped = self._export_survivors()
                if shipped:
                    log.info(
                        "engine '%s' exported %d request(s) at the "
                        "drain deadline", self.name, shipped,
                    )
                self._fail_all("drain deadline exceeded at shutdown")
                return False
        if time.monotonic() - self._last_reap > 10.0:
            self._last_reap = time.monotonic()
            reaped = self.engine.reap_stuck(self.max_queue_seconds)
            if reaped:
                self._emit_stage.flush()
            for req in reaped:
                cb = self._subscribers.pop(req.id, None)
                if cb:
                    cb(
                        TokenEvent(
                            request_id=req.id, token_id=-1,
                            finished=True, finish_reason="error",
                            error="request timed out in queue",
                        )
                    )
        self._memory_pressure_tick()
        if self._handoff_work():
            # disaggregated prefill export (ISSUE 14): the export
            # gathers pages + syncs device sampler state, so the
            # step in flight (if any) reconciles first
            if not self._reconcile_or_fail():
                return True
            self._disagg_tick()
        ctick = getattr(self.engine, "checkpoint_tick", None)
        if ctick is not None and self.engine.checkpoint_due():
            # leader-state checkpoint (ISSUE 17): capture is a pure
            # host-side read of queue/digest bookkeeping (the blob
            # write happens off-thread), but the snapshot must not
            # straddle a step in flight
            if not self._reconcile_or_fail():
                return True
            ctick(sched=self.sched)
        if not self.engine.has_work():
            if not self._reconcile_or_fail():
                return True
            if self.engine.has_work():
                return True  # the reconcile freed/advanced work
            with obs_trace.phase("helix.loop.idle"):
                self._wake.wait(timeout=0.05)
            self._wake.clear()
            return True
        sched_ph = obs_trace.Phases()
        if self._sched_active:
            # scheduler pass (engine thread — the wait queue's
            # owner): rewrite the queue into dispatch order (strict
            # classes + per-tenant DRR) and refresh the per-step
            # prefill-admission budget from the live TTFT burn.
            # With a step in flight this only touches the wait
            # queue and burn-rate reads (the sched.reorder contract).
            with obs_trace.phase("helix.sched.reorder", into=sched_ph):
                self.sched.reorder(self.engine.waiting)
                self.engine.prefill_budget = (
                    self.sched.prefill_budget(self.slo)
                )
        # may this pass's dispatch run on predicted state, behind a
        # step still in flight?  The engine names what reconciles
        # first (speculation, parked preemptions, tiered rows; for a
        # plan leader anything but steady decode); draining does too
        lookahead = (
            self.async_enabled
            and not self._draining
            and self.engine.pipeline_ready()
        )
        if self._inflight is not None and not lookahead:
            if not self._reconcile_or_fail():
                return True
        ph = self._new_phases()
        ph.merge(sched_ph)
        if self._watched.during:
            self._close_between()   # a stall before the step, not of it
        with obs_trace.phase("helix.loop.step", step_num=self.steps):
            self._step_pass(ph, lookahead)
        return True

    def _step_pass(self, ph: obs_trace.Phases, lookahead: bool) -> None:
        """One engine step: admit and launch step N+1 (behind step N if
        one is in flight), then fetch and reconcile step N, then either
        leave N+1 in flight or complete it too.

        N+1 stays in flight when ``lookahead`` holds and nothing more
        could be admitted before it ends (``Engine.admission_blocked``:
        requests still queue after this pass's admission, or no slot is
        free): the next pass's host work then runs under its device time.
        With the queue empty and a slot free it is completed at once — a
        window queued ahead would stand between a new arrival and its
        prefill — which is the synchronous order: the same code with
        nothing in flight."""
        eng = self.engine
        t_step = time.monotonic()
        c_step = obs_trace.thread_cpu()
        flight_pre = self._flight_pre()
        prev = self._inflight
        overlapped = prev is not None
        try:
            emitted, pend = self._dispatch_once()
        except Exception as e:  # noqa: BLE001 — fail requests, not the loop
            self._inflight = None
            # the in-flight step is healthy already-dispatched work:
            # reconcile it first so its tokens are not lost — and
            # flight-record it (its own pass recorded the dispatch only)
            if prev is not None:
                pre_prev = self._flight_pre()
                t0_prev = time.monotonic()
                try:
                    prev_emitted = self._complete(prev)
                except Exception:  # noqa: BLE001 — poisoned chain
                    eng.discard_pending(prev)
                else:
                    self._emit_stage.push(
                        self._snapshot_events(prev_emitted)
                    )
                    dt_prev = time.monotonic() - t0_prev
                    self._flight_record(
                        dt_prev, pre_prev,
                        generated=len(prev_emitted),
                        timing={
                            "host_build_s": 0.0,
                            "device_wait_s": round(dt_prev, 6),
                            "emit_s": 0.0,
                            "idle_gap_s": 0.0,
                            "wall_s": round(dt_prev, 6),
                            "pipelined": 1,
                        },
                        stall=self._stall_of(dt_prev, dt_prev, None),
                    )
            self._handle_step_failure(
                e, time.monotonic() - t_step, flight_pre
            )
            return
        self._inflight = None
        build_cpu = obs_trace.thread_cpu() - c_step
        t_build_end = time.monotonic()
        dt_build = t_build_end - t_step
        idle_gap = 0.0
        if not overlapped and self._device_busy_until:
            # nothing was queued on the device while this step was
            # built: it sat idle from the last completion's return until
            # this step's first launch (an admission wave's, or the step
            # program's own)
            launched = getattr(eng, "first_launch_time", None)
            idle_gap = max(
                0.0, (launched or t_build_end) - self._device_busy_until
            )
        dt_wait = 0.0
        try:
            if prev is not None:
                # step N+1 is now queued on the device: fetch step N's
                # results — the block covers only the device time the
                # host build did not already overlap
                t_w = time.monotonic()
                prev_emitted = self._complete(prev)
                prev = None
                dt_wait += time.monotonic() - t_w
                emitted = prev_emitted + emitted
            if (
                pend is not None
                and lookahead
                and pend.kind in ("decode", "mixed")
                and eng.admission_blocked()
            ):
                self._inflight, pend = pend, None
                self.pipelined_steps += 1
            elif pend is not None:
                t_w = time.monotonic()
                if hasattr(eng, "prefetch_cold"):
                    # stage the NEXT step's cold-middle KV chunks while
                    # the dispatched step still runs on the device — the
                    # gathers queue behind the step on the device
                    # stream, so this is free overlap
                    eng.prefetch_cold()
                eng.step_complete(pend, emitted)
                pend = None
                dt_wait += time.monotonic() - t_w
                self._device_busy_until = time.monotonic()
        except Exception as e:  # noqa: BLE001 — fail requests, not the loop
            for p in (prev, pend):
                if p is not None:
                    eng.discard_pending(p)
            self._inflight = None
            self._handle_step_failure(
                e, time.monotonic() - t_step, flight_pre
            )
            return
        dt_step = time.monotonic() - t_step
        self.obs.host_build.observe(dt_build)
        self._consec_failures = 0
        self._barren_rounds = 0
        self.steps += 1
        dt_emit = self._push_emit(emitted, ph)
        if self._inflight is not None and not emitted:
            # fill pass: dispatched with nothing reconciled yet — no
            # flight record (a dispatch-only pass would read as
            # zero_progress to the watchdog); the step's numbers land
            # with its completion next pass
            stall = self._stall_of(
                dt_step, dt_step, time.thread_time() - c_step)
            self._observe_step(dt_step, ph, idle_gap, build_cpu, stall)
            if stall is not None:
                self.flight.note_anomaly(
                    "stall", self._close_stall(stall), step=self.steps)
            return
        self._deliver_resume_failures()
        wall = time.monotonic() - t_step
        stall = self._stall_of(dt_step, wall, time.thread_time() - c_step)
        account = self._observe_step(wall, ph, idle_gap, build_cpu, stall)
        self._flight_record(
            dt_step, flight_pre, generated=len(emitted),
            timing={
                "host_build_s": round(dt_build, 6),
                "device_wait_s": round(dt_wait, 6),
                "emit_s": round(dt_emit, 6),
                "idle_gap_s": round(idle_gap, 6),
                "wall_s": round(wall, 6),
                "pipelined": 1 if overlapped else 0,
                "phases": _rounded(ph),
                **account,
            },
            stall=stall,
        )

    # -- poisoned-request quarantine ----------------------------------------

    def _active_by_recency(self) -> list:
        """Unfinished submitted requests, oldest admission first."""
        out = []
        for rid in self._admit_order:
            req = self.engine.get_request(rid)
            if req is not None and not req.finished:
                out.append(req)
        # prune finished ids so the order list doesn't grow unboundedly
        self._admit_order = [r.id for r in out]
        return out

    def _evict_victim(self, cands: list, msg: str) -> None:
        """Shed ONE of ``cands`` (oldest-admission-first): the scheduler
        picks the victim — the policy ladder (lowest class, then
        most-over-fair-share tenant, then newest) under WFQ, the
        historical newest-first under the FIFO baseline — and the
        decision is recorded in the admission audit ring."""
        victim = self.sched.pick_shed_victim(cands)
        if victim is None:
            return
        if self._sched_active:
            self.sched.note_shed_victim(victim)
            self._audit(
                SHED_VICTIM,
                tenant=getattr(victim, "tenant", ANON_TENANT),
                trace_id=victim.trace_id or "",
                request_id=victim.id,
                detail=f"policy victim among {len(cands)} candidate(s)",
            )
        self._evict(victim, msg)

    def _evict(self, req, msg: str) -> None:
        self._emit_stage.flush()   # no error frame may overtake tokens
        self.engine.abort(req.id)
        self.quarantine_evictions += 1
        self.flight.note_anomaly(
            "quarantine", request_id=req.id, detail=msg[:200]
        )
        self._audit(
            "quarantine", tenant=getattr(req, "tenant", ANON_TENANT),
            trace_id=req.trace_id or "", request_id=req.id, detail=msg,
        )
        log.warning(
            "engine '%s' evicting request_id=%s trace_id=%s: %s",
            self.name, req.id, req.trace_id or "-", msg,
            extra={"trace_id": req.trace_id or "", "request_id": req.id},
        )
        if req.trace_id:
            now = time.monotonic()
            self._trace.record(
                req.trace_id, "quarantine", self._first_emit.get(req.id, now),
                now, plane="engine", request_id=req.id, reason=msg,
            )
        self._forget_request(req.id)
        cb = self._subscribers.pop(req.id, None)
        if cb:
            cb(
                TokenEvent(
                    request_id=req.id, token_id=-1, finished=True,
                    finish_reason="error", error=msg,
                )
            )

    @staticmethod
    def _clone_for_readmit(req) -> Request:
        """A fresh Request (same id — subscribers stay valid) for a
        quarantined request that never emitted a token, so it can be
        re-prefilled from scratch during bisection."""
        return Request(
            id=req.id,
            prompt_tokens=list(req.prompt_tokens),
            sampling=req.sampling,
            stop_token_ids=req.stop_token_ids,
            image_embeds=req.image_embeds,
            image_positions=req.image_positions,
            positions3=req.positions3,
            mrope_delta=req.mrope_delta,
            trace_id=req.trace_id,
            tenant=getattr(req, "tenant", ANON_TENANT),
            sched_class=getattr(req, "sched_class", ""),
            adapter=getattr(req, "adapter", ""),
        )

    def _trial(self, group: list) -> bool:
        """Re-admit ``group`` (clones) and step until each member emits or
        finishes.  True = group is clean (members left running); False =
        a step failed, members re-aborted (subscribers kept)."""
        clones = []
        for req in group:
            clone = self._clone_for_readmit(req)
            try:
                self.engine.add_request(clone)
            except Exception as e:  # noqa: BLE001 — validation changed?
                self._evict(clone, f"engine rejected request: {e}")
                continue
            clones.append(clone)
        if not clones:
            return True
        # budget: admission + every prefill chunk + slack; prevents an
        # unbounded spin if a clone can never reach its first token
        chunk = max(1, self.engine.cfg.max_prefill_len)
        budget = 8 + sum(
            len(c.prompt_tokens) // chunk + 1 for c in clones
        )
        for _ in range(budget):
            try:
                emitted = self._step_once()
            except Exception:  # noqa: BLE001 — the culprit is in this group
                for c in clones:
                    self.engine.abort(c.id)
                return False
            self.steps += 1
            self._emit(emitted)
            if all(c.finished or c.output_tokens for c in clones):
                return True
        return True   # budget exhausted without a failure: call it clean

    def _quarantine(self, err: Exception) -> None:
        """The step failed twice on the same state: blame the most
        recently admitted request(s) instead of aborting the world.

        Requests that have not emitted a token yet (just-admitted — the
        usual poison: a prompt whose prefill trips the fault) can be
        safely re-prefilled, so they are pulled out and bisected back in;
        only the subset whose re-admission still fails the step is
        evicted.  A control step with the suspects removed guards the
        other direction: if the fault persists without them, it lives in
        an already-emitting request — the suspects are re-admitted
        untouched and requests are shed newest-first instead (bounded
        collateral, never abort-all)."""
        self._emit_stage.flush()   # bisection emits directly from here
        active = self._active_by_recency()
        suspects = [r for r in active if not r.output_tokens]
        emitting = [r for r in active if r.output_tokens]
        if suspects:
            for r in suspects:
                self.engine.abort(r.id)   # keep subscribers: clones re-emit
            if emitting and self.engine.has_work():
                # control step: suspects quarantined, only the emitting
                # set runs.  A failure here exonerates the suspects.
                try:
                    emitted = self._step_once()
                    self.steps += 1
                    self._emit(emitted)
                except Exception:  # noqa: BLE001 — fault is in the batch
                    for r in suspects:
                        try:
                            self.engine.add_request(
                                self._clone_for_readmit(r)
                            )
                        except Exception as e:  # noqa: BLE001
                            self._evict(r, f"engine rejected request: {e}")
                    self._evict_victim(
                        emitting,
                        f"evicted after repeated engine step failures "
                        f"({err})",
                    )
                    return
            culprits: list = []
            stack = [suspects]
            while stack:
                group = stack.pop()
                if self._trial(group):
                    continue
                if len(group) == 1:
                    culprits.append(group[0])
                    continue
                mid = len(group) // 2
                stack.append(group[:mid])    # older half
                stack.append(group[mid:])    # newer half tested first
            for r in culprits:
                self.quarantine_evictions += 1
                msg = (
                    f"request quarantined: engine step failed while "
                    f"scheduled ({err})"
                )
                self.flight.note_anomaly(
                    "quarantine", request_id=r.id, detail=msg[:200]
                )
                self._audit(
                    "quarantine",
                    tenant=getattr(r, "tenant", ANON_TENANT),
                    trace_id=r.trace_id or "", request_id=r.id,
                    detail=msg,
                )
                log.warning(
                    "engine '%s' quarantined request_id=%s trace_id=%s: %s",
                    self.name, r.id, r.trace_id or "-", msg,
                    extra={"trace_id": r.trace_id or "", "request_id": r.id},
                )
                if r.trace_id:
                    now = time.monotonic()
                    self._trace.record(
                        r.trace_id, "quarantine", now, now,
                        plane="engine", request_id=r.id, reason=msg,
                    )
                self._forget_request(r.id)
                cb = self._subscribers.pop(r.id, None)
                if cb:
                    cb(
                        TokenEvent(
                            request_id=r.id, token_id=-1, finished=True,
                            finish_reason="error", error=msg,
                        )
                    )
            if culprits:
                self._barren_rounds = 0
                return
            # all suspects came back clean: either the fault was
            # transient (give the loop one more chance) or it lives in an
            # already-emitting request (shed newest-first next round)
            self._barren_rounds += 1
            if self._barren_rounds < 2:
                return
        # no fresh suspect to blame — shed the policy's pick (baseline:
        # the most recently admitted active request) and let the loop
        # retry with the remainder
        if active:
            self._evict_victim(
                active,
                f"evicted after repeated engine step failures ({err})",
            )

    def _fail_all(self, msg: str) -> None:
        self._emit_stage.flush()   # no error frame may overtake tokens
        for req in self._active_by_recency():
            self.engine.abort(req.id)
            self._forget_request(req.id)
            cb = self._subscribers.pop(req.id, None)
            if cb:
                cb(
                    TokenEvent(
                        request_id=req.id, token_id=-1, finished=True,
                        finish_reason="error", error=msg,
                    )
                )
