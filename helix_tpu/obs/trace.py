"""Lightweight in-process request tracing for the serving spine.

A trace ID is minted at the OpenAI endpoint (control plane or runner —
whichever sees the request first), propagated via the
``X-Helix-Trace-Id`` header through dispatch (every failover attempt is
its own span), the reverse tunnel, the runner's HTTP surface, and down
into the engine loop.  Spans land in a bounded ring-buffer
:class:`TraceStore`; ``/v1/debug/traces/{id}`` serves them as JSON or
Chrome ``trace_event`` format (load in ``chrome://tracing`` / Perfetto).

This is deliberately NOT OpenTelemetry: no exporters, no context
objects, no dependency — just monotonic timestamps and one dict per
span, cheap enough to leave on in production.  Recording is a no-op for
requests without a trace ID, so the engine hot path pays one truthiness
check when tracing is unused.
"""

from __future__ import annotations

import collections
import math
import os
import re
import threading
import time
import uuid
from typing import Optional

TRACE_HEADER = "X-Helix-Trace-Id"

# what an adoptable trace id looks like (uuid hex + room for external
# id schemes); anything else from a client header is replaced, never
# stored or echoed verbatim
_TRACE_ID_RE = re.compile(r"[A-Za-z0-9_-]{8,64}")

# monotonic -> wall anchor, fixed at import: spans are recorded on the
# monotonic clock (immune to NTP steps) and converted for display
_MONO0 = time.monotonic()
_WALL0 = time.time()

# stable Chrome-trace pids per plane so cross-plane spans of one request
# line up as separate process tracks
_PLANE_PIDS = {"control": 1, "runner": 2, "engine": 3}

# -- federation knobs (ISSUE 18) --------------------------------------
#
# Export cadence rides the heartbeat — there is no separate push timer,
# so "interval" is the node agent's heartbeat interval.  These knobs
# bound how much trace data each hop may carry or hold.


def federation_enabled() -> bool:
    """``HELIX_TRACE_FEDERATION`` — runners push completed spans to the
    control plane inside the heartbeat payload (default on)."""
    return os.environ.get("HELIX_TRACE_FEDERATION", "1").lower() not in (
        "0", "false", "off", ""
    )


def _int_env(name: str, default: int, lo: int, hi: int) -> int:
    try:
        return max(lo, min(int(os.environ.get(name, default)), hi))
    except (TypeError, ValueError):
        return default


def export_batch() -> int:
    """``HELIX_TRACE_EXPORT_BATCH`` — max spans per heartbeat push (and
    the control plane's per-batch ingest clamp)."""
    return _int_env("HELIX_TRACE_EXPORT_BATCH", 256, 1, 4096)


def export_buffer() -> int:
    """``HELIX_TRACE_BUFFER`` — runner-side pending-export ring size;
    overflow drops the oldest unsent span and counts it."""
    return _int_env("HELIX_TRACE_BUFFER", 2048, 16, 65536)


def cp_retention() -> int:
    """``HELIX_TRACE_CP_TRACES`` — how many federated traces the
    control plane retains (LRU beyond that)."""
    return _int_env("HELIX_TRACE_CP_TRACES", 2048, 16, 65536)


def mono_to_wall(mono: float) -> float:
    return _WALL0 + (mono - _MONO0)


def new_trace_id() -> str:
    return uuid.uuid4().hex


def is_trace_id(value) -> bool:
    """Whether ``value`` is shaped like an adoptable trace id (the
    header/regex contract) — for callers that FORWARD an id and must
    not fabricate one when it is missing or garbage."""
    return isinstance(value, str) and bool(_TRACE_ID_RE.fullmatch(value))


def adopt_trace_id(value: Optional[str]) -> str:
    """Adopt a caller-supplied trace id if it is shaped like one, else
    mint fresh — multi-KB garbage header values must not become store
    keys or ride back in response headers."""
    if value and _TRACE_ID_RE.fullmatch(value):
        return value
    return new_trace_id()


class Span:
    __slots__ = ("trace_id", "name", "plane", "start", "end", "attrs")

    def __init__(self, trace_id: str, name: str, plane: str,
                 start: float, end: float, attrs: Optional[dict] = None):
        self.trace_id = trace_id
        self.name = name
        self.plane = plane
        self.start = start
        self.end = end
        self.attrs = attrs or {}

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "plane": self.plane,
            "start_unix": mono_to_wall(self.start),
            "duration_ms": (self.end - self.start) * 1000.0,
            "attrs": self.attrs,
        }


class TraceStore:
    """Bounded in-memory trace storage: an LRU ring of traces, each a
    capped span list.  Thread-safe — spans arrive from the event loop,
    the engine thread and executor threads concurrently."""

    def __init__(self, max_traces: int = 512,
                 max_spans_per_trace: int = 256):
        self.max_traces = max_traces
        self.max_spans_per_trace = max_spans_per_trace
        # trace_id -> [spans list, dropped count]
        self._traces: "collections.OrderedDict[str, list]" = (
            collections.OrderedDict()
        )
        self._lock = threading.Lock()
        self.dropped_spans = 0   # spans lost to the per-trace cap (global)
        # pending-export ring (federation): None until enable_export();
        # a bounded deque so a dead heartbeat loop cannot grow memory
        self._export: Optional[collections.deque] = None
        self.export_dropped = 0  # spans lost to export-ring overflow

    def enable_export(self, cap: Optional[int] = None) -> None:
        """Start buffering completed spans for federation push.  Spans
        recorded before this call are not exported retroactively."""
        with self._lock:
            if self._export is None:
                self._export = collections.deque(
                    maxlen=cap or export_buffer()
                )

    def drain_export(self, limit: Optional[int] = None) -> list:
        """Pop up to ``limit`` pending wire spans (oldest first) for the
        next heartbeat push.  Returns ``[]`` when export is off."""
        n = limit if limit is not None else export_batch()
        out: list = []
        with self._lock:
            if self._export is None:
                return out
            while self._export and len(out) < n:
                out.append(self._export.popleft())
        return out

    def record(self, trace_id: str, name: str, start: float, end: float,
               plane: str = "", **attrs) -> None:
        """Record one completed span.  No-op without a trace id, so
        callers can pass ``req.trace_id`` unconditionally."""
        if not trace_id:
            return
        span = Span(trace_id, name, plane, start, end, attrs)
        with self._lock:
            entry = self._traces.get(trace_id)
            if entry is None:
                entry = self._traces[trace_id] = [[], 0]
                while len(self._traces) > self.max_traces:
                    self._traces.popitem(last=False)
            else:
                self._traces.move_to_end(trace_id)
            if len(entry[0]) >= self.max_spans_per_trace:
                # ring: drop the OLDEST span so a flooded trace keeps
                # its most recent activity (the part being debugged)
                entry[0].pop(0)
                self.dropped_spans += 1
                entry[1] += 1
            entry[0].append(span)
            if self._export is not None:
                if len(self._export) == self._export.maxlen:
                    self.export_dropped += 1
                self._export.append(span_to_wire(span))

    def ids(self) -> list:
        with self._lock:
            return list(self._traces)

    def __len__(self) -> int:
        with self._lock:
            return len(self._traces)

    def get(self, trace_id: str) -> Optional[dict]:
        with self._lock:
            entry = self._traces.get(trace_id)
            if entry is None:
                return None
            spans, dropped = list(entry[0]), entry[1]
        spans.sort(key=lambda s: s.start)
        doc = {
            "trace_id": trace_id,
            "spans": [s.to_dict() for s in spans],
        }
        if dropped:
            # truncation must be visible in the payload, not silent — a
            # flooded trace otherwise reads as "no decode/emit happened"
            doc["dropped_spans"] = dropped
        return doc

    def chrome_trace(self, trace_id: str) -> Optional[dict]:
        """Chrome ``trace_event`` JSON (complete 'X' events, one pid per
        plane) — load the payload in chrome://tracing or Perfetto."""
        with self._lock:
            entry = self._traces.get(trace_id)
            if entry is None:
                return None
            spans = list(entry[0])
        spans.sort(key=lambda s: s.start)
        events = []
        seen_planes = set()
        for s in spans:
            pid = _PLANE_PIDS.get(s.plane, 9)
            if s.plane not in seen_planes:
                seen_planes.add(s.plane)
                events.append({
                    "name": "process_name", "ph": "M", "pid": pid,
                    "args": {"name": f"helix:{s.plane or 'other'}"},
                })
            events.append({
                "name": s.name,
                "cat": s.plane or "other",
                "ph": "X",
                "pid": pid,
                "tid": 1,
                "ts": mono_to_wall(s.start) * 1e6,
                "dur": max((s.end - s.start) * 1e6, 1.0),
                "args": {k: str(v) for k, v in s.attrs.items()},
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}


# -- federation wire format + validation (ISSUE 18) -------------------
#
# Runners push completed spans inside the heartbeat payload as a
# ``"traces": {"spans": [...]}`` block.  Wire spans carry WALL-clock
# endpoints (monotonic clocks are per-host and meaningless across the
# fleet); the control plane re-anchors them with per-host skew
# correction at stitch time.

# clamps mirror the PR 7 tenant-rollup discipline: every field is
# bounded so a hostile runner cannot grow control-plane memory or leak
# arbitrary strings into debug payloads
_WIRE_MAX_NAME = 120
_WIRE_MAX_PLANE = 24
_WIRE_MAX_ATTRS = 8
_WIRE_MAX_ATTR_KEY = 64
_WIRE_MAX_ATTR_VAL = 256
_NAME_OK_RE = re.compile(r"[A-Za-z0-9_.:/ \-]{1,120}")


def span_to_wire(span: Span) -> dict:
    """One completed span in federation wire shape (wall-clock)."""
    start = mono_to_wall(span.start)
    return {
        "trace_id": span.trace_id,
        "name": span.name,
        "plane": span.plane,
        "start_unix": start,
        "end_unix": start + max(0.0, span.end - span.start),
        "attrs": {k: str(v) for k, v in span.attrs.items()},
    }


def _clean_span(doc) -> Optional[dict]:
    """One wire span, clamped to schema — None if unsalvageable."""
    if not isinstance(doc, dict):
        return None
    tid = doc.get("trace_id")
    if not (isinstance(tid, str) and _TRACE_ID_RE.fullmatch(tid)):
        return None
    name = doc.get("name")
    if not (isinstance(name, str) and _NAME_OK_RE.fullmatch(name)):
        return None
    try:
        start = float(doc.get("start_unix"))
        end = float(doc.get("end_unix"))
    except (TypeError, ValueError):
        return None
    if not (math.isfinite(start) and math.isfinite(end)):
        return None
    plane = doc.get("plane")
    if not isinstance(plane, str):
        plane = ""
    plane = plane[:_WIRE_MAX_PLANE]
    attrs = {}
    raw_attrs = doc.get("attrs")
    if isinstance(raw_attrs, dict):
        for k, v in list(raw_attrs.items())[:_WIRE_MAX_ATTRS]:
            attrs[str(k)[:_WIRE_MAX_ATTR_KEY]] = (
                str(v)[:_WIRE_MAX_ATTR_VAL]
            )
    return {
        "trace_id": tid,
        "name": name,
        "plane": plane,
        "start_unix": start,
        "end_unix": max(start, end),
        "attrs": attrs,
    }


def validate_span_batch(raw, max_spans: Optional[int] = None):
    """Clamp one runner-supplied span batch to the wire schema.

    Returns ``(spans, rejected)`` — the clean spans plus how many were
    thrown away (malformed spans AND overflow past the batch clamp).
    Like the PR 7 tenant blocks this NEVER raises: a malformed batch
    degrades to ``([], n)`` so span garbage can't reject a heartbeat
    and TTL-evict a healthy runner.
    """
    cap = max_spans if max_spans is not None else export_batch()
    if not isinstance(raw, dict):
        return [], (1 if raw not in (None, {}) else 0)
    items = raw.get("spans")
    if not isinstance(items, list):
        return [], (1 if items is not None else 0)
    rejected = max(0, len(items) - cap)
    spans = []
    for doc in items[:cap]:
        clean = _clean_span(doc)
        if clean is None:
            rejected += 1
        else:
            spans.append(clean)
    return spans, rejected


class TraceFederation:
    """Control-plane side of trace federation: per-trace-id storage of
    runner-pushed wire spans, stitched with the cp's own local spans
    and skew-corrected at query time.

    * bounded: LRU over :func:`cp_retention` traces, per-trace span cap
      shared with :class:`TraceStore`; overflow counts, never grows.
    * pruned with the runner: ``prune_runner`` drops a dead host's
      spans the same moment the router forgets it.
    * skew correction: wall clocks disagree across hosts, but causality
      doesn't — the cp's dispatch span STARTS before any runner span of
      that trace exists.  Per host, if the earliest pushed span starts
      before the cp's anchor span, the whole host is shifted forward by
      the difference (recorded in the stitched doc, not hidden).
    """

    def __init__(self, local: Optional[TraceStore] = None,
                 max_traces: Optional[int] = None,
                 max_spans_per_trace: int = 256):
        self.local = local if local is not None else default_store()
        self.max_traces = max_traces or cp_retention()
        self.max_spans_per_trace = max_spans_per_trace
        # trace_id -> {host -> [wire spans]}
        self._fed: "collections.OrderedDict[str, dict]" = (
            collections.OrderedDict()
        )
        self._trace_dropped: dict = {}   # trace_id -> overflow count
        self._by_runner: dict = {}       # runner_id -> set of trace ids
        self._lock = threading.Lock()
        self.ingest_spans = 0     # clean spans accepted
        self.ingest_dropped = 0   # accepted then dropped to a cap
        self.ingest_rejected = 0  # malformed / overflow at validation

    def ingest(self, runner_id: str, raw) -> int:
        """Fold one heartbeat's span block in.  Returns the number of
        spans accepted; never raises (heartbeat-safe)."""
        spans, rejected = validate_span_batch(raw)
        with self._lock:
            self.ingest_rejected += rejected
            accepted = 0
            for span in spans:
                tid = span["trace_id"]
                entry = self._fed.get(tid)
                if entry is None:
                    entry = self._fed[tid] = {}
                    while len(self._fed) > self.max_traces:
                        old_tid, old = self._fed.popitem(last=False)
                        self._trace_dropped.pop(old_tid, None)
                        for host_tids in self._by_runner.values():
                            host_tids.discard(old_tid)
                else:
                    self._fed.move_to_end(tid)
                host_spans = entry.setdefault(runner_id, [])
                total = sum(len(v) for v in entry.values())
                if total >= self.max_spans_per_trace:
                    self.ingest_dropped += 1
                    self._trace_dropped[tid] = (
                        self._trace_dropped.get(tid, 0) + 1
                    )
                    continue
                host_spans.append(span)
                accepted += 1
                self._by_runner.setdefault(runner_id, set()).add(tid)
            self.ingest_spans += accepted
        return accepted

    def prune_runner(self, runner_id: str) -> None:
        """Forget a dead runner's spans (router eviction hook)."""
        with self._lock:
            tids = self._by_runner.pop(runner_id, None)
            if not tids:
                return
            for tid in tids:
                entry = self._fed.get(tid)
                if entry is None:
                    continue
                entry.pop(runner_id, None)
                if not entry:
                    self._fed.pop(tid, None)
                    self._trace_dropped.pop(tid, None)

    def __len__(self) -> int:
        with self._lock:
            return len(self._fed)

    def ids(self) -> list:
        """Union of locally-traced and federated trace ids (insertion
        order, local first)."""
        out = list(self.local.ids())
        seen = set(out)
        with self._lock:
            out.extend(t for t in self._fed if t not in seen)
        return out

    def _corrected(self, trace_id: str):
        """Merge local + federated spans with per-host skew applied.

        Returns ``(spans, skew, dropped)`` where ``spans`` is a sorted
        list of ``(host, wire_span_with_corrected_times)``, ``skew``
        maps host -> applied shift in seconds — or ``None`` when the
        trace is unknown everywhere.
        """
        local_doc = self.local.get(trace_id)
        with self._lock:
            entry = self._fed.get(trace_id)
            hosts = (
                {h: list(v) for h, v in entry.items()} if entry else {}
            )
            dropped = self._trace_dropped.get(trace_id, 0)
        if local_doc is None and not hosts:
            return None, None, 0
        merged = []
        anchor = None
        if local_doc is not None:
            dropped += local_doc.get("dropped_spans", 0)
            for s in local_doc["spans"]:
                wire = {
                    "trace_id": trace_id,
                    "name": s["name"],
                    "plane": s["plane"],
                    "start_unix": s["start_unix"],
                    "end_unix": (
                        s["start_unix"] + s["duration_ms"] / 1000.0
                    ),
                    "attrs": s["attrs"],
                }
                merged.append(("control-plane", wire))
                if anchor is None or wire["start_unix"] < anchor:
                    anchor = wire["start_unix"]
        skew = {}
        for host, spans in sorted(hosts.items()):
            offset = 0.0
            if anchor is not None and spans:
                earliest = min(s["start_unix"] for s in spans)
                if earliest < anchor:
                    # causality anchor: no runner span of this trace
                    # can truly predate the cp span that dispatched it
                    offset = anchor - earliest
            if offset:
                skew[host] = offset
            for s in spans:
                fixed = dict(s)
                fixed["start_unix"] = s["start_unix"] + offset
                fixed["end_unix"] = s["end_unix"] + offset
                merged.append((host, fixed))
        merged.sort(key=lambda hs: hs[1]["start_unix"])
        return merged, skew, dropped

    def stitched(self, trace_id: str) -> Optional[dict]:
        """The cluster-wide timeline for one trace id — every host's
        spans in one skew-corrected, monotone-ordered list."""
        merged, skew, dropped = self._corrected(trace_id)
        if merged is None:
            return None
        spans = []
        for host, s in merged:
            spans.append({
                "host": host,
                "name": s["name"],
                "plane": s["plane"],
                "start_unix": s["start_unix"],
                "duration_ms": (
                    (s["end_unix"] - s["start_unix"]) * 1000.0
                ),
                "attrs": s["attrs"],
            })
        doc = {
            "trace_id": trace_id,
            "hosts": sorted({h for h, _ in merged}),
            "spans": spans,
        }
        if skew:
            doc["clock_skew_applied_s"] = {
                h: round(v, 6) for h, v in skew.items()
            }
        if dropped:
            doc["dropped_spans"] = dropped
        return doc

    def chrome_trace(self, trace_id: str) -> Optional[dict]:
        """Chrome ``trace_event`` JSON for the stitched timeline — one
        pid per HOST (tid per plane) so cross-host handoffs read as
        arrows between process tracks."""
        merged, _, _ = self._corrected(trace_id)
        if merged is None:
            return None
        events = []
        host_pids: dict = {}
        for host, s in merged:
            pid = host_pids.get(host)
            if pid is None:
                pid = 1 if host == "control-plane" else (
                    10 + len(host_pids)
                )
                host_pids[host] = pid
                events.append({
                    "name": "process_name", "ph": "M", "pid": pid,
                    "args": {"name": f"helix:{host}"},
                })
            events.append({
                "name": s["name"],
                "cat": s["plane"] or "other",
                "ph": "X",
                "pid": pid,
                "tid": _PLANE_PIDS.get(s["plane"], 9),
                "ts": s["start_unix"] * 1e6,
                "dur": max(
                    (s["end_unix"] - s["start_unix"]) * 1e6, 1.0
                ),
                "args": {k: str(v) for k, v in s["attrs"].items()},
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}


# -- step phases and request stages on the profiler's clock (ISSUE 25) ----


class Phases(dict):
    """One engine step's phase seconds, ``{span name: self seconds}``.
    ``open`` is the innermost phase still running, so a phase that closes
    inside another is taken out of the outer one and the values add up to
    the time the step spent inside any phase.  ``cpu`` holds the same
    spans' CPU seconds of the thread that ran them
    (:func:`thread_cpu`), nested phases taken out the same way: a
    phase's wall less its cpu is the time its thread did not run (it
    waited for the GIL, was descheduled, or was blocked in a runtime call
    that released the GIL)."""

    __slots__ = ("open", "cpu")

    def __init__(self):
        super().__init__()
        self.open = None
        self.cpu = {}

    def clear(self):
        super().clear()
        self.cpu.clear()

    def merge(self, other: "Phases") -> None:
        """Take over the closed phases of ``other`` (wall and cpu)."""
        self.update(other)
        self.cpu.update(other.cpu)


_annotations = None
_local = threading.local()


class Mark:
    """Where one thread is, for the stall watch (``obs.flight.StallWatch``)
    to read WHILE the thread hangs.  ``at`` is ``(pass number, step
    number, span name, t_mono the span was entered)`` or None (nothing
    open), written by the thread itself with one plain attribute store
    and no lock: the spans a marked thread opens (:class:`phase`) write
    it as they enter and put back what stood as they leave, so ``at`` is
    always the innermost span still open.  The owner sets ``pass_no`` and
    ``step`` (what the next spans stamp) and may write ``at`` for a place
    with no span.  ``attrs`` keeps the attributes of the last span of each
    name that carried any: ``helix.loop.launch``'s are the step's launch
    record."""

    __slots__ = ("at", "pass_no", "step", "attrs")

    def __init__(self):
        self.at = None
        self.pass_no = self.step = 0
        self.attrs = {}


def mark_thread(mark: Optional[Mark]) -> None:
    """The calling thread's spans write ``mark`` from now on (None: no
    more)."""
    _local.mark = mark


def thread_cpu() -> float:
    """``time.thread_time()``, or this thread's last reading if that is
    under 20 us old.  The read is a system call (0.3 us on a plain Linux
    host, 6 us on the chip's sealed one, where the clock also
    advances in 10 ms ticks), and phase boundaries come in pairs: one span
    closes and the next opens.  A span's cpu is exact to 20 us a
    boundary."""
    last = getattr(_local, "read", None)
    if last is not None and time.monotonic() - last[0] < 2e-5:
        return last[1]
    cpu = time.thread_time()
    _local.read = (time.monotonic(), cpu)
    return cpu


class phase:
    """``with phase(name, hist=None, into=None, **attrs)``: one named
    span with four sinks.  (a) A ``jax.profiler.TraceAnnotation`` (a
    ``StepTraceAnnotation`` when ``step_num`` is among ``attrs``), which
    records only while a profiler capture is active and is then on the
    profiler's clock by construction.  (b) ``into[name]`` gains the
    elapsed seconds less the phases nested inside this one (``into`` is
    the current step's :class:`Phases`, which lands in the flight record
    as ``phases``).  (c) ``hist`` observes the elapsed seconds, which
    ``seconds`` holds once the block has ended.  (d) On a thread with a
    :class:`Mark` (``mark_thread``) the span is where the thread is,
    for the stall watch to read while it runs.

    JAX is imported on first use: the control plane imports ``obs`` and
    never opens a phase."""

    __slots__ = ("name", "hist", "into", "seconds", "_ann", "_t0", "_c0",
                 "_parent", "_child", "_child_cpu", "_mark", "_was")

    def __init__(self, name: str, hist=None, into: Optional[Phases] = None,
                 **attrs):
        global _annotations
        if _annotations is None:
            from jax.profiler import StepTraceAnnotation, TraceAnnotation

            _annotations = (TraceAnnotation, StepTraceAnnotation)
        self.name = name
        self.hist = hist
        self.into = into
        self._ann = _annotations["step_num" in attrs](name, **attrs)
        self._child = self._child_cpu = 0.0
        mark = self._mark = getattr(_local, "mark", None)
        if attrs and mark is not None:
            mark.attrs[name] = attrs

    def __enter__(self):
        into = self.into
        if into is not None:
            self._parent = into.open
            into.open = self
        self._ann.__enter__()
        self._t0 = time.monotonic()
        mark = self._mark
        if mark is not None:
            self._was = mark.at
            mark.at = (mark.pass_no, mark.step, self.name, self._t0)
        if into is not None:
            # inside the wall clock's pair: cpu passes wall by no more
            # than a shared reading's 20 us
            self._c0 = thread_cpu()
        return self

    def __exit__(self, *exc):
        into = self.into
        if into is not None:
            dc = thread_cpu() - self._c0
        dt = self.seconds = time.monotonic() - self._t0
        if self._mark is not None:
            self._mark.at = self._was
        self._ann.__exit__(*exc)
        if into is not None:
            name, cpu = self.name, into.cpu
            into[name] = into.get(name, 0.0) + dt - self._child
            cpu[name] = cpu.get(name, 0.0) + dc - self._child_cpu
            parent = into.open = self._parent
            if parent is not None:
                parent._child += dt
                parent._child_cpu += dc
        if self.hist is not None:
            self.hist.observe(dt)
        return False


def clock_stamp() -> int:
    """A ``helix.clock`` event in a running capture whose attribute is
    ``time.monotonic_ns()`` at that instant: flight records and request
    spans are on the monotonic clock, and the two stamps of a capture
    (its start and stop) lay them onto the profiler's.  Returns the
    stamp."""
    now = time.monotonic_ns()
    with phase("helix.clock", monotonic_ns=now):
        pass
    return now


# -- metric minting (lint_metrics contract 13) ------------------------
#
# Every helix_trace_* / helix_cp_trace* series is minted HERE and only
# here; the serving/control planes import these collectors.


def collect_trace_metrics(c, store: Optional[TraceStore] = None) -> None:
    """Runner-side trace-loss series (scrape-time collector)."""
    st = store if store is not None else default_store()
    c.counter(
        "helix_trace_dropped_spans_total",
        st.dropped_spans + st.export_dropped,
        help="Spans lost to the per-trace cap or the export ring",
    )


def collect_cp_trace_ingest(c, fed: Optional["TraceFederation"]) -> None:
    """Control-plane federation-ingest series (scrape-time collector).
    Also owns ``helix_cp_traces_stored`` so trace-store exposition has
    one minting site."""
    if fed is None:
        return
    c.gauge(
        "helix_cp_traces_stored",
        len(fed.ids()),
        help="Trace ids resident on the control plane (local+federated)",
    )
    c.counter(
        "helix_cp_trace_ingest_spans_total", fed.ingest_spans,
        help="Runner spans accepted into the federation store",
    )
    c.counter(
        "helix_cp_trace_ingest_dropped_total", fed.ingest_dropped,
        help="Accepted spans dropped to the per-trace federation cap",
    )
    c.counter(
        "helix_cp_trace_ingest_rejected_total", fed.ingest_rejected,
        help="Runner spans rejected at validation (malformed/overflow)",
    )


# one process-wide store by default: in-process deployments (tests, the
# single-binary dev stack) see control-plane, runner and engine spans of
# one request in the same trace; split deployments each hold their own
# half, queryable per plane
_default_store = TraceStore()


def default_store() -> TraceStore:
    return _default_store
