"""Shared metrics registry: counters, gauges, fixed-bucket histograms.

Every ``/metrics`` surface in the serving spine renders through this
module — Prometheus text exposition lives HERE and only here
(``tools/lint_metrics.py`` fails the build on exposition strings built
anywhere else).  Two usage shapes:

- **Registered metrics** (``registry.counter(...)`` etc.): owned by the
  registry, rendered on every scrape.  Use for series whose lifetime is
  the server's (dispatch outcome counters, latency histograms).
- **Scrape-time collectors** (``registry.register_callback(fn)``): the
  callback receives a :class:`Collector` and emits point-in-time samples
  from live objects (per-model engine gauges, circuit-breaker states,
  standalone histograms owned by an ``EngineLoop``).  This is how
  per-model labels attach at scrape time without the engine knowing
  about HTTP servers.

The reference control plane exposes Go/Prometheus client series; this is
the in-process Python equivalent sized for the serving spine (no
dependency on prometheus_client, which the TPU containers don't ship).
"""

from __future__ import annotations

import math
import re
import threading
from typing import Callable, Iterable, Optional, Sequence

# the naming contract: lowercase snake_case under the helix_ prefix.
# tools/lint_metrics.py additionally rejects non-base-unit suffixes
# (_ms, _cnt, ...) repo-wide — keep the two in sync.
METRIC_NAME_RE = re.compile(r"helix_[a-z0-9_]+")

# fixed latency buckets (seconds).  One shared ladder keeps TTFT /
# queue-wait / dispatch-attempt histograms comparable across planes; the
# FAST ladder covers per-step and inter-token scales.
LATENCY_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)
FAST_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
)
# tokens: a step of a few short rows to one of 64 rows at 8k each
CONTEXT_BUCKETS = tuple(float(2 ** n) for n in range(8, 20))


def validate_metric_name(name: str) -> str:
    if not METRIC_NAME_RE.fullmatch(name):
        raise ValueError(
            f"metric name {name!r} violates the helix naming contract "
            "helix_[a-z0-9_]+ (lowercase snake_case; base-unit suffixes "
            "_total/_seconds/_bytes)"
        )
    return name


def escape_label_value(v: str) -> str:
    """Prometheus exposition-format label escaping — label values arrive
    verbatim from runner ids / model names, and one stray quote would
    invalidate the whole scrape."""
    return (
        v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")
    )


def format_value(v) -> str:
    """Sample value formatting: integral values render without a decimal
    point (tests and dashboards compare counter values textually)."""
    if isinstance(v, bool):
        return str(int(v))
    if isinstance(v, int):
        return str(v)
    f = float(v)
    if math.isnan(f):
        return "NaN"
    if math.isinf(f):
        return "+Inf" if f > 0 else "-Inf"
    if f.is_integer() and abs(f) < 1e15:
        return str(int(f))
    return f"{f:.10g}"


def format_labels(labels: Optional[dict]) -> str:
    if not labels:
        return ""
    inner = ",".join(
        f'{k}="{escape_label_value(str(v))}"' for k, v in labels.items()
    )
    return "{" + inner + "}"


def render_sample(name: str, labels: Optional[dict], value) -> str:
    return f"{name}{format_labels(labels)} {format_value(value)}"


# ---------------------------------------------------------------------------
# metric families
# ---------------------------------------------------------------------------


class _Metric:
    """One family: a name, a type, and labelled children."""

    kind = "untyped"

    def __init__(self, name: str, help: str = "",
                 labelnames: Sequence[str] = ()):
        validate_metric_name(name)
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self._children: dict = {}
        self._lock = threading.Lock()

    def _new_child(self):
        raise NotImplementedError

    def labels(self, **labelvalues):
        if set(labelvalues) != set(self.labelnames):
            raise ValueError(
                f"{self.name}: expected labels {self.labelnames}, "
                f"got {tuple(labelvalues)}"
            )
        key = tuple(str(labelvalues[n]) for n in self.labelnames)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = self._children[key] = self._new_child()
            return child

    def _default_child(self):
        if self.labelnames:
            raise ValueError(
                f"{self.name} has labels {self.labelnames}; call "
                ".labels(...) first"
            )
        with self._lock:
            child = self._children.get(())
            if child is None:
                child = self._children[()] = self._new_child()
            return child

    def samples(self) -> Iterable[tuple]:
        """Yields (suffix, labels_dict, value) for every child."""
        with self._lock:
            items = list(self._children.items())
        for key, child in items:
            base = dict(zip(self.labelnames, key))
            for suffix, extra, value in child.samples():
                merged = dict(base)
                merged.update(extra)
                yield suffix, merged, value


class _CounterChild:
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0

    def inc(self, n=1):
        if n < 0:
            raise ValueError("counters only go up")
        self.value += n

    def samples(self):
        yield "", {}, self.value


class Counter(_Metric):
    kind = "counter"

    def _new_child(self):
        return _CounterChild()

    def inc(self, n=1):
        self._default_child().inc(n)

    @property
    def value(self):
        return self._default_child().value


class _GaugeChild:
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def set(self, v):
        self.value = v

    def inc(self, n=1):
        self.value += n

    def dec(self, n=1):
        self.value -= n

    def samples(self):
        yield "", {}, self.value


class Gauge(_Metric):
    kind = "gauge"

    def _new_child(self):
        return _GaugeChild()

    def set(self, v):
        self._default_child().set(v)

    def inc(self, n=1):
        self._default_child().inc(n)

    def dec(self, n=1):
        self._default_child().dec(n)

    @property
    def value(self):
        return self._default_child().value


class _HistogramChild:
    __slots__ = ("buckets", "counts", "sum", "count")

    def __init__(self, buckets):
        self.buckets = buckets
        self.counts = [0] * len(buckets)   # per-bucket (non-cumulative)
        self.sum = 0.0
        self.count = 0

    def observe(self, v):
        v = float(v)
        self.sum += v
        self.count += 1
        for i, b in enumerate(self.buckets):
            if v <= b:
                self.counts[i] += 1
                break

    def samples(self):
        # ints are GIL-atomic but the tuple of reads is not; a scrape
        # racing an observe may be off by one observation — acceptable
        # for monitoring, never corrupt
        cum = 0
        for b, c in zip(self.buckets, list(self.counts)):
            cum += c
            yield "_bucket", {"le": format_value(b)}, cum
        yield "_bucket", {"le": "+Inf"}, self.count
        yield "_sum", {}, self.sum
        yield "_count", {}, self.count


class Histogram(_Metric):
    """Fixed-bucket histogram.  ``le`` labels are cumulative per the
    exposition format; bucket bounds are frozen at construction so every
    scrape of every process slices latency identically."""

    kind = "histogram"

    def __init__(self, name: str, help: str = "",
                 buckets: Sequence[float] = LATENCY_BUCKETS,
                 labelnames: Sequence[str] = ()):
        super().__init__(name, help, labelnames)
        if list(buckets) != sorted(buckets):
            raise ValueError(f"{name}: buckets must be sorted")
        self.buckets = tuple(float(b) for b in buckets)

    def _new_child(self):
        return _HistogramChild(self.buckets)

    def observe(self, v):
        self._default_child().observe(v)

    @property
    def count(self):
        return self._default_child().count

    @property
    def sum(self):
        return self._default_child().sum


_KIND_CLASSES = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}


# ---------------------------------------------------------------------------
# scrape-time collection
# ---------------------------------------------------------------------------


class Collector:
    """Scrape-time sample buffer handed to registry callbacks.

    Callbacks read live objects (router breaker snapshots, engine
    counters) and emit samples; the registry renders everything in one
    pass with correct ``# TYPE`` grouping."""

    def __init__(self):
        # name -> [kind, help, [(suffix, labels, value), ...]]
        self.families: dict = {}

    def _family(self, name: str, kind: str, help: str):
        validate_metric_name(name)
        fam = self.families.get(name)
        if fam is None:
            fam = self.families[name] = [kind, help, []]
        elif fam[0] != kind:
            raise ValueError(
                f"metric {name} collected as both {fam[0]} and {kind}"
            )
        return fam

    def counter(self, name: str, value, labels: Optional[dict] = None,
                help: str = ""):
        self._family(name, "counter", help)[2].append(
            ("", dict(labels or {}), value)
        )

    def gauge(self, name: str, value, labels: Optional[dict] = None,
              help: str = ""):
        self._family(name, "gauge", help)[2].append(
            ("", dict(labels or {}), value)
        )

    def metric(self, m: _Metric, labels: Optional[dict] = None):
        """Fold a standalone (unregistered) metric family in, merging
        ``labels`` into every sample — how an EngineLoop's private
        histograms pick up their ``model`` label at scrape time."""
        fam = self._family(m.name, m.kind, m.help)
        extra = dict(labels or {})
        for suffix, sample_labels, value in m.samples():
            merged = dict(extra)
            merged.update(sample_labels)
            fam[2].append((suffix, merged, value))


class Registry:
    """A set of metric families + scrape-time callbacks, rendered as one
    Prometheus text document."""

    def __init__(self):
        self._metrics: dict = {}
        self._callbacks: list = []
        self._lock = threading.Lock()

    def _get_or_create(self, kind: str, name: str, help: str, **kw):
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if m.kind != kind:
                    raise ValueError(
                        f"metric {name} already registered as {m.kind}"
                    )
                return m
            m = _KIND_CLASSES[kind](name, help, **kw)
            self._metrics[name] = m
            return m

    def counter(self, name: str, help: str = "",
                labelnames: Sequence[str] = ()) -> Counter:
        return self._get_or_create(
            "counter", name, help, labelnames=labelnames
        )

    def gauge(self, name: str, help: str = "",
              labelnames: Sequence[str] = ()) -> Gauge:
        return self._get_or_create("gauge", name, help, labelnames=labelnames)

    def histogram(self, name: str, help: str = "",
                  buckets: Sequence[float] = LATENCY_BUCKETS,
                  labelnames: Sequence[str] = ()) -> Histogram:
        return self._get_or_create(
            "histogram", name, help, buckets=buckets, labelnames=labelnames
        )

    def register_callback(self, fn: Callable[[Collector], None]) -> None:
        with self._lock:
            self._callbacks.append(fn)

    def render(self) -> str:
        """The Prometheus text exposition for everything this registry
        knows about — registered families first, then callback samples.
        May run off the event loop (callbacks can take locks)."""
        col = Collector()
        with self._lock:
            metrics = list(self._metrics.values())
            callbacks = list(self._callbacks)
        for m in metrics:
            col.metric(m)
        for cb in callbacks:
            cb(col)
        lines: list = []
        for name, (kind, help, samples) in col.families.items():
            if help:
                lines.append(f"# HELP {name} {help}")
            lines.append(f"# TYPE {name} {kind}")
            for suffix, labels, value in samples:
                lines.append(render_sample(name + suffix, labels, value))
        return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# pre-wired bundles
# ---------------------------------------------------------------------------


class EngineLoopObs:
    """The latency surface one EngineLoop feeds (APEX-style per-phase
    breakdown: where did each millisecond of a request go?).  Standalone
    families — the runner's /metrics folds them in with a ``model`` label
    via ``Collector.metric`` at scrape time."""

    def __init__(self):
        self.queue_wait = Histogram(
            "helix_queue_wait_seconds",
            "Submit to slot admission (queueing + page waits)",
        )
        self.ttft = Histogram(
            "helix_ttft_seconds",
            "Submit to first token (queue + prefill)",
        )
        self.inter_token = Histogram(
            "helix_inter_token_seconds",
            "Gap between consecutive emitted tokens of one request",
            buckets=FAST_BUCKETS,
        )
        self.step_seconds = Histogram(
            "helix_engine_step_seconds",
            "Engine step wall time (host view, includes device sync)",
            buckets=FAST_BUCKETS,
        )
        # the step's time split (ISSUE 13): where each step's host
        # milliseconds go — schedule/plan/dispatch vs token emission.
        # While the loop runs a step ahead both overlap device execution;
        # exposed_host below (the flight recorder's idle_gap_s, the
        # helix_device_idle_ratio gauge) is what still leaves the device
        # waiting.
        self.host_build = Histogram(
            "helix_step_host_build_seconds",
            "Host-side step build time (scheduling + plan packing + "
            "metadata upload + dispatch) per engine step",
            buckets=FAST_BUCKETS,
        )
        self.exposed_host = Histogram(
            "helix_step_exposed_host_seconds",
            "Host time the device waited out per engine step: the last "
            "completion's return to this step's first launch when nothing "
            "was queued on the device, 0 for a step launched behind a "
            "running one",
            buckets=FAST_BUCKETS,
        )
        self.emit_seconds = Histogram(
            "helix_step_emit_seconds",
            "The engine thread handing a step's tokens to the emission "
            "stage (event snapshot + enqueue, and any block on a full "
            "queue) per engine step; the delivery itself only in a loop "
            "that was never started",
            buckets=FAST_BUCKETS,
        )
        # the emission worker (ISSUE 26): what delivery costs now that
        # it is off the engine thread, how long a batch sits before its
        # delivery starts, and how often the slowest subscriber stalled
        # the engine
        self.emit_deliver = Histogram(
            "helix_emit_deliver_seconds",
            "Token delivery on the emission worker (subscriber "
            "callbacks, latency histograms, per-tenant SLO accounting) "
            "per step batch, less its waits for a parked engine thread",
            buckets=FAST_BUCKETS,
        )
        self.emit_queue_wait = Histogram(
            "helix_emit_queue_wait_seconds",
            "A step batch's push to the start of its delivery on the "
            "emission worker",
            buckets=FAST_BUCKETS,
        )
        self.emit_backpressure = Counter(
            "helix_emit_backpressure_total",
            "Pushes that found the emission queue full and blocked the "
            "engine thread",
        )
        self.emit_backpressure.inc(0)   # exported from the first scrape
        # engine-step phases (ISSUE 25): each is the span of the same
        # name on the profiler's clock (obs.trace.phase) and is observed
        # once an engine step, 0 where the phase did not run, so the
        # phase means add up to the mean of helix_engine_step_seconds.
        # host_build above times admit + prefill_sync + dispatch from
        # outside; device_wait_s in the flight record, fetch + reconcile.
        self.step_phases = {
            "helix.loop.admit": Histogram(
                "helix_step_admit_seconds",
                "Admission per engine step: claim, page allocation, plan "
                "packing and the prefill launch, less the first-token "
                "fetch",
                buckets=FAST_BUCKETS,
            ),
            "helix.loop.prefill_sync": Histogram(
                "helix_step_prefill_sync_seconds",
                "Blocked on the admission wave's prefill program (its "
                "first-token fetch) per engine step",
                buckets=FAST_BUCKETS,
            ),
            "helix.loop.dispatch": Histogram(
                "helix_step_dispatch_seconds",
                "Step dispatch per engine step: metadata build, upload "
                "and the jitted call",
                buckets=FAST_BUCKETS,
            ),
            "helix.loop.fetch": Histogram(
                "helix_step_fetch_seconds",
                "Blocked on the device in the step's one device_get per "
                "engine step",
                buckets=FAST_BUCKETS,
            ),
            "helix.loop.reconcile": Histogram(
                "helix_step_reconcile_seconds",
                "Host effects after the fetch (emits, stop conditions, "
                "slot frees, page adoption) per engine step",
                buckets=FAST_BUCKETS,
            ),
        }
        # the same for a model with recurrent (conv) state: nested in the
        # phases above (a nested phase's seconds are taken out of the
        # outer one's), 0 for every other model
        self.state_phases = {
            "helix.state.snapshot": Histogram(
                "helix_state_snapshot_seconds",
                "Keeping the recurrent states a step handed back for the "
                "page boundaries its prefill rows passed, per engine step",
                buckets=FAST_BUCKETS,
            ),
            "helix.state.restore": Histogram(
                "helix_state_restore_seconds",
                "Writing filed recurrent states into the slots of "
                "admitted prefix hits, per engine step",
                buckets=FAST_BUCKETS,
            ),
        }
        # the host's account (ISSUE 37), each observed once an engine
        # step like the phases above, 0 where nothing ran.  The PARTS of
        # admit and dispatch: spans that write to Engine.step_parts (a
        # sink beside step_phases, so the parents read what they read),
        # self time among the parts, each the sum of both parents' calls
        # (a wave's launch runs under admit, the step program's under
        # dispatch); host_build less the four is slot scans, the decode
        # window and the scheduler's pops
        self.step_parts = {
            "helix.loop.claim": Histogram(
                "helix_step_claim_seconds",
                "Claiming slots and pages per engine step: prefix lookup, "
                "page allocation, state restore, and the bookkeeping and "
                "page adoption of the prompts just launched",
                buckets=FAST_BUCKETS,
            ),
            "helix.loop.plan": Histogram(
                "helix_step_plan_seconds",
                "Building the ragged plan per engine step: the rows, the "
                "plan's device arrays and the rows' sampling state",
                buckets=FAST_BUCKETS,
            ),
            "helix.loop.sync_state": Histogram(
                "helix_step_sync_state_seconds",
                "Uploading the slots' host mirrors and merging them into "
                "the device's decode state per engine step",
                buckets=FAST_BUCKETS,
            ),
            "helix.loop.launch": Histogram(
                "helix_step_launch_seconds",
                "The jitted calls of an engine step: parameter graft, "
                "argument flattening, donation and the runtime's enqueue",
                buckets=FAST_BUCKETS,
            ),
        }
        # CPU beside wall: the engine thread's CPU over the interval
        # host_build times, and each host thread's CPU since the step
        # before (the engine thread reads the three thread clocks); the
        # three over the step's wall bound the one GIL's use from above
        # (what a thread runs outside the GIL is CPU too)
        self.host_build_cpu = Histogram(
            "helix_step_host_build_cpu_seconds",
            "CPU seconds of the engine thread inside the interval "
            "helix_step_host_build_seconds times, per engine step",
            buckets=FAST_BUCKETS,
        )
        self.threads_cpu = {
            "engine": Histogram(
                "helix_step_engine_cpu_seconds",
                "CPU seconds of the engine thread since the step before, "
                "per engine step",
                buckets=FAST_BUCKETS,
            ),
            "emit": Histogram(
                "helix_step_emit_cpu_seconds",
                "CPU seconds of the emission worker since the step "
                "before, per engine step",
                buckets=FAST_BUCKETS,
            ),
            "http": Histogram(
                "helix_step_http_cpu_seconds",
                "CPU seconds of the thread that submits requests (the "
                "HTTP event loop) since the step before, per engine step",
                buckets=FAST_BUCKETS,
            ),
        }
        self.gc_seconds = Histogram(
            "helix_step_gc_seconds",
            "Collector pauses (the span helix.gc, whichever thread "
            "triggered them) since the step before, per engine step",
            buckets=FAST_BUCKETS,
        )
        # stalls (ISSUE 51).  The two step series are observed once for
        # every step the histograms above observe, 0 for a step that was
        # not flagged (by the recorder's slow-step rule or by the stall
        # watch): their means are per engine step like the rest, and mean
        # x steps is the seconds a window lost.  The counter counts closed
        # stall records of every kind (a step's, the emission worker's,
        # the event loop's, one between passes): the operator's alert.
        self.stall_seconds = Histogram(
            "helix_step_stall_seconds",
            "Wall time of an engine step that was flagged a stall (over "
            "the slow-step rule, or caught overdue by the stall watch), "
            "0 for every other step, per engine step",
            buckets=LATENCY_BUCKETS,
        )
        self.stall_offcpu = Histogram(
            "helix_step_stall_offcpu_seconds",
            "Of a flagged step's wall, the part its engine thread was not "
            "on a CPU (wall less the thread's CPU time), 0 for every "
            "other step, per engine step",
            buckets=LATENCY_BUCKETS,
        )
        self.stalls = Counter(
            "helix_engine_stalls_total",
            "Closed stall records: one a stall, each also one 'helix "
            "stall' log line and one anomalies entry of the flight "
            "recorder",
        )
        self.stalls.inc(0)   # exported from the first scrape
        # what a token's call_soon_threadsafe, a handler and an SSE write
        # wait for the serving event loop's thread: how late its
        # heartbeat ran, ten observations a second, none on a token's path
        self.http_loop_lag = Histogram(
            "helix_http_loop_lag_seconds",
            "How late the serving event loop ran its 0.1 s heartbeat "
            "(the time anything scheduled on that thread waits for it)",
            buckets=FAST_BUCKETS,
        )
        # request stages (ISSUE 25), beside queue_wait: handler entry to
        # first SSE chunk in five consecutive pieces, each also a span in
        # the request's trace
        self.http_pre_submit = Histogram(
            "helix_http_pre_submit_seconds",
            "Chat handler entry to loop.submit (parse, template, "
            "tokenize)",
            buckets=FAST_BUCKETS,
        )
        self.admit_to_first_token = Histogram(
            "helix_admit_to_first_token_seconds",
            "Slot admission to the engine holding the first token",
        )
        self.first_token_hold = Histogram(
            "helix_first_token_hold_seconds",
            "The engine holding the first token to its emission (the "
            "decode window it travels through)",
            buckets=FAST_BUCKETS,
        )
        # live tokens the rows of a step's last launch attended over (the
        # decode rows' positions and a chunk's history and fresh tokens, from
        # the host's mirrors): what the attention kernels' time follows
        self.step_context_tokens = Histogram(
            "helix_step_context_tokens",
            "Live tokens a step's rows attend over (decode rows' positions "
            "plus prefill rows' history and fresh tokens) per engine step",
            buckets=CONTEXT_BUCKETS,
        )
        self.http_first_write = Histogram(
            "helix_http_first_write_seconds",
            "First token's emission to the first SSE chunk written",
            buckets=FAST_BUCKETS,
        )

    def collect(self, c: Collector, labels: Optional[dict] = None) -> None:
        for m in (
            self.queue_wait, self.ttft, self.inter_token,
            self.step_seconds, self.host_build, self.exposed_host,
            self.emit_seconds, self.emit_deliver, self.emit_queue_wait, self.emit_backpressure,
            *self.step_phases.values(), *self.state_phases.values(),
            *self.step_parts.values(), self.host_build_cpu,
            *self.threads_cpu.values(), self.gc_seconds,
            self.stall_seconds, self.stall_offcpu, self.stalls,
            self.http_loop_lag,
            self.http_pre_submit, self.admit_to_first_token,
            self.first_token_hold, self.http_first_write,
            self.step_context_tokens,
        ):
            c.metric(m, labels)
