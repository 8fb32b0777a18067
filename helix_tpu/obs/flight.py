"""Engine flight recorder + the shared saturation-summary schema.

Two capacity-observability pieces that several layers share:

- :class:`FlightRecorder` — a bounded ring of per-step records fed by
  ``EngineLoop`` (host-side bookkeeping ONLY: every field is a plain-int
  delta of counters the engine already keeps, so nothing here touches
  the jitted path).  A watchdog marks anomalous steps (wall time blowing
  past a multiple of the trailing p99, a quarantine firing, a
  zero-progress step with busy slots) and FREEZES a snapshot of the ring
  at that moment — the per-step batch composition leading up to an
  incident survives even after the ring wraps.  External anomaly
  sources freeze the same tail via ``note_anomaly`` — the correctness
  canary (``obs/canary.py``) calls it on a golden-probe bit-identity
  mismatch, so the steps that produced wrong tokens are preserved.
  Served at ``GET /v1/debug/flight`` on the runner.
- :class:`StallWatch` — the watchdog's other half (ISSUE 51): the
  recorder flags a slow step AFTER it ends; the watch, one daemon thread
  a process, reads where each engine loop's threads ARE
  (``obs.trace.Mark``) four times a second and, once a stall, captures
  what they are doing while one hangs (:class:`Watched`).  The thread
  that ends the stall closes the record: one ``anomalies`` entry, one
  ``helix stall`` log line, one count.
- ``SATURATION_KEYS`` — the one schema for the compact saturation
  summary a runner heartbeats to the control plane.  The node agent
  builds the payload from this tuple and the control plane renders one
  ``helix_cp_runner_saturation_<key>`` gauge per entry;
  ``tools/lint_metrics.py`` fails the build if either side drifts from
  it.
- :class:`RateTracker` — windowed rate over a monotonically increasing
  counter (goodput tokens/s for /metrics and the heartbeat summary).
"""

from __future__ import annotations

import collections
import json
import logging
import os
import resource
import sys
import threading
import time
from typing import Callable, Optional

from helix_tpu.obs.trace import Mark

log = logging.getLogger("helix.stall")

# The heartbeat saturation-summary schema: the node agent emits exactly
# these keys, the control plane stores/renders exactly these keys
# (helix_cp_runner_saturation_<key> gauges).  Both sides import THIS
# tuple; lint_metrics cross-checks any hard-coded gauge name against it.
SATURATION_KEYS = (
    "kv_occupancy",      # used KV pages / allocatable pages, 0..1
    "slots_busy",        # occupied decode slots (all engines)
    "slots_total",       # decode-slot capacity (all engines)
    "queue_depth",       # requests waiting for a slot (inbox + engine)
    "tokens_per_sec",    # generated tokens/s over the trailing window
    "prefix_hit_rate",   # prefix-cache page hit rate, 0..1
    "spec_acceptance_ratio",  # speculative drafts accepted/drafted, 0..1
    "kv_host_occupancy",  # host KV tier bytes used / budget, 0..1
    "preempted_requests",  # decoders swapped out, parked for resume
    "prefill_budget_tokens",  # scheduler prefill-admission budget/step
    "adapters_resident",  # multi-LoRA adapters in the HBM pool (ISSUE 15)
    "kv_cold_pages",     # demoted cold-middle KV pages host-resident (ISSUE 20)
)


class RateTracker:
    """Windowed rate of a monotonically increasing counter.

    ``rate(value)`` banks a ``(now, value)`` sample (throttled to one
    per ``min_sample_interval`` so a per-step caller stays bounded),
    prunes until the anchor is the newest sample older than the window,
    and returns the average rate from the anchor to now.  The engine
    loop feeds it every step, so while the engine is working the anchor
    stays within ~one window of now and the value is a true trailing
    rate; across pure idle stretches the counter delta is zero and the
    rate correctly reads 0 regardless of anchor age.  Thread-safe:
    the engine-loop, heartbeat, and /metrics scrape threads share one
    tracker per engine loop."""

    def __init__(
        self,
        window_seconds: float = 60.0,
        min_sample_interval: float = 1.0,
    ):
        self.window = window_seconds
        self.min_interval = min_sample_interval
        self._samples: collections.deque = collections.deque()
        self._lock = threading.Lock()

    def rate(self, value: float, now: Optional[float] = None) -> float:
        now = time.monotonic() if now is None else now
        with self._lock:
            if (
                not self._samples
                or now - self._samples[-1][0] >= self.min_interval
            ):
                self._samples.append((now, float(value)))
            while (
                len(self._samples) > 1
                and now - self._samples[1][0] >= self.window
            ):
                self._samples.popleft()
            t0, v0 = self._samples[0]
            dt = now - t0
            if dt <= 0.0:
                return 0.0
            return max(0.0, (float(value) - v0) / dt)


class _Filed(dict):
    """A record as filed: nothing writes it from here on, so its JSON is
    made once, the first time it is served (``js``), and the ring, the
    frozen tails and every later answer share it."""

    __slots__ = ("js",)

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.js = None

    def json(self) -> str:
        if self.js is None:
            self.js = json.dumps(self)
        return self.js


def _anomaly_json(a: _Filed) -> str:
    """A frozen anomaly's JSON, once: its record and its tail bring
    theirs."""
    if a.js is None:
        parts = []
        for k, v in a.items():
            if k == "record":
                js = v.json()
            elif k == "steps":
                js = "[" + ", ".join(r.json() for r in v) + "]"
            else:
                js = json.dumps(v)
            parts.append(json.dumps(k) + ": " + js)
        a.js = "{" + ", ".join(parts) + "}"
    return a.js


class FlightRecorder:
    """Bounded per-step flight ring with an anomaly watchdog.

    ``record_step`` is called once per engine step from the engine-loop
    thread with plain host-side numbers; reads (``snapshot``) come from
    HTTP threads, so all state is guarded by one lock.  Step records are
    plain dicts (JSON-ready as-is).

    Anomaly detection, checked per step:

    - ``slow_step``: wall time > ``slow_factor`` x the trailing p99 of
      recent successful steps (after ``min_samples`` are banked, and
      only above ``min_step_seconds`` so tiny-engine jitter can't trip
      it);
    - ``zero_progress``: busy decode slots but zero tokens generated and
      zero prefill progress — decode must always emit, so this is a
      wedged engine;
    - explicit anomalies handed in by the caller (``step_failure``,
      ``quarantine``).

    On any anomaly the current ring tail is FROZEN into a bounded
    anomaly list: the batch composition of the steps preceding the
    incident stays retrievable after the live ring has wrapped."""

    def __init__(
        self,
        capacity: int = 512,
        freeze_steps: int = 64,
        max_anomalies: int = 8,
        slow_factor: float = 4.0,
        min_step_seconds: float = 0.25,
        min_samples: int = 32,
    ):
        self.capacity = capacity
        self.freeze_steps = freeze_steps
        self.slow_factor = slow_factor
        self.min_step_seconds = min_step_seconds
        self.min_samples = min_samples
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._durations: collections.deque = collections.deque(maxlen=256)
        self._anomalies: collections.deque = collections.deque(
            maxlen=max_anomalies
        )
        self._lock = threading.Lock()
        self.steps_recorded = 0
        self.anomalies_total = 0

    # -- write side (engine-loop thread) -----------------------------------

    def _trailing_p99_locked(self) -> float:
        if not self._durations:
            return 0.0
        s = sorted(self._durations)
        return s[min(len(s) - 1, int(len(s) * 0.99))]

    def _slow_locked(self, duration: float) -> bool:
        return (
            len(self._durations) >= self.min_samples
            and duration > self.min_step_seconds
            and duration > self.slow_factor * self._trailing_p99_locked()
        )

    def slow(self, duration: float) -> bool:
        """Whether a step of this duration would be filed ``slow_step``:
        for a caller that has to know before it files the record."""
        with self._lock:
            return self._slow_locked(duration)

    def stall_after(self) -> float:
        """Seconds a thread's marker may stand before the stall watch
        takes it for a stall under way: the slow-step rule's multiple of
        the trailing p99, and never under ``STALL_SECONDS``."""
        with self._lock:
            return max(
                STALL_SECONDS,
                self.slow_factor * self._trailing_p99_locked(),
            )

    def record_step(self, rec: dict, **frozen) -> Optional[str]:
        """Append one step record; returns the anomaly reason when the
        watchdog fired (the record itself is annotated + frozen, and
        ``frozen`` is filed with the anomaly: a stall's ``where``,
        ``stall`` and ``during``)."""
        rec = _Filed(rec)
        with self._lock:
            reason = rec.get("anomaly")
            duration = float(rec.get("duration", 0.0))
            if reason is None:
                if self._slow_locked(duration):
                    reason = "slow_step"
                elif (
                    rec.get("slots_busy", 0) > 0
                    and rec.get("generated_tokens", 0) == 0
                    and rec.get("prefill_tokens", 0) == 0
                ):
                    reason = "zero_progress"
                if reason is not None:
                    rec["anomaly"] = reason
            else:
                rec["anomaly"] = reason
            if rec.get("anomaly") is None:
                # only clean steps feed the p99 baseline: one incident
                # must not raise the bar for detecting the next one
                self._durations.append(duration)
            self._ring.append(rec)
            self.steps_recorded += 1
            if reason is not None:
                self._freeze_locked(reason, rec, frozen)
            return reason

    def reset_baseline(self) -> None:
        """Drop the banked step-duration samples.  XLA-compile-laden
        first steps record as 'clean' multi-second durations and would
        inflate the trailing p99 until the window turns over; callers
        that know a compile wave just ended (warmup, profile apply)
        reset so the watchdog re-learns the true serving cadence."""
        with self._lock:
            self._durations.clear()

    def note_anomaly(self, reason: str, frozen: Optional[dict] = None,
                     **attrs) -> None:
        """Freeze a snapshot for an event that is not itself a step
        (a quarantine eviction decided between steps; a stall of the
        emission worker or the event loop, whose ``frozen`` is filed
        with the anomaly as a stalled step's is)."""
        with self._lock:
            rec = _Filed({"ts": time.time(), "anomaly": reason, **attrs})
            self._freeze_locked(reason, rec, frozen or {})

    def _freeze_locked(self, reason: str, rec: _Filed, more: dict) -> None:
        self.anomalies_total += 1
        self._anomalies.append(
            _Filed({
                "reason": reason,
                "ts": rec.get("ts", time.time()),
                "step": rec.get("step"),
                "record": rec,
                # the frozen tail: batch composition of the steps
                # PRECEDING the anomaly (filed records: nothing writes
                # them any more, so the tail shares the ring's)
                "steps": list(self._ring)[-self.freeze_steps:],
                **more,
            })
        )

    # -- read side (HTTP threads) ------------------------------------------

    def window_ratio(
        self, num_key: str, den_keys: tuple, recent: int = 256
    ) -> float:
        """Sum of ``num_key`` over the last ``recent`` step records
        divided by the summed ``den_keys`` (0.0 on an empty window).

        Feeds ratio gauges computed over the flight window rather than
        process lifetime — e.g. ``helix_prefill_padding_ratio`` =
        padding / (padding + useful prefill) over recent steps, so a
        config change shows up in the gauge instead of being averaged
        away by history."""
        with self._lock:
            recs = list(self._ring)[-recent:]
        num = float(sum(r.get(num_key, 0) or 0 for r in recs))
        den = float(
            sum(r.get(k, 0) or 0 for r in recs for k in den_keys)
        )
        return num / den if den > 0 else 0.0

    def idle_share(self, recent: int = 256) -> float:
        """Summed ``idle_gap_s`` of the last ``recent`` timed step
        records over the time they span, from the first one's start
        (``t_mono - wall_s``) to the last one's end.  Every gap ends
        inside its own record and starts after the record before it, so
        but for the first, which is cut at its record's start, the gaps
        lie inside the span and do not overlap: the share cannot pass 1,
        however sparse the steps."""
        with self._lock:
            recs = [r for r in list(self._ring)[-recent:] if "wall_s" in r]
        if not recs:
            return 0.0
        start = recs[0]["t_mono"] - recs[0]["wall_s"]
        span = recs[-1]["t_mono"] - start
        idle = min(recs[0]["idle_gap_s"], recs[0]["wall_s"]) + sum(
            r["idle_gap_s"] for r in recs[1:]
        )
        return idle / span if span > 0 else 0.0

    def _head_locked(self) -> dict:
        return {
            "steps_recorded": self.steps_recorded,
            "anomalies_total": self.anomalies_total,
            "trailing_p99_seconds": self._trailing_p99_locked(),
            "config": {
                "capacity": self.capacity,
                "freeze_steps": self.freeze_steps,
                "slow_factor": self.slow_factor,
                "min_step_seconds": self.min_step_seconds,
                "min_samples": self.min_samples,
            },
        }

    def snapshot(self, recent: int = 64) -> dict:
        with self._lock:
            return {
                **self._head_locked(),
                "recent": [dict(r) for r in list(self._ring)[-recent:]],
                "anomalies": [
                    {
                        **a,
                        "record": dict(a["record"]),
                        "steps": [dict(r) for r in a["steps"]],
                    }
                    for a in self._anomalies
                ],
            }

    def snapshot_json(self, recent: int = 64) -> str:
        """``json.dumps(self.snapshot(recent))``, letter for letter, with
        each filed record serialised once in its life and not under the
        lock: the lock covers taking the references."""
        with self._lock:
            head = self._head_locked()
            ring = list(self._ring)[-recent:]
            anomalies = list(self._anomalies)
        return "".join((
            json.dumps(head)[:-1],
            ', "recent": [', ", ".join(r.json() for r in ring),
            '], "anomalies": [',
            ", ".join(_anomaly_json(a) for a in anomalies), "]}",
        ))


# -- the stall watch (ISSUE 51) ----------------------------------------------

# A marker that has stood this long (and over the recorder's slow-step
# multiple of the trailing p99) is a stall under way: "no step ends for
# a second" is what a far-off benchmark run looks like from outside.
STALL_SECONDS = 1.0
WATCH_TICK = 0.25           # the watcher wakes this often
STACK_FRAMES = 12           # frames of a thread's stack a capture keeps
LOG_LINE_BYTES = 4000       # a stall's log line stays under this
PARKED = "helix.loop.idle"  # the engine thread waiting for work: no stall
_RUSAGE = ("ru_nivcsw", "ru_nvcsw", "ru_majflt", "ru_utime", "ru_stime")
# of a closed record, what the anomaly's ``stall`` leaves out: its own
# keys and what the step's flight record files already
_NOT_IN_STALL = frozenset((
    "model", "where", "during", "ts", "step", "kind", "phases",
    "phases_cpu", "parts", "parts_cpu", "threads_cpu", "gc_s",
    "device_wait_s"))


def rusage() -> dict:
    """The process's counters that tell a thread that was descheduled
    (involuntary switches) or paging (major faults) from one that
    waited (neither, and no CPU)."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return {name[3:]: getattr(ru, name) for name in _RUSAGE}


def _first_number(path: str, after: str) -> Optional[float]:
    """The number that follows ``after`` on the first line of ``path``
    that has it; None where the file or the word is missing."""
    try:
        with open(path) as f:
            for line in f:
                if after in line:
                    return float(line.split(after, 1)[1].split()[0])
    except (OSError, ValueError, IndexError):
        pass
    return None


def host_state() -> dict:
    """What the host says of itself: load, pressure stalls (``some
    avg10`` of each resource, where the kernel has them) and the memory
    still available."""
    out = {"loadavg": [round(v, 2) for v in os.getloadavg()]}
    for res in ("cpu", "memory", "io"):
        v = _first_number(f"/proc/pressure/{res}", "some avg10=")
        if v is not None:
            out[f"pressure_{res}"] = v
    kb = _first_number("/proc/meminfo", "MemAvailable:")
    if kb is not None:
        out["mem_available_kb"] = int(kb)
    return out


def _stack(frame) -> list:
    """``file:line:function`` of a thread's innermost frames, innermost
    first (the file by its last two path parts)."""
    out = []
    while frame is not None and len(out) < STACK_FRAMES:
        code = frame.f_code
        path = "/".join(code.co_filename.rsplit("/", 2)[-2:])
        out.append(f"{path}:{frame.f_lineno}:{code.co_name}")
        frame = frame.f_back
    return out


class Watched:
    """One engine loop as the stall watch sees it.  ``marks`` is where
    its threads are (``engine`` and ``emit`` its own, written by those
    threads; ``http`` the process's serving event loop, stamped by its
    heartbeat).  ``probe()`` is the loop's own account of itself, read by
    the watcher's thread at a capture: ``{"threads": {name: (ident, cpu
    clock, cpu seconds at the last step's end)}, ...state}``.  ``during``
    holds the watcher's capture of a stall under way by marker, until
    the thread that ends the stall takes it (``take``)."""

    def __init__(self, name: str, recorder: Callable[[], "FlightRecorder"],
                 obs, probe: Callable[[], dict], watch: "StallWatch"):
        self.name = name
        self.recorder = recorder    # (asked each time: a loop's may change)
        self.obs = obs
        self.probe = probe
        self.watch = watch
        self.marks = {"engine": Mark(), "emit": Mark(), "http": watch.http}
        self.during: dict = {}
        self._seen: dict = {}     # marker -> the pass it was last captured in

    def take(self, where: str) -> Optional[dict]:
        """The capture of ``where``'s stall, if the watcher made one."""
        return self.during.pop(where, None) if self.during else None

    def look(self, now: float, late: float = 0.0) -> None:
        """The watcher's tick, ``late`` seconds after it was due:
        capture, once a stall, each marker that has stood past the rule.
        A watcher that was itself held up past the rule (the process
        frozen whole, or the GIL held) saw nothing hang: every thread
        stood with it, so it captures the engine thread's marker alone
        (one stall, one record) and what it reads is the thaw's."""
        limit = None
        for where, mark in self.marks.items():
            at = mark.at
            if at is None or at[2] == PARKED or now - at[3] < STALL_SECONDS:
                continue
            if limit is None:
                limit = self.recorder().stall_after()
            if now - at[3] < limit or self._seen.get(where) == at[0]:
                continue
            self._seen[where] = at[0]
            if late >= STALL_SECONDS and where != "engine":
                continue
            if where == "http" and not self.watch.http_running():
                continue    # an event loop that has stopped is not stalled
            try:
                self.during[where] = {
                    **self._capture(where, at, now),
                    "watcher_late_s": round(late, 3)}
            except Exception:  # noqa: BLE001 — the watch must outlive a bad read
                log.exception("stall capture failed")

    def _capture(self, where: str, at: tuple, now: float) -> dict:
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("helix.stall", step=at[1], span=at[2]):
            state = self.probe()
            frames = sys._current_frames()
            threads = {}
            for name, (ident, clock, seen) in state.pop("threads").items():
                t = {"stack": _stack(frames.get(ident))}
                try:
                    cpu = time.clock_gettime(clock)
                    t["cpu_s"] = round(cpu, 6)
                    if seen is not None:
                        t["cpu_since_step_s"] = round(cpu - seen, 6)
                except (OSError, TypeError):   # the thread has gone
                    pass
                threads[name] = t
            del frames
            return {
                "thread": where, "pass": at[0], "step": at[1],
                "span": at[2], "since": round(at[3], 6),
                "stood_s": round(now - at[3], 6),
                "t_mono": round(now, 6), "ts": time.time(),
                "threads": threads, "rusage": rusage(),
                **host_state(), **state, **self.watch.http_state(),
            }

    def closed(self, where: str, during: Optional[dict], wall: float,
               cpu: Optional[float], **known) -> dict:
        """A stall's closed record: what the log line carries.  ``wall``
        the seconds it lasted and ``cpu`` the stalled thread's CPU
        seconds inside them (None: not known), ``known`` what the thread
        that ends it alone knows; the ``rusage`` deltas run from the
        watcher's last reading before the stall began to now, and
        ``watcher_late_s`` says whether the watcher ran inside it."""
        now = time.monotonic()
        rec = {
            "model": self.name, "where": where,
            "seen": during is not None,
            "ts": time.time(), "t_mono": round(now, 6),
            "wall_s": round(wall, 6),
        }
        if cpu is not None:
            rec["offcpu_s"] = round(max(0.0, wall - max(0.0, cpu)), 6)
        rec.update(known)
        rec["rusage"], rec["watcher_late_s"] = self.watch.since(now - wall)
        if during is not None:
            rec["during"] = during
        return rec

    def file(self, closed: dict) -> dict:
        """One count and one log line a closed record; returns what the
        recorder files with the anomaly: ``where``, ``during`` (None for
        a stall the watcher never saw) and under ``stall`` what the
        step's own record does not hold."""
        self.obs.stalls.inc()
        log.warning("helix stall %s", stall_line(closed))
        return {
            "where": closed["where"],
            "stall": {k: v for k, v in closed.items()
                      if k not in _NOT_IN_STALL},
            "during": closed.get("during"),
        }

    def end(self, where: str, during: Optional[dict], wall: float,
            cpu: Optional[float] = None, **known) -> None:
        """Close a stall that is no engine step (``where``: ``emit``,
        ``http``, ``between``): the same record, with no launch, filed as
        an anomaly of its own."""
        closed = self.closed(where, during, wall, cpu, **known)
        self.recorder().note_anomaly(
            "stall", self.file(closed), where=where,
            wall_s=closed["wall_s"])


class StallWatch:
    """The process's one stall watcher: a daemon thread,
    ``helix-stallwatch``, started with the first engine loop attached
    and stopped with the last, that wakes every ``WATCH_TICK`` seconds,
    looks at each attached loop's markers and keeps a short trail of the
    process's ``getrusage`` counters and of how late its own ticks ran
    (``since``: what a closing record reads, so the deltas cover the
    whole stall and cost the engine thread nothing).  While a watch runs, seconds of XLA
    compilation are summed from ``jax.monitoring``'s duration events
    (``compile_seconds``), where the installed JAX has them."""

    def __init__(self):
        self._lock = threading.Lock()
        self._watched: list = []
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._trail: collections.deque = collections.deque(maxlen=256)
        self.http = Mark()      # the serving event loop's heartbeat
        # what the serving surface says of itself at a capture (a
        # profiler capture running, /metrics or flight renders open)
        self.http_state: Callable[[], dict] = dict
        self.http_loop = None   # that loop, while its app is up
        self._http_cpu = 0.0    # that thread's CPU as of its last beat
        self.compile_seconds = 0.0

    def watched(self) -> list:
        return self._watched

    def attach(self, w: Watched) -> None:
        with self._lock:
            self._watched = [*self._watched, w]
            if self._thread is None:
                self._stop = threading.Event()
                self._listen(True)
                self._thread = threading.Thread(
                    target=self._run, args=(self._stop,),
                    name="helix-stallwatch", daemon=True,
                )
                self._thread.start()

    def detach(self, w: Watched) -> None:
        with self._lock:
            self._watched = [x for x in self._watched if x is not w]
            if self._watched or self._thread is None:
                return
            thread, self._thread = self._thread, None
            self._stop.set()
            self._listen(False)
        thread.join(timeout=5)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        # tracing, lowering and the backend's compile, one after another
        # (the compilation cache's events credit time SAVED: not these)
        if event.startswith("/jax/core/compile/"):
            self.compile_seconds += duration

    def _listen(self, on: bool) -> None:
        try:
            from jax import monitoring
            if on:
                monitoring.register_event_duration_secs_listener(
                    self._on_duration)
            else:
                monitoring.unregister_event_duration_listener(
                    self._on_duration)
        except Exception:  # noqa: BLE001 — an older JAX: compiled_shapes alone
            pass

    def _run(self, stop: threading.Event) -> None:
        due = time.monotonic() + WATCH_TICK
        while not stop.wait(WATCH_TICK):
            now = time.monotonic()
            self._trail.append((now, rusage(), now - due))
            for w in self._watched:
                w.look(now, now - due)
            due = now + WATCH_TICK

    def since(self, t_mono: float) -> tuple:
        """Over a stall that began at ``t_mono`` and ends now: the
        process's ``rusage`` counters now less the trail's last reading
        from before it (the oldest it has, failing that; zeros with no
        trail), and the latest the watcher's own tick ran in it (the tick
        still owed counts).  A watcher as late as the stall was long saw
        nothing hang because it hung too: the process stood still whole,
        or something held the GIL."""
        now, ru = time.monotonic(), rusage()
        trail = list(self._trail)
        before = next((r for t, r, _ in reversed(trail) if t <= t_mono),
                      trail[0][1] if trail else ru)
        late = [lt for t, _, lt in trail if t >= t_mono]
        if trail:
            late.append(now - trail[-1][0] - WATCH_TICK)
        return ({k: round(ru[k] - before[k], 6) for k in ru},
                round(max(late, default=0.0), 3))

    # -- the serving event loop (its heartbeat calls these) -----------------

    def http_running(self) -> bool:
        loop = self.http_loop
        return loop is not None and loop.is_running()

    def beat(self, lag: float) -> None:
        """One heartbeat of the serving event loop, ``lag`` seconds late,
        on that loop's thread: observed by every attached loop (a token
        of any of them waits for that thread), stamped where the watcher
        reads it, and the end of a stall of that thread if the watcher
        caught one."""
        now, cpu = time.monotonic(), time.thread_time()
        mark = self.http
        for w in self._watched:
            w.obs.http_loop_lag.observe(lag)
            during = w.take("http")
            if during is not None:
                w.end("http", during, now - during["since"],
                      cpu - self._http_cpu, span=during["span"])
        self._http_cpu = cpu
        mark.pass_no += 1
        mark.at = (mark.pass_no, 0, "helix.http.beat", now)


WATCH = StallWatch()


def stall_line(rec: dict) -> str:
    """A closed stall record as ONE log line under ``LOG_LINE_BYTES``:
    the whole of it where it fits, else without the per-phase CPU and the
    parts and with shorter stacks."""
    rec = dict(rec)
    for keep in (STACK_FRAMES, 6, 3, 0):
        during = rec.get("during")
        if during:
            rec["during"] = {**during, "threads": {
                n: {**t, "stack": t["stack"][:keep]}
                for n, t in during["threads"].items()}}
        line = json.dumps(rec, separators=(",", ":"), default=str)
        if len(line) <= LOG_LINE_BYTES:
            return line
        for k in ("phases_cpu", "parts_cpu", "parts"):
            rec.pop(k, None)
    rec.pop("during", None)
    rec.pop("phases", None)
    return json.dumps(rec, separators=(",", ":"), default=str)
