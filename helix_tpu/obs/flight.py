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
import threading
import time
from typing import Optional

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

    def record_step(self, rec: dict) -> Optional[str]:
        """Append one step record; returns the anomaly reason when the
        watchdog fired (the record itself is annotated + frozen)."""
        with self._lock:
            reason = rec.get("anomaly")
            duration = float(rec.get("duration", 0.0))
            if reason is None:
                if (
                    len(self._durations) >= self.min_samples
                    and duration > self.min_step_seconds
                    and duration
                    > self.slow_factor * self._trailing_p99_locked()
                ):
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
                self._freeze_locked(reason, rec)
            return reason

    def reset_baseline(self) -> None:
        """Drop the banked step-duration samples.  XLA-compile-laden
        first steps record as 'clean' multi-second durations and would
        inflate the trailing p99 until the window turns over; callers
        that know a compile wave just ended (warmup, profile apply)
        reset so the watchdog re-learns the true serving cadence."""
        with self._lock:
            self._durations.clear()

    def note_anomaly(self, reason: str, **attrs) -> None:
        """Freeze a snapshot for an event that is not itself a step
        (a quarantine eviction decided between steps)."""
        with self._lock:
            rec = {"ts": time.time(), "anomaly": reason, **attrs}
            self._freeze_locked(reason, rec)

    def _freeze_locked(self, reason: str, rec: dict) -> None:
        self.anomalies_total += 1
        self._anomalies.append(
            {
                "reason": reason,
                "ts": rec.get("ts", time.time()),
                "step": rec.get("step"),
                "record": dict(rec),
                # the frozen tail: batch composition of the steps
                # PRECEDING the anomaly (copies — immutable from here)
                "steps": [dict(r) for r in list(self._ring)[-self.freeze_steps:]],
            }
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

    def snapshot(self, recent: int = 64) -> dict:
        with self._lock:
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
                "recent": [dict(r) for r in list(self._ring)[-recent:]],
                "anomalies": [
                    {
                        "reason": a["reason"],
                        "ts": a["ts"],
                        "step": a["step"],
                        "record": dict(a["record"]),
                        "steps": [dict(r) for r in a["steps"]],
                    }
                    for a in self._anomalies
                ],
            }
