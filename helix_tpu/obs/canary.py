"""Correctness canaries: continuous golden-output probing per runner.

Every layer since PR 9 stakes its claim on bit-identity — spec decode,
the async pipeline, migration, multihost plan replay, adapter slots,
int8 KV all carry "greedy outputs bit-identical" proofs — but those
proofs run once, in tests.  A production runner that starts emitting
silently WRONG tokens (a stale adapter slot, a corrupted restored page
that dodged a checksum, a skewed promoted leader, a bad host) is
invisible to every speed gauge this tree exports.  This module is the
correctness counterpart of PR 4's saturation federation:

- **Golden probes** — at profile apply/warmup the runner mints one
  pinned greedy probe per serving axis the model actually exercises
  (plain decode, prefix-cache hit, spec-on row, adapter identity slot,
  int8 KV, post-migration resume).  Prompts are DERIVED (a stable hash
  of ``model:axis`` rendered into token ids), so minting is
  deterministic across process restarts; the golden token sequence is
  whatever greedy produced at mint time on this host's weights.
- :class:`CanaryProber` — a node-agent scheduler that periodically
  replays every probe through the REAL serving path
  (``EngineLoop.submit`` under the reserved ``__canary__`` tenant +
  batch sched class, riding the ordinary ragged step and WFQ ladder)
  and verifies token-level bit-identity plus black-box SLIs (TTFT,
  queue wait, tokens/s) against the golden record.  A mismatch freezes
  the flight-recorder tail, lands a typed ``canary_mismatch`` record in
  the admission-audit ring, and feeds the breaker-style health rungs:
  ``ok`` -> (``HELIX_CANARY_FAILURES`` consecutive mismatched rounds)
  -> ``failing`` -> (clean round after the reprobe backoff) ->
  ``reprobing`` -> (consecutive clean rounds) -> ``ok``.
- **Federation** — the health block rides the existing heartbeat
  payload; :func:`validate_canary_block` clamps it PR-7-style (a
  malformed block degrades to ``{}``, never rejects a heartbeat), the
  cp renders the bounded ``helix_cp_canary_*`` family and a ``canary``
  block in ``/v1/cluster/status``, and the router (opt-in
  ``HELIX_ROUTER_CANARY_AVOID=1``) hard-avoids runners whose canaries
  fail — with a serve-with-warning fallback when a possibly-false-
  positive probe would otherwise strand the LAST runner for a model.

False-positive story: only token-level MISMATCHES move the health
rungs (and only after ``HELIX_CANARY_FAILURES`` consecutive mismatched
rounds); latency SLIs and probe errors (timeout, shed under load) are
reported but never flip correctness health, and a failing runner keeps
probing so a transient corruption recovers on its own.

Every ``helix_canary_*`` / ``helix_cp_canary_*`` series is minted HERE
and only here (``tools/lint_metrics.py`` contract 14); the node agent,
control plane and router import :class:`CanaryProber`,
:func:`validate_canary_block` and :func:`canary_failing`.
"""

from __future__ import annotations

import hashlib
import logging
import math
import os
import re
import threading
import time
from typing import Callable, Optional

from helix_tpu.obs.slo import CANARY_TENANT  # noqa: F401 — re-exported

log = logging.getLogger("helix.canary")

# the serving axes a probe can cover; a model mints only the axes its
# engine actually exercises (README "Correctness canaries")
CANARY_AXES = ("decode", "prefix", "spec", "adapter", "int8", "resume")

# breaker-style health rungs.  ``failing`` AND ``reprobing`` are both
# router-avoided: during recovery only canary traffic (not foreground)
# should test a runner that was recently emitting wrong tokens.
CANARY_OK = "ok"
CANARY_FAILING = "failing"
CANARY_REPROBING = "reprobing"
CANARY_STATES = (CANARY_OK, CANARY_FAILING, CANARY_REPROBING)

# wire-block clamps (the PR 7 tenant-rollup discipline): every field a
# runner heartbeats is bounded so a hostile runner cannot grow
# control-plane memory or leak arbitrary strings into status payloads
_WIRE_MAX_AXES = 16
_WIRE_MAX_AXIS_LEN = 96
_AXIS_OK_RE = re.compile(r"[A-Za-z0-9_.:@/\-]{1,96}")

_STATE_CODES = {CANARY_OK: 0, CANARY_REPROBING: 1, CANARY_FAILING: 2}


# -- knobs (README "Config reference") ---------------------------------


def canary_enabled() -> bool:
    """``HELIX_CANARY`` — run the continuous canary scheduler (default
    off: probes consume real device steps, so the operator opts in the
    way scored routing is opted into)."""
    return os.environ.get("HELIX_CANARY", "0").lower() not in (
        "0", "false", "off", ""
    )


def _float_env(name: str, default: float, lo: float, hi: float) -> float:
    try:
        v = float(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default
    if not math.isfinite(v):
        return default
    return max(lo, min(v, hi))


def _int_env(name: str, default: int, lo: int, hi: int) -> int:
    try:
        return max(lo, min(int(os.environ.get(name, default)), hi))
    except (TypeError, ValueError):
        return default


def probe_interval() -> float:
    """``HELIX_CANARY_INTERVAL`` — seconds between probe rounds."""
    return _float_env("HELIX_CANARY_INTERVAL", 60.0, 0.05, 3600.0)


def failure_threshold() -> int:
    """``HELIX_CANARY_FAILURES`` — consecutive mismatched rounds before
    health flips to ``failing`` (and clean rounds required to recover
    from ``reprobing``)."""
    return _int_env("HELIX_CANARY_FAILURES", 2, 1, 100)


def reprobe_backoff() -> float:
    """``HELIX_CANARY_REPROBE_BACKOFF`` — seconds a failing runner
    waits between recovery probe rounds."""
    return _float_env("HELIX_CANARY_REPROBE_BACKOFF", 30.0, 0.05, 3600.0)


def axes_from_env() -> tuple:
    """``HELIX_CANARY_AXES`` — comma list restricting which axes are
    minted ('' = every axis the engine exercises; the ``resume`` axis
    is only minted when listed explicitly)."""
    raw = os.environ.get("HELIX_CANARY_AXES", "")
    if not raw.strip():
        return ()
    return tuple(
        a for a in (p.strip().lower() for p in raw.split(","))
        if a in CANARY_AXES
    )


# -- golden probes ------------------------------------------------------


def mint_prompt(model: str, axis: str, vocab_size: int,
                length: int = 8) -> list:
    """Deterministic probe prompt: a stable blake2b stream keyed on
    ``model:axis`` rendered into token ids below ``vocab_size`` — the
    same (model, axis) mints the same prompt in every process, so a
    restarted runner's canaries are comparable to its peers'.  The
    ``spec`` axis repeats its head so prompt-lookup drafting has an
    n-gram to bite on."""
    vocab = max(2, int(vocab_size))
    stream = hashlib.blake2b(
        f"helix-canary:{model}:{axis}".encode("utf-8", "replace"),
        digest_size=32,
    ).digest()
    toks = [1 + (stream[i % len(stream)] % (vocab - 1))
            for i in range(length)]
    if axis == "spec":
        half = max(1, length // 2)
        toks = toks[:half] + toks[:half]
    return toks[:length]


class GoldenProbe:
    """One pinned probe: a deterministic greedy prompt plus the token
    sequence + SLIs it produced at mint time on this host."""

    __slots__ = (
        "model", "axis", "prompt", "golden", "max_tokens",
        "golden_ttft", "golden_queue_wait", "mismatches",
        "last_ok", "last_ttft",
    )

    def __init__(self, model: str, axis: str, prompt: list,
                 golden: list, max_tokens: int,
                 golden_ttft: float = 0.0,
                 golden_queue_wait: float = 0.0):
        self.model = model
        self.axis = axis
        self.prompt = list(prompt)
        self.golden = list(golden)
        self.max_tokens = max_tokens
        self.golden_ttft = golden_ttft
        self.golden_queue_wait = golden_queue_wait
        self.mismatches = 0
        self.last_ok = True
        self.last_ttft = 0.0

    @property
    def key(self) -> str:
        return f"{self.model}:{self.axis}"


def probe_axes_for(loop) -> list:
    """The serving axes one EngineLoop actually exercises — each axis
    mints only where its code path is live, so a canary can never fail
    on a feature the model does not serve.  ``resume`` is opt-in via
    HELIX_CANARY_AXES (it replays the pinned sequence the way a
    migrated-in request would, and most deployments don't migrate)."""
    eng = getattr(loop, "engine", None)
    axes = ["decode"]
    if getattr(eng, "prefix_cache", None) is not None:
        axes.append("prefix")
    cfg = getattr(eng, "cfg", None)
    if getattr(cfg, "enable_spec_decode", False):
        axes.append("spec")
    if getattr(eng, "adapter_pool", None) is not None:
        axes.append("adapter")
    if getattr(cfg, "kv_cache_dtype", "auto") == "int8":
        axes.append("int8")
    wanted = axes_from_env()
    if wanted:
        axes = [a for a in axes if a in wanted]
        if "resume" in wanted:
            axes.append("resume")
    return axes


class CanaryProber:
    """The node-agent canary scheduler: mints golden probes at profile
    apply, replays them through the real serving path on a timer, and
    keeps the runner-level health rungs the heartbeat federates.

    Thread model: ``mint_models`` runs on the apply thread; the probe
    loop is one daemon thread; ``summary``/``snapshot``/``collect``
    are called from heartbeat and /metrics threads — shared state is
    guarded by one lock, and ``inflight`` is a plain int (GIL-atomic)
    the node agent subtracts from its saturation queue-depth so probes
    never feed the autoscaler."""

    def __init__(
        self,
        runner_id: str = "",
        models_fn: Optional[Callable[[], list]] = None,
        interval: Optional[float] = None,
        failures: Optional[int] = None,
        backoff: Optional[float] = None,
        probe_tokens: int = 8,
        probe_timeout: float = 120.0,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.runner_id = runner_id
        self.models_fn = models_fn or (lambda: [])
        self.interval = interval if interval is not None else probe_interval()
        self.failures = failures if failures is not None else (
            failure_threshold()
        )
        self.backoff = backoff if backoff is not None else reprobe_backoff()
        self.probe_tokens = probe_tokens
        self.probe_timeout = probe_timeout
        self.clock = clock
        self._lock = threading.Lock()
        self._probes: dict[str, GoldenProbe] = {}   # key -> probe
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.inflight = 0          # probes currently submitted (GIL-atomic)
        self.state = CANARY_OK
        self.rounds = 0            # completed probe rounds
        self.probes_run = 0        # individual probe replays
        self.mismatches = 0        # token-level bit-identity failures
        self.probe_errors = 0      # sheds/timeouts — never move the rungs
        self._consec_bad = 0
        self._consec_good = 0
        self.last_round_unix = 0.0
        self.last_ttft = 0.0
        self._seq = 0

    # -- minting (profile apply thread) --------------------------------

    def mint_models(self, served_models: list) -> int:
        """Mint golden probes for every newly served model (idempotent
        per (model, axis): a re-apply keeps existing goldens so a
        hot-swap cannot re-baseline around a corruption).  Returns how
        many probes were minted; never raises — a canary must not fail
        a profile apply."""
        minted = 0
        for served in served_models:
            loop = getattr(served, "loop", None)
            if loop is None or not hasattr(loop, "submit"):
                continue
            name = getattr(served, "name", "") or getattr(loop, "name", "")
            try:
                minted += self._mint_one(name, loop)
            except Exception:  # noqa: BLE001 — apply must survive
                log.warning(
                    "canary minting failed for model %s", name,
                    exc_info=True,
                )
        return minted

    def _mint_one(self, name: str, loop) -> int:
        vocab = getattr(
            getattr(loop.engine, "model_cfg", None), "vocab_size", 256
        )
        minted = 0
        for axis in probe_axes_for(loop):
            key = f"{name}:{axis}"
            with self._lock:
                if key in self._probes:
                    continue
            prompt = mint_prompt(name, axis, vocab)
            toks, ttft, qwait, err = self._replay(
                loop, name, axis, prompt
            )
            if err or not toks:
                log.warning(
                    "canary golden mint for %s skipped: %s",
                    key, err or "no tokens",
                )
                continue
            if axis == "prefix":
                # warm the cache with a second pass so steady-state
                # replays exercise the hit path the axis names
                self._replay(loop, name, axis, prompt)
            probe = GoldenProbe(
                name, axis, prompt, toks, self.probe_tokens,
                golden_ttft=ttft, golden_queue_wait=qwait,
            )
            with self._lock:
                self._probes[key] = probe
            minted += 1
        return minted

    def drop_model(self, name: str) -> None:
        """Forget a torn-down model's probes (profile diff-apply)."""
        with self._lock:
            for key in [k for k in self._probes
                        if k.split(":", 1)[0] == name]:
                del self._probes[key]

    # -- replay (probe thread; also the mint path) ---------------------

    def _replay(self, loop, model: str, axis: str, prompt: list):
        """One probe through the REAL serving path: EngineLoop.submit
        under the reserved canary tenant + batch class.  Returns
        ``(tokens, ttft_s, queue_wait_s, error)``."""
        from helix_tpu.engine.engine import Request
        from helix_tpu.engine.sampling import SamplingParams

        self._seq += 1
        rid = f"__canary__-{model}-{axis}-{self._seq}"
        done = threading.Event()
        toks: list = []
        errs: list = []
        t0 = time.monotonic()
        first = [0.0]

        def on_event(ev):
            if ev.error:
                errs.append(ev.error)
            elif ev.token_id >= 0:
                if not toks:
                    first[0] = time.monotonic() - t0
                toks.append(ev.token_id)
            if ev.finished:
                done.set()

        req = Request(
            id=rid,
            prompt_tokens=list(prompt),
            sampling=SamplingParams(
                temperature=0.0, max_tokens=self.probe_tokens,
            ),
            trace_id=rid,
            tenant=CANARY_TENANT,
            sched_class="batch",
        )
        self.inflight += 1
        try:
            loop.submit(req, on_event)
            if not done.wait(self.probe_timeout):
                try:
                    loop.abort(rid)
                except Exception:  # noqa: BLE001 — best-effort cleanup
                    pass
                return [], 0.0, 0.0, "probe_timeout"
        finally:
            self.inflight -= 1
        queue_wait = max(
            0.0, (req.admitted_time or t0) - (req.submit_time or t0)
        )
        return list(toks), first[0], queue_wait, (
            errs[0] if errs else None
        )

    # -- probe rounds + health rungs -----------------------------------

    def probe_round(self) -> dict:
        """Replay every minted probe once; compare token-level
        bit-identity against the golden record; advance the health
        rungs.  Returns ``{probes, mismatched, errors}`` for callers
        that drive rounds directly (tests, chaos)."""
        with self._lock:
            probes = list(self._probes.values())
        by_model = {}
        for served in self.models_fn():
            loop = getattr(served, "loop", None)
            if loop is not None:
                by_model[getattr(served, "name", "")] = loop
        ran = mismatched = errors = 0
        for probe in probes:
            loop = by_model.get(probe.model)
            if loop is None:
                continue
            toks, ttft, qwait, err = self._replay(
                loop, probe.model, probe.axis, probe.prompt
            )
            ran += 1
            self.probes_run += 1
            self.last_ttft = ttft
            probe.last_ttft = ttft
            if err:
                # a shed/timeout under load is a CAPACITY event the
                # saturation plane already reports — it must not brand
                # the runner as emitting wrong tokens
                self.probe_errors += 1
                errors += 1
                continue
            if toks == probe.golden:
                probe.last_ok = True
                continue
            mismatched += 1
            self.mismatches += 1
            probe.mismatches += 1
            probe.last_ok = False
            self._on_mismatch(loop, probe, toks)
        self.rounds += 1
        self.last_round_unix = time.time()
        self._advance_rungs(ran, mismatched)
        return {"probes": ran, "mismatched": mismatched,
                "errors": errors, "state": self.state}

    def _on_mismatch(self, loop, probe: GoldenProbe, got: list) -> None:
        """One bit-identity failure: freeze the flight-recorder tail,
        land the typed admission-audit record, log with the trace id."""
        detail = (
            f"axis={probe.axis} expected={probe.golden[:8]} "
            f"got={got[:8]}"
        )
        flight = getattr(loop, "flight", None)
        if flight is not None:
            flight.note_anomaly(
                "canary_mismatch", model=probe.model, axis=probe.axis,
                expected=list(probe.golden), got=list(got),
            )
        slo = getattr(loop, "slo", None)
        if slo is not None:
            slo.audit.record(
                "canary_mismatch", tenant=CANARY_TENANT,
                trace_id=f"__canary__-{probe.key}",
                request_id=f"__canary__-{probe.key}", detail=detail,
            )
        log.warning(
            "canary mismatch on runner %s model %s trace_id=%s: %s",
            self.runner_id or "-", probe.model,
            f"__canary__-{probe.key}", detail,
        )

    def _advance_rungs(self, ran: int, mismatched: int) -> None:
        if ran == 0:
            return
        if mismatched:
            self._consec_bad += 1
            self._consec_good = 0
            if (
                self.state == CANARY_OK
                and self._consec_bad >= self.failures
            ) or self.state == CANARY_REPROBING:
                self.state = CANARY_FAILING
            return
        self._consec_bad = 0
        self._consec_good += 1
        if self.state == CANARY_FAILING:
            self.state = CANARY_REPROBING
        elif self.state == CANARY_REPROBING:
            if self._consec_good >= self.failures:
                self.state = CANARY_OK

    # -- scheduler thread ----------------------------------------------

    def start(self) -> "CanaryProber":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="helix-canary", daemon=True
            )
            self._thread.start()
        set_default_prober(self)
        return self

    def _run(self) -> None:
        while not self._stop.is_set():
            # failing runners reprobe on the (usually shorter) backoff
            # cadence so recovery is not gated on the full interval
            wait = (
                self.backoff if self.state != CANARY_OK else self.interval
            )
            if self._stop.wait(wait):
                return
            try:
                self.probe_round()
            except Exception:  # noqa: BLE001 — the canary must not die
                log.warning("canary probe round failed", exc_info=True)

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=10)
            self._thread = None

    # -- read side ------------------------------------------------------

    def failing_axes(self) -> list:
        with self._lock:
            return sorted(
                p.key for p in self._probes.values() if not p.last_ok
            )[:_WIRE_MAX_AXES]

    def summary(self) -> dict:
        """The heartbeat ``canary`` block: bounded, wire-schema shaped
        (the control plane re-validates regardless).  ``{}`` before any
        probe has been minted, so idle heartbeats stay small."""
        with self._lock:
            n_probes = len(self._probes)
        if n_probes == 0 and self.rounds == 0:
            return {}
        return {
            "state": self.state,
            "rounds": self.rounds,
            "probes": n_probes,
            "mismatches": self.mismatches,
            "probe_errors": self.probe_errors,
            "failing_axes": self.failing_axes(),
            "last_round_unix": self.last_round_unix,
            "last_ttft_seconds": round(self.last_ttft, 6),
        }

    def snapshot(self) -> dict:
        """Operator introspection (debug surfaces): summary plus
        per-probe golden/latest detail."""
        with self._lock:
            probes = [
                {
                    "model": p.model,
                    "axis": p.axis,
                    "prompt_tokens": len(p.prompt),
                    "golden_tokens": len(p.golden),
                    "golden_ttft_seconds": round(p.golden_ttft, 6),
                    "mismatches": p.mismatches,
                    "ok": p.last_ok,
                }
                for p in sorted(
                    self._probes.values(), key=lambda p: p.key
                )
            ]
        return {**self.summary(), "probe_detail": probes}


def canary_failing(block) -> bool:
    """Router predicate: is this runner's federated canary health in an
    avoid rung?  ``failing`` and ``reprobing`` both avoid — while a
    runner recovers, only canary traffic (not foreground) should test
    it.  Unknown/absent/malformed health is NOT an avoid signal (a
    runner that never probed must stay routable)."""
    return isinstance(block, dict) and block.get("state") in (
        CANARY_FAILING, CANARY_REPROBING,
    )


# -- federation wire validation (the PR 7 pattern) ---------------------


def _count(v) -> int:
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return 0
    try:
        f = float(v)
    except (OverflowError, ValueError):
        return 0
    if not math.isfinite(f) or f < 0:
        return 0
    return int(min(f, 2**53))


def validate_canary_block(raw) -> dict:
    """Clamp one runner-supplied canary health block to the wire
    schema.  Like the PR 7 tenant blocks this NEVER raises and never
    rejects: a malformed block (NaN counters, oversized axis lists,
    bogus states, wrong types) degrades to ``{}`` or clamped fields —
    rejecting would TTL-evict a healthy runner over a telemetry bug."""
    if not isinstance(raw, dict):
        return {}
    state = raw.get("state")
    if state not in CANARY_STATES:
        # a bogus state cannot be trusted to mean "failing" either:
        # degrade to absent rather than letting a garbage heartbeat
        # flip routing or mint a surprise label value
        return {}
    axes = []
    raw_axes = raw.get("failing_axes")
    if isinstance(raw_axes, list):
        for a in raw_axes[:_WIRE_MAX_AXES]:
            if isinstance(a, str) and _AXIS_OK_RE.fullmatch(a):
                axes.append(a[:_WIRE_MAX_AXIS_LEN])
    try:
        last_round = float(raw.get("last_round_unix", 0.0))
    except (TypeError, ValueError):
        last_round = 0.0
    if not math.isfinite(last_round) or last_round < 0:
        last_round = 0.0
    try:
        ttft = float(raw.get("last_ttft_seconds", 0.0))
    except (TypeError, ValueError):
        ttft = 0.0
    if not math.isfinite(ttft) or ttft < 0:
        ttft = 0.0
    return {
        "state": state,
        "rounds": _count(raw.get("rounds")),
        "probes": _count(raw.get("probes")),
        "mismatches": _count(raw.get("mismatches")),
        "probe_errors": _count(raw.get("probe_errors")),
        "failing_axes": axes,
        "last_round_unix": last_round,
        "last_ttft_seconds": ttft,
    }


# -- metric minting (lint_metrics contract 14) -------------------------
#
# Every helix_canary_* / helix_cp_canary_* series is minted HERE and
# only here; the runner surface and the control plane import these
# collectors.


def collect_canary_metrics(c, prober: Optional["CanaryProber"]) -> None:
    """Runner-side canary series (scrape-time collector; plain
    GIL-atomic reads).  No-op before a prober exists."""
    if prober is None:
        return
    c.gauge(
        "helix_canary_state",
        _STATE_CODES.get(prober.state, 0),
        help="Canary health rung (0 ok / 1 reprobing / 2 failing)",
    )
    c.counter(
        "helix_canary_rounds_total", prober.rounds,
        help="Completed canary probe rounds",
    )
    c.counter(
        "helix_canary_probes_total", prober.probes_run,
        help="Individual golden-probe replays through the serving path",
    )
    c.counter(
        "helix_canary_mismatches_total", prober.mismatches,
        help="Probe replays whose tokens diverged from the golden "
             "record (bit-identity failures)",
    )
    c.counter(
        "helix_canary_probe_errors_total", prober.probe_errors,
        help="Probe replays shed or timed out (capacity events — "
             "these never move the health rungs)",
    )
    c.gauge(
        "helix_canary_last_probe_ttft_seconds",
        round(prober.last_ttft, 6),
        help="TTFT of the most recent probe (black-box SLI)",
    )


def collect_cp_canary(
    c, canary_map: dict, avoided: int = 0, served_failing: int = 0,
) -> None:
    """Control-plane canary series: one bounded row per reporting
    runner (the blocks live on RunnerState, so a runner evicted for
    staleness drops its whole series — the breaker-gauge rule), plus
    the router's avoid/fallback counters."""
    failing = 0
    for rid, block in sorted(canary_map.items()):
        state = block.get("state")
        if state in (CANARY_FAILING, CANARY_REPROBING):
            failing += 1
        lbl = {"runner": rid}
        c.gauge(
            "helix_cp_canary_state",
            _STATE_CODES.get(state, 0), lbl,
            help="Federated canary health rung per runner "
                 "(0 ok / 1 reprobing / 2 failing)",
        )
        c.counter(
            "helix_cp_canary_rounds_total",
            _count(block.get("rounds")), lbl,
            help="Probe rounds reported by the runner",
        )
        c.counter(
            "helix_cp_canary_mismatches_total",
            _count(block.get("mismatches")), lbl,
            help="Bit-identity failures reported by the runner",
        )
    c.gauge(
        "helix_cp_canary_failing_runners", failing,
        help="Runners currently in an avoid rung (failing/reprobing)",
    )
    c.counter(
        "helix_cp_canary_route_avoided_total", avoided,
        help="Picks that steered around a canary-failing runner",
    )
    c.counter(
        "helix_cp_canary_route_served_failing_total", served_failing,
        help="Picks served BY a canary-failing runner because it was "
             "the last candidate for the model (serve-with-warning)",
    )


# one process-wide prober handle so the runner's /metrics surface can
# render canary series without threading the node agent through the
# HTTP app (the obs.trace.default_store pattern)
_default_prober: Optional[CanaryProber] = None


def set_default_prober(p: Optional[CanaryProber]) -> None:
    global _default_prober
    _default_prober = p


def default_prober() -> Optional[CanaryProber]:
    return _default_prober
