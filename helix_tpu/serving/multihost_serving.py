"""Multi-host serving: plan-broadcast SPMD engines over a DCN feed.

SURVEY §2.2/§7 puts inter-slice DCN in the engine's court.  In JAX's
multi-controller model every process must issue the SAME jit calls in
the same order for collectives over a cross-host mesh to line up.
Serving has dynamic admission, so this module makes the call sequence
deterministic by construction — but unlike the original command-replay
journal (which made followers re-derive every host decision and
therefore pinned off every feature whose host state could drift), the
contract is now a **per-step plan broadcast**:

- the **leader** (process 0) takes HTTP traffic and runs the full host
  stack — admission, WFQ reorder, spec drafting, preemption-by-swap,
  prefix/filestore restoration, the async pipelined loop.  Its
  ``step_dispatch`` finalizes everything the device call needs; a
  ``PlanRecorder`` captures those decisions as *data* (admitted request
  docs with ``cached_tokens``, resume order, draft tokens, the prefill
  budget, the queue-pressure bit) and publishes ONE versioned
  ``StepPlan`` record per step; abort/preempt publish immediately as
  standalone ``ops`` records in arrival order;
- **followers** are pure device executors: ``FollowerLoop`` decodes a
  plan and drives the *same* engine step through a ``PlanDrive`` that
  pins every host decision to the leader's values.  No follower-side
  admission queue, scheduler, drafter, or clock participates — the
  follower's compiled step shapes are the leader's by construction.

Because plans pin decisions rather than forbidding them, the features
the old journal disabled are all live on meshes: spec decode (drafts
ride the plan), the adapter pool (followers stage residency before the
step), WFQ (budget + victim order are leader-decided data), preemption
(``ops`` records replay the swap in arrival order), the async pipeline
(plan N+1 publishes while device step N completes), and filestore
prefix hits (the plan carries ``cached_tokens``; point both hosts at
the same filestore dir and the drive verifies the restore matched).

Emission digests (rolling blake2s over per-step (request, token)
emissions, aborted requests excluded over a one-plan window to absorb
abort-arrival skew) let a follower detect silent divergence; the
``HELIX_MH_DIGEST`` knob picks strict/warn/off.

Transport is pluggable: in-process ``CommandLog`` (tests, and the ring
buffer the leader serves), or ``HTTPFeed`` (follower long-polls the
leader's ``/multihost/commands`` route over DCN with a pooled session).

ISSUE 17 grows the plane past two hosts and makes the leader
restartable:

- **N-follower fan-out** — every poll registers the follower's health
  with the leader (:meth:`PlanLeader.note_poll`): last-acked seq,
  applied step, apply latency, digest counters.  A follower sustained
  more than ``HELIX_MH_LAG_STEPS`` behind enters a typed ``lagging``
  state and the leader throttles admission (prefill budget pinned to 0,
  the PR 8 discipline) instead of letting the ring overflow into a
  fatal error; catch-up flips it back to ``healthy``.
- **Typed resync** — ``CommandLog.read_since`` no longer raises an
  unconditional fatal ``LagError``: overflow / leader-restart surface
  as a ``resync_required`` record whose ``reason`` distinguishes "I
  fell behind" (restart the follower process; it replays the ring)
  from "the leader restarted" (re-apply the profile), so the node
  agent can log the right operator action.
- **Leader failover** — the leader periodically checkpoints its
  host-side queue state (waiting-queue wire docs, parked-request
  snapshots, WFQ virtual service, prefill budget, spec EMAs, plan
  index + digest chain head) through :class:`CheckpointStore` (the
  PR 14 filestore tier: checksummed, versioned, written off the
  engine thread).  :func:`promote_follower` turns a live standby into
  the publishing leader at a digest-verified step boundary: the
  checkpoint's digest must match the standby's own chain BEFORE any
  allocator mutation, every active request parks (slot order) so the
  handoff boundary is reproducible, unknown waiting/parked state
  imports from the checkpoint, and the new leader's first record is a
  ``handoff`` carrying the chain head + a fresh checkpoint reference.
  Peers at the exact boundary cross over seamlessly (and keep
  verifying the chained digest across the handoff); fresh followers
  bootstrap from the referenced checkpoint; anything else fails typed
  and degrades to the full resync ladder — never worse than a leader
  restart today.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import itertools
import json
import logging
import os
import random
import re
import struct
import threading
import time
from typing import Optional

from helix_tpu.engine.engine import Request
from helix_tpu.engine.sampling import SamplingParams
from helix_tpu.obs import trace as obs_trace

log = logging.getLogger("helix.mh-serving")

#: Plan/request wire format version.  v1 was the command-replay journal
#: ({admits, aborts, step} records whose request docs dropped tenant /
#: sched_class / adapter / max_len); v2 is the step-plan broadcast.
#: Mixed-version clusters are rejected typed, never misparsed.
WIRE_VERSION = 2

#: Leader-state checkpoint format version (CheckpointStore envelopes).
CHECKPOINT_VERSION = 1

_DIGEST_SEED = b"\x00" * 16

# plan-plane trace ids must satisfy the adoptable-id shape contract
_PLAN_TID_RE = re.compile(r"[^A-Za-z0-9_-]")


def plan_trace_id(model: str) -> str:
    """The mesh's PLAN-PLANE trace identity (ISSUE 18): one stable,
    well-shaped trace id per model mesh, shared by the leader and every
    follower so plan publishes, follower applies, digest verifies,
    checkpoints and takeovers stitch into ONE federated timeline — a
    takeover blackout reads as a gap between the last leader publish
    and the promoted host's first, not just ``takeover_ms`` in the
    mesh's stats."""
    return ("mh-plan-" + _PLAN_TID_RE.sub("-", model or "default"))[:64]

#: Follower health states in the leader's registry (ISSUE 17).  Minted
#: ONLY here — lint contract 12 fences the literals; consumers
#: (node agent, control plane, /metrics) import these names.
FOLLOWER_HEALTHY = "healthy"
FOLLOWER_LAGGING = "lagging"
FOLLOWER_LOST = "lost"
FOLLOWER_STATES = (FOLLOWER_HEALTHY, FOLLOWER_LAGGING, FOLLOWER_LOST)

#: Typed reasons on ``resync_required`` records / ResyncRequired — each
#: maps to a DIFFERENT operator action (RESYNC_ACTIONS), which is the
#: point of typing them instead of one fatal LagError.
RESYNC_RING_OVERFLOW = "ring_overflow"
RESYNC_LEADER_RESTART = "leader_restart"
RESYNC_HANDOFF_MISMATCH = "handoff_mismatch"
RESYNC_CHECKPOINT_REJECTED = "checkpoint_rejected"

RESYNC_ACTIONS = {
    RESYNC_RING_OVERFLOW: (
        "this follower fell behind the leader's plan ring: restart the "
        "follower process — it rejoins by replaying the ring from the "
        "current head (raise HELIX_MH_RING to widen the window)"
    ),
    RESYNC_LEADER_RESTART: (
        "the leader restarted and its plan sequence reset: re-apply "
        "the serving profile on every host of the mesh"
    ),
    RESYNC_HANDOFF_MISMATCH: (
        "a new leader took over at a step boundary this follower is "
        "not at: restart the follower process fresh — it bootstraps "
        "from the handoff checkpoint"
    ),
    RESYNC_CHECKPOINT_REJECTED: (
        "the takeover checkpoint failed validation on this follower: "
        "restart the follower process; if it repeats, re-apply the "
        "serving profile (the checkpoint store may be corrupt)"
    ),
}


class LagError(RuntimeError):
    """Follower fell off the ring (or ahead of it — leader restart)."""


class ResyncRequired(LagError):
    """Typed resync: carries WHY lockstep must restart (``reason`` is
    one of the RESYNC_* constants) so operators get the right action
    instead of one undifferentiated fatal error."""

    def __init__(self, msg: str, reason: str = ""):
        super().__init__(msg)
        self.reason = reason


class WireVersionError(ValueError):
    """Record from a different wire version; upgrade hosts together."""


class DivergenceError(RuntimeError):
    """Replica state no longer matches the leader's plan — lockstep lost."""


class CheckpointError(RuntimeError):
    """Leader-state checkpoint unusable (typed ``code``): corrupt blob,
    unsupported version, or no checkpoint at all."""

    def __init__(self, msg: str, code: str = "checkpoint_corrupt"):
        super().__init__(msg)
        self.code = code


class CommandLog:
    """Sequenced ring buffer with blocking reads (the leader's journal).

    The ring is a ``collections.deque``: overflow past capacity is an
    O(1) ``popleft`` per dropped record, not an O(n) list re-slice per
    publish (which made sustained publish throughput quadratic once the
    ring was full)."""

    def __init__(self, capacity: int = 4096, start_seq: int = 1):
        self.capacity = capacity
        self._records: collections.deque = collections.deque()
        # a takeover leader continues the dead leader's sequence
        # (start_seq = standby's applied seq + 1) so peers at the
        # boundary poll straight across the handoff
        self._first = start_seq
        self._next = start_seq
        self._start = start_seq
        self._cond = threading.Condition()

    def publish(self, record: dict) -> int:
        with self._cond:
            seq = self._next
            self._next += 1
            self._records.append({**record, "seq": seq})
            while len(self._records) > self.capacity:
                self._records.popleft()
                self._first += 1
            self._cond.notify_all()
            return seq

    def _resync_record(self, reason: str, since: int, msg: str) -> dict:
        """Typed ``resync_required`` record (ISSUE 17 bugfix): overflow
        and leader-restart used to surface as one unconditional fatal
        LagError raised here; as a RECORD the reason rides the feed
        transparently (HTTP included), the follower's stats can tell
        "leader restarted" from "I fell behind", and the node agent
        logs the matching operator action (RESYNC_ACTIONS)."""
        return {
            "v": WIRE_VERSION,
            "kind": "resync_required",
            "reason": reason,
            "seq": since,       # echoes the reader: applied_seq unchanged
            "first": self._first,
            "next": self._next,
            "error": msg,
        }

    def read_since(self, since: int, timeout: float = 30.0) -> list:
        """Records with seq > since; blocks up to timeout when none.
        A reader the ring can no longer serve gets a single typed
        ``resync_required`` record instead of an exception."""
        deadline = time.monotonic() + timeout
        with self._cond:
            while True:
                if since + 1 < self._first:
                    if since < self._start and self._first == self._start:
                        # the reader predates this leader's epoch (a
                        # fresh follower joining after a takeover) and
                        # the epoch head — the handoff record — is
                        # still retained: serve from the head so it
                        # can bootstrap from the handoff checkpoint
                        since = self._start - 1
                    else:
                        return [self._resync_record(
                            RESYNC_RING_OVERFLOW, since,
                            f"follower at seq {since} fell behind the "
                            f"ring (first retained: {self._first})",
                        )]
                if since >= self._next:
                    # AHEAD of the journal: the leader restarted and its
                    # sequence reset — silent empty polls here would hang
                    # the whole cluster mid-collective; surface it typed
                    # so the follower restarts and resyncs
                    return [self._resync_record(
                        RESYNC_LEADER_RESTART, since,
                        f"follower at seq {since} is ahead of the "
                        f"journal (next: {self._next}) — leader "
                        "restart?",
                    )]
                skip = max(0, since + 1 - self._first)
                out = list(itertools.islice(self._records, skip, None))
                if out:
                    return out
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return []
                self._cond.wait(remaining)


def request_to_wire(req: Request) -> dict:
    if req.image_embeds is not None:
        raise ValueError(
            "multi-host serving covers text models (VL image embeds are "
            "device-resident and not broadcast)"
        )
    return {
        "v": WIRE_VERSION,
        "id": req.id,
        "prompt_tokens": list(req.prompt_tokens),
        "sampling": dataclasses.asdict(req.sampling),
        "stop_token_ids": list(req.stop_token_ids),
        "tenant": req.tenant,
        "sched_class": req.sched_class,
        "adapter": req.adapter,
        "max_len": req.max_len,
        "trace_id": req.trace_id,
    }


def request_from_wire(doc: dict) -> Request:
    v = doc.get("v")
    if v != WIRE_VERSION:
        raise WireVersionError(
            f"request wire record version {v!r} (this host speaks "
            f"{WIRE_VERSION}); v1 records dropped tenant/sched_class/"
            "adapter/max_len and are rejected rather than misparsed — "
            "upgrade the leader and followers together"
        )
    return Request(
        id=doc["id"],
        prompt_tokens=list(doc["prompt_tokens"]),
        sampling=SamplingParams(**doc["sampling"]),
        stop_token_ids=tuple(doc["stop_token_ids"]),
        tenant=doc["tenant"],
        sched_class=doc["sched_class"],
        adapter=doc["adapter"],
        max_len=doc["max_len"],
        trace_id=doc.get("trace_id", ""),
    )


def mh_checkpoint_dir() -> str:
    """HELIX_MH_CHECKPOINT_DIR: root of the leader-state checkpoint
    store ('' = failover disabled).  Point every host of the mesh at
    the SAME directory (the PR 14 cluster-wide filestore tier)."""
    return os.environ.get("HELIX_MH_CHECKPOINT_DIR", "")


def checkpoint_store_from_env() -> Optional["CheckpointStore"]:
    d = mh_checkpoint_dir()
    return CheckpointStore(d) if d else None


class CheckpointStore:
    """Leader-state checkpoints through the PR 14 filestore tier.

    Same discipline as the KV filestore rung: a rooted
    ``control.filestore.Filestore`` under a reserved owner, every blob
    a checksummed + versioned envelope verified BEFORE use (corruption
    = typed rejection, never a misparse), writes queued to a single
    background writer so the engine thread never blocks on disk, and a
    keep-newest-K prune so the store stays bounded."""

    #: reserved owner prefix — tenants can't collide with it
    #: (Filestore._resolve keeps owners disjoint)
    OWNER = "__mh_ckpt__"

    def __init__(self, root: str, keep: Optional[int] = None):
        from helix_tpu.control.filestore import Filestore

        self.store = Filestore(root)
        if keep is None:
            try:
                keep = int(os.environ.get("HELIX_MH_CHECKPOINT_KEEP",
                                          "3") or 3)
            except ValueError:
                keep = 3
        self.keep = max(1, keep)
        self._mu = threading.Lock()
        self._writeq = None
        self._writer = None
        # counters (mh_stats / collect_mh_metrics)
        self.writes = 0
        self.write_errors = 0
        self.write_drops = 0
        self.corrupt_rejected = 0
        self.bytes_last = 0

    @staticmethod
    def _model_dir(model: str) -> str:
        safe = "".join(
            ch if ch.isalnum() or ch in "._-" else "_"
            for ch in (model or "model")
        )
        return safe or "model"

    def _blob_name(self, model: str, plan_idx: int, seq: int) -> str:
        # plan_idx starts at -1 (nothing published yet); +1 keeps the
        # zero-padded name sortable
        return (f"{self._model_dir(model)}/"
                f"ckpt-{plan_idx + 1:016d}-{max(0, seq):016d}.json")

    def save(self, model: str, state: dict) -> tuple:
        """Synchronous write (the promote path: the handoff record
        references the blob, so it must be durable first).  Returns
        ``(ref, nbytes)``."""
        payload = json.dumps(state, separators=(",", ":"),
                             sort_keys=True)
        blob_doc = {
            "v": CHECKPOINT_VERSION,
            "checksum": hashlib.blake2b(
                payload.encode(), digest_size=16
            ).hexdigest(),
            "payload": payload,
        }
        blob = json.dumps(blob_doc).encode()
        blob = self._maybe_corrupt(model, blob)
        ref = self._blob_name(
            model, int(state.get("plan_idx", -1)),
            int(state.get("seq", 0)),
        )
        self.store.write(self.OWNER, ref, blob)
        self.writes += 1
        self.bytes_last = len(blob)
        self._prune(model)
        return ref, len(blob)

    @staticmethod
    def _maybe_corrupt(model: str, blob: bytes) -> bytes:
        """Deterministic fault hook (testing/faults.py ``checkpoint``
        rules): flip one payload byte so the NEXT load rejects the blob
        the way real disk corruption would."""
        try:
            from helix_tpu.testing.faults import active
        except Exception:  # noqa: BLE001 — faults module optional
            return blob
        inj = active()
        if inj is None or not inj.checkpoint_fault(model):
            return blob
        mid = len(blob) // 2
        return blob[:mid] + bytes([blob[mid] ^ 0xFF]) + blob[mid + 1:]

    def save_async(self, model: str, state: dict) -> None:
        """Queue a periodic checkpoint for the background writer (the
        engine thread captures state; disk latency must not stall the
        step cadence — the ``_store_filestore_pages`` discipline).
        Bounded queue: a stuck disk drops checkpoints (counted), it
        never backpressures serving."""
        import queue as _queue

        with self._mu:
            if self._writer is None:
                self._writeq = _queue.Queue(maxsize=4)
                self._writer = threading.Thread(
                    target=self._write_loop,
                    name="mh-ckpt-writer", daemon=True,
                )
                self._writer.start()
        try:
            self._writeq.put_nowait((model, state))
        except _queue.Full:
            self.write_drops += 1

    def _write_loop(self) -> None:
        while True:
            model, state = self._writeq.get()
            try:
                self.save(model, state)
            except Exception:  # noqa: BLE001 — background writer
                self.write_errors += 1
                log.exception("leader checkpoint write failed")
            finally:
                self._writeq.task_done()

    def flush(self, timeout: float = 30.0) -> None:
        """Block until queued async writes land (tests, promote)."""
        q = self._writeq
        if q is None:
            return
        deadline = time.monotonic() + timeout
        while q.unfinished_tasks and time.monotonic() < deadline:
            time.sleep(0.01)

    def load(self, ref: str) -> dict:
        """Read + validate one checkpoint blob.  Every rung is typed:
        unreadable/corrupt envelope, checksum mismatch, or a version
        this build does not speak — callers NEVER see a half-trusted
        state dict (validate before mutate)."""
        try:
            blob = self.store.read(self.OWNER, ref)
        except OSError as e:
            raise CheckpointError(
                f"checkpoint {ref!r} unreadable: {e}",
                code="checkpoint_missing",
            )
        try:
            doc = json.loads(blob.decode())
        except (ValueError, UnicodeDecodeError):
            self.corrupt_rejected += 1
            raise CheckpointError(
                f"checkpoint {ref!r} is not a valid envelope"
            )
        if doc.get("v") != CHECKPOINT_VERSION:
            raise CheckpointError(
                f"checkpoint {ref!r} version {doc.get('v')!r} (this "
                f"build speaks {CHECKPOINT_VERSION})",
                code="checkpoint_version",
            )
        payload = doc.get("payload", "")
        claimed = str(doc.get("checksum", ""))
        have = hashlib.blake2b(
            payload.encode(), digest_size=16
        ).hexdigest()
        if not claimed or have != claimed:
            self.corrupt_rejected += 1
            raise CheckpointError(
                f"checkpoint {ref!r} checksum mismatch"
            )
        state = json.loads(payload)
        if state.get("version") != CHECKPOINT_VERSION:
            raise CheckpointError(
                f"checkpoint {ref!r} state version "
                f"{state.get('version')!r}", code="checkpoint_version",
            )
        return state

    def list_refs(self, model: str) -> list:
        """Checkpoint refs for ``model``, newest first."""
        d = self._model_dir(model)
        try:
            entries = self.store.list(self.OWNER, d)
        except PermissionError:
            return []
        names = sorted(
            (e["path"] for e in entries if not e.get("is_dir")),
            reverse=True,
        )
        return [f"{d}/{os.path.basename(n)}" for n in names]

    def load_latest(self, model: str) -> tuple:
        """Newest USABLE checkpoint as ``(ref, state)``.  A corrupt or
        version-skewed blob is skipped (counted) and the next older one
        tried — one bad write must not take failover down with it.
        Raises typed CheckpointError when nothing usable exists."""
        last_err = None
        for ref in self.list_refs(model):
            try:
                return ref, self.load(ref)
            except CheckpointError as e:
                last_err = e
                continue
        if last_err is not None:
            raise CheckpointError(
                f"no usable checkpoint for {model!r} (newest failure: "
                f"{last_err})", code=last_err.code,
            )
        raise CheckpointError(
            f"no checkpoint exists for {model!r}",
            code="checkpoint_missing",
        )

    def _prune(self, model: str) -> None:
        refs = self.list_refs(model)
        for ref in refs[self.keep:]:
            try:
                self.store.delete(self.OWNER, ref)
            except Exception:  # noqa: BLE001 — best-effort prune
                pass

    def stats(self) -> dict:
        return {
            "writes": self.writes,
            "write_errors": self.write_errors,
            "write_drops": self.write_drops,
            "corrupt_rejected": self.corrupt_rejected,
            "bytes_last": self.bytes_last,
        }


def export_sched_state(sched) -> Optional[dict]:
    """WFQ virtual-service snapshot for the leader checkpoint (None for
    FIFO / no scheduler: nothing worth carrying across a takeover)."""
    vsrv = getattr(sched, "_vsrv", None)
    vfloor = getattr(sched, "_vfloor", None)
    lock = getattr(sched, "_lock", None)
    if vsrv is None or vfloor is None or lock is None:
        return None
    with lock:
        return {
            "vsrv": {c: dict(t) for c, t in vsrv.items()},
            "vfloor": dict(vfloor),
        }


def restore_sched_state(sched, doc) -> bool:
    """Seed a fresh scheduler with a checkpointed WFQ snapshot so the
    promoted leader keeps charging tenants where the dead one left off
    (fair-share does not reset to zero on failover)."""
    if not doc:
        return False
    vsrv = getattr(sched, "_vsrv", None)
    vfloor = getattr(sched, "_vfloor", None)
    lock = getattr(sched, "_lock", None)
    if vsrv is None or vfloor is None or lock is None:
        return False
    with lock:
        for cls, tenants in (doc.get("vsrv") or {}).items():
            if cls in vsrv and isinstance(tenants, dict):
                vsrv[cls].update(
                    {str(t): float(v) for t, v in tenants.items()}
                )
        for cls, v in (doc.get("vfloor") or {}).items():
            if cls in vfloor:
                vfloor[cls] = float(v)
    return True


def export_spec_state(engine) -> Optional[dict]:
    """Per-request speculative-decoding acceptance EMAs (engine/spec.py
    ``_slots``): carried across a takeover so drafting does not re-probe
    every request from the optimistic start."""
    spec = getattr(engine, "spec", None)
    slots = getattr(spec, "_slots", None)
    if not slots:
        return None
    out = {}
    for rid, st in list(slots.items()):
        out[rid] = {
            "ema": float(getattr(st, "ema", 1.0)),
            "enabled": bool(getattr(st, "enabled", True)),
            "cooldown": int(getattr(st, "cooldown", 0)),
            "drafted": int(getattr(st, "drafted", 0)),
            "accepted": int(getattr(st, "accepted", 0)),
        }
    return out


def restore_spec_state(engine, doc) -> int:
    spec = getattr(engine, "spec", None)
    if spec is None or not doc:
        return 0
    n = 0
    for rid, st in doc.items():
        try:
            slot = spec._state(rid)
            slot.ema = float(st.get("ema", 1.0))
            slot.enabled = bool(st.get("enabled", True))
            slot.cooldown = int(st.get("cooldown", 0))
            slot.drafted = int(st.get("drafted", 0))
            slot.accepted = int(st.get("accepted", 0))
            n += 1
        except Exception:  # noqa: BLE001 — EMAs are best-effort
            continue
    return n


class PlanRecorder:
    """Captures the leader engine's per-dispatch host decisions as data.

    The engine duck-types this via ``self._plan_recorder`` (set around
    ``step_dispatch`` by :class:`PlanLeader`): admission claims call
    ``note_admit`` after ``cached_tokens`` is final, resumes append the
    resumed request id, spec drafting stores the drafted tokens per
    slot, the dispatch prologue stores the prefill budget and the
    queue-pressure bit that pins the decode window, and each admission
    wave stores the slots it launched live (the running rows decode a
    token inside the wave)."""

    __slots__ = ("admits", "resumes", "drafts", "budget", "queue_blocked",
                 "wave_rows")

    def __init__(self):
        self.admits: list = []
        self.resumes: list = []
        self.drafts: list = []
        self.budget = None
        self.queue_blocked = False
        self.wave_rows: list = []   # one list of slots a wave, launch order

    def note_admit(self, req: Request) -> None:
        doc = request_to_wire(req)
        doc["cached_tokens"] = int(req.cached_tokens)
        self.admits.append(doc)


class PlanDrive:
    """Pins a follower engine's host decisions to the leader's plan.

    The engine duck-types this via ``self._plan_drive`` (set around
    ``step()`` by :class:`FollowerLoop`): the prefill budget and the
    queue-pressure bit are overridden, spec drafting consumes the
    plan's draft tokens verbatim instead of running the host drafter,
    each admission wave launches live the slots the leader's did,
    resumes happen exactly in plan order, and each admission claim
    verifies its locally-restored ``cached_tokens`` against the
    leader's value (a mismatch means the prefix/filestore rungs drifted
    between hosts and the device steps would desync)."""

    __slots__ = ("budget", "queue_blocked", "drafts", "resumes",
                 "cached_tokens", "wave_rows")

    def __init__(self, budget, queue_blocked, drafts, resumes,
                 cached_tokens, wave_rows=()):
        self.budget = budget
        self.queue_blocked = bool(queue_blocked)
        self.drafts = drafts
        self.resumes = resumes
        self.cached_tokens = cached_tokens
        self.wave_rows = [list(slots) for slots in wave_rows]


def _fold_digest(prev: bytes, step_idx: int, emissions, excluded) -> bytes:
    """Roll the emission digest forward over one step.

    Emissions are folded sorted (order within a step is host-side
    bookkeeping, not model output) and requests in ``excluded`` —
    aborted in this plan or the next — are skipped: an abort lands on
    the leader at arrival but on followers at the next plan boundary,
    so tail emissions of an aborted request legitimately differ over a
    one-plan window."""
    h = hashlib.blake2s(prev)
    h.update(struct.pack("<q", step_idx))
    for rid, tok in sorted(emissions):
        if rid in excluded:
            continue
        b = rid.encode("utf-8", "surrogatepass")
        h.update(struct.pack("<I", len(b)))
        h.update(b)
        h.update(struct.pack("<q", int(tok)))
    return h.digest()[:16]


class PlanLeader:
    """Engine wrapper for the leader: broadcasts one StepPlan per step.

    Duck-types the Engine surface EngineLoop uses (add_request / abort /
    step / step_dispatch / step_complete / pipeline_ready /
    discard_pending / has_work / validate_request / reap_stuck / slots /
    waiting ...).  Unlike the old command-replay journal
    it does NOT disable anything: preemption, spec decode, adapters,
    WFQ, the async pipeline, filestore prefix hits, and drain-time
    snapshot export all run on the leader and replicate as plan data.
    """

    def __init__(self, engine, journal: Optional[CommandLog] = None,
                 checkpoint_store: Optional[CheckpointStore] = None,
                 name: str = ""):
        self.engine = engine
        if journal is None:
            cap = int(os.environ.get("HELIX_MH_RING", "4096") or 4096)
            journal = CommandLog(capacity=cap)
        self.journal = journal
        self.name = name
        self._seed_counter = itertools.count(0x5EED)
        # -- N-follower health registry (ISSUE 17) ----------------------
        # follower_id -> {last_poll, last_seq, applied_step, lag_steps,
        # state, apply_ms, digest_checks, digest_mismatches, standby}
        self._followers: dict = {}
        self._followers_mu = threading.Lock()
        self.lag_steps_limit = int(
            os.environ.get("HELIX_MH_LAG_STEPS", "64") or 64
        )
        self.max_followers = int(
            os.environ.get("HELIX_MH_MAX_FOLLOWERS", "16") or 16
        )
        self.follower_ttl = float(
            os.environ.get("HELIX_MH_FOLLOWER_TTL", "15") or 15
        )
        self.followers_dropped = 0
        self.throttled_steps = 0
        self.takeovers = 0
        self.takeover_ms = 0.0
        # -- leader-state checkpointing (failover) ----------------------
        self.checkpoint_store = checkpoint_store
        self.checkpoint_seconds = float(
            os.environ.get("HELIX_MH_CHECKPOINT_SECONDS", "5") or 5
        )
        self._ckpt_last = 0.0
        self._ckpt_sched = None   # last sched snapshot seen (takeover carry)
        self.checkpoints_captured = 0
        self.checkpoint_errors = 0
        # serializes abort/preempt arrival against plan assembly: ops
        # publish IMMEDIATELY in arrival order, so the stream position
        # of an op relative to the surrounding plans is exactly the
        # order the leader's engine saw it
        self._mu = threading.Lock()
        self._carry_admits: list = []     # re-carried from a failed plan
        self._carry_resumes: list = []
        self._carry_emissions: list = []
        self._step_counter = 0
        self._last_plan_idx = -1
        self._dispatch_steps: dict = {}   # id(pend) -> plan idx
        self._plan_content: dict = {}     # plan idx -> (admits, resumes)
        self._emissions: dict = {}        # plan idx -> [(rid, tok)]
        self._done_steps: set = set()
        # plan idx -> rids aborted between that plan and the next one
        # (the digest-exclusion window: those aborts race the step's
        # completion on the leader but land post-step on followers)
        self._aborts_after_plan: dict = {}
        self._fold_next = 0
        self._digest = _DIGEST_SEED
        self._digest_step: Optional[int] = None
        self._digest_reset_pending = False
        # surfaced by /admin stats
        self.plans_published = 0
        self.plan_bytes_total = 0
        self.plan_bytes_max = 0
        # plan-plane tracing (ISSUE 18): publish/checkpoint/takeover
        # spans land under one stable per-mesh trace id in the
        # process-wide store and federate to the cp like any runner
        # span; tests swap the store per "host"
        self._trace = obs_trace.default_store()
        self.plan_trace_id = plan_trace_id(name)

    # -- attributes EngineLoop SETS on its engine must reach the real
    # engine (a plain __getattr__ passthrough would shadow them here and
    # silently break WFQ fair-share charging and victim ordering) ------
    @property
    def prefill_budget(self):
        return self.engine.prefill_budget

    @prefill_budget.setter
    def prefill_budget(self, value):
        self.engine.prefill_budget = value

    @property
    def on_admit(self):
        return self.engine.on_admit

    @on_admit.setter
    def on_admit(self, value):
        self.engine.on_admit = value

    @property
    def device_wait(self):
        return self.engine.device_wait

    @device_wait.setter
    def device_wait(self, value):
        self.engine.device_wait = value

    @property
    def victim_policy(self):
        return self.engine.victim_policy

    @victim_policy.setter
    def victim_policy(self, value):
        self.engine.victim_policy = value

    # -- mutations ----------------------------------------------------------
    def add_request(self, req: Request) -> None:
        if req.sampling.seed is None:
            # pin a seed so follower sampling is bit-identical without
            # relying on engine-internal PRNG call order
            req.sampling = dataclasses.replace(
                req.sampling, seed=next(self._seed_counter)
            )
        # validate wire-encodability up front (VL rejects here, not at
        # admission time deep inside a step)
        request_to_wire(req)
        self.engine.add_request(req)

    def _req_trace(self, rid: str) -> str:
        """The request's trace id if the engine still knows it (looked
        up BEFORE the engine op — an aborted request is gone after)."""
        get = getattr(self.engine, "get_request", None)
        req = get(rid) if callable(get) else None
        tid = getattr(req, "trace_id", "") if req is not None else ""
        return tid if obs_trace.is_trace_id(tid) else ""

    def _publish_op(self, op: str, rid: str, tid: str = "") -> None:
        # ops records publish at arrival (not at the next dispatch):
        # an abort with no step behind it must still reach followers,
        # or they keep a zombie request parked forever
        t0 = time.monotonic()
        rec: dict = {
            "v": WIRE_VERSION, "kind": "ops", "ops": [[op, rid]],
        }
        if tid:
            # ISSUE 18 bugfix: the op carries the request's trace id so
            # a cp-initiated abort is traceable THROUGH the follower —
            # HTTPFeed poll responses deliver it with the record
            rec["traces"] = {rid: tid}
        self.journal.publish(rec)
        if tid:
            self._trace.record(
                tid, "mh op publish", t0, time.monotonic(),
                plane="engine", op=op, request_id=rid,
                seq=self.journal._next - 1,
            )
        if op == "abort":
            self._aborts_after_plan.setdefault(
                self._last_plan_idx, set()
            ).add(rid)

    def abort(self, request_id: str) -> None:
        with self._mu:
            tid = self._req_trace(request_id)
            self.engine.abort(request_id)
            self._publish_op("abort", request_id, tid)

    def preempt(self, request_id: str) -> bool:
        with self._mu:
            tid = self._req_trace(request_id)
            ok = self.engine.preempt(request_id)
            if ok:
                self._publish_op("preempt", request_id, tid)
            return ok

    def preempt_for_pressure(self) -> Optional[str]:
        with self._mu:
            rid = self.engine.preempt_for_pressure()
            if rid is not None:
                self._publish_op("preempt", rid, self._req_trace(rid))
            return rid

    # snapshot IMPORT and the disaggregated prefill handoff (ISSUE
    # 11/14) would create device state that exists only on the leader —
    # a migrated-in request has no admission plan row followers could
    # replay, so its later resume would diverge.  Export stays live
    # (drain-time snapshots are leader-owned; the shipped request's
    # abort rides the next plan like any abort).
    import_request = None
    export_prefill = None

    def reap_stuck(self, max_queue_seconds: float) -> list:
        # the reaper scans the waiting queue only, and waiting requests
        # are never broadcast (only ADMITTED requests ride plans) — so a
        # reap needs no wire record at all: followers never knew the
        # request existed
        return self.engine.reap_stuck(max_queue_seconds)

    # -- follower health (ISSUE 17: N-follower fan-out) ---------------------
    def note_poll(self, follower_id: str, since: int,
                  applied_step: Optional[int] = None,
                  apply_ms: Optional[float] = None,
                  digest_checks: Optional[int] = None,
                  digest_mismatches: Optional[int] = None,
                  standby: bool = False) -> None:
        """Register one follower poll.  Called by the plan-feed route
        (HTTPFeed sends the health fields as query params) or directly
        by in-process feeds.  Bounded: at most ``max_followers``
        registrations; beyond that, new ids are dropped (counted) so a
        querystring fuzzer cannot grow the registry — or /metrics label
        cardinality — without bound."""
        if not follower_id:
            return
        now = time.monotonic()
        with self._followers_mu:
            st = self._followers.get(follower_id)
            if st is None:
                if len(self._followers) >= self.max_followers:
                    self._prune_followers(now)
                if len(self._followers) >= self.max_followers:
                    self.followers_dropped += 1
                    return
                st = self._followers[follower_id] = {
                    "state": FOLLOWER_HEALTHY,
                    "registered_ago": 0.0,
                    "applied_step": -1,
                    "lag_steps": 0,
                    "apply_ms": 0.0,
                    "digest_checks": 0,
                    "digest_mismatches": 0,
                    "standby": False,
                    "_registered": now,
                }
            st["last_poll"] = now
            st["last_seq"] = int(since)
            st["standby"] = bool(standby) or st["standby"]
            if applied_step is not None:
                st["applied_step"] = int(applied_step)
            if apply_ms is not None:
                st["apply_ms"] = float(apply_ms)
            if digest_checks is not None:
                st["digest_checks"] = int(digest_checks)
            if digest_mismatches is not None:
                st["digest_mismatches"] = int(digest_mismatches)
            lag = max(0, self._last_plan_idx - st["applied_step"])
            st["lag_steps"] = lag
            # the lag ladder: healthy <-> lagging with hysteresis (a
            # follower hovering at the limit must not flap the
            # admission throttle every poll); a lost follower that
            # polls again rejoins through the same rungs
            if lag > self.lag_steps_limit:
                st["state"] = FOLLOWER_LAGGING
            elif (st["state"] != FOLLOWER_HEALTHY
                  and lag <= max(1, self.lag_steps_limit // 2)):
                st["state"] = FOLLOWER_HEALTHY
            elif st["state"] == FOLLOWER_LOST:
                st["state"] = (FOLLOWER_LAGGING
                               if lag > self.lag_steps_limit // 2
                               else FOLLOWER_HEALTHY)

    def _refresh_follower_states(self, now: float) -> None:
        """Lock must be held: a follower that stopped polling for the
        TTL is ``lost`` — it no longer counts toward the admission
        throttle (a dead host must not freeze admission forever)."""
        for st in self._followers.values():
            if now - st.get("last_poll", 0.0) > self.follower_ttl:
                st["state"] = FOLLOWER_LOST

    def _prune_followers(self, now: float) -> None:
        """Lock must be held: evict long-lost followers to make room."""
        self._refresh_follower_states(now)
        for fid in [
            fid for fid, st in self._followers.items()
            if st["state"] == FOLLOWER_LOST
            and now - st.get("last_poll", 0.0) > 4 * self.follower_ttl
        ]:
            del self._followers[fid]

    def _lag_throttle_active(self) -> bool:
        """True while any live follower is lagging: the leader stops
        admitting new prefills (budget pinned to 0, the PR 8 budget
        discipline) so decode-only steps let the follower drain the
        ring, instead of the ring overflowing into a fatal resync."""
        with self._followers_mu:
            self._refresh_follower_states(time.monotonic())
            return any(
                st["state"] == FOLLOWER_LAGGING
                for st in self._followers.values()
            )

    def follower_health(self) -> dict:
        now = time.monotonic()
        with self._followers_mu:
            self._refresh_follower_states(now)
            out = {}
            for fid, st in self._followers.items():
                doc = {k: v for k, v in st.items()
                       if not k.startswith("_")}
                doc["registered_ago"] = round(
                    now - st.get("_registered", now), 3
                )
                doc["last_poll_ago"] = round(
                    now - st.get("last_poll", now), 3
                )
                doc.pop("last_poll", None)
                out[fid] = doc
            return out

    def mh_stats(self) -> dict:
        """Leader-side mesh health: plan-stream counters + the
        per-follower registry + checkpoint/takeover accounting.  Duck-
        typed by EngineLoop.stats() and collect_mh_metrics()."""
        followers = self.follower_health()
        states = {s: 0 for s in FOLLOWER_STATES}
        for st in followers.values():
            states[st["state"]] = states.get(st["state"], 0) + 1
        cs = self.checkpoint_store
        return {
            "role": "leader",
            "plans_published": self.plans_published,
            "plan_bytes_total": self.plan_bytes_total,
            "plan_bytes_max": self.plan_bytes_max,
            "last_plan_idx": self._last_plan_idx,
            "last_seq": self.journal._next - 1,
            "followers": followers,
            "follower_states": states,
            "followers_dropped": self.followers_dropped,
            "lag_steps_limit": self.lag_steps_limit,
            "throttled_steps": self.throttled_steps,
            "takeovers": self.takeovers,
            "takeover_ms": round(self.takeover_ms, 3),
            "checkpoints_captured": self.checkpoints_captured,
            "checkpoint_errors": self.checkpoint_errors,
            "checkpoint_seconds": self.checkpoint_seconds,
            "checkpoint_store": cs.stats() if cs is not None else None,
        }

    # -- leader-state checkpointing (ISSUE 17: failover) --------------------
    def checkpoint_due(self) -> bool:
        """Cheap gate the engine loop polls each iteration; the real
        capture is fenced behind a pipeline reconcile by the caller."""
        if self.checkpoint_store is None or self.checkpoint_seconds <= 0:
            return False
        return (time.monotonic() - self._ckpt_last
                >= self.checkpoint_seconds)

    def checkpoint_tick(self, sched=None) -> None:
        """Capture host-side queue state at a quiescent step boundary
        (engine thread, no step in flight — the caller reconciled) and
        queue it for the background filestore writer.  Capture is
        host-state only (waiting-queue wire docs + PARKED request
        snapshots from the host pool — no device gathers), so the step
        cadence pays dict-building, not disk."""
        if not self.checkpoint_due():
            return
        self._ckpt_last = time.monotonic()
        t0 = self._ckpt_last
        if sched is not None:
            self._ckpt_sched = export_sched_state(sched)
        try:
            state = self._capture_state()
        except Exception:  # noqa: BLE001 — checkpointing must not kill steps
            self.checkpoint_errors += 1
            log.exception("leader checkpoint capture failed")
            return
        if state is None:
            return
        self.checkpoints_captured += 1
        self.checkpoint_store.save_async(self.name, state)
        # capture cost on the step cadence is part of the plan-plane
        # timeline (write-out is async; this span is the capture only)
        self._trace.record(
            self.plan_trace_id, "mh checkpoint", t0, time.monotonic(),
            plane="engine", plan_idx=self._last_plan_idx,
            snapshots=len(state.get("snapshots", ())),
        )

    def _capture_state(self) -> Optional[dict]:
        """Everything a standby needs to continue the leader's host
        decisions: the waiting queue as wire docs, parked/preempted
        requests as full PR 11 snapshots, WFQ virtual service, prefill
        budget, spec EMAs, and the plan index + digest chain head that
        anchor the handoff verification."""
        from helix_tpu.serving.migration import snapshot_to_wire

        eng = self.engine
        with self._mu:
            if self._dispatch_steps:
                return None   # step in flight: not a plan boundary
            snaps = []
            for st in list(getattr(eng, "preempted", [])):
                rid = st.req.id
                try:
                    snap = eng.export_request(rid)
                except Exception:  # noqa: BLE001 — skip one, keep the rest
                    log.exception("checkpoint export failed for %s", rid)
                    snap = None
                if snap is not None:
                    snaps.append(snapshot_to_wire(snap))
            waiting = []
            for r in list(eng.waiting):
                try:
                    doc = request_to_wire(r)
                except ValueError:
                    continue   # VL cannot ride the wire
                waiting.append(doc)
            return {
                "version": CHECKPOINT_VERSION,
                "model": self.name,
                "plan_idx": self._last_plan_idx,
                "seq": self.journal._next - 1,
                "step_counter": self._step_counter,
                "digest": self._digest.hex(),
                "digest_step": self._digest_step,
                "fold_next": self._fold_next,
                "digest_reset_pending": self._digest_reset_pending,
                "pending_emissions": {
                    str(k): [[rid, int(t)] for rid, t in v]
                    for k, v in self._emissions.items()
                },
                "done_steps": sorted(self._done_steps),
                "aborts_after_plan": {
                    str(k): sorted(v)
                    for k, v in self._aborts_after_plan.items()
                },
                "active_ids": [
                    r.id for r in eng.slots if r is not None
                ],
                "snapshots": snaps,
                "waiting": waiting,
                "budget": eng.prefill_budget,
                "sched": self._ckpt_sched,
                "spec": export_spec_state(eng),
                "adapters": sorted(
                    getattr(eng, "resident_adapters", lambda: [])()
                ) if hasattr(eng, "resident_adapters") else [],
            }

    # -- the step plan ------------------------------------------------------
    def step_dispatch(self):
        eng = self.engine
        throttled = self._followers and self._lag_throttle_active()
        if throttled:
            # pin the prefill budget to 0 for THIS dispatch: no new
            # admissions, decode-only — the plan carries budget=0 so
            # followers see the same decision, and the loop's scheduler
            # re-derives its own budget next pass once the lagging
            # follower catches up
            saved_budget = eng.prefill_budget
            eng.prefill_budget = 0
            self.throttled_steps += 1
        try:
            return self._step_dispatch_inner(eng)
        finally:
            if throttled:
                eng.prefill_budget = saved_budget

    def _step_dispatch_inner(self, eng):
        t0 = time.monotonic()
        with self._mu:
            carry_admits, self._carry_admits = self._carry_admits, []
            carry_resumes, self._carry_resumes = self._carry_resumes, []
            carry_ems, self._carry_emissions = self._carry_emissions, []
            step_idx = self._step_counter
            self._step_counter += 1
            rec = PlanRecorder()
            eng._plan_recorder = rec
            try:
                emitted, pend = eng.step_dispatch()
            except Exception:
                # dispatch failed part-way: admissions recorded before
                # the failure already mutated engine state and MUST
                # still reach followers — re-carry them into the retry's
                # plan, reuse the index, and restart the digest chain
                # (emission attribution across the failure is not
                # reconstructible)
                self._carry_admits = carry_admits + rec.admits
                self._carry_resumes = carry_resumes + rec.resumes
                self._carry_emissions = carry_ems
                self._step_counter = step_idx
                self._reset_digest_chain()
                raise
            finally:
                eng._plan_recorder = None
            admits = carry_admits + rec.admits
            resumes = carry_resumes + rec.resumes
            self._advance_digest(step_idx)
            record = {
                "v": WIRE_VERSION,
                "kind": "plan",
                "step": step_idx,
                "admits": admits,
                "resumes": resumes,
                "budget": rec.budget,
                "queue_blocked": rec.queue_blocked,
                "wave_rows": rec.wave_rows,
                "drafts": rec.drafts,
                "digest_step": self._digest_step,
                "digest": (self._digest.hex()
                           if self._digest_step is not None else None),
            }
            if self._digest_reset_pending:
                record["digest_reset"] = True
                self._digest_reset_pending = False
            self.journal.publish(record)
            self._last_plan_idx = step_idx
            self.plans_published += 1
            nbytes = len(json.dumps(record, separators=(",", ":")))
            self.plan_bytes_total += nbytes
            self.plan_bytes_max = max(self.plan_bytes_max, nbytes)
            # plan-plane span (ISSUE 18): dispatch through publish,
            # keyed by the plan seq so the follower's apply span for
            # the same step correlates across hosts
            self._trace.record(
                self.plan_trace_id, "mh plan publish", t0,
                time.monotonic(), plane="engine", step=step_idx,
                seq=self.journal._next - 1, bytes=nbytes,
                admits=len(admits),
            )
            ems = carry_ems + [(r.id, int(t)) for r, t in emitted]
            self._emissions[step_idx] = ems
            if pend is None:
                self._done_steps.add(step_idx)
            else:
                self._dispatch_steps[id(pend)] = step_idx
                self._plan_content[step_idx] = (admits, resumes)
            return emitted, pend

    def pipeline_ready(self) -> bool:
        """A plan leader looks ahead in plain decode steady state only
        (``Engine.steady_decode`` says why): every other pass reconciles
        the step in flight before it dispatches."""
        return self.engine.pipeline_ready() and self.engine.steady_decode()

    def step_complete(self, pend, emitted=None):
        base = len(emitted) if emitted is not None else 0
        out = self.engine.step_complete(pend, emitted)
        with self._mu:
            idx = self._dispatch_steps.pop(id(pend), None)
            if idx is not None:
                self._emissions.setdefault(idx, []).extend(
                    (r.id, int(t)) for r, t in out[base:]
                )
                self._done_steps.add(idx)
                self._plan_content.pop(idx, None)
        return out

    def step(self):
        emitted, pend = self.step_dispatch()
        if pend is None:
            return emitted
        try:
            return self.step_complete(pend, emitted)
        except Exception:
            self.discard_pending(pend)
            raise

    def discard_pending(self, pend) -> None:
        self.engine.discard_pending(pend)
        with self._mu:
            idx = self._dispatch_steps.pop(id(pend), None)
            if idx is None:
                return
            # the published plan never ran to completion on the leader.
            # Publish a discard marker so a replaying/rejoining follower
            # skips the dead plan; its host effects (admissions and
            # resumes survive the positional rollback) are re-carried
            # into the retry's plan.  A live follower that already
            # executed the plan treats the marker as lost lockstep and
            # restarts — on a real cross-host mesh the failed collective
            # has desynced the slice anyway, so the restart ladder is
            # the honest recovery path.
            admits, resumes = self._plan_content.pop(idx)
            self._carry_admits = admits + self._carry_admits
            self._carry_resumes = resumes + self._carry_resumes
            self._carry_emissions = (
                self._emissions.pop(idx, []) + self._carry_emissions
            )
            self._done_steps.discard(idx)
            self.journal.publish(
                {"v": WIRE_VERSION, "kind": "discard", "step": idx}
            )
            self._reset_digest_chain()

    def _reset_digest_chain(self) -> None:
        self._digest = _DIGEST_SEED
        self._digest_step = None
        self._digest_reset_pending = True
        self._emissions.clear()
        self._done_steps.clear()
        self._aborts_after_plan.clear()
        self._fold_next = self._step_counter

    def _advance_digest(self, plan_idx: int) -> None:
        # digest(M) folds step M's emissions minus requests aborted in
        # the stream window between plan M and plan M+1: those aborts
        # race step M's completion on the leader (the engine skips a
        # freed slot's emission at reconcile) but land post-step on
        # followers, so both sides exclude them.  Folding M therefore
        # waits until plan M+1 is being published, when the window has
        # closed.
        while self._fold_next < plan_idx:
            m = self._fold_next
            if m not in self._done_steps:
                break
            excl = self._aborts_after_plan.pop(m, set())
            ems = self._emissions.pop(m, [])
            self._digest = _fold_digest(self._digest, m, ems, excl)
            self._digest_step = m
            self._done_steps.discard(m)
            self._fold_next += 1

    # -- passthrough --------------------------------------------------------
    def __getattr__(self, name):
        return getattr(self.engine, name)


class FollowerLoop:
    """Drives this host's engine replica from the leader's plan feed.

    Recovery posture (round-3 verdict weak #7 — the failure paths need
    drills, not just detection):

    - **Follower killed mid-stream**: start a NEW FollowerLoop with a
      fresh engine replica and replay from seq 0 — as long as the ring
      still retains the journal head, replay reconstructs bit-identical
      engine state (``test_multihost_serving.TestFailureDrills``).  The
      engine is deterministic given the plan sequence, so rejoining is
      a pure function of the ring.
    - **Fell off the ring / leader restarted / divergence detected**:
      fatal for lockstep.  The loop stops, ``error`` carries an
      operator-actionable message, and ``on_lost_lockstep(error)`` fires
      so the node agent can surface it (restart the serving process; it
      will resync by replaying the ring, or from the profile re-apply
      if the ring head is gone).
    - **Transient feed errors** retry with capped exponential backoff +
      jitter (``HELIX_MH_BACKOFF_BASE``/``HELIX_MH_BACKOFF_CAP``);
      counters are surfaced in :meth:`stats`.
    """

    def __init__(self, engine, feed, poll_timeout: float = 5.0,
                 on_lost_lockstep=None, name: str = "",
                 follower_id: str = "", standby: Optional[bool] = None,
                 checkpoint_store: Optional[CheckpointStore] = None,
                 on_leader_lost=None):
        self.engine = engine
        self.feed = feed                  # .read_since(seq, timeout)
        self.poll_timeout = poll_timeout
        self.name = name                  # model (fault keying, ckpt refs)
        self.follower_id = follower_id or (
            os.environ.get("HELIX_MH_FOLLOWER_ID", "")
            or f"follower-{os.getpid():x}"
        )
        if standby is None:
            standby = (os.environ.get("HELIX_MH_STANDBY", "")
                       .strip().lower() in ("1", "true", "yes", "on"))
        self.standby = bool(standby)
        self.checkpoint_store = checkpoint_store
        self.applied_seq = 0
        self.steps = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.error: Optional[str] = None
        self.on_lost_lockstep = on_lost_lockstep
        # standby auto-promotion trigger: after this many CONSECUTIVE
        # transient feed failures (the leader host is gone, not just a
        # blip) a standby stops retrying and fires on_leader_lost so
        # the node agent can promote it (0 = never self-trigger)
        self.on_leader_lost = on_leader_lost
        self.promote_after = int(
            os.environ.get("HELIX_MH_PROMOTE_AFTER", "0") or 0
        )
        self.digest_mode = (
            os.environ.get("HELIX_MH_DIGEST", "strict").strip().lower()
            or "strict"
        )
        self.backoff_base = float(
            os.environ.get("HELIX_MH_BACKOFF_BASE", "0.05") or 0.05
        )
        self.backoff_cap = float(
            os.environ.get("HELIX_MH_BACKOFF_CAP", "5.0") or 5.0
        )
        self._skip: set = set()            # plan idxs discarded by the leader
        self._applied_step = -1
        self._prev = None                  # (step idx, emissions)
        # plan idx -> rids aborted by ops records seen after that plan;
        # mirrors the leader's digest-exclusion window by stream position
        self._aborts_after_plan: dict = {}
        self._digest = _DIGEST_SEED
        self._digest_by_step: collections.OrderedDict = (
            collections.OrderedDict()
        )
        self._last_folded_step: Optional[int] = None
        # fresh-bootstrap digest adoption (see _fold_and_check): after
        # joining via a handoff checkpoint we track the leader's chain
        # verbatim until it catches up to our own first executed step
        self._adopt_digest = False
        # rids aborted via ops records (bounded): a takeover must not
        # resurrect them from a pre-abort checkpoint
        self._ops_aborted: collections.OrderedDict = (
            collections.OrderedDict()
        )
        # counters (stats())
        self.plans_applied = 0
        self.plans_skipped = 0
        self.feed_errors = 0
        self.backoff_seconds_total = 0.0
        self.digest_checks = 0
        self.digest_mismatches = 0
        self.records_duplicate = 0
        self.records_gap = 0
        self.handoffs = 0
        self.resync_reason = ""
        self.apply_ms = 0.0                # EMA of per-plan apply wall
        # plan-plane tracing (ISSUE 18): apply/digest spans land under
        # the mesh's shared plan trace id, keyed by plan step/seq so
        # they correlate with the leader's publish spans after
        # federation stitches both hosts on the cp
        self._trace = obs_trace.default_store()
        self.plan_trace_id = plan_trace_id(name)
        # in-process feeds register our health with the leader the way
        # HTTPFeed does via query params
        if hasattr(feed, "bind_follower"):
            feed.bind_follower(self)

    # -- plan application ---------------------------------------------------
    def apply(self, record: dict) -> None:
        v = record.get("v")
        if v != WIRE_VERSION:
            raise WireVersionError(
                f"plan record version {v!r} (this host speaks "
                f"{WIRE_VERSION}) — upgrade the leader and followers "
                "together"
            )
        if record.get("kind") == "resync_required":
            reason = record.get("reason", "")
            self.resync_reason = reason
            raise ResyncRequired(
                record.get("error")
                or f"leader requires resync ({reason})",
                reason=reason,
            )
        if record.get("kind") == "handoff":
            self._apply_handoff(record)
            return
        if record.get("kind") == "discard":
            self._handle_discard(record)
            self.applied_seq = record["seq"]
            return
        if record.get("kind") == "ops":
            self._apply_ops(record)
            self.applied_seq = record["seq"]
            return
        step_idx = record["step"]
        if step_idx in self._skip:
            # the leader discarded this plan before completing it
            self._skip.discard(step_idx)
            self.plans_skipped += 1
            self.applied_seq = record["seq"]
            return
        if step_idx <= self._applied_step:
            # a plan we already executed arriving again is not a
            # harmless duplicate (seq dedup upstream catches those):
            # the stream itself went backwards — lockstep is gone
            raise DivergenceError(
                f"plan {step_idx} arrived again (this replica already "
                f"applied through step {self._applied_step})"
            )
        t0 = time.monotonic()
        self._fold_and_check(record)
        eng = self.engine
        cached = {}
        for doc in record.get("admits", []):
            req = request_from_wire(doc)
            if req.adapter and hasattr(eng, "ensure_adapter_resident"):
                if not eng.ensure_adapter_resident(req.adapter):
                    raise DivergenceError(
                        f"plan {step_idx}: adapter {req.adapter!r} for "
                        f"{req.id} is not stageable on this replica"
                    )
            cached[req.id] = int(doc.get("cached_tokens", 0))
            eng.add_request(req)
        if record.get("resumes") and hasattr(eng, "ensure_adapter_resident"):
            want = set(record["resumes"])
            for st in list(getattr(eng, "preempted", [])):
                if st.req.id in want and st.req.adapter:
                    eng.ensure_adapter_resident(st.req.adapter)
        drive = PlanDrive(
            budget=record.get("budget"),
            queue_blocked=record.get("queue_blocked", False),
            drafts=[(int(s), [int(t) for t in toks])
                    for s, toks in record.get("drafts", [])],
            resumes=list(record.get("resumes", [])),
            cached_tokens=cached,
            wave_rows=[[int(i) for i in slots]
                       for slots in record.get("wave_rows", [])],
        )
        eng._plan_drive = drive
        try:
            emitted = eng.step()
        finally:
            eng._plan_drive = None
        if eng.waiting:
            raise DivergenceError(
                f"plan {step_idx}: {len(eng.waiting)} admitted requests "
                "left unclaimed after the step — replica resources do "
                "not match the leader's"
            )
        if drive.resumes:
            raise DivergenceError(
                f"plan {step_idx}: resumes not applied: {drive.resumes}"
            )
        if drive.wave_rows:
            raise DivergenceError(
                f"plan {step_idx}: {len(drive.wave_rows)} of the leader's "
                "admission waves not launched, rows "
                f"{drive.wave_rows} did not decode in them"
            )
        self._prev = (step_idx, [(r.id, int(t)) for r, t in emitted])
        self._applied_step = step_idx
        self.steps += 1
        self.plans_applied += 1
        self.applied_seq = record["seq"]
        dt_ms = (time.monotonic() - t0) * 1000.0
        self.apply_ms = (dt_ms if self.apply_ms == 0.0
                         else 0.8 * self.apply_ms + 0.2 * dt_ms)
        # the follower half of the plan-plane timeline (ISSUE 18):
        # same trace id and step/seq as the leader's publish span
        self._trace.record(
            self.plan_trace_id, "mh plan apply", t0, time.monotonic(),
            plane="engine", step=step_idx, seq=record["seq"],
            follower=self.follower_id,
        )

    def _apply_ops(self, record: dict) -> None:
        # ops records sit in the stream exactly where the leader's
        # engine saw the abort/preempt relative to the surrounding
        # plans, so applying them in stream order keeps the replica's
        # slot/page state in step
        eng = self.engine
        raw_traces = record.get("traces")
        op_traces = raw_traces if isinstance(raw_traces, dict) else {}
        for op in record.get("ops", []):
            kind, rid = op[0], op[1]
            t0 = time.monotonic()
            if kind == "abort":
                eng.abort(rid)
                self._aborts_after_plan.setdefault(
                    self._applied_step, set()
                ).add(rid)
                # remember the abort (bounded): a later takeover must
                # not resurrect this request from an older checkpoint
                self._ops_aborted[rid] = True
                while len(self._ops_aborted) > 65536:
                    self._ops_aborted.popitem(last=False)
            elif kind == "preempt":
                if not eng.preempt(rid):
                    raise DivergenceError(
                        f"ops after step {self._applied_step}: preempt "
                        f"of {rid} failed on this replica (request "
                        "unknown or not swappable)"
                    )
            else:
                raise DivergenceError(
                    f"ops after step {self._applied_step}: unknown op "
                    f"{kind!r}"
                )
            # under the REQUEST's trace id (carried by the op record,
            # ISSUE 18 bugfix): a cp-initiated abort now shows its
            # follower-side application on the same stitched timeline
            tid = op_traces.get(rid, "")
            if obs_trace.is_trace_id(tid):
                self._trace.record(
                    tid, "mh op apply", t0, time.monotonic(),
                    plane="engine", op=kind, request_id=rid,
                    follower=self.follower_id,
                )

    def _handle_discard(self, record: dict) -> None:
        target = record["step"]
        self._skip.discard(target)
        if target <= self._applied_step:
            raise DivergenceError(
                f"this replica already executed step {target} that the "
                "leader discarded after a step failure"
            )
        # the plan was skipped (or predates our join): restart the
        # digest chain in step with the leader's reset
        self._prev = None
        self._digest = _DIGEST_SEED
        self._aborts_after_plan.clear()
        self._last_folded_step = None

    # -- takeover handoff (ISSUE 17) ----------------------------------------
    def _apply_handoff(self, record: dict) -> None:
        """A new leader took over at plan ``plan_idx``.  Three rungs:

        - **seamless cross-over** — this replica is at EXACTLY the
          boundary and its digest chain matches the record's head: park
          every active request (slot order — the same boundary parking
          the promoted leader did), keep going.  Zero lost state.
        - **fresh bootstrap** — this replica has executed nothing:
          import the referenced checkpoint (validated before any
          mutation) and join at the boundary.
        - anything else is typed ``resync_required``: restart fresh
          and take the bootstrap rung — the degrade ladder, never a
          silent divergence."""
        plan_idx = int(record["plan_idx"])
        fresh = self._applied_step < 0 and self.plans_applied == 0
        if fresh:
            self._bootstrap_from_handoff(record)
        elif self._applied_step == plan_idx:
            # verify the chained digest ACROSS the handoff before any
            # mutation: the new leader adopted the standby's chain; if
            # ours disagrees we were already diverged from the old
            # stream and must not cross over
            ds = record.get("digest_step")
            want = record.get("digest")
            if ds is not None and want:
                have = self._digest_by_step.get(ds)
                self.digest_checks += 1
                if have is not None and have != want:
                    self.digest_mismatches += 1
                    msg = (f"handoff digest mismatch at step {ds}: new "
                           f"leader {want}, replica {have}")
                    if self.digest_mode == "strict":
                        raise DivergenceError(msg)
                    log.warning("%s", msg)
            self._preempt_all_active()
        else:
            self.resync_reason = RESYNC_HANDOFF_MISMATCH
            raise ResyncRequired(
                f"leader handoff at step {plan_idx} but this replica "
                f"is at step {self._applied_step} — "
                + RESYNC_ACTIONS[RESYNC_HANDOFF_MISMATCH],
                reason=RESYNC_HANDOFF_MISMATCH,
            )
        self.handoffs += 1
        self.applied_seq = record["seq"]

    def _preempt_all_active(self) -> None:
        """Park every slot-active request in slot order: the promoted
        leader did exactly this at the boundary, so replica slot/page
        state matches and the resumes the new leader schedules replay
        deterministically on both sides."""
        eng = self.engine
        for req in list(eng.slots):
            if req is None:
                continue
            if not eng.preempt(req.id):
                raise DivergenceError(
                    f"handoff: cannot park active request {req.id} on "
                    "this replica (leader failover needs the host KV "
                    "tier — host_pool_bytes > 0 — on every host)"
                )

    def _bootstrap_from_handoff(self, record: dict) -> None:
        """Fresh replica joining a post-takeover stream: rebuild engine
        state from the handoff's checkpoint.  All snapshots decode and
        checksum-validate BEFORE the first import touches the
        allocator; a failure leaves this (empty) replica restartable
        with a typed reason."""
        ref = record.get("ckpt")
        if not ref or self.checkpoint_store is None:
            self.resync_reason = RESYNC_CHECKPOINT_REJECTED
            raise ResyncRequired(
                "handoff carries no loadable checkpoint (set "
                "HELIX_MH_CHECKPOINT_DIR to the shared filestore on "
                "every host) — "
                + RESYNC_ACTIONS[RESYNC_CHECKPOINT_REJECTED],
                reason=RESYNC_CHECKPOINT_REJECTED,
            )
        from helix_tpu.serving.migration import wire_to_snapshot

        try:
            ckpt = self.checkpoint_store.load(ref)
            # decode + meta-checksum EVERY snapshot before importing
            # any (import_request re-verifies page checksums before
            # its own allocator mutation)
            snaps = [wire_to_snapshot(doc)
                     for doc in ckpt.get("snapshots", [])]
        except Exception as e:  # noqa: BLE001 — typed reject, not a crash
            self.resync_reason = RESYNC_CHECKPOINT_REJECTED
            raise ResyncRequired(
                f"handoff checkpoint {ref!r} rejected: {e} — "
                + RESYNC_ACTIONS[RESYNC_CHECKPOINT_REJECTED],
                reason=RESYNC_CHECKPOINT_REJECTED,
            )
        eng = self.engine
        for snap in snaps:
            eng.import_request(snap)   # parks KV-bearing snapshots
        # waiting-queue docs are NOT imported: the new leader holds the
        # queue and will admit them through future plan records
        restore_spec_state(eng, ckpt.get("spec"))
        self._applied_step = int(record["plan_idx"])
        self._prev = None
        self._adopt_digest = True

    def _fold_and_check(self, record: dict) -> None:
        if record.get("digest_reset"):
            self._prev = None
            self._digest = _DIGEST_SEED
            self._aborts_after_plan.clear()
            self._last_folded_step = None
            self._adopt_digest = False
        if self._adopt_digest:
            # fresh bootstrap from a handoff checkpoint: the steps the
            # leader is still folding digests for ran before we joined,
            # so we ADOPT its published chain verbatim until it reaches
            # our own first executed step — from there normal folding
            # takes over and mismatches are detectable again
            ds = record.get("digest_step")
            want = record.get("digest")
            if ds is not None and want:
                self._digest = bytes.fromhex(want)
                self._digest_by_step[ds] = want
                self._last_folded_step = ds
                if self._prev is not None and self._prev[0] <= ds:
                    self._prev = None
                for k in [k for k in self._aborts_after_plan
                          if k <= ds]:
                    self._aborts_after_plan.pop(k, None)
                if (self._prev is not None
                        and self._prev[0] == ds + 1):
                    self._adopt_digest = False
            return
        if self._prev is not None:
            m, ems = self._prev
            excl = self._aborts_after_plan.pop(m, set())
            self._digest = _fold_digest(self._digest, m, ems, excl)
            self._digest_by_step[m] = self._digest.hex()
            self._last_folded_step = m
            self._prev = None
            while len(self._digest_by_step) > 128:
                self._digest_by_step.popitem(last=False)
        want = record.get("digest")
        ds = record.get("digest_step")
        if want is None or ds is None or self.digest_mode == "off":
            return
        have = self._digest_by_step.get(ds)
        if have is None:
            # we joined (or reset) after step ds; nothing to compare
            return
        self.digest_checks += 1
        t0 = time.monotonic()
        ok = have == want
        # digest verification is a first-class plan-plane event
        # (ISSUE 18): a mismatch must be findable on the stitched
        # timeline at the exact step where lockstep died
        self._trace.record(
            self.plan_trace_id, "mh digest verify", t0,
            time.monotonic(), plane="engine", step=ds,
            seq=record.get("seq", -1),
            outcome="ok" if ok else "mismatch",
            follower=self.follower_id,
        )
        if not ok:
            self.digest_mismatches += 1
            msg = (f"emission digest mismatch at step {ds}: leader "
                   f"{want}, replica {have}")
            if self.digest_mode == "strict":
                raise DivergenceError(msg)
            log.warning("%s", msg)

    # -- pump ----------------------------------------------------------------
    def _pump(self, records: list) -> int:
        """Apply one poll's batch under strict sequence discipline:
        records sort by seq (a reordering transport is repaired, not
        fatal), already-applied seqs skip idempotently (duplicates),
        and a GAP stops the batch — the missing record re-reads from
        the ring on the next poll.  This is what makes the plan-feed
        fault family (drop/duplicate/reorder) recoverable instead of a
        divergence."""
        records = _maybe_fault_records(self.name, records)
        records = sorted(records, key=lambda r: r.get("seq", 0))
        # prescan for discard markers so a replayed/batched feed skips
        # dead plans instead of executing steps the leader rolled back
        for r in records:
            if r.get("kind") == "discard":
                self._skip.add(r.get("step"))
        applied = 0
        for r in records:
            kind = r.get("kind")
            if kind == "handoff":
                # epoch-boundary record: carries its own seq semantics,
                # but a re-delivered handoff we already crossed must
                # still dedup (a second preempt-all would diverge)
                if 0 < r.get("seq", 0) <= self.applied_seq:
                    self.records_duplicate += 1
                    continue
                self.apply(r)
                applied += 1
                continue
            if kind == "resync_required":
                # typed ladder record: seq mirrors OUR position, so it
                # bypasses the gap/dup discipline by design
                self.apply(r)
                applied += 1
                continue
            seq = r.get("seq", 0)
            if seq <= self.applied_seq:
                self.records_duplicate += 1
                continue
            if seq > self.applied_seq + 1:
                self.records_gap += 1
                break
            self.apply(r)
            applied += 1
        return applied

    def run_once(self, timeout: Optional[float] = None) -> int:
        records = self.feed.read_since(
            self.applied_seq,
            timeout=self.poll_timeout if timeout is None else timeout,
        )
        return self._pump(records)

    def _fail(self, msg: str) -> None:
        action = RESYNC_ACTIONS.get(
            self.resync_reason,
            "restart this follower with a fresh engine replica (it "
            "replays the leader's ring from the retained head on "
            "start); if the ring no longer retains it, re-apply the "
            "serving profile on every host",
        )
        self.error = f"{msg} — lockstep lost; {action}"
        log.error("follower lost lockstep: %s", self.error)
        if self.on_lost_lockstep is not None:
            try:
                self.on_lost_lockstep(self.error)
            except Exception:  # noqa: BLE001 — operator hook
                log.exception("on_lost_lockstep hook failed")

    def start(self) -> "FollowerLoop":
        def run():
            attempt = 0
            while not self._stop.is_set():
                try:
                    records = self.feed.read_since(
                        self.applied_seq, timeout=self.poll_timeout
                    )
                except LagError as e:
                    # falling off the ring (or a leader restart) is
                    # fatal for lockstep: the process must restart and
                    # resync from the ring head (or a profile re-apply
                    # when the head is gone)
                    self._fail(str(e))
                    return
                except Exception as e:  # noqa: BLE001 — transient feed
                    attempt += 1
                    self.feed_errors += 1
                    if (self.standby and self.promote_after > 0
                            and attempt >= self.promote_after):
                        # the leader host is GONE, not blinking: a
                        # standby stops retrying and hands control to
                        # the promotion hook (node agent / operator)
                        self.error = (
                            f"leader unreachable after {attempt} "
                            f"consecutive feed failures ({e}) — "
                            "standby ready for promotion"
                        )
                        log.error("%s", self.error)
                        if self.on_leader_lost is not None:
                            try:
                                self.on_leader_lost(self)
                            except Exception:  # noqa: BLE001 — hook
                                log.exception(
                                    "on_leader_lost hook failed"
                                )
                        return
                    delay = min(
                        self.backoff_cap,
                        self.backoff_base * (2 ** min(attempt, 16)),
                    ) * (0.5 + random.random() / 2.0)
                    self.backoff_seconds_total += delay
                    log.warning(
                        "follower feed error (attempt %d, retry in "
                        "%.2fs): %s", attempt, delay, e,
                    )
                    self._stop.wait(delay)
                    continue
                attempt = 0
                try:
                    self._pump(records)
                except (LagError, WireVersionError, DivergenceError) as e:
                    self._fail(str(e))
                    return
                except Exception as e:  # noqa: BLE001 — half-applied plan
                    # an engine error mid-plan cannot be retried (the
                    # plan may be half-applied) — treat as divergence
                    self._fail(f"plan apply failed: {e!r}")
                    return

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=10)

    def drain_feed(self, timeout: float = 0.25) -> int:
        """Consume whatever tail the feed still serves without blocking
        on new publishes (the promote path: a leader that died AFTER
        publishing records the standby has not applied yet must not
        lose them — this is the CommandLog-tail replay that carries the
        standby to the digest-verified boundary)."""
        total = 0
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                n = self.run_once(timeout=0.02)
            except Exception:  # noqa: BLE001 — feed is dying; tail over
                break
            if n == 0:
                break
            total += n
        return total

    def stats(self) -> dict:
        return {
            "follower_id": self.follower_id,
            "standby": self.standby,
            "applied_seq": self.applied_seq,
            "applied_step": self._applied_step,
            "steps": self.steps,
            "plans_applied": self.plans_applied,
            "plans_skipped": self.plans_skipped,
            "feed_errors": self.feed_errors,
            "backoff_seconds_total": round(self.backoff_seconds_total, 3),
            "digest_mode": self.digest_mode,
            "digest_checks": self.digest_checks,
            "digest_mismatches": self.digest_mismatches,
            "records_duplicate": self.records_duplicate,
            "records_gap": self.records_gap,
            "handoffs": self.handoffs,
            "resync_reason": self.resync_reason,
            "apply_ms": round(self.apply_ms, 3),
            "reconnects": getattr(self.feed, "reconnects", 0),
        }


class HTTPFeed:
    """Follower-side transport: long-poll the leader over DCN.

    Keeps a pooled ``requests.Session`` alive across polls (one TCP/TLS
    handshake per leader, not per long-poll); on a transport error the
    pool is dropped so the next poll reconnects cleanly, counted in
    ``reconnects``."""

    def __init__(self, leader_url: str, model: str):
        self.leader_url = leader_url.rstrip("/")
        self.model = model
        self._session = None
        self.reconnects = 0
        self._follower = None

    def bind_follower(self, follower) -> None:
        """FollowerLoop self-registration: every poll carries the
        follower's identity + health as query params so the leader's
        registry (PlanLeader.note_poll) sees N followers without a
        second control channel."""
        self._follower = follower

    def _sess(self):
        if self._session is None:
            import requests

            self._session = requests.Session()
        return self._session

    def read_since(self, since: int, timeout: float = 30.0) -> list:
        params = {
            "since": since, "timeout": timeout, "model": self.model,
        }
        f = self._follower
        if f is not None:
            params.update({
                "follower_id": f.follower_id,
                "applied_step": f._applied_step,
                "apply_ms": round(f.apply_ms, 3),
                "digest_checks": f.digest_checks,
                "digest_mismatches": f.digest_mismatches,
                "standby": int(f.standby),
            })
        try:
            resp = self._sess().get(
                f"{self.leader_url}/multihost/commands",
                params=params,
                timeout=timeout + 10,
            )
            doc = resp.json()
        except Exception:
            # drop the pooled connections; the next poll reconnects
            self.reconnects += 1
            sess, self._session = self._session, None
            if sess is not None:
                try:
                    sess.close()
                except Exception:  # noqa: BLE001 — best-effort teardown
                    pass
            raise
        if doc.get("lagged"):
            raise LagError(doc.get("error", "fell off the leader's ring"))
        return doc.get("records", [])


class LocalFeed:
    """In-process feed (tests, chaos): reads the leader's ring
    directly AND registers the bound follower's health on every poll —
    the same contract HTTPFeed provides via query params over DCN, so
    the N-follower registry and lag ladder exercise without HTTP."""

    def __init__(self, leader: PlanLeader, follower_id: str = ""):
        self.leader = leader
        self.follower_id = follower_id
        self._follower = None
        self.reconnects = 0

    def bind_follower(self, follower) -> None:
        self._follower = follower
        if not self.follower_id:
            self.follower_id = follower.follower_id

    def retarget(self, leader: PlanLeader) -> None:
        """Point the feed at a NEW leader (post-takeover re-point)."""
        self.leader = leader
        self.reconnects += 1

    def read_since(self, since: int, timeout: float = 30.0) -> list:
        f = self._follower
        self.leader.note_poll(
            self.follower_id or "local", since,
            applied_step=f._applied_step if f is not None else None,
            apply_ms=f.apply_ms if f is not None else None,
            digest_checks=f.digest_checks if f is not None else None,
            digest_mismatches=(f.digest_mismatches
                               if f is not None else None),
            standby=f.standby if f is not None else False,
        )
        return self.leader.journal.read_since(since, timeout)


def _maybe_fault_records(model: str, records: list) -> list:
    """Plan-feed fault hook (testing/faults.py): deterministically
    drop / duplicate / delay / reorder records of one poll batch, keyed
    by model+step.  The seq discipline in FollowerLoop._pump is what
    makes these recoverable — which is exactly what the fault family
    exists to prove."""
    if not records:
        return records
    try:
        from helix_tpu.testing.faults import active
    except Exception:  # noqa: BLE001 — faults module optional
        return records
    inj = active()
    if inj is None:
        return records
    out = []
    reorder = False
    for r in records:
        act = inj.plan_feed_fault(
            model, r.get("step", r.get("seq", 0))
        )
        if act is None:
            out.append(r)
            continue
        action = act.get("action", "")
        if action == "drop":
            continue
        if action == "duplicate":
            out.extend([r, r])
        elif action == "delay":
            time.sleep(float(act.get("seconds", 0.05)))
            out.append(r)
        elif action == "reorder":
            reorder = True
            out.append(r)
        else:
            out.append(r)
    if reorder and len(out) > 1:
        out = list(reversed(out))
    return out


# ---------------------------------------------------------------------------
# leader failover (ISSUE 17)
# ---------------------------------------------------------------------------

def promote_follower(follower: FollowerLoop,
                     store: Optional[CheckpointStore] = None,
                     name: str = "",
                     journal_capacity: Optional[int] = None,
                     sched=None) -> PlanLeader:
    """Promote a live standby follower into the publishing leader.

    The digest-verified handoff, in order — every rung validates BEFORE
    it mutates, and every failure raises typed (DivergenceError /
    ResyncRequired / CheckpointError) leaving the operator on today's
    full-resync ladder, never worse:

    1. stop the pump thread and **drain the feed tail** — records the
       dead leader published that this standby has not applied yet
       replay now (the CommandLog-tail replay to the boundary);
    2. load the newest usable checkpoint and **verify its digest chain
       head against the standby's own chain** — a standby that would
       diverge refuses here, before any allocator mutation;
    3. **park every slot-active request in slot order** — the boundary
       every surviving peer can reproduce from the handoff record (and
       the reason failover requires the host KV tier);
    4. import checkpoint state the standby never saw (the waiting
       queue and parked requests admitted before the standby joined),
       skipping everything the replica already knows or saw aborted;
    5. build the PlanLeader with the **digest chain continued
       exactly** (same chain value, same pending fold window, same
       abort-exclusion windows) and the journal sequence continued
       (peers at the boundary poll straight across);
    6. write a **fresh checkpoint at the boundary** and publish a
       ``handoff`` record referencing it as the first record of the
       new epoch — fresh followers bootstrap from it, peers verify the
       chained digest across the handoff.
    """
    t0 = time.monotonic()
    name = name or follower.name
    store = store if store is not None else follower.checkpoint_store
    follower.stop()
    follower.drain_feed()
    eng = follower.engine
    ckpt = None
    if store is not None:
        try:
            _ref, ckpt = store.load_latest(name)
        except CheckpointError as e:
            if e.code != "checkpoint_missing":
                raise
            # no checkpoint yet (young leader): promote from live
            # replica state alone — the dead leader's waiting queue
            # and WFQ history are lost, which is exactly the pre-17
            # behavior for those requests
            log.warning(
                "promoting %s without a checkpoint: %s", name, e
            )
    boundary = follower._applied_step
    if ckpt is not None:
        ds = ckpt.get("digest_step")
        want = ckpt.get("digest")
        if ds is not None and want and want != _DIGEST_SEED.hex():
            have = follower._digest_by_step.get(ds)
            if have is not None and have != want:
                raise DivergenceError(
                    f"takeover refused: checkpoint digest at step {ds} "
                    f"is {want} but this standby's chain says {have} — "
                    "the standby diverged from the dead leader's "
                    "stream; re-apply the serving profile (full resync)"
                )
            if have is None and boundary < int(ckpt.get("plan_idx", -1)):
                raise ResyncRequired(
                    f"takeover refused: this standby is at step "
                    f"{boundary}, behind the checkpoint's plan "
                    f"{ckpt.get('plan_idx')} and the ring tail is "
                    "gone — "
                    + RESYNC_ACTIONS[RESYNC_RING_OVERFLOW],
                    reason=RESYNC_RING_OVERFLOW,
                )
    # ---- validation is done; mutation starts here ----
    for req in list(eng.slots):
        if req is not None and not eng.preempt(req.id):
            raise DivergenceError(
                f"takeover: cannot park active request {req.id} at the "
                "handoff boundary (leader failover needs the host KV "
                "tier — host_pool_bytes > 0)"
            )
    if ckpt is not None:
        from helix_tpu.serving.migration import wire_to_snapshot

        known = getattr(eng, "_requests", {})
        for doc in ckpt.get("snapshots", []):
            rid = doc.get("request_id", "")
            if rid in known or rid in follower._ops_aborted:
                continue   # replica state is newer — authoritative
            eng.import_request(wire_to_snapshot(doc))
        for doc in ckpt.get("waiting", []):
            rid = doc.get("id", "")
            if rid in known or rid in follower._ops_aborted:
                continue
            eng.add_request(request_from_wire(doc))
        if ckpt.get("budget") is not None:
            eng.prefill_budget = ckpt["budget"]
        restore_spec_state(eng, ckpt.get("spec"))
        if sched is not None:
            restore_sched_state(sched, ckpt.get("sched"))
    cap = journal_capacity or int(
        os.environ.get("HELIX_MH_RING", "4096") or 4096
    )
    journal = CommandLog(capacity=cap,
                         start_seq=follower.applied_seq + 1)
    leader = PlanLeader(eng, journal=journal, checkpoint_store=store,
                        name=name)
    # continue the digest chain EXACTLY where the replica's stands:
    # the first new plan folds the boundary step and surviving peers
    # verify the chain across the handoff
    leader._step_counter = boundary + 1
    leader._last_plan_idx = boundary
    leader._digest = follower._digest
    leader._digest_step = follower._last_folded_step
    if follower._prev is not None:
        pstep, ems = follower._prev
        leader._emissions[pstep] = list(ems)
        leader._done_steps.add(pstep)
        leader._fold_next = pstep
    else:
        leader._fold_next = boundary + 1
    leader._aborts_after_plan = {
        k: set(v) for k, v in follower._aborts_after_plan.items()
    }
    if ckpt is not None and sched is None:
        leader._ckpt_sched = ckpt.get("sched")
    leader.takeovers = 1
    ref = None
    if store is not None:
        state = leader._capture_state()
        if sched is not None:
            state["sched"] = export_sched_state(sched)
        ref, _n = store.save(name, state)   # durable BEFORE the handoff
    journal.publish({
        "v": WIRE_VERSION,
        "kind": "handoff",
        "plan_idx": boundary,
        "digest": (leader._digest.hex()
                   if leader._digest_step is not None else None),
        "digest_step": leader._digest_step,
        "ckpt": ref,
    })
    leader.takeover_ms = (time.monotonic() - t0) * 1000.0
    # the takeover itself is a plan-plane span (ISSUE 18): on the
    # stitched timeline the blackout reads as the gap between the dead
    # leader's last publish and this span, and this span's width is
    # the promotion cost
    leader._trace.record(
        leader.plan_trace_id, "mh promote follower", t0,
        time.monotonic(), plane="engine", boundary=boundary,
        follower=follower.follower_id,
        ckpt=ref or "(none)",
    )
    log.warning(
        "standby %s promoted to leader for %s at step %d in %.1f ms "
        "(checkpoint %s)", follower.follower_id, name or "<model>",
        boundary, leader.takeover_ms, ref,
    )
    return leader


def cold_start_leader(engine, store: CheckpointStore, name: str = "",
                      journal_capacity: Optional[int] = None) -> PlanLeader:
    """Last-resort failover rung: a FRESH process (no live replica
    state) becomes leader from the newest checkpoint alone.  Honest
    about its limits: steps the dead leader ran after the checkpoint
    are lost and will be re-decided, so delivery for requests active
    at the checkpoint degrades from exactly-once to at-least-once, and
    surviving followers past the checkpoint boundary get a typed
    resync instead of a seamless cross-over.  Use a live standby
    (promote_follower) when one exists."""
    t0 = time.monotonic()
    ref, ckpt = store.load_latest(name)   # typed CheckpointError if unusable
    from helix_tpu.serving.migration import wire_to_snapshot

    snaps = [wire_to_snapshot(d) for d in ckpt.get("snapshots", [])]
    for snap in snaps:                    # all validated above, pre-mutation
        engine.import_request(snap)
    for doc in ckpt.get("waiting", []):
        engine.add_request(request_from_wire(doc))
    if ckpt.get("budget") is not None:
        engine.prefill_budget = ckpt["budget"]
    restore_spec_state(engine, ckpt.get("spec"))
    boundary = int(ckpt.get("plan_idx", -1))
    cap = journal_capacity or int(
        os.environ.get("HELIX_MH_RING", "4096") or 4096
    )
    journal = CommandLog(capacity=cap,
                         start_seq=int(ckpt.get("seq", 0)) + 1)
    leader = PlanLeader(engine, journal=journal, checkpoint_store=store,
                        name=name)
    leader._step_counter = max(boundary + 1,
                               int(ckpt.get("step_counter", 0)))
    leader._last_plan_idx = boundary
    leader._digest = bytes.fromhex(
        ckpt.get("digest") or _DIGEST_SEED.hex()
    )
    leader._digest_step = ckpt.get("digest_step")
    leader._fold_next = int(ckpt.get("fold_next", boundary + 1))
    leader._digest_reset_pending = bool(
        ckpt.get("digest_reset_pending", False)
    )
    leader._emissions = {
        int(k): [(rid, int(t)) for rid, t in v]
        for k, v in (ckpt.get("pending_emissions") or {}).items()
    }
    leader._done_steps = set(ckpt.get("done_steps") or [])
    leader._aborts_after_plan = {
        int(k): set(v)
        for k, v in (ckpt.get("aborts_after_plan") or {}).items()
    }
    leader._ckpt_sched = ckpt.get("sched")
    leader.takeovers = 1
    journal.publish({
        "v": WIRE_VERSION,
        "kind": "handoff",
        "plan_idx": boundary,
        "digest": (leader._digest.hex()
                   if leader._digest_step is not None else None),
        "digest_step": leader._digest_step,
        "ckpt": ref,
    })
    leader.takeover_ms = (time.monotonic() - t0) * 1000.0
    log.warning(
        "cold-start leader for %s from checkpoint %s at step %d "
        "(at-least-once window: steps after the checkpoint were "
        "re-decided)", name or "<model>", ref, boundary,
    )
    return leader


# ---------------------------------------------------------------------------
# observability: the ONLY minting site for helix_mh_* series and the
# heartbeat mesh-health block (lint contract 12 fences both here)
# ---------------------------------------------------------------------------

def collect_mh_metrics(c, loop, labels: dict) -> None:
    """Scrape-time helix_mh_* family for a leader engine (bounded: one
    follower label per registry entry, and the registry itself is
    bounded by HELIX_MH_MAX_FOLLOWERS)."""
    eng = getattr(loop, "engine", None)
    ms = getattr(eng, "mh_stats", None)
    if not callable(ms):
        return
    st = ms()
    c.counter(
        "helix_mh_plans_published_total", st["plans_published"], labels,
        help="Step-plan records published by this leader",
    )
    c.counter(
        "helix_mh_plan_bytes_total", st["plan_bytes_total"], labels,
        help="Serialized bytes of all published step plans",
    )
    c.gauge(
        "helix_mh_last_plan_idx", st["last_plan_idx"], labels,
        help="Newest published plan index",
    )
    c.counter(
        "helix_mh_throttled_steps_total", st["throttled_steps"], labels,
        help="Dispatches with admission throttled for a lagging follower",
    )
    c.counter(
        "helix_mh_followers_dropped_total", st["followers_dropped"],
        labels,
        help="Follower registrations dropped at the registry bound",
    )
    c.counter(
        "helix_mh_takeovers_total", st["takeovers"], labels,
        help="Leader takeovers this process performed",
    )
    c.counter(
        "helix_mh_checkpoints_total", st["checkpoints_captured"], labels,
        help="Leader-state checkpoints captured",
    )
    c.counter(
        "helix_mh_checkpoint_errors_total", st["checkpoint_errors"],
        labels,
        help="Checkpoint captures that failed",
    )
    cs = st.get("checkpoint_store") or {}
    c.gauge(
        "helix_mh_checkpoint_bytes_last", cs.get("bytes_last", 0),
        labels, help="Size of the newest written checkpoint blob",
    )
    c.counter(
        "helix_mh_checkpoint_corrupt_total",
        cs.get("corrupt_rejected", 0), labels,
        help="Checkpoint blobs rejected by checksum/version validation",
    )
    for state, n in st["follower_states"].items():
        c.gauge(
            "helix_mh_followers", n, {**labels, "state": state},
            help="Registered followers by health state",
        )
    for fid, f in st["followers"].items():
        fl = {**labels, "follower": fid}
        c.gauge(
            "helix_mh_follower_lag_steps", f["lag_steps"], fl,
            help="Steps this follower trails the newest plan",
        )
        c.gauge(
            "helix_mh_follower_apply_seconds",
            f.get("apply_ms", 0.0) / 1000.0, fl,
            help="Follower-reported per-plan apply wall (EMA)",
        )
        c.counter(
            "helix_mh_follower_digest_mismatches_total",
            f.get("digest_mismatches", 0), fl,
            help="Digest mismatches this follower reported",
        )


def mh_heartbeat_block(models) -> dict:
    """Per-model mesh-health block for the node agent's heartbeat (the
    /v1/cluster/status source).  Leaders report the follower registry
    summary; followers/standbys report their applied position and any
    typed resync reason."""
    out = {}
    for m in models:
        f = getattr(m, "follower", None)
        if f is not None:
            st = f.stats()
            out[m.name] = {
                "role": "standby" if f.standby else "follower",
                "follower_id": st["follower_id"],
                "applied_seq": st["applied_seq"],
                "applied_step": st["applied_step"],
                "digest_mismatches": st["digest_mismatches"],
                "resync_reason": st["resync_reason"],
                "error": getattr(f, "error", None) or "",
            }
            continue
        loop = getattr(m, "loop", None)
        eng = getattr(loop, "engine", None)
        ms = getattr(eng, "mh_stats", None)
        if not callable(ms):
            continue
        st = ms()
        worst_lag = max(
            (fs["lag_steps"] for fs in st["followers"].values()),
            default=0,
        )
        out[m.name] = {
            "role": "leader",
            "last_plan_idx": st["last_plan_idx"],
            "followers": st["follower_states"],
            "worst_lag_steps": worst_lag,
            "throttled_steps": st["throttled_steps"],
            "takeovers": st["takeovers"],
            "checkpoints_captured": st["checkpoints_captured"],
        }
    return out


def validate_mh_block(raw) -> dict:
    """Control-plane-side sanitation of a heartbeat's mesh block: a
    runner-supplied dict, so entries clamp to the known schema with
    finite numbers and bounded counts — malformed blocks degrade to {}
    and never reject the heartbeat (the PR 4/7 hardening pattern)."""
    import math

    if not isinstance(raw, dict):
        return {}
    out = {}
    for model, doc in list(raw.items())[:32]:
        if not isinstance(model, str) or not isinstance(doc, dict):
            continue
        role = doc.get("role")
        if role not in ("leader", "follower", "standby"):
            continue
        ent = {"role": role}
        for key in ("last_plan_idx", "worst_lag_steps",
                    "throttled_steps", "takeovers",
                    "checkpoints_captured", "applied_seq",
                    "applied_step", "digest_mismatches"):
            v = doc.get(key)
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            try:
                fv = float(v)
            except (OverflowError, ValueError):
                continue
            if math.isfinite(fv):
                ent[key] = int(fv)
        followers = doc.get("followers")
        if isinstance(followers, dict):
            ent["followers"] = {
                s: int(followers[s])
                for s in FOLLOWER_STATES
                if isinstance(followers.get(s), int)
                and not isinstance(followers.get(s), bool)
            }
        for key in ("follower_id", "resync_reason", "error"):
            v = doc.get(key)
            if isinstance(v, str):
                ent[key] = v[:256]
        out[model[:128]] = ent
    return out


# the old name survived one release; keep the alias so operator tooling
# importing LockstepLeader keeps working against the plan broadcast
LockstepLeader = PlanLeader
