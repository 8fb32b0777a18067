"""Host-side metadata for the unified ragged device step.

The engine compiles ONE device-step entry point per (model, backend,
token-bucket) — ``engine._build_ragged_step_fn`` — and every caller
(packed/cache-hit prefill, chunked prefill, plain decode, the mixed
step, spec-verify) is a thin metadata builder over it.  This module owns
the host-side pieces of that contract:

- the **token-bucket ladder**: the prefill segment's flat token axis is
  padded to a rung so XLA compiles O(log max_prefill_len) shapes, not
  one per prompt length.  Default: powers of two from ``page_size`` to
  ``max_prefill_len``; ``HELIX_TOKEN_BUCKETS`` overrides with an
  explicit comma-separated ladder (finer rungs trade a few extra
  compiles for less padding — the padding-ratio gauge shows whether it
  paid off).
- :class:`PrefillPlan` — accumulates prefill **rows** (one per admitted
  prompt / in-flight chunk) and finalizes them into the device arrays
  the unified step consumes: flat tokens + positions + segment ids + KV
  write destinations, and per-row (t0, q_len, hist, table, end,
  sampling, key).
- the **compiled-shape registry** — every distinct (token-bucket,
  has-history) entry point the unified builder traces is recorded per
  model key, so ``helix_compiled_step_shapes`` can report the shape-zoo
  collapse instead of asserting it.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Optional

import numpy as np


def parse_token_buckets(
    spec: Optional[str], page_size: int, cap: int
) -> tuple:
    """The prefill token-bucket ladder, ascending, capped at ``cap``.

    ``spec`` (from ``HELIX_TOKEN_BUCKETS``) is a comma-separated list of
    rung sizes; invalid entries raise (a typo'd ladder must not silently
    become the default).  ``None``/empty: powers of two from
    ``page_size`` up to ``cap``.  The top rung is always ``cap`` so any
    admissible chunk has a home."""
    if spec:
        rungs = sorted(
            {min(int(tok), cap) for tok in spec.split(",") if tok.strip()}
        )
        if not rungs or any(r <= 0 for r in rungs):
            raise ValueError(
                f"HELIX_TOKEN_BUCKETS {spec!r}: rungs must be positive ints"
            )
    else:
        rungs = []
        b = page_size
        while b < cap:
            rungs.append(b)
            b *= 2
    if not rungs or rungs[-1] != cap:
        rungs.append(cap)
    return tuple(rungs)


def bucket_tokens(n: int, ladder: tuple) -> int:
    """Smallest rung >= n (callers guarantee n <= ladder[-1])."""
    for b in ladder:
        if b >= n:
            return b
    return ladder[-1]


# ---------------------------------------------------------------------------
# compiled-shape registry (feeds helix_compiled_step_shapes)
# ---------------------------------------------------------------------------

_SHAPES: dict = {}           # model key -> set of shape tuples
_SHAPES_LOCK = threading.Lock()


def note_step_shape(model_key, shape: tuple) -> None:
    """Record one distinct compiled device-step entry point for a model.
    Called from the unified builder on cache miss (and from the VL
    prefill path per bucket), so the count IS the number of live traced
    step programs."""
    with _SHAPES_LOCK:
        _SHAPES.setdefault(model_key, set()).add(shape)


def step_program_name(token_bucket: int, has_hist: bool, prefill_rows: int,
                      ring_hist_pages: int = 0, cold_chunks: int = 0) -> str:
    """The unified step's function name for one compiled shape; a
    profiler trace calls the program ``jit_<name>``.  Every name starts
    with ``step_fn`` (what reads a trace matches ``^jit_step_fn``), and
    ``step_fn_t0`` is the decode-only program: a trace tells a chunk
    that continues a long prompt (``step_fn_t512_r1_h``) from a decode
    window."""
    name = f"step_fn_t{token_bucket}"
    if token_bucket:
        name += f"_r{prefill_rows}" + ("_h" if has_hist else "")
    if ring_hist_pages:
        name += f"_g{ring_hist_pages}"
    if cold_chunks:
        name += f"_c{cold_chunks}"
    return name


def compiled_step_shapes(model_key) -> int:
    with _SHAPES_LOCK:
        return len(_SHAPES.get(model_key, ()))


def step_shape_set(model_key) -> frozenset:
    """Snapshot of the distinct compiled step shapes for a model key.
    The multihost parity tests diff this across a leader run and a
    follower replay: a plan-driven follower must trace ZERO shapes of
    its own (same model key -> same registry entry, so the assertion is
    'no new members after replay')."""
    with _SHAPES_LOCK:
        return frozenset(_SHAPES.get(model_key, ()))


@dataclasses.dataclass
class PrefillRow:
    req: object                 # engine.Request (None for warmup rows)
    table: np.ndarray           # full page table row [maxP]
    start: int                  # pages-resident history tokens
    rem: int                    # fresh tokens this call
    tokens: list                # the rem token ids
    key: np.ndarray             # [2] u32 sampling sub-key
    sampling: object            # SamplingParams
    t0: int = 0                 # assigned at finalize
    adapter: int = 0            # multi-LoRA pool slot (0 = identity)
    slot: int = -1              # the row's decode slot (its row of a state
                                # pool); -1: none (warmup)
    snap: int = 0               # tokens of the row before the page boundary
                                # whose recurrent state the step hands back


class PrefillPlan:
    """One call's prefill segment: rows packed back-to-back on a flat
    token axis, finalized to a ladder rung.

    The unification win lives here: cache-hit prompts (nonzero
    ``start``), cold packed prompts and the in-flight chunk all share
    ONE segment instead of one padded call each — padding is charged
    once, ``rung - sum(rem)``, by the engine's ``_charge_padding``."""

    def __init__(self, page_size: int, max_pages: int, max_rows: int):
        self.page_size = page_size
        self.max_pages = max_pages
        self.max_rows = max_rows
        self.rows: list = []
        self.used = 0

    def fits(self, rem: int, cap: int) -> bool:
        return len(self.rows) < self.max_rows and self.used + rem <= cap

    def add(self, req, table, start: int, rem: int, tokens, key,
            sampling, adapter: int = 0, slot: int = -1,
            snap: int = 0) -> None:
        row = PrefillRow(
            req=req, table=np.asarray(table), start=int(start),
            rem=int(rem), tokens=list(tokens), key=key, sampling=sampling,
            t0=self.used, adapter=int(adapter), slot=int(slot),
            snap=int(snap),
        )
        self.rows.append(row)
        self.used += row.rem

    @property
    def has_hist(self) -> bool:
        return any(r.start > 0 for r in self.rows)

    def finalize(self, rung: int):
        """Device arrays for the unified step's prefill inputs.

        Returns a dict of host arrays (the engine asarray's them):
        ``tokens/pos/seg/pages/offsets/aids [1, rung]``, per-row
        ``t0/qlen/hist/ends [R]`` and ``tables [R, maxP]``, plus the
        rows' sampling params and keys.  ``aids`` carries each token's
        multi-LoRA pool slot (0 = identity — padding and adapter-free
        rows contribute an exact zero delta in the batched gather-
        matmul)."""
        R = self.max_rows
        ps = self.page_size
        tokens = np.zeros((1, rung), np.int32)
        pos = np.zeros((1, rung), np.int32)
        seg = np.zeros((1, rung), np.int32)
        pages = np.zeros((1, rung), np.int32)
        offsets = np.zeros((1, rung), np.int32)
        aids = np.zeros((1, rung), np.int32)
        t0 = np.zeros((R,), np.int32)
        qlen = np.zeros((R,), np.int32)
        hist = np.zeros((R,), np.int32)
        ends = np.zeros((R,), np.int32)
        tables = np.zeros((R, self.max_pages), np.int32)
        keys = np.zeros((R, 2), np.uint32)
        # a row with no slot points past any state pool: it writes nothing
        slots = np.full((R,), np.iinfo(np.int32).max, np.int32)
        snaps = np.zeros((R,), np.int32)
        for j, row in enumerate(self.rows):
            sl = slice(row.t0, row.t0 + row.rem)
            tokens[0, sl] = row.tokens
            abs_pos = np.arange(row.start, row.start + row.rem)
            pos[0, sl] = abs_pos
            seg[0, sl] = j + 1
            # clamp like the device paths: real rows never exceed their
            # table (admission caps max_len), warmup's garbage-page rows
            # may — they write page 0 regardless
            pages[0, sl] = row.table[
                np.minimum(abs_pos // ps, len(row.table) - 1)
            ]
            offsets[0, sl] = abs_pos % ps
            aids[0, sl] = row.adapter
            t0[j] = row.t0
            qlen[j] = row.rem
            hist[j] = row.start
            ends[j] = row.t0 + row.rem - 1
            tables[j, : len(row.table)] = row.table
            keys[j] = row.key
            if row.slot >= 0:
                slots[j] = row.slot
            snaps[j] = row.snap
        # unused rows park at the segment end (ascending-start contract)
        t0[len(self.rows):] = self.used
        return {
            "tokens": tokens, "pos": pos, "seg": seg,
            "pages": pages, "offsets": offsets, "aids": aids,
            "t0": t0, "qlen": qlen, "hist": hist, "ends": ends,
            "tables": tables, "keys": keys,
            "slots": slots, "snaps": snaps,
        }

    def finalize_device(self, rung: int, with_state: bool = False):
        """``finalize`` + the host->device upload, in one place.

        The engine calls this at DISPATCH time so the conversion (and
        the transfers jax issues for it) overlap whatever device step is
        already in flight — the async engine loop's double-buffered
        metadata upload.  Plan building itself stays pure host work and
        may run against the loop's PREDICTED post-step state; nothing
        here reads device values."""
        import jax.numpy as jnp

        # ``slots`` and ``snaps`` go up only for a model with a state pool
        return {k: jnp.asarray(v) for k, v in self.finalize(rung).items()
                if with_state or k not in ("slots", "snaps")}
